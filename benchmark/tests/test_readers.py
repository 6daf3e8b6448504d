"""Every reader reduces the small recorded span dump and trace in
benchmark/fixtures/ to numbers worked out by hand."""

import json
import os

import pytest

from benchmark.harness import readers, spec, trace

FIX = os.path.join(spec.BENCH_DIR, "fixtures")


def _load(name):
    with open(os.path.join(FIX, name)) as f:
        return json.load(f)


@pytest.fixture()
def rec():
    s = _load("spans_small.json")
    t = _load("trace_small.json")
    return readers.RunRecord(
        spans=s["spans"], counters=s["counters"], ops=s["ops"],
        device_kind="TPU v5 lite", requests=s["requests"],
        trace=trace.summarize(t["events"]), traced_ops=2,
        traced_input_bytes=819e9 * 0.0045)


def test_trace_summary_matches_the_hand_count():
    t = _load("trace_small.json")
    s = trace.summarize(t["events"])
    want = t["expect"]
    assert s["window_s"] == pytest.approx(want["window_s"])
    # union of [10.10,10.30] [10.50,10.70] [10.95,11.00]; the op before
    # the window is left out and the one across its end is clipped
    assert s["busy_s"] == pytest.approx(want["busy_s"])
    assert trace.op_seconds(s, r"^fusion") == pytest.approx(want["fusion_s"])
    assert trace.op_seconds(s, r"^sort") == pytest.approx(want["sort_s"])
    assert trace.op_seconds(s, r"^no_such_op") is None
    assert dict(map(tuple, s["idle_gaps"])) == pytest.approx(
        want["gap_by_host"])
    assert s["device_ops"][0][0] == "fusion.1" or \
        s["device_ops"][0][1] >= s["device_ops"][1][1]


def test_recorded_tpu_trace_reduces_to_the_grid_count():
    """One replay of query96 as the chip's profiler recorded it."""
    t = _load("trace_tpu_query96.json")
    s = trace.summarize(t["events"])
    assert len(t["events"]["devices"]["/device:TPU:0"]) == \
        t["expect"]["n_device_events"]
    assert s["window_s"] == pytest.approx(t["expect"]["window_s"])
    assert s["busy_s"] == pytest.approx(t["expect"]["busy_s"], abs=2e-6)
    assert 0 < s["busy_s"] < s["window_s"]
    # the device was idle only while the host prepared and fetched
    assert [n for n, _s in s["idle_gaps"]] == ["part=query96.d0"]
    top_name, top_s = s["device_ops"][0]
    assert top_name.startswith("%fusion") and top_s > 0.5 * s["busy_s"]


def test_host_ops_stand_for_device_ops_in_a_rehearsal_only(monkeypatch):
    """A trace with no /device:TPU plane: XLA:CPU's executions stand in
    under the rehearsal flag, and it is an error without it."""
    import types
    ev = types.SimpleNamespace
    plane = ev(name="/host:CPU", lines=[
        ev(name="tf_XLAPjRtCpuClient/1", events=[
            ev(name="dot.1", start_ns=1000, duration_ns=500)]),
        ev(name="python", events=[
            ev(name=trace.WINDOW_MARK, start_ns=0, duration_ns=4000)])])
    import jax.profiler
    monkeypatch.setattr(jax.profiler.ProfileData, "from_file",
                        lambda path: ev(planes=[plane]))
    got = trace.read_xplane("x.pb", rehearsal=True)
    assert list(got["devices"]) == ["/host:CPU (rehearsal)"]
    assert got["host"][0][0] == trace.WINDOW_MARK
    with pytest.raises(ValueError, match="no /device:TPU plane"):
        trace.read_xplane("x.pb")


def test_no_window_mark_or_no_device_gives_nothing():
    t = _load("trace_small.json")["events"]
    assert trace.summarize({"devices": t["devices"], "host": []}) is None
    assert trace.summarize({"devices": {}, "host": t["host"]}) is None


@pytest.mark.parametrize("reader,arguments,want", [
    # statements 0.100 + 0.090 less replays 0.080 + 0.060, over 2
    ("span_diff_mean_ms", {"outer": "statement", "inner": "replay"}, 25.0),
    # query spans 0.100 + 0.140 (0.040 of it waiting for the device
    # gate) less the statements in them, over 2
    ("span_diff_mean_ms", {"outer_cat": "query", "inner": "statement"},
     25.0),
    ("span_mean_ms", {"span": "replay"}, 70.0),
    ("span_mean_ms", {"span": "replay", "attr": "host_prep_s"}, 5.0),
    ("counter_per_op", {"counter": "engine.cache.plan.hit"}, 1.0),
    ("trace_idle_pct", {}, 55.0),
    # fusion ops: 0.25 s over 2 traced operations
    ("trace_op_ms", {"pattern": "^fusion"}, 125.0),
    # 0.0045 s of reading at the peak over 0.45 s busy
    ("input_roofline_pct", {}, 1.0),
    # r0: 0.300 s from due to reply less its 0.100 s server span
    ("client_queue_ms", {"span_cat": "query"}, 200.0),
    # sent - due: 2 ms and 40 ms; the 95th percentile of two is the larger
    ("generator_late_ms", {"quantile": 0.95}, 40.0),
])
def test_reader_gives_the_hand_number(rec, reader, arguments, want):
    got = readers.read_metric({"reader": reader, "arguments": arguments},
                              rec)
    assert got == pytest.approx(want)


@pytest.mark.parametrize("reader,arguments", [
    ("span_mean_ms", {"span": "absent"}),
    ("span_diff_mean_ms", {"outer": "absent", "inner": "replay"}),
    ("span_diff_mean_ms", {"outer_cat": "absent", "inner": "replay"}),
    ("counter_per_op", {"counter": "absent"}),
    ("trace_op_ms", {"pattern": "^absent"}),
])
def test_reader_that_finds_nothing_returns_nothing(rec, reader, arguments):
    assert readers.read_metric(
        {"reader": reader, "arguments": arguments}, rec) is None


def test_trace_readers_return_nothing_without_a_trace(rec):
    rec.trace = None
    for reader in ("trace_idle_pct", "input_roofline_pct"):
        assert readers.read_metric({"reader": reader}, rec) is None
    assert readers.read_metric(
        {"reader": "trace_op_ms", "arguments": {"pattern": "x"}},
        rec) is None


def test_unknown_device_has_no_peak(rec):
    rec.device_kind = "TPU v9 imaginary"
    with pytest.raises(KeyError, match="no published peaks"):
        readers.read_metric({"reader": "input_roofline_pct"}, rec)


def test_unknown_reader_is_an_error(rec):
    with pytest.raises(KeyError, match="unknown reader"):
        readers.read_metric({"reader": "made_up"}, rec)
