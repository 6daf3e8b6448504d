"""Run ledger, regression sentinel, and budget-queue unit tests
(ndstpu/obs/ledger.py, ndstpu/obs/sentinel.py,
ndstpu/harness/progress.py — docs/OBSERVABILITY.md)."""

import json
import os

import pytest

from ndstpu.harness import progress
from ndstpu.obs import ledger as ledger_mod
from ndstpu.obs import sentinel

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------- ledger

def test_make_entry_derives_warmth_from_split():
    cold = ledger_mod.make_entry("q1", 10.0, compile_s=8.0,
                                 execute_s=2.0)
    assert cold["warmth"] == "cold"
    warm = ledger_mod.make_entry("q1", 10.0, compile_s=0.0,
                                 execute_s=9.9)
    assert warm["warmth"] == "warm"
    # explicit warmth (legacy artifacts) wins over the split
    forced = ledger_mod.make_entry("q1", 10.0, compile_s=8.0,
                                   warmth="warm")
    assert forced["warmth"] == "warm"


def test_fingerprint_distinguishes_configs():
    fps = {ledger_mod.make_entry("q1", 1.0, engine=e, scale_factor=sf,
                                 seed=sd)["fingerprint"]
           for e in ("cpu", "tpu") for sf in ("1", "10")
           for sd in ("bench", "777")}
    assert len(fps) == 8


def test_append_reload_roundtrip_and_corrupt_tolerance(tmp_path):
    path = str(tmp_path / "ledger.jsonl")
    led = ledger_mod.Ledger(path)
    led.record_query("query1", 2.0, 0.0, 1.9, engine="cpu",
                     scale_factor="1", seed="s", source="t1")
    led.record_query("query2", 3.0, 2.9, 0.1, engine="cpu",
                     scale_factor="1", seed="s", source="t1")
    # interrupted append / junk must not poison the history
    with open(path, "a") as f:
        f.write("{truncated json\n")
        f.write("[1,2,3]\n")
    led2 = ledger_mod.Ledger(path)
    assert len(led2) == 2
    assert led2.corrupt_lines == 2
    assert led2.best_warm("query1", engine="cpu",
                          scale_factor="1") == 2.0


def test_best_warm_uses_cold_execute_split_as_proxy():
    led = ledger_mod.Ledger(path=None)
    # first-ever run is cold: 60s wall, 55 compile, 5 execute
    led.record_query("query4", 60.0, 55.0, 5.0, engine="tpu",
                     scale_factor="1")
    # the split seeds the baseline — a second warm run at 5.2s is flat,
    # not "regressed vs nothing" and not judged against the 60s wall
    assert led.best_warm("query4", engine="tpu",
                         scale_factor="1") == 5.0
    v = sentinel.classify_query("query4", 5.2, 0.0, 5.2, 5.0)
    assert v["verdict"] == "flat"


def test_prior_scope_strict_but_estimate_relaxes():
    led = ledger_mod.Ledger(path=None)
    led.record_query("query5", 1.5, 0.0, 1.5, engine="cpu",
                     scale_factor="1")
    assert led.best_warm("query5", engine="tpu",
                         scale_factor="1") is None
    assert led.best_warm("query5", engine="cpu",
                         scale_factor="10") is None
    # the ETA estimator relaxes scope: any history beats no history
    assert led.estimate("query5", engine="tpu",
                        scale_factor="10") == 1.5
    assert led.estimate("missing", engine="cpu", default=7.0) == 7.0


def test_expected_cold_is_median():
    led = ledger_mod.Ledger(path=None)
    for wall in (10.0, 30.0, 20.0):
        led.record_query("query6", wall, compile_s=wall * 0.9,
                         execute_s=wall * 0.1, engine="tpu",
                         scale_factor="1")
    assert led.expected_cold("query6", engine="tpu",
                             scale_factor="1") == 20.0


def _sidecar(path, engine, queries):
    path.write_text(json.dumps({
        "engine": engine,
        "queries": [{"query": q, "wall_s": wall, "compile_s": comp,
                     "execute_s": wall - comp, "mode": mode}
                    for q, wall, comp, mode in queries],
        "totals": {}}))


def test_ingest_shapes(tmp_path):
    """Both shapes ingest_file reads: a power-run sidecar and another
    ledger (JSONL), with measured warmth carried through and re-ingest
    deduped."""
    sidecar = tmp_path / "t.csv.metrics.json"
    _sidecar(sidecar, "tpu", [("query1", 12.0, 11.6, "cold"),
                              ("query2", 1.0, 0.0, "warm")])
    other = ledger_mod.Ledger(str(tmp_path / "other.jsonl"))
    other.record_query("query1", 0.4, 0.0, 0.4, engine="tpu",
                       scale_factor="1")
    led = ledger_mod.Ledger(path=None)
    assert led.ingest_file(str(sidecar), scale_factor="1") == 2
    assert led.ingest_file(other.path) == 1
    # warmth came through: cold wall is the expected-cold prior, the
    # warm entry from the merged ledger is the best-known-warm
    assert led.expected_cold("query1", engine="tpu",
                             scale_factor="1") == 12.0
    assert led.best_warm("query1", engine="tpu",
                         scale_factor="1") == 0.4
    assert led.best_warm("query2", engine="tpu",
                         scale_factor="1") == 1.0
    # re-ingest is a no-op (dedupe)
    assert led.ingest_file(str(sidecar), scale_factor="1") == 0
    assert led.ingest_file(other.path) == 0


def test_ingest_history_sweeps_sidecars(tmp_path):
    """ingest_history over a synthetic tree: every sidecar at the root
    and under docs/ is swept, nothing else is."""
    (tmp_path / "docs").mkdir()
    _sidecar(tmp_path / "power_time.csv.metrics.json", "tpu",
             [(f"query{i}", 1.0 + i, 0.0, "warm") for i in range(1, 61)])
    _sidecar(tmp_path / "docs" / "hw.csv.metrics.json", "tpu",
             [(f"query{i}", 0.5 + i, 0.0, "warm") for i in range(1, 61)])
    (tmp_path / "notes.json").write_text(json.dumps({"queries": "no"}))
    led = ledger_mod.Ledger(path=None)
    counts = led.ingest_history(str(tmp_path))
    assert len(counts) == 2
    assert sum(counts.values()) == 120
    assert led.best_warm("query1", engine="tpu",
                         scale_factor="unknown") == 1.5


# -------------------------------------------------------------- sentinel

def test_cold_compile_is_never_a_regression():
    # 60s wall vs a 1s baseline would be a 60x "regression" — but the
    # split says it was compile work, so the verdict is cold-compile
    v = sentinel.classify_query("q", 60.0, 55.0, 5.0, 1.0)
    assert v["verdict"] == "cold-compile"


@pytest.mark.parametrize("wall,base,verdict", [
    (2.0, 1.0, "regressed"),       # +1s, 2x: beyond both guards
    (1.2, 1.0, "flat"),            # +0.2s: under the 0.25s floor
    (1.3, 1.1, "flat"),            # +18%: under the 25% relative tol
    (0.5, 1.0, "improved"),
    (0.9, 1.0, "flat"),
    (1.0, None, "new"),
])
def test_warm_verdict_table(wall, base, verdict):
    v = sentinel.classify_query("q", wall, 0.0, wall, base)
    assert v["verdict"] == verdict, v


def test_classify_run_counts_and_failed():
    led = ledger_mod.Ledger(path=None)
    led.record_query("query1", 1.0, 0.0, 1.0, engine="cpu",
                     scale_factor="1")
    qsums = [
        {"query": "query1", "wall_s": 1.02, "compile_s": 0.0,
         "execute_s": 1.02},
        {"query": "query2", "wall_s": 9.0, "compile_s": 8.5,
         "execute_s": 0.5},
        {"query": "query3", "wall_s": 0.1, "compile_s": 0.0,
         "execute_s": 0.1, "attrs": {"error": "boom"}},
    ]
    res = sentinel.classify_run(qsums, led, engine="cpu",
                                scale_factor="1")
    assert res["counts"] == {"flat": 1, "cold-compile": 1, "failed": 1}
    assert res["regressions"] == []
    md = sentinel.markdown_table(res)
    assert "| query1 |" in md and "cold-compile" in md


def test_regression_exits_reports(tmp_path):
    led = ledger_mod.Ledger(path=None)
    led.record_query("query1", 1.0, 0.0, 1.0, engine="cpu",
                     scale_factor="1")
    res = sentinel.classify_run(
        [{"query": "query1", "wall_s": 3.0, "compile_s": 0.0,
          "execute_s": 3.0}], led, engine="cpu", scale_factor="1")
    assert res["regressions"] == ["query1"]
    paths = sentinel.write_reports(res,
                                   str(tmp_path / "REGRESSIONS.json"),
                                   str(tmp_path / "REGRESSIONS.md"))
    with open(paths["json"]) as f:
        assert json.load(f)["regressions"] == ["query1"]


# -------------------------------------------------------- budget / queue

def test_budgeted_queue_fifo_without_budget():
    q = progress.BudgetedQueue(["a", "b", "c"], None, None)
    assert [q.next(0), q.next(0), q.next(0), q.next(0)] == \
        ["a", "b", "c", None]
    assert q.skipped == {}


def test_budgeted_queue_reorders_cheapest_first_then_cuts():
    est = {"a": 1.0, "b": 100.0, "c": 2.0}.get
    events = []
    q = progress.BudgetedQueue(["b", "a", "c"], 10.0, est, phase="p",
                               on_event=events.append)
    order, elapsed = [], 0.0
    while True:
        n = q.next(elapsed)
        if n is None:
            break
        order.append(n)
        elapsed += est(n)
    assert order == ["a", "c"]
    assert set(q.skipped) == {"b"}
    assert "prior" in q.skipped["b"] and "budget" in q.skipped["b"]
    assert any("cheapest-first" in e for e in events)


def test_budgeted_queue_cuts_everything_when_exhausted():
    q = progress.BudgetedQueue(["a", "b"], 5.0, lambda n: 1.0,
                               on_event=lambda s: None)
    assert q.next(6.0) is None
    assert sorted(q.skipped) == ["a", "b"]
    for reason in q.skipped.values():
        assert "exhausted" in reason


def test_heartbeat_line_grammar():
    lines = []
    hb = progress.Heartbeat("power", total=9, budget_s=100.0,
                            out=lines.append)
    hb.beat(3, "query7", 12.5, eta_s=40.0)
    assert lines == ["[heartbeat] power 3/9 query7 elapsed=12.5s "
                     "eta=40.0s budget=100s remaining=87.5s"]


def test_ledger_estimator_feeds_queue():
    led = ledger_mod.Ledger(path=None)
    led.record_query("query1", 2.5, 0.0, 2.5, engine="cpu",
                     scale_factor="1")
    est = progress.ledger_estimator(led, engine="cpu",
                                    scale_factor="1")
    q = progress.BudgetedQueue(["query1", "queryX"], 100.0, est)
    assert q.cost("query1") == 2.5
    assert q.cost("queryX") == progress.DEFAULT_COST_S
    assert progress.ledger_estimator(None)("query1") is None


# ------------------------------------------- snapshot-epoch awareness


def _epoch_ledger():
    """Warm baselines under epoch eAAA plus one unstamped legacy row."""
    led = ledger_mod.Ledger(path=None)
    led.record_query("query1", 2.0, 0.0, 1.9, engine="cpu",
                     scale_factor="1", extra={"snapshot_epoch": "eAAA"})
    led.record_query("query2", 3.0, 0.0, 2.9, engine="cpu",
                     scale_factor="1", extra={"snapshot_epoch": "eAAA"})
    led.record_query("query3", 4.0, 0.0, 3.9, engine="cpu",
                     scale_factor="1")  # legacy: no epoch stamp
    return led


def test_best_warm_scopes_to_snapshot_epoch():
    led = _epoch_ledger()
    # same epoch: baseline applies
    assert led.best_warm("query1", engine="cpu", scale_factor="1",
                         snapshot_epoch="eAAA") == 2.0
    # other epoch: the data changed — the eAAA wall must not be used
    assert led.best_warm("query1", engine="cpu", scale_factor="1",
                         snapshot_epoch="eBBB") is None
    # no epoch given (legacy caller): everything stays comparable
    assert led.best_warm("query1", engine="cpu",
                         scale_factor="1") == 2.0
    # unstamped legacy entries qualify under ANY epoch
    assert led.best_warm("query3", engine="cpu", scale_factor="1",
                         snapshot_epoch="eBBB") == 4.0


def test_warm_epochs_lists_stamped_epochs():
    led = _epoch_ledger()
    led.record_query("query1", 2.5, 0.0, 2.4, engine="cpu",
                     scale_factor="1", extra={"snapshot_epoch": "eCCC"})
    assert led.warm_epochs("query1", engine="cpu",
                           scale_factor="1") == {"eAAA", "eCCC"}
    # legacy unstamped entries contribute no epoch
    assert led.warm_epochs("query3", engine="cpu",
                           scale_factor="1") == set()


def test_sentinel_data_changed_not_regressed_across_epochs():
    """A warm wall 10x the baseline under a DIFFERENT snapshot epoch
    is the data changing, not the engine regressing."""
    led = _epoch_ledger()
    run = [{"query": "query1", "wall_s": 20.0, "compile_s": 0.0,
            "execute_s": 19.9}]
    res = sentinel.classify_run(run, led, engine="cpu",
                                scale_factor="1",
                                snapshot_epoch="eBBB")
    v = res["verdicts"][0]
    assert v["verdict"] == "data-changed"
    assert "eAAA" in v["reason"]
    assert res["regressions"] == []
    # the SAME wall under the SAME epoch is a genuine regression
    res2 = sentinel.classify_run(run, led, engine="cpu",
                                 scale_factor="1",
                                 snapshot_epoch="eAAA")
    assert res2["verdicts"][0]["verdict"] == "regressed"


def test_sentinel_epoch_unstamped_stays_comparable():
    """Legacy ledgers (no epoch stamps) keep classifying normally under
    an epoch-stamped run — no data-changed false positives."""
    led = ledger_mod.Ledger(path=None)
    led.record_query("query1", 2.0, 0.0, 1.9, engine="cpu",
                     scale_factor="1")
    run = [{"query": "query1", "wall_s": 2.1, "compile_s": 0.0,
            "execute_s": 2.0}]
    res = sentinel.classify_run(run, led, engine="cpu",
                                scale_factor="1",
                                snapshot_epoch="eNEW")
    assert res["verdicts"][0]["verdict"] == "flat"


def test_sentinel_genuinely_new_query_stays_new_under_epoch():
    led = _epoch_ledger()
    run = [{"query": "query9", "wall_s": 1.0, "compile_s": 0.0,
            "execute_s": 0.9}]
    res = sentinel.classify_run(run, led, engine="cpu",
                                scale_factor="1",
                                snapshot_epoch="eBBB")
    assert res["verdicts"][0]["verdict"] == "new"
