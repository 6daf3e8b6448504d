"""The host runtime's metrics (``host_gc_ms_per_op`` / ``host_stall_ms_per_op``
``.power`` / ``.serve``, ``replay_gc_ms.power``) load by name in the cells
that report them, and read 0.0 -- a number, not nothing -- where the
program's counters hold the keys at 0; a program without the
instruments reports nothing."""

import pytest

from benchmark.harness import readers, spec

POWER = ["power-sf1.opclass7", "power-sf1.joinclass6", "power-sf1.aggwin3"]
SERVE = ["serve-sf1.short4-r80"]
METRICS = {
    "host_gc_ms_per_op.power": (POWER, "power_pass_s", "counter_per_op",
                                {"counter": "host.gc.pause_ms"}),
    "host_gc_ms_per_op.serve": (SERVE, "serve_p95_ms", "counter_per_op",
                                {"counter": "host.gc.pause_ms"}),
    "host_stall_ms_per_op.power": (POWER, "power_pass_s", "counter_per_op",
                                   {"counter": "host.stall_ms"}),
    "host_stall_ms_per_op.serve": (SERVE, "serve_p95_ms", "counter_per_op",
                                   {"counter": "host.stall_ms"}),
    "replay_gc_ms.power": (POWER, "power_pass_s", "span_mean_ms",
                           {"span": "replay", "attr": "gc_s"}),
}
ZERO_COUNTERS = {"host.gc.collections": 0, "host.gc.pause_ms": 0.0,
                 "host.gc.gen2.collections": 0, "host.gc.gen2.pause_ms": 0.0,
                 "host.stall.count": 0, "host.stall_ms": 0.0}


@pytest.mark.parametrize("name", sorted(METRICS))
def test_the_metric_loads_by_name_in_its_cells(name):
    cells, moves, reader, arguments = METRICS[name]
    entry, = [m for m in spec.load_benchmark()["per_layer"]
              if m["name"] == name]
    assert entry["workloads"] == cells and entry["moves"] == moves
    assert entry["better"] == "lower"
    loaded = spec.check_all()
    for cell in cells:
        by_name = {m["name"]: m for m in loaded[cell].per_layer}
        doc = by_name[name]["file"]
        assert doc["reader"] == reader and doc["arguments"] == arguments


@pytest.mark.parametrize("name", sorted(METRICS))
def test_a_quiet_window_reads_zero_and_a_parent_nothing(name):
    cell = spec.load_cell(METRICS[name][0][0])
    file = {m["name"]: m for m in cell.per_layer}[name]["file"]
    quiet = readers.RunRecord(
        spans=[{"name": "replay", "wall_s": 0.01,
                "args": {"gc_s": 0.0, "wait_gc_s": 0.0}}],
        counters=dict(ZERO_COUNTERS), ops=7)
    assert readers.read_metric(file, quiet) == 0.0
    parent = readers.RunRecord(
        spans=[{"name": "replay", "wall_s": 0.01, "args": {}}],
        counters={}, ops=7)
    assert readers.read_metric(file, parent) is None
