"""``exists_extremes_per_op.power`` loads by name beside its neighbours
of ``test_joinclass_cell.py``: the counter it reads, the cells that
report it, and what a run of a program without the counter gives (no
value, so the line leaves the metric out)."""

from benchmark.harness import readers, spec

NAME = "exists_extremes_per_op.power"
COUNTER = "engine.replay.exists_extremes"
CELLS = ["power-sf1.opclass7", "power-sf1.joinclass6"]


def test_the_metric_loads_by_name_in_both_power_cells():
    cells = spec.check_all()
    for cell in CELLS:
        by_name = {m["name"]: m for m in cells[cell].per_layer}
        doc = by_name[NAME]["file"]
        assert doc["reader"] == "counter_per_op"
        assert doc["arguments"] == {"counter": COUNTER}
        assert by_name[NAME]["moves"] == "power_pass_s"
    entry, = [m for m in spec.load_benchmark()["per_layer"]
              if m["name"] == NAME]
    assert entry["workloads"] == CELLS


class _Record:
    def __init__(self, counters, ops):
        self.counters, self.ops = counters, ops


def test_a_program_without_the_counter_reports_nothing():
    assert readers.counter_per_op(_Record({}, 6), COUNTER) is None
    assert readers.counter_per_op(_Record({COUNTER: 1}, 6), COUNTER) \
        == 1 / 6
