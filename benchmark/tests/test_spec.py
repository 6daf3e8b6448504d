"""Names in BENCHMARK.json resolve to files; a name with no file fails
loudly; the README's worked examples load as files plus entries."""

import json
import os
import shutil

import pytest

from benchmark.harness import readers, spec


def test_every_name_in_the_committed_benchmark_resolves():
    cells = spec.check_all()
    assert "power-sf1.opclass7" in cells
    for cell in cells.values():
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer, cell.name
        for m in cell.per_layer:
            assert m["file"]["reader"] in readers.READERS
            assert m["moves"] in names


def _copy_tree(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(spec.ROOT, "benchmark"),
                    root / "benchmark",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), root)
    return root


@pytest.mark.parametrize("kind", ["workload", "config", "metric"])
def test_a_name_without_a_file_fails_loudly(tmp_path, kind):
    root = _copy_tree(tmp_path)
    with open(root / "BENCHMARK.json") as f:
        bench = json.load(f)
    if kind == "workload":
        bench["workloads"].append(dict(bench["workloads"][0],
                                       name="power-sf1.nofile",
                                       traffic="nofile"))
        target = "power-sf1.nofile"
    elif kind == "config":
        bench["configs"][0]["file"] = "benchmark/configs/absent.json"
        target = bench["workloads"][0]["name"]
    else:
        bench["per_layer"].append(dict(bench["per_layer"][0],
                                       name="absent_metric.power"))
        target = bench["per_layer"][0]["workloads"][0]
    with open(root / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    with pytest.raises(spec.SpecError, match="no file"):
        spec.load_cell(target, str(root))


def test_unknown_workload_is_an_error():
    with pytest.raises(spec.SpecError, match="no workload"):
        spec.load_cell("no-such-cell")


def test_readme_examples_load_as_files_plus_entries(tmp_path):
    """benchmark/README.md's worked examples: the tput-sf1.inproc4 cell
    and a counter_per_op metric over engine.cache.plan.hit, added
    without touching a file that is there."""
    root = _copy_tree(tmp_path)
    with open(root / "BENCHMARK.json") as f:
        bench = json.load(f)
    with open(root / "benchmark" / "workloads" / "tput-sf1.inproc4.json",
              "w") as f:
        json.dump({"name": "tput-sf1.inproc4",
                   "config": "nds-sf1-power-1chip",
                   "driver": "closed_loop", "clients": 4,
                   "parts": ["query96", "query3"], "draws": 4,
                   "order": "round_robin"}, f)
    bench["workloads"].append({
        "name": "tput-sf1.inproc4", "config": "nds-sf1-power-1chip",
        "traffic": "inproc4", "chips": 1, "why": "example"})
    for m in bench["end_to_end"]:
        if m["name"] == "power_pass_s":
            m["workloads"].append("tput-sf1.inproc4")
    with open(root / "benchmark" / "metrics" / "plan_hits_per_op.power.json",
              "w") as f:
        json.dump({"reader": "counter_per_op",
                   "arguments": {"counter": "engine.cache.plan.hit"}}, f)
    bench["per_layer"].append({
        "name": "plan_hits_per_op.power", "unit": "hits/op",
        "better": "higher", "source": "program_counter",
        "layer": "session + planner", "moves": "power_pass_s",
        "workloads": ["tput-sf1.inproc4"]})
    with open(root / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    cells = spec.check_all(str(root))
    cell = cells["tput-sf1.inproc4"]
    assert [m["name"] for m in cell.per_layer] == ["plan_hits_per_op.power"]
    rec = readers.RunRecord(spans=[], counters={
        "engine.cache.plan.hit": 12}, ops=4)
    assert readers.read_metric(cell.per_layer[0]["file"], rec) == 3.0
