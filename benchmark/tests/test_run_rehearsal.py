"""The whole of a run, walked on the CPU at the configurations' tiny
rehearsal scale: both drivers end to end, the shape of the last line,
the controls that ``correct`` has to refuse (the program's own
lower-precision path, decimals as floats; the plain reference computed
in float32 in the program's place), and the fault a cell can have — an
answer altered where it is produced (``faulty.py``).  The look for a chip is
skipped (``--rehearse-cpu``); without it, and without a TPU, a run
gives no result.

These start the program several times (about half a minute each on the
first seed); they share one seed so that its data is made once.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark.harness import spec

SEED = 2**31 + 4242      # more than 32 signed bits hold
CELLS = ["power-sf1.opclass7", "serve-sf1.short4-r80"]


def _run(cell, *extra, seconds=3, trace=0, env=None, cwd=spec.ROOT,
         script=None):
    cmd = [sys.executable, script or os.path.join(
        spec.BENCH_DIR, "run.py"), "--workload", cell, "--seed",
        str(SEED), "--seconds", str(seconds), "--trace", str(trace),
        *extra]
    e = dict(os.environ, BENCH_RUN="ignored")
    e.update(env or {})
    p = subprocess.run(cmd, cwd=cwd, env=e, capture_output=True, text=True,
                       timeout=900)
    lines = [ln for ln in p.stdout.splitlines() if ln.strip()]
    return p, lines


def _line(p, lines):
    assert p.returncode == 0, p.stderr[-3000:]
    doc = json.loads(lines[-1])
    assert list(doc)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(doc)[-1] == "checks"
    return doc


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_prints_the_contract_line(cell):
    p, lines = _run(cell, "--rehearse-cpu")
    doc = _line(p, lines)
    loaded = spec.load_cell(cell)
    assert doc["device"]["platform"] == "cpu"      # never a result
    assert doc["correct"] is True and doc["failed"] == 0
    assert doc["attempted"] > 0
    assert set(doc["metrics"]) == {m["name"] for m in loaded.end_to_end}
    assert all(v["value"] > 0 for v in doc["metrics"].values())
    assert doc["notes"]["rehearsal"] is True
    for c in doc["checks"].values():
        assert c["value"] <= c["limit"]
    # the numbers compared stand beside their limits at the end of stderr
    tail = p.stderr.strip().splitlines()[-7:]
    assert tail[-1] == "correct = true"
    assert all("limit" in ln for ln in tail[:-1])


@pytest.mark.parametrize("cell", CELLS)
def test_traced_rehearsal_reports_layers_and_breakdown(cell):
    p, lines = _run(cell, "--rehearse-cpu", seconds=6, trace=1)
    doc = _line(p, lines)
    loaded = spec.load_cell(cell)
    names = {m["name"] for m in loaded.per_layer}
    assert set(doc["metrics"]) <= names and doc["metrics"]
    # no chip, no peaks: the roofline reader finds nothing to read and
    # the metric is left out, never reported as 0
    assert not any("roofline" in k for k in doc["metrics"])
    assert doc["device"]["busy_s"] > 0 and doc["device"]["window_s"] > 0
    assert set(doc["breakdown"]) == {"device_ops", "idle_gaps"}
    assert len(doc["breakdown"]["device_ops"]) <= 10


@pytest.mark.parametrize("cell", CELLS)
def test_the_float_control_is_refused(cell):
    p, lines = _run(cell, "--rehearse-cpu", "--control", "floats")
    doc = _line(p, lines)
    assert doc["correct"] is False
    assert doc["checks"]["decimal_cells_off"]["value"] > 0
    assert p.stderr.strip().splitlines()[-1] == "correct = false"


@pytest.mark.parametrize("cell", CELLS)
def test_the_float32_reference_in_the_programs_place_is_refused(cell):
    p, lines = _run(cell, "--rehearse-cpu", "--control", "ref-f32")
    doc = _line(p, lines)
    assert doc["correct"] is False
    gap = doc["checks"]["float_gap_max"]
    assert gap["value"] > gap["limit"]
    # nothing else of the control is off: the float limit alone fails it
    assert all(c["value"] <= c["limit"] for name, c in
               doc["checks"].items() if name != "float_gap_max")


@pytest.mark.parametrize("cell", CELLS)
def test_an_altered_answer_is_refused(cell):
    p, lines = _run(cell, "--rehearse-cpu", script=os.path.join(
        spec.BENCH_DIR, "tests", "faulty.py"))
    doc = _line(p, lines)
    assert doc["correct"] is False
    assert doc["checks"]["answers_off"]["value"] \
        + doc["checks"]["decimal_cells_off"]["value"] > 0


def test_without_a_tpu_there_is_no_result():
    p, lines = _run(CELLS[0], env={"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert not any(ln.startswith("{") for ln in lines)
    assert "TPU" in p.stderr


def test_a_directory_with_only_the_benchmark_gives_no_result(tmp_path):
    shutil.copytree(spec.BENCH_DIR, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), tmp_path)
    p, lines = _run(CELLS[0], "--rehearse-cpu", cwd=str(tmp_path),
                    script=str(tmp_path / "benchmark" / "run.py"))
    assert p.returncode != 0 and not lines
