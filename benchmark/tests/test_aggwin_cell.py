"""The cell ``power-sf1.aggwin3``: its configuration, workload and four
metric files load by name; a CPU rehearsal of it ends ``correct`` with
the four new per-layer metrics in its line; the ``floats`` control is
refused; the float32 reference is refused by ``float_gap_max`` (query2's
ratios and query47's average are float cells); and the plain reference
raises on a half-cent tie it cannot decide.
"""

import json
import os
import subprocess
import sys

import pytest

from benchmark.harness import spec
from benchmark.reference import nds_templates as base
from benchmark.reference import nds_templates_aggwin as ref

SEED = 2**31 + 4747      # more than 32 signed bits hold
CELL = "power-sf1.aggwin3"
NEW_METRICS = {"window_rank_per_op.power": "engine.replay.window_rank",
               "window_running_per_op.power": "engine.replay.window_running",
               "window_whole_per_op.power": "engine.replay.window_whole",
               "agg_wide_per_op.power": "engine.replay.agg_wide"}
POWER_CELLS = ["power-sf1.opclass7", "power-sf1.joinclass6", CELL]


def _run(*extra, seconds=5, trace=0):
    cmd = [sys.executable, os.path.join(spec.BENCH_DIR, "run.py"),
           "--workload", CELL, "--seed", str(SEED), "--seconds",
           str(seconds), "--trace", str(trace), "--rehearse-cpu", *extra]
    p = subprocess.run(cmd, cwd=spec.ROOT, capture_output=True, text=True,
                       env=dict(os.environ, BENCH_RUN="ignored"),
                       timeout=1500)
    assert p.returncode == 0, p.stderr[-3000:]
    lines = [ln for ln in p.stdout.splitlines() if ln.strip()]
    return p, json.loads(lines[-1])


def test_the_new_files_load_by_name():
    cells = spec.check_all()
    cell = cells[CELL]
    assert cell.chips == 1
    assert cell.config_name == "nds-sf1-power-aggwindow-1chip"
    cfg, wl = cell.config, cell.workload
    power = cells["power-sf1.opclass7"]
    assert cfg["reference"] == "benchmark.reference.nds_templates_aggwin"
    assert cfg["architecture"] is None
    assert cfg["reduced"] == ["sf", "query_parts", "tables"]
    assert set(cfg["reduced_why"]) == set(cfg["reduced"])
    for key in ("sf", "entry", "engine", "chips", "properties",
                "guarantees", "published"):
        assert cfg[key] == power.config[key], key
    assert sorted(cfg["tables"]) == ["catalog_sales", "date_dim", "item",
                                     "store", "store_sales", "web_sales"]
    assert "env" not in cfg["rehearsal"] and cfg["rehearsal"]["why"]
    assert {"query67", "query57"} <= set(cfg["left_out"])
    assert wl["parts"] == ["query2", "query47", "query51"]
    assert wl["draws"] == 1 and wl["clients"] == 1
    assert wl["driver"] == "closed_loop"
    # another data directory than the other power cells' (harness/data.py
    # keys it by scale factor and seed alone)
    assert wl["fixed_seed"] == 3000000047
    assert wl["fixed_seed"] not in {c.workload.get("fixed_seed")
                                    for n, c in cells.items() if n != CELL}
    assert {m["name"] for m in cell.end_to_end} == {"setup_s",
                                                    "power_pass_s"}
    by_name = {m["name"]: m for m in cell.per_layer}
    for name, counter in NEW_METRICS.items():
        doc = by_name[name]["file"]
        assert doc["reader"] == "counter_per_op"
        assert doc["arguments"] == {"counter": counter}
        assert by_name[name]["moves"] == "power_pass_s"
        assert by_name[name]["layer"] == "executor programs + segsum kernels"
    # the cell reports every .power metric; the four new ones are read
    # in all three power cells (the counters always tick: a 0 is read)
    for m in spec.load_benchmark()["per_layer"]:
        if m["name"].endswith(".power"):
            assert CELL in m["workloads"], m["name"]
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == POWER_CELLS


def test_the_traced_rehearsal_is_correct_with_the_new_metrics():
    p, doc = _run(trace=1)
    assert doc["device"]["platform"] == "cpu"      # never a result
    assert doc["correct"] is True and doc["failed"] == 0
    assert doc["attempted"] >= 3
    for c in doc["checks"].values():
        assert c["value"] <= c["limit"]
    got = {name: doc["metrics"][name]["value"] for name in NEW_METRICS}
    # per replay of the three parts: query47 ranks, averages and sorts
    # three copies of its CTE, query51 runs four running frames, query2
    # sums its pivot over the wide domain in two copies of its CTE
    assert got == pytest.approx({"window_rank_per_op.power": 1.0,
                                 "window_running_per_op.power": 4 / 3,
                                 "window_whole_per_op.power": 1.0,
                                 "agg_wide_per_op.power": 2 / 3})
    assert doc["metrics"]["join_full_per_op.power"]["value"] > 0
    assert not any("roofline" in k for k in doc["metrics"])   # no chip
    assert p.stderr.strip().splitlines()[-1] == "correct = true"


def test_the_float_control_is_refused():
    p, doc = _run("--control", "floats")
    assert doc["correct"] is False
    assert doc["checks"]["decimal_cells_off"]["value"] > 0
    assert p.stderr.strip().splitlines()[-1] == "correct = false"


def test_the_float32_reference_is_refused():
    """query2's ratios and query47's monthly average are float cells:
    computed in float32 they miss the limit by orders of magnitude."""
    p, doc = _run("--control", "ref-f32")
    assert doc["correct"] is False
    gap = doc["checks"]["float_gap_max"]
    assert gap["value"] > 100 * gap["limit"]
    assert p.stderr.strip().splitlines()[-1] == "correct = false"


def _write_raw(home, table, rows):
    """One raw file of ``table``: each row a {column: text} dict, every
    other column NULL."""
    names = [f.split(":")[0] for f in base._RAW_COLUMNS[table].split()]
    (home / table).mkdir(parents=True)
    with open(home / table / f"{table}_1_1.dat", "w") as f:
        for row in rows:
            f.write("|".join(row.get(n, "") for n in names) + "|\n")


def test_the_reference_raises_on_a_half_cent_tie(tmp_path):
    """query2 over two weeks 53 apart with one Sunday sale each: 1.00
    against 200.00 is 0.005, a tie that a float64 quotient may round
    either way."""
    days = [("1", "5", "1999", "Sunday"), ("2", "58", "2000", "Sunday")]
    _write_raw(tmp_path, "date_dim", [
        {"d_date_sk": sk, "d_week_seq": wk, "d_year": yr, "d_day_name": dn}
        for sk, wk, yr, dn in days])
    _write_raw(tmp_path, "web_sales", [
        {"ws_sold_date_sk": "1", "ws_ext_sales_price": "1.00"},
        {"ws_sold_date_sk": "2", "ws_ext_sales_price": "150.00"}])
    _write_raw(tmp_path, "catalog_sales", [
        {"cs_sold_date_sk": "2", "cs_ext_sales_price": "50.00"}])
    sql = "select ... where d_year = 1999 ..."
    with pytest.raises(ref.TieError, match="half-cent tie"):
        ref.answer(ref.RawTables(str(tmp_path)), "query2", sql)
    # one cent more on the first Sunday and the ratio is decided
    _write_raw(tmp_path / "more", "date_dim", [
        {"d_date_sk": sk, "d_week_seq": wk, "d_year": yr, "d_day_name": dn}
        for sk, wk, yr, dn in days])
    _write_raw(tmp_path / "more", "web_sales", [
        {"ws_sold_date_sk": "1", "ws_ext_sales_price": "1.01"},
        {"ws_sold_date_sk": "2", "ws_ext_sales_price": "200.00"}])
    _write_raw(tmp_path / "more", "catalog_sales", [      # joins nothing
        {"cs_ext_sales_price": "5.00"}])
    kinds, rows = ref.answer(ref.RawTables(str(tmp_path / "more")),
                             "query2", sql)
    assert kinds == "i" + "f" * 7
    assert rows == [(5, 0.01, None, None, None, None, None, None)]
