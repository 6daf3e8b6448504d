"""The cell ``power-sf1.joinclass6``: its configuration, workload and
six metric files load by name; a CPU rehearsal of it (at the
configuration's rehearsal scale, where query94 and query95 answer from
rows) ends ``correct`` with the new per-layer metrics in the line; the
``floats`` control is refused there; the six answers hold no float
cell, so the float32 reference moves nothing (pinned, not asserted as a
refusal); and the plain reference refuses raw files whose web orders
have 3 lines.
"""

import json
import os
import subprocess
import sys

import pytest

from benchmark.harness import spec
from benchmark.reference import nds_templates_joins as ref

SEED = 2**31 + 4243      # more than 32 signed bits hold
CELL = "power-sf1.joinclass6"
NEW_METRICS = {"join_semi_per_op.power": "engine.replay.join_semi",
               "join_mark_per_op.power": "engine.replay.join_mark",
               "join_residual_per_op.power": "engine.replay.join_residual",
               "join_full_per_op.power": "engine.replay.join_full",
               "setop_per_op.power": "engine.replay.setop",
               "agg_sort_per_op.power": "engine.replay.agg_sort"}


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
    assert cell.config_name == "nds-sf1-power-joinclasses-1chip"
    cfg, wl = cell.config, cell.workload
    power = cells["power-sf1.opclass7"]
    assert cfg["reference"] == "benchmark.reference.nds_templates_joins"
    assert cfg["architecture"] is None
    assert cfg["reduced"] == ["sf", "query_parts", "tables"]
    assert set(cfg["reduced_why"]) == set(cfg["reduced"])
    # a sibling of the power configuration
    for key in ("sf", "entry", "engine", "chips", "properties",
                "guarantees", "published"):
        assert cfg[key] == power.config[key], key
    assert "inventory" not in cfg["tables"] and "item" not in cfg["tables"]
    assert len(cfg["tables"]) == 9
    assert "env" not in cfg["rehearsal"] and cfg["rehearsal"]["why"]
    assert wl["parts"] == ["query69", "query10", "query94", "query97",
                           "query38", "query95"]
    assert wl["draws"] == 1 and wl["clients"] == 1
    # another data directory than the power cell's (harness/data.py
    # keys it by scale factor and seed alone)
    assert wl["fixed_seed"] != power.workload["fixed_seed"]
    assert {m["name"] for m in cell.end_to_end} == {"setup_s",
                                                    "power_pass_s"}
    by_name = {m["name"]: m for m in cell.per_layer}
    for name, counter in NEW_METRICS.items():
        doc = by_name[name]["file"]
        assert doc["reader"] == "counter_per_op"
        assert doc["arguments"] == {"counter": counter}
        assert by_name[name]["moves"] == "power_pass_s"
    # every size this repo set itself is said
    assert "8 to 16" in cfg["assumed"]["web_order_lines"]
    assert {"order", "replays", "order_attributes", "order_items",
            "web_only"} <= set(cfg["assumed"])
    # the cell reports every .power metric, and the old power cell the
    # six new ones (the counters are always incremented: a 0 is read)
    bench = spec.load_benchmark()
    for m in bench["per_layer"]:
        if m["name"].endswith(".power"):
            assert CELL in m["workloads"], m["name"]
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == ["power-sf1.opclass7", CELL]


def test_the_traced_rehearsal_is_correct_with_the_new_metrics():
    p, doc = _run(trace=1)
    assert doc["device"]["platform"] == "cpu"      # never a result
    assert doc["correct"] is True and doc["failed"] == 0
    assert doc["attempted"] >= 6
    for c in doc["checks"].values():
        assert c["value"] <= c["limit"]
    for name in NEW_METRICS:
        assert doc["metrics"][name]["value"] > 0, name
    assert doc["metrics"]["join_expand_per_op.power"]["value"] > 2
    assert doc["metrics"]["join_lookup_per_op.power"]["value"] > 0
    assert not any("roofline" in k for k in doc["metrics"])   # no chip
    assert p.stderr.strip().splitlines()[-1] == "correct = true"


def test_the_float_control_is_refused():
    p, doc = _run("--control", "floats")
    assert doc["correct"] is False
    assert doc["checks"]["decimal_cells_off"]["value"] > 0
    assert p.stderr.strip().splitlines()[-1] == "correct = false"


def test_the_six_answers_hold_no_float_cell():
    """The float32 reference in the program's place changes nothing
    here: counts, strings and exact decimals are all these templates
    return, so this configuration's lower precision is the ``floats``
    control alone (decimals as floats, refused above)."""
    _p, doc = _run("--control", "ref-f32")
    assert doc["correct"] is True
    assert doc["checks"]["float_gap_max"]["value"] == 0.0


def _raw_with_orders_of(tmp_path, lines: int) -> str:
    """A raw directory whose web_sales has 60 rows in orders of
    ``lines`` rows (every other column NULL)."""
    names = [f.split(":")[0]
             for f in ref._base._RAW_COLUMNS["web_sales"].split()]
    n_cols, at = len(names), names.index("ws_order_number")
    home = tmp_path / f"raw{lines}" / "web_sales"
    home.mkdir(parents=True)
    with open(home / "web_sales_1_1.dat", "w") as f:
        for row in range(60):
            cells = [""] * n_cols
            cells[at] = str(row // lines + 1)
            f.write("|".join(cells) + "|\n")
    return str(home.parent)


def test_the_reference_refuses_raw_files_with_3_line_orders(tmp_path):
    """A checkout whose generator predates the source's order structure
    cannot set this cell's baseline: its run ends with no result."""
    with pytest.raises(ValueError, match="assumed.web_order_lines"):
        ref.RawTables(_raw_with_orders_of(tmp_path, 3))
    ref.RawTables(_raw_with_orders_of(tmp_path, 12))
