"""Find a cell's files by the names ``BENCHMARK.json`` gives.

The harness never lists a directory to discover cells, configurations or
metrics: a name in ``BENCHMARK.json`` with no file is an error, and a
file nobody names is ignored.  A later PR adds a configuration, a cell
or a per-layer metric by adding a file and an entry.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Dict, List

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH_DIR = os.path.join(ROOT, "benchmark")


class SpecError(Exception):
    """``BENCHMARK.json`` or a file it names is missing or malformed."""


def _read_json(path: str, what: str) -> dict:
    if not os.path.isfile(path):
        raise SpecError(f"{what}: no file {os.path.relpath(path, ROOT)}")
    with open(path) as f:
        try:
            doc = json.load(f)
        except ValueError as e:
            raise SpecError(f"{what}: {path} is not JSON: {e}") from e
    if not isinstance(doc, dict):
        raise SpecError(f"{what}: {path} is not a JSON object")
    return doc


@dataclasses.dataclass
class Cell:
    """One entry of ``workloads`` with every file it names, loaded."""

    name: str
    chips: int
    config_name: str
    config: dict          # benchmark/configs/<config>.json
    workload: dict        # benchmark/workloads/<cell>.json
    end_to_end: List[dict]   # BENCHMARK.json entries this cell reports
    per_layer: List[dict]    # each with its metrics/<name>.json as "file"
    run_seconds: int


def load_benchmark(root: str = ROOT) -> dict:
    return _read_json(os.path.join(root, "BENCHMARK.json"),
                      "BENCHMARK.json")


def _reports(metric: dict, cell: str) -> bool:
    cells = metric.get("workloads")
    return cells is None or cell in cells


def load_cell(name: str, root: str = ROOT) -> Cell:
    bench = load_benchmark(root)
    entries = {w["name"]: w for w in bench.get("workloads", [])}
    if name not in entries:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json "
                        f"(it has {sorted(entries)})")
    entry = entries[name]
    configs = {c["name"]: c for c in bench.get("configs", [])}
    if entry["config"] not in configs:
        raise SpecError(f"workload {name!r} names configuration "
                        f"{entry['config']!r}, which BENCHMARK.json lacks")
    cfg_entry = configs[entry["config"]]
    config = _read_json(os.path.join(root, cfg_entry["file"]),
                        f"configuration {cfg_entry['name']!r}")
    bdir = os.path.join(root, "benchmark")
    workload = _read_json(
        os.path.join(bdir, "workloads", f"{name}.json"),
        f"workload {name!r}")
    if workload.get("config") != entry["config"]:
        raise SpecError(
            f"workload file of {name!r} says configuration "
            f"{workload.get('config')!r}, BENCHMARK.json says "
            f"{entry['config']!r}")
    e2e = [m for m in bench["end_to_end"] if _reports(m, name)]
    layer = []
    for m in bench["per_layer"]:
        if not _reports(m, name):
            continue
        doc = _read_json(
            os.path.join(bdir, "metrics", f"{m['name']}.json"),
            f"per-layer metric {m['name']!r}")
        layer.append(dict(m, file=doc))
    return Cell(name=name, chips=int(entry["chips"]),
                config_name=entry["config"], config=config,
                workload=workload, end_to_end=e2e, per_layer=layer,
                run_seconds=int(bench["run_seconds"]))


def check_all(root: str = ROOT) -> Dict[str, Cell]:
    """Load every cell (the tests' and the rehearsal's whole-file
    check): every name resolves, every metric's ``moves`` is an
    end-to-end metric its cells report."""
    bench = load_benchmark(root)
    cells = {w["name"]: load_cell(w["name"], root)
             for w in bench["workloads"]}
    e2e_names = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        if m["moves"] not in e2e_names:
            raise SpecError(f"per-layer metric {m['name']!r} moves "
                            f"{m['moves']!r}, which is no end-to-end "
                            f"metric")
        for c in m.get("workloads", cells):
            if c not in cells:
                raise SpecError(f"per-layer metric {m['name']!r} lists "
                                f"unknown workload {c!r}")
            if m["moves"] not in {e["name"] for e in cells[c].end_to_end}:
                raise SpecError(
                    f"per-layer metric {m['name']!r} moves "
                    f"{m['moves']!r}, which cell {c!r} does not report")
    return cells
