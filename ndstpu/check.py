"""Preflight checks and small shared CLI utilities.

Parity with the reference's check util (/root/reference/nds/check.py:38-152):
python-version gate, build-artifact discovery (here: the C++ `ndsgen` binary,
auto-built with g++ on first use instead of a Makefile+maven flow), range and
parallel-value validation, directory sizing, and report-folder guards.
"""

from __future__ import annotations

import argparse
import os
import subprocess
import sys
from pathlib import Path

MIN_PYTHON = (3, 10)


def check_version() -> None:
    if sys.version_info < MIN_PYTHON:
        raise RuntimeError(
            f"Python {MIN_PYTHON[0]}.{MIN_PYTHON[1]}+ required, "
            f"found {sys.version_info.major}.{sys.version_info.minor}"
        )


_DATAGEN_DIR = Path(__file__).resolve().parent / "datagen"
_NDSGEN_SRC = _DATAGEN_DIR / "ndsgen.cpp"
_NDSGEN_BIN = _DATAGEN_DIR / "_build" / "ndsgen"
_DISTS_JSON = _DATAGEN_DIR / "dists.json"
_DISTS_HEADER = _DATAGEN_DIR / "_build" / "dists_gen.h"
_BUILD_STAMP = _DATAGEN_DIR / "_build" / "ndsgen.stamp"


def _source_stamp() -> str:
    """Content hash of everything the generator binary is built from.
    ``_build/`` is never committed, and staleness is decided by this
    hash, not by mtimes: a checkout or a copy orders mtimes arbitrarily,
    and a binary built elsewhere must never generate this run's data."""
    import hashlib
    h = hashlib.sha256()
    for src in (_NDSGEN_SRC, _DISTS_JSON):
        h.update(src.read_bytes())
    return h.hexdigest()


def render_dists_header() -> Path:
    """Render dists.json into the C++ header the generator compiles
    against — the one mechanism keeping data generation and query-
    parameter generation on the SAME distribution tables (the dsdgen/
    dsqgen .dst-file sharing analog; streamgen.py reads the json
    directly)."""
    import json
    with open(_DISTS_JSON) as f:
        dists = json.load(f)

    def esc(s: str) -> str:
        return s.replace("\\", "\\\\").replace('"', '\\"')

    lines = [
        "// GENERATED from dists.json by ndstpu.check.render_dists_header",
        "// -- do not edit; edit dists.json.",
        "#pragma once",
        "struct DistEntry { const char* v; int w; };",
        "struct DistTable { const DistEntry* e; int n; int total; };",
    ]
    for name, d in dists.items():
        if name.startswith("_"):
            continue
        vals, weights = d["values"], d["weights"]
        if len(vals) != len(weights):
            raise RuntimeError(f"dists.json {name}: {len(vals)} values "
                               f"vs {len(weights)} weights")
        entries = ", ".join(f'{{"{esc(v)}", {w}}}'
                            for v, w in zip(vals, weights))
        lines.append(f"static const DistEntry kDist_{name}_e[] = "
                     f"{{{entries}}};")
        lines.append(f"static const DistTable kDist_{name} = "
                     f"{{kDist_{name}_e, {len(vals)}, {sum(weights)}}};")
    _DISTS_HEADER.parent.mkdir(parents=True, exist_ok=True)
    _DISTS_HEADER.write_text("\n".join(lines) + "\n")
    return _DISTS_HEADER


def check_build(rebuild: bool = False) -> Path:
    """Locate the native data-generation tool, compiling it if missing.

    Returns the path to the `ndsgen` binary (the analog of the reference's
    check_build returning the tpcds-gen jar + dsdgen paths,
    check.py:47-66)."""
    check_version()
    stamp = _source_stamp()
    if _NDSGEN_BIN.exists() and not rebuild and \
            _BUILD_STAMP.exists() and \
            _BUILD_STAMP.read_text().strip() == stamp:
        return _NDSGEN_BIN
    render_dists_header()
    # build to a private name, then publish binary and stamp by rename:
    # concurrent drivers (pod slices on a shared filesystem, a test run
    # beside a bench) may race into the first build
    tmp = _NDSGEN_BIN.with_name(f"ndsgen.tmp.{os.getpid()}")
    cmd = ["g++", "-O2", f"-I{_DISTS_HEADER.parent}",
           "-o", str(tmp), str(_NDSGEN_SRC)]
    print("building native generator:", " ".join(cmd))
    subprocess.run(cmd, check=True)
    os.replace(tmp, _NDSGEN_BIN)
    tmp_stamp = _BUILD_STAMP.with_name(f"ndsgen.stamp.tmp.{os.getpid()}")
    tmp_stamp.write_text(stamp + "\n")
    os.replace(tmp_stamp, _BUILD_STAMP)
    return _NDSGEN_BIN


def get_abs_path(input_path: str) -> str:
    return str(Path(input_path).expanduser().resolve())


def valid_range(range_str: str, parallel) -> tuple[int, int]:
    """Validate --range 'start,end' against the parallel value
    (reference: check.py:88-113)."""
    try:
        start, end = (int(x) for x in range_str.split(","))
    except Exception as exc:
        raise argparse.ArgumentTypeError(
            f'invalid range: "{range_str}", expected "start,end"'
        ) from exc
    if not (1 <= start <= end <= int(parallel)):
        raise argparse.ArgumentTypeError(
            f"range {start},{end} must satisfy 1 <= start <= end <= parallel"
            f" ({parallel})"
        )
    return start, end


def parallel_value_type(val: str) -> str:
    """--parallel must be an int >= 2 (reference: check.py:116-123)."""
    try:
        ival = int(val)
    except ValueError as exc:
        raise argparse.ArgumentTypeError(f"{val!r} is not an integer") from exc
    if ival < 2:
        raise argparse.ArgumentTypeError("PARALLEL must be >= 2")
    return val


def get_dir_size(path: str) -> int:
    total = 0
    for dirpath, _dirnames, filenames in os.walk(path):
        for f in filenames:
            fp = os.path.join(dirpath, f)
            if not os.path.islink(fp):
                total += os.path.getsize(fp)
    return total


def check_json_summary_folder(folder: str | None) -> None:
    """Require an empty/new folder for per-query JSON summaries
    (reference: check.py:136-145)."""
    if folder is None:
        return
    if os.path.exists(folder):
        if not os.path.isdir(folder):
            raise RuntimeError(f"{folder} is not a directory")
        if os.listdir(folder):
            raise RuntimeError(
                f"json summary folder {folder} is not empty; "
                "choose an empty or new folder"
            )
    else:
        os.makedirs(folder)


def check_query_subset_exists(query_dict: dict, subset: list[str]) -> bool:
    """All requested sub-queries must exist in the stream
    (reference: check.py:147-152)."""
    for q in subset:
        if q not in query_dict:
            raise RuntimeError(f"query {q} not found in the query stream")
    return True
