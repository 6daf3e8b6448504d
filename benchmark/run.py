#!/usr/bin/env python3
"""The benchmark's one command: run one cell once.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its per-layer metrics are found by the
names ``BENCHMARK.json`` gives (``benchmark/harness/spec.py``).  The
last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics
with ``--trace 0``, its per-layer metrics with ``--trace 1``),
``device``, with ``--trace 1`` ``breakdown``, then ``notes`` and, last,
``checks``: each number compared beside its limit.

Without a TPU the run exits non-zero and prints no result.
``--rehearse-cpu`` pins the platform to the CPU at the configuration's
tiny rehearsal scale and walks the same code end to end; its line says
``"platform": "cpu"`` and is never a result.  ``--control`` runs a
control that ``correct`` has to refuse: ``floats`` switches on the
program's own lower-precision path (decimals as floats); ``ref-f32``
puts the plain reference, computed in float32, in the program's place
once the window has closed.  (``benchmark/sweep.py`` finds a served
cell's knee; ``benchmark/tests/faulty.py`` breaks the timed path.)
"""

from __future__ import annotations

import time

T_START = time.time()   # set-up is counted from the process's start

import argparse   # noqa: E402
import importlib  # noqa: E402
import json       # noqa: E402
import os         # noqa: E402
import sys        # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import data, judge, readers, spec  # noqa: E402


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse-cpu", action="store_true")
    p.add_argument("--control", choices=("floats", "ref-f32"),
                   default=None)
    return p


def set_up(args):
    """The cell, its driver's module, this seed's data and the scale
    factor as the generator takes it."""
    if not os.path.isdir(os.path.join(ROOT, "ndstpu")):
        raise SystemExit(
            "benchmark/run.py: the system under test (ndstpu/) is not in "
            "this directory; the benchmark measures it and nothing else")
    cell = spec.load_cell(args.workload)
    cfg = cell.config
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(data.CACHE_DIR, "xla"))
    os.environ["NDSTPU_LEDGER"] = "none"
    sf = str(cfg["sf"])
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        for k, v in (cfg.get("rehearsal", {}).get("env") or {}).items():
            os.environ[k] = str(v)
        sf = str(cfg["rehearsal"]["sf"])
        os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
            data.CACHE_DIR, "xla-rehearsal")
    # the driver is found by name, like every other file of a cell:
    # benchmark/harness/<driver>.py with a run(cell, args, ...) of its own
    driver = str(cell.workload.get("driver"))
    if not driver.isidentifier() or not os.path.isfile(os.path.join(
            spec.BENCH_DIR, "harness", f"{driver}.py")):
        raise spec.SpecError(f"workload {cell.name!r}: no driver "
                             f"benchmark/harness/{driver}.py")
    drv = importlib.import_module(f"benchmark.harness.{driver}")
    if getattr(drv, "HOLDS_CHIP", False):
        # this process will hold the chip: look for it before any data
        # is made (a served cell's daemon looks for itself)
        import jax
        from benchmark.harness import closed_loop
        closed_loop.device_block(jax, cell.chips, args.rehearse_cpu)
    # a cell whose workload file gives fixed_seed runs that seed's data
    # and texts on every --seed (which then only moves where it starts)
    paths = data.ensure(cfg, int(cell.workload.get("fixed_seed", args.seed)),
                        sf)
    return cell, drv, paths, sf


def run_cell(args) -> dict:
    """One run of one cell; returns the result line as a dict."""
    cell, drv, paths, sf = set_up(args)
    out = drv.run(cell, args, T_START, paths, sf)
    if args.trace:
        metrics = {}
        for m in cell.per_layer:
            v = readers.read_metric(m["file"], out["record"])
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": out["end_to_end"][m["name"]],
                               "unit": m["unit"]}
                   for m in cell.end_to_end}
    line = {"correct": bool(out["correct"]),
            "attempted": int(out["attempted"]),
            "failed": int(out["failed"]),
            "metrics": metrics, "device": out["device"]}
    if args.trace and out.get("breakdown"):
        line["breakdown"] = out["breakdown"]
    line["notes"] = dict(out.get("notes") or {}, cell=cell.name,
                         seed=args.seed, seconds=args.seconds,
                         end_to_end=out["end_to_end"],
                         rehearsal=bool(args.rehearse_cpu),
                         control=args.control)
    line["checks"] = out["checks"]
    return line


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    try:
        line = run_cell(args)
    except spec.SpecError as e:
        print(f"benchmark/run.py: {e}", file=sys.stderr)
        return 2
    except Exception as e:  # noqa: BLE001 - no result line on failure
        import traceback
        traceback.print_exc()
        print(f"benchmark/run.py: no result: {type(e).__name__}: {e}",
              file=sys.stderr)
        return 3
    judge.print_checks(line["correct"], line["checks"])
    sys.stdout.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
