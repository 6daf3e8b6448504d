"""Data set-up: the warehouse, the rendered query streams and the raw
files the plain reference reads, made from ``--seed`` and kept in the
checkout.

What is made, per (scale factor, seed), under
``benchmark/.cache/data/sf<sf>-seed<seed>/``:

    raw/      the generator's '|'-separated files (``ndstpu.datagen``),
              of the configuration's tables only: the reference's input
    wh/       the warehouse the system loads (``ndstpu.io.transcode``)
    streams/  dsqgen-style rendered streams (``ndstpu.queries.streamgen``)

The program's own command-line tools make them, each as a child process
that has ended before anything touches the chip.  A later run with the
same seed finds the directory and makes nothing.  The seed is
``--seed``, or the workload file's ``fixed_seed`` where a cell runs the
same data and texts on every ``--seed`` (``run.py``).
"""

from __future__ import annotations

import os
import shutil
import subprocess
import sys
from typing import Dict, List

from benchmark.harness import spec

CACHE_DIR = os.path.join(spec.BENCH_DIR, ".cache")


class SetupError(Exception):
    pass


def _run(cmd: List[str], env: Dict[str, str], log_path: str) -> None:
    with open(log_path, "a") as log:
        log.write(f"+ {' '.join(cmd)}\n")
        log.flush()
        p = subprocess.run(cmd, cwd=spec.ROOT, env=env, stdout=log,
                           stderr=subprocess.STDOUT)
    if p.returncode != 0:
        with open(log_path) as f:
            tail = f.read()[-3000:]
        raise SetupError(f"{cmd[2] if len(cmd) > 2 else cmd[0]} exited "
                         f"{p.returncode}; end of {log_path}:\n{tail}")


def child_env() -> Dict[str, str]:
    """Environment of every child that runs the program: the repo on
    the path, the program's own run ledger off."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (spec.ROOT, env.get("PYTHONPATH")) if p)
    env["NDSTPU_LEDGER"] = "none"
    return env


def ensure(config: dict, seed: int, sf: str) -> Dict[str, str]:
    """Paths of this seed's data, made if absent.  ``sf`` is the scale
    factor as the generator takes it (the configuration's, or the
    rehearsal's tiny one)."""
    home = os.path.join(CACHE_DIR, "data", f"sf{sf}-seed{seed}")
    paths = {"home": home, "raw": os.path.join(home, "raw"),
             "wh": os.path.join(home, "wh"),
             "streams": os.path.join(home, "streams"),
             "seed": seed, "made": False}
    ready = os.path.join(home, "READY")
    if os.path.exists(ready):
        return paths
    shutil.rmtree(home, ignore_errors=True)   # a run cut mid-way
    os.makedirs(home)
    log = os.path.join(home, "setup.log")
    env = child_env()
    # host-only children: keep them off the chip whatever they import
    env["JAX_PLATFORMS"] = "cpu"
    tables = list(config["tables"])
    py = sys.executable
    _run([py, "-m", "ndstpu.datagen.driver", "local", sf,
          str(config.get("datagen_chunks", 4)), paths["raw"],
          "--seed", str(seed)], env, log)
    _run([py, "-m", "ndstpu.io.transcode", "--input_prefix", paths["raw"],
          "--output_prefix", paths["wh"], "--report_file",
          os.path.join(home, "load.txt"), "--tables", ",".join(tables)],
         env, log)
    # the reference reads the raw files of the configuration's tables;
    # the rest of the generator's output serves nothing
    for name in os.listdir(paths["raw"]):
        if name not in tables:
            shutil.rmtree(os.path.join(paths["raw"], name),
                          ignore_errors=True)
    _run([py, "-m", "ndstpu.queries.streamgen", "--streams",
          str(config.get("streams", 4)), "--rngseed", str(seed),
          "--output_dir", paths["streams"]], env, log)
    with open(ready, "w") as f:
        f.write("ok\n")
    paths["made"] = True
    return paths


def dir_file_count(path: str) -> int:
    n = 0
    for _root, _dirs, files in os.walk(path):
        n += len(files)
    return n
