"""Which device a process runs on, and where its compiled code is kept.

Every entry point that builds an accelerator session (power run,
in-process scheduler, serve daemon, chip_smoke.py) goes
through this module for three decisions:

* **May an accelerator engine run here?**  :func:`require_accelerator`
  refuses a default backend other than ``tpu`` unless the platform is
  explicitly pinned to ``cpu`` (``JAX_PLATFORMS=cpu`` or
  ``jax_platforms="cpu"`` — the tests and CPU rehearsals).  With no
  platform list JAX falls back to the CPU quietly when the chip is
  missing or held by another process, and the ``tpu`` engine would carry
  on there and file host times as device times.
* **What ran it?**  :func:`describe` names ``platform``, ``device_kind``
  and the device count for every report, sidecar and JSON summary.
* **Where is the persistent XLA cache?**  :func:`compile_cache_dir`:
  ``JAX_COMPILATION_CACHE_DIR`` when set — JAX reads it itself and the
  program sets no directory in code — else one fixed path inside the
  checkout.  A directory that moves (``/tmp``, a pid, a timestamp) is
  never found again by the next process.

Importing this module does not import jax: the throughput runner and
the fleet supervisor use it while staying off the chip.
"""

from __future__ import annotations

import json
import os
import sys
from pathlib import Path
from typing import Optional

ACCEL_ENGINES = ("tpu", "tpu-spmd")

REPO_ROOT = Path(__file__).resolve().parents[2]
FIXED_CACHE_DIR = REPO_ROOT / ".bench_cache" / "xla_cache_tpu"
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


class NoAcceleratorError(RuntimeError):
    """An accelerator engine was asked to run without a TPU backend."""


def is_accel(engine: Optional[str]) -> bool:
    return engine in ACCEL_ENGINES


def pinned_platforms() -> str:
    """The explicit platform list, lower-cased ('' = none given).

    ``jax.config.jax_platforms`` once jax is imported (its default is
    the ``JAX_PLATFORMS`` variable, and ``config.update`` overrides it);
    the variable alone in a process that has stayed off jax."""
    if "jax" in sys.modules:
        import jax
        return (jax.config.jax_platforms or "").strip().lower()
    return os.environ.get("JAX_PLATFORMS", "").strip().lower()


def cpu_pinned() -> bool:
    return pinned_platforms() == "cpu"


def wants_chip(engine: Optional[str]) -> bool:
    """True when a process running ``engine`` here opens a TPU: an
    accelerator engine on a platform that is not pinned to cpu."""
    return is_accel(engine) and not cpu_pinned()


def require_accelerator(engine: str) -> None:
    """Refuse to run an accelerator engine on a non-TPU default backend
    unless the platform is pinned to ``cpu``.  No-op for the numpy
    engine."""
    if not wants_chip(engine):
        return
    import jax
    try:
        backend = jax.default_backend()
    except RuntimeError as e:
        # an explicit platform list that names tpu fails here when the
        # chip is absent or held by another process
        raise NoAcceleratorError(
            f"engine {engine!r} needs a TPU and JAX could not open one "
            f"(JAX_PLATFORMS={pinned_platforms()!r}): {e}") from e
    if backend != "tpu":
        raise NoAcceleratorError(
            f"engine {engine!r} needs a TPU but JAX's default backend is "
            f"{backend!r} (JAX_PLATFORMS={pinned_platforms()!r}): no chip "
            f"is attached, or another process holds it.  One process "
            f"owns a chip at a time; for a CPU rehearsal pin the "
            f"platform explicitly with JAX_PLATFORMS=cpu")


def describe(engine: Optional[str] = None) -> dict:
    """``{"platform", "device_kind", "count"}`` of what ``engine`` runs
    on, as JAX reports it; the numpy engine uses no JAX device."""
    if not is_accel(engine):
        return {"platform": "cpu", "device_kind": "numpy interpreter",
                "count": 0}
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform,
            "device_kind": devs[0].device_kind, "count": len(devs)}


def compile_cache_dir() -> str:
    """The resolved persistent-cache directory (also what the
    ``xla_cache_files`` gauge counts)."""
    return os.environ.get(CACHE_ENV) or str(FIXED_CACHE_DIR)


def configure_compile_cache() -> str:
    """Point JAX's persistent compile cache at the resolved directory
    (no directory is set when ``JAX_COMPILATION_CACHE_DIR`` is), and
    keep Python frames out of every program this process lowers
    (``jax_traceback_in_locations_limit`` 0), so that a program's cache
    key is its own.

    Why the frames: a Pallas kernel is serialized into its program with
    the frames of whoever traced that kernel shape FIRST in the process,
    so the key of every kernel-bearing program depended on which
    statement ran first: a warm power set-up recompiled two to six of
    seven programs (160-385 s of XLA) by the text the pass started at
    (builder's chip runs, PR 32).  What it costs: an op of a lowered
    program, an HLO dump or a profiler trace no longer names the Python
    file and line that made it, and an XLA or Mosaic error no longer
    points at one; operator scopes (``jax.named_scope``), kernel names
    and every Python traceback of an exception stay
    (docs/OBSERVABILITY.md)."""
    import jax
    if not os.environ.get(CACHE_ENV):
        jax.config.update("jax_compilation_cache_dir",
                          str(FIXED_CACHE_DIR))
    jax.config.update("jax_traceback_in_locations_limit", 0)
    return compile_cache_dir()


def visible_chips() -> int:
    """TPU chips this host exposes, counted from their device files —
    nothing is opened, so a supervisor can count while its replicas
    hold the chips, and stays off jax doing it."""
    import glob
    return len(glob.glob("/dev/accel[0-9]*")) or \
        len(glob.glob("/dev/vfio/[0-9]*"))


def chip_binding_env(index: int) -> dict:
    """Environment that shows a child process exactly ONE chip of a
    multi-chip host (libtpu's own variables), so N one-chip server
    replicas can each own a different chip."""
    port = 8476 + index
    return {
        "TPU_VISIBLE_CHIPS": str(index),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_ADDRESSES": f"localhost:{port}",
        "TPU_PROCESS_PORT": str(port),
        "CLOUD_TPU_TASK_ID": "0",
    }


def probe() -> dict:
    """What this process's JAX sees — the doc ``python -m
    ndstpu.engine.device`` prints (chip_smoke.py stage 0)."""
    import jax
    import jaxlib
    try:
        import libtpu
        libtpu_version = getattr(libtpu, "__version__", "unknown")
    except ImportError:
        libtpu_version = None
    devs = jax.devices()
    try:
        cpu_registered = bool(jax.devices("cpu"))
    except RuntimeError:
        cpu_registered = False
    stats = devs[0].memory_stats() or {}
    return {
        "default_backend": jax.default_backend(),
        "platform": devs[0].platform,
        "device_kind": devs[0].device_kind,
        "count": len(devs),
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "libtpu": libtpu_version,
        "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS"),
        # host_compute() (engine/jaxexec.py) runs eager discovery on
        # this backend when it is registered beside the accelerator
        "cpu_backend_registered": cpu_registered,
        "compile_cache_dir": compile_cache_dir(),
        "bytes_limit": stats.get("bytes_limit"),
    }


if __name__ == "__main__":
    print(json.dumps(probe()))
