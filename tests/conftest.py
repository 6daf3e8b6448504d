"""Test configuration: run JAX on a virtual 8-device CPU mesh.

Why the platform is pinned to cpu: the suite has to give the same
answers on a machine with no accelerator (CI, the build sandbox) and on
one with a chip, and it needs 8 devices for the distributed tests, which
only the host platform can fake (--xla_force_host_platform_device_count).
The explicit pin is also what allows ``Session(backend="tpu")`` to run
here at all: without it the accelerator engines refuse a non-TPU
default backend (ndstpu/engine/device.py), so that a missing or busy
chip can never turn into a quiet CPU run.  Pallas kernels run in the
interpreter under the pin.  Nothing in tests/ says anything about the
chip; ``python chip_smoke.py`` does.

The variable is set, not defaulted, before jax is imported (pytest
loads conftest before the test modules): a TPU host exports
``JAX_PLATFORMS=tpu,cpu``, and the CLIs the tests start as child
processes inherit the environment.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
# replay warm-up (compile at discovery) would add minutes of XLA:CPU
# compiles across the suite; tests that exercise it opt in explicitly
os.environ.setdefault("NDSTPU_WARM_REPLAY", "0")
# keep test power runs (and their subprocesses, which inherit env) out
# of the developer's real .bench_cache/ledger.jsonl — tests that need a
# ledger pass --ledger explicitly, which wins over this default
os.environ.setdefault("NDSTPU_LEDGER", "none")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

# a pytest plugin may have imported jax before this file ran, with the
# machine's own JAX_PLATFORMS already read into the config
jax.config.update("jax_platforms", "cpu")

import pathlib
import sys

import pytest

REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent
if str(REPO_ROOT) not in sys.path:
    sys.path.insert(0, str(REPO_ROOT))


@pytest.fixture(scope="session")
def tiny_sf():
    """Scale factor used for in-process fixture datasets."""
    return 0.01


@pytest.fixture()
def without_sidecar(tmp_path):
    """``without_sidecar(warehouse, table)``: a new warehouse holding
    that table as transcode wrote it, less its global-dictionary
    sidecar (a warehouse transcoded before the layer)."""
    import shutil
    from ndstpu.io import gdict

    def strip(warehouse, table):
        shutil.copytree(pathlib.Path(warehouse) / table, tmp_path / table,
                        ignore=shutil.ignore_patterns(gdict.GDICT_FILE))
        assert not gdict.has_sidecar(str(tmp_path / table))
        return tmp_path

    return strip


@pytest.fixture(scope="session")
def sf002_warehouse(tmp_path_factory):
    """ONE tiny plain-parquet warehouse (SF0.002, 2 chunks, the
    generator's built-in seed) shared by every module that only reads
    it.  Six modules used to build identical copies, ~9 s apiece
    (customer_demographics is 1.9M rows at any scale factor) in a suite
    that runs close to its time limit."""
    import os
    import subprocess
    data = tmp_path_factory.mktemp("sf002_raw")
    wh = tmp_path_factory.mktemp("sf002_wh")
    env = dict(os.environ, PYTHONPATH=str(REPO_ROOT))
    subprocess.run([sys.executable, "-m", "ndstpu.datagen.driver", "local",
                    "0.002", "2", str(data)], check=True, env=env,
                   cwd=str(REPO_ROOT))
    subprocess.run([sys.executable, "-m", "ndstpu.io.transcode",
                    "--input_prefix", str(data),
                    "--output_prefix", str(wh),
                    "--report_file", str(wh / "load.txt")],
                   check=True, env=env, cwd=str(REPO_ROOT),
                   stdout=subprocess.DEVNULL)
    return wh
