"""String streaming smoke: the global-dictionary layer end to end.

CI gate for ndstpu/io/gdict.py (docs/ARCHITECTURE.md "Global
dictionary layer"): renders a tiny warehouse, forces a 2-device
virtual mesh, and runs a string-keyed join + string group-by with the
string table as the sharded fact, proving off-hardware that:

* **SPMD string join, no translation** — the probe side shards
  directly on frozen global-dictionary codes
  (``engine.dict.identity_joins`` ticks; before the layer, string keys
  went through a per-query build-dictionary searchsorted translation);
* **out-of-core string streaming** — the same query streams the
  string fact chunk-wise through ``ParquetChunkSource`` (>= 3
  launches) bit-identical to the resident run: every chunk decodes
  against the same frozen sidecar dictionary, which is exactly the
  invariant that made string tables streamable at all;
* **no-sidecar parity** — a copy of the table without its
  ``_GLOBAL_DICTS.json`` sidecar (per-call dictionaries) produces
  byte-identical rows, and its chunk source rejects the string table
  (``StreamUnsupported``) as it did before the layer existed.

Usage::

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=2 \
        python scripts/string_stream_smoke.py [warehouse_dir]
"""
from __future__ import annotations

import os
import pathlib
import shutil
import subprocess
import sys
import tempfile

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

N_DEV = 2
CHUNK_ROWS = 1000        # customer_address ~5k rows at SF 0.002
SHARD_THRESHOLD = 500    # makes the string table the sharded fact

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + f" --xla_force_host_platform_device_count={N_DEV}"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# string-keyed join (probe and build share one frozen column dict, so
# the identity fast path engages) + string group key + sorted output:
# any code-space disagreement anywhere surfaces as a row diff
SQL = ("select ca.ca_state, count(*) as cnt from customer_address ca "
       "join (select distinct ca_state as st from customer_address "
       "where ca_address_sk < 500) d on ca.ca_state = d.st "
       "group by ca.ca_state order by ca.ca_state")


def dist_rows(catalog, chunk_rows=None):
    from ndstpu.engine.session import Session
    from ndstpu.parallel import dplan, mesh as pmesh
    plan, _ = Session(catalog, backend="cpu").plan(SQL)
    kw = {"chunk_rows": chunk_rows} if chunk_rows else {}
    exe = dplan.DistributedPlanExecutor(
        catalog, pmesh.make_mesh(N_DEV),
        shard_threshold_rows=SHARD_THRESHOLD, **kw)
    return list(map(str, exe.execute_plan(plan).to_rows())), exe


def without_sidecar(wh: str, table: str) -> str:
    """A warehouse holding ``table`` as transcode wrote it, less the
    global-dictionary sidecar (one transcoded before the layer)."""
    from ndstpu.io import gdict
    bare = tempfile.mkdtemp(prefix="ndstpu_strsmoke_bare")
    shutil.copytree(os.path.join(wh, table), os.path.join(bare, table),
                    ignore=shutil.ignore_patterns(gdict.GDICT_FILE))
    return bare


def main() -> int:
    from ndstpu import obs
    from ndstpu.engine import physical
    from ndstpu.engine.session import Session
    from ndstpu.io import loader

    if len(sys.argv) > 1:
        wh = sys.argv[1]
    else:
        root = pathlib.Path(tempfile.mkdtemp(prefix="ndstpu_strsmoke"))
        env = dict(os.environ, PYTHONPATH=str(REPO))
        for cmd in (
            [sys.executable, "-m", "ndstpu.datagen.driver", "local",
             "0.002", "2", str(root / "raw")],
            [sys.executable, "-m", "ndstpu.io.transcode",
             "--input_prefix", str(root / "raw"),
             "--output_prefix", str(root / "wh"),
             "--report_file", str(root / "load.txt")],
        ):
            print("+", " ".join(cmd), flush=True)
            subprocess.run(cmd, check=True, env=env,
                           stdout=subprocess.DEVNULL)
        wh = str(root / "wh")

    assert len(jax.devices()) == N_DEV, \
        f"expected a {N_DEV}-device mesh, got {len(jax.devices())}"
    catalog = loader.load_catalog(wh)
    plan, _ = Session(catalog, backend="cpu").plan(SQL)
    oracle = list(map(str, physical.execute(plan, catalog).to_rows()))
    if not oracle:
        return print("smoke broken: empty oracle result") or 1

    failures = []

    # resident distributed: identity fast path, no translation
    before = obs.counters_snapshot()
    resident, _ = dist_rows(catalog)
    d = obs.counter_delta(before)
    ident = d.get("engine.dict.identity_joins", 0)
    if resident != oracle:
        failures.append("resident distributed rows != numpy oracle")
    if not ident:
        failures.append(
            "string join did not take the global-code identity path "
            "(engine.dict.identity_joins did not tick)")

    # out-of-core: stream the string fact chunk-wise
    loader.attach_stream_source(
        catalog, "customer_address",
        loader.ParquetChunkSource(wh, "customer_address"))
    streamed, exe = dist_rows(catalog, chunk_rows=CHUNK_ROWS)
    chunked, n_launches = exe._chunk_info[0], exe._chunk_info[1]
    if not chunked or n_launches < 3:
        failures.append(
            f"expected >= 3 chunked launches over the string fact, got "
            f"chunked={chunked} n_launches={n_launches}")
    if streamed != oracle:
        failures.append(
            "chunk-streamed string rows are not bit-identical to the "
            "resident oracle")

    # no sidecar: per-call-dictionary rows byte-identical, streaming
    # rejected
    bare = without_sidecar(wh, "customer_address")
    bare_rows, _ = dist_rows(
        loader.load_catalog(bare, ["customer_address"]))
    if bare_rows != oracle:
        failures.append(
            "rows from the table without a sidecar differ from the "
            "global-dict rows")
    try:
        loader.ParquetChunkSource(bare, "customer_address")
        failures.append(
            "a chunk source over a table without a sidecar should "
            "reject string columns (StreamUnsupported) but did not")
    except loader.StreamUnsupported:
        pass

    if failures:
        print("\nstring stream smoke FAILED:")
        for f in failures:
            print(f"  {f}")
        return 1
    print(f"\nstring stream smoke ok: {len(oracle)} rows bit-identical "
          f"across resident / {n_launches}-launch chunked stream / "
          f"no-sidecar per-call dictionaries on a {N_DEV}-device mesh "
          f"(identity_joins={ident})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
