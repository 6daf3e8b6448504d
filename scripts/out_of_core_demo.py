"""Out-of-core demonstration: stream a fact table through the chunked
executor and record before/after evidence for the streaming pipeline
(sharded chunking + parallel scan/decode + H2D prefetch ring).

Two modes, one artifact (docs/OUT_OF_CORE.json):

* **hardware** — ``.bench_cache/sf10_wh/store_sales`` exists (SF10,
  ~28.8M rows): stream it on the real accelerator at prefetch depth 0
  (the pre-pipeline synchronous behavior) and depth 2, validating
  against the numpy interpreter.  The "SF >> HBM" scaling axis of
  SURVEY §5 (the reference's analog is
  `spark.sql.files.maxPartitionBytes` scan chunking + executor spill).
* **cpu_synthetic** — no SF10 warehouse: render a tiny one, pad the
  scan source with synthetic disk/decode latency, and measure the same
  before/after walls + overlap counters on the virtual CPU backend.
  Hardware walls are marked not measured in the artifact.

Usage:  python scripts/out_of_core_demo.py [chunk_rows]
"""

import json
import os
import pathlib
import subprocess
import sys
import tempfile
import time

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

SQL = ("select ss_store_sk, count(*) as n, sum(ss_ext_sales_price) as s, "
       "avg(ss_quantity) as q, min(ss_sold_date_sk) as dmin, "
       "max(ss_sold_date_sk) as dmax "
       "from store_sales group by ss_store_sk order by ss_store_sk")

SYNTH_READ_SLEEP_S = 0.08   # per-read latency pad in cpu_synthetic mode


class SlowSource:
    """Latency-padded scan source for the synthetic mode."""

    def __init__(self, inner, sleep_s):
        self._inner, self._sleep = inner, sleep_s
        self.table = inner.table
        self.columns = inner.columns
        self.num_rows = inner.num_rows

    def column_meta(self):
        return self._inner.column_meta()

    def read(self, start, count):
        time.sleep(self._sleep)
        return self._inner.read(start, count)


def run_depth(catalog, chunk_rows, depth):
    """First + repeat execution at one prefetch depth, with the repeat
    pass's counter movement (compile excluded from that window)."""
    from ndstpu import obs
    from ndstpu.engine.session import Session

    sess = Session(catalog, backend="tpu", spmd_threshold=500,
                   spmd_chunk_rows=chunk_rows, spmd_prefetch_depth=depth)
    t0 = time.time()
    rows = sess.sql(SQL).to_rows()
    t_first = time.time() - t0
    before = obs.counters_snapshot()
    t0 = time.time()
    rows2 = sess.sql(SQL).to_rows()
    t_again = time.time() - t0
    delta = obs.counter_delta(before)
    assert getattr(sess, "_spmd_used", False), \
        "chunked executor did not engage (fell back to whole-fact path)"
    assert rows == rows2, "re-execution differs"
    wall = delta.get("engine.stream.execute_s", 0.0)
    return rows, {
        "prefetch_depth": depth,
        "first_s": round(t_first, 3),
        "again_s": round(t_again, 3),
        "execute_wall_s": round(wall, 3),
        "io.scan.wait_s": round(delta.get("io.scan.wait_s", 0.0), 3),
        "io.scan.wait_bg_s": round(
            delta.get("io.scan.wait_bg_s", 0.0), 3),
        "io.scan.wait_pct_of_wall": round(
            100.0 * delta.get("io.scan.wait_s", 0.0) / wall, 1)
        if wall else None,
        "engine.h2d.overlap_s": round(
            delta.get("engine.h2d.overlap_s", 0.0), 3),
        "engine.h2d.bytes": int(delta.get("engine.h2d.bytes", 0)),
        "io.prefetch.hit": int(delta.get("io.prefetch.hit", 0)),
        "io.prefetch.miss": int(delta.get("io.prefetch.miss", 0)),
    }


def main():
    from ndstpu.io import loader

    sf10 = REPO / ".bench_cache" / "sf10_wh"
    hardware = (sf10 / "store_sales").exists()
    if hardware:
        mode = "hardware"
        chunk = int(sys.argv[1]) if len(sys.argv) > 1 else 4_000_000
        t0 = time.time()
        catalog = loader.load_catalog(str(sf10), tables=["store_sales"])
        print(f"loaded store_sales in {time.time() - t0:.1f}s",
              flush=True)
    else:
        mode = "cpu_synthetic"
        chunk = int(sys.argv[1]) if len(sys.argv) > 1 else 1000
        root = pathlib.Path(tempfile.mkdtemp(prefix="ndstpu_ooc_demo"))
        env = dict(os.environ, PYTHONPATH=str(REPO))
        # these children import no jax, and this process has not opened
        # a device yet: nobody competes for a chip
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
        catalog = loader.load_catalog(str(root / "wh"))
        fact = catalog.get("store_sales")
        cols = ["ss_store_sk", "ss_ext_sales_price", "ss_quantity",
                "ss_sold_date_sk"]
        loader.attach_stream_source(
            catalog, "store_sales",
            SlowSource(loader.TableChunkSource(fact, "store_sales",
                                               cols),
                       SYNTH_READ_SLEEP_S))

    n_rows = catalog.get("store_sales").num_rows
    rows_before, before = run_depth(catalog, chunk, 0)
    rows_after, after = run_depth(catalog, chunk, 2)
    assert rows_before == rows_after, "depth changed the result"

    from ndstpu.engine.session import Session
    t0 = time.time()
    cpu_rows = Session(catalog, backend="cpu").sql(SQL).to_rows()
    t_cpu = time.time() - t0

    def canon(rows):
        return [tuple(round(v, 4) if isinstance(v, float) else v
                      for v in r) for r in rows]

    ok = canon(rows_after) == canon(cpu_rows)
    import jax
    rec = {
        "pipeline": ("sharded chunking + parallel scan/decode + "
                     "H2D prefetch ring (docs/ARCHITECTURE.md "
                     "'Streaming out-of-core pipeline')"),
        "mode": mode,
        "table": "store_sales",
        "rows": int(n_rows),
        "chunk_rows": chunk,
        "n_chunks": -(-n_rows // chunk),
        "platform": str(jax.devices()),
        "sql": SQL,
        "synthetic_read_sleep_s": (None if hardware
                                   else SYNTH_READ_SLEEP_S),
        "before_sync_stream": before,
        "after_prefetch_ring": after,
        "cpu_numpy_s": round(t_cpu, 2),
        "rows_match_cpu": ok,
        "groups": len(rows_after),
        "hardware_walls": ("this run" if hardware else
                           "not measured on today's code"),
    }
    out = REPO / "docs" / "OUT_OF_CORE.json"
    with open(out, "w") as f:
        json.dump(rec, f, indent=1)
        f.write("\n")
    print(json.dumps(rec, indent=1), flush=True)
    assert ok, "chunked result != numpy oracle"


if __name__ == "__main__":
    main()
