"""Streaming out-of-core pipeline tests: sharded chunking composed with
the H2D prefetch ring, the parallel scan/decode pool, the spill-aware
memory planner, and the NDS311 fall-through diagnostic.

Correctness bar: distributed-chunked results are bit-identical — rows
AND row order — to the single-chip chunked path and the numpy oracle,
at every prefetch depth, under injected io.read / io.prefetch faults,
and across a mid-stream SIGKILL + --resume."""

import json
import os
import subprocess
import time

import numpy as np
import pytest

from ndstpu import faults, obs
from ndstpu.engine import memplan
from ndstpu.io import loader
from ndstpu.parallel import mesh as pmesh


@pytest.fixture(scope="module")
def env():
    return dict(os.environ, PYTHONPATH=os.getcwd())


@pytest.fixture(scope="module")
def stream_root(tmp_path_factory, env, sf002_warehouse):
    """Tiny plain-parquet warehouse (ParquetChunkSource cannot stream
    ndslake ACID layouts) + one query stream for the power CLI."""
    root = tmp_path_factory.mktemp("stream")
    os.symlink(sf002_warehouse, root / "wh", target_is_directory=True)
    subprocess.run(["python", "-m", "ndstpu.queries.streamgen",
                    "--output_dir", str(root / "streams"),
                    "--rngseed", "07291122510", "--streams", "1"],
                   check=True, env=env, stdout=subprocess.DEVNULL)
    return root


@pytest.fixture(scope="module")
def catalog(stream_root):
    return loader.load_catalog(str(stream_root / "wh"))


# exact-order queries: unique ORDER BY keys for the aggregate, original
# fact row order (__rowid__ restore) for the row-mode spine
Q_AGG = ("select d_year, i_brand_id, sum(ss_ext_sales_price) as s, "
         "count(*) as n from store_sales, date_dim, item "
         "where ss_sold_date_sk = d_date_sk and ss_item_sk = i_item_sk "
         "group by d_year, i_brand_id order by d_year, i_brand_id")
Q_ROWS = ("select ss_item_sk, ss_quantity from store_sales "
          "where ss_quantity > 90")


def _chunked_rows(catalog, n_dev, sql, depth, chunk_rows=1000):
    """Plan once on the cpu session, execute on an n_dev mesh with the
    chunked executor; return (exact row list, executor)."""
    from ndstpu.engine.session import Session
    from ndstpu.parallel import dplan

    plan, _ = Session(catalog, backend="cpu").plan(sql)
    exe = dplan.DistributedPlanExecutor(
        catalog, pmesh.make_mesh(n_dev), shard_threshold_rows=500,
        broadcast_limit_rows=50, chunk_rows=chunk_rows,
        prefetch_depth=depth)
    got = exe.execute_plan(plan)
    return list(map(str, got.to_rows())), exe


def _oracle_rows(catalog, sql):
    from ndstpu.engine import physical
    from ndstpu.engine.session import Session
    plan, _ = Session(catalog, backend="cpu").plan(sql)
    return list(map(str, physical.execute(plan, catalog).to_rows()))


# -- memory planner ---------------------------------------------------------


def test_memplan_resident_when_fact_fits():
    p = memplan.plan_stream(1000, 100, 2, budget_bytes=2 << 30)
    assert p.chunk_rows is None and p.prefetch_depth == 0
    assert "resident" in p.describe()


def test_memplan_chunked_pow2_and_depth():
    p = memplan.plan_stream(1_000_000, 100, 2, budget_bytes=8 << 20)
    assert p.chunk_rows == 8192 and p.prefetch_depth == 2
    assert p.chunk_rows & (p.chunk_rows - 1) == 0
    assert "chunk_rows=8192 depth=2" in p.describe()


def test_memplan_shallower_ring_buys_bigger_chunks():
    # budget too tight for MIN_CHUNK_ROWS at depth 2: the planner trades
    # ring depth for chunk size all the way down to synchronous
    p = memplan.plan_stream(1_000_000, 100, 2, budget_bytes=200_000)
    assert p.prefetch_depth == 0
    assert p.chunk_rows == 256          # pow2 floor, >= n_dev


def test_memplan_budget_sources(monkeypatch):
    monkeypatch.setenv("NDSTPU_HBM_BYTES", "12345")
    assert memplan.device_budget_bytes() == (12345, "env")
    monkeypatch.delenv("NDSTPU_HBM_BYTES")
    budget, source = memplan.device_budget_bytes()
    assert budget > 0 and source in ("memory_stats", "default")


def test_memplan_row_widths():
    assert memplan.row_bytes([8, 8]) == 19    # data + validity + alive
    from ndstpu import schema as nds_schema
    schema = nds_schema.get_schemas(True)["store_sales"]
    sub = memplan.schema_row_bytes(schema, ["ss_item_sk", "ss_quantity"])
    assert 0 < sub < memplan.schema_row_bytes(schema)


# -- scan/decode pool -------------------------------------------------------


def _payload(s, n=4):
    return {"x": (np.full(n, s, dtype=np.int64), np.ones(n, bool))}


def test_scan_pool_reads_ahead():
    reads = []

    def read_fn(s):
        reads.append(s)
        return _payload(s)

    before = obs.counters_snapshot()
    pool = loader.ChunkScanPool(read_fn, range(5), workers=2, depth=2)
    try:
        for s in range(5):
            got = pool.get(s)
            np.testing.assert_array_equal(got["x"][0],
                                          np.full(4, s, dtype=np.int64))
            time.sleep(0.05)     # let the ahead workers land
    finally:
        pool.close()
    assert sorted(reads) == [0, 1, 2, 3, 4]   # KeyedLatch: no re-decode
    d = obs.counter_delta(before)
    assert d.get("io.scan.ahead.hit", 0) >= 3
    assert "io.scan.wait_s" in d


def test_scan_pool_degrades_to_synchronous_on_failure():
    calls = {0: 0}

    def read_fn(s):
        if s == 0:
            calls[0] += 1
            if calls[0] == 1:
                raise RuntimeError("disk went away")
        return _payload(s)

    before = obs.counters_snapshot()
    pool = loader.ChunkScanPool(read_fn, range(3), workers=2, depth=2)
    try:
        for s in range(3):
            np.testing.assert_array_equal(pool.get(s)["x"][0],
                                          np.full(4, s, dtype=np.int64))
    finally:
        pool.close()
    d = obs.counter_delta(before)
    assert d.get("io.scan.degraded") == 1
    assert calls[0] == 2       # failed worker read + sync retry


# -- parquet chunk source ---------------------------------------------------


def test_parquet_chunk_source_windows_match_resident(stream_root, catalog):
    cols = ["ss_item_sk", "ss_quantity"]
    src = loader.ParquetChunkSource(str(stream_root / "wh"),
                                    "store_sales", columns=cols)
    resident = catalog.get("store_sales")
    assert src.num_rows == resident.num_rows
    n = src.num_rows
    for start, count in [(0, 100), (n - 57, 57), (n // 3, 1000),
                         (0, n), (n, 10)]:
        got = src.read(start, count)
        for c in cols:
            data, valid = got[c]
            ref = resident.column(c)
            np.testing.assert_array_equal(
                data, ref.data[start:start + count])
            np.testing.assert_array_equal(
                valid, ref.validity()[start:start + count])
    meta = src.column_meta()
    assert set(meta) == set(cols)


def test_parquet_chunk_source_rejects_string_columns(stream_root,
                                                     without_sidecar):
    """With the global-dict sidecar present string columns stream; from
    a table without it the source refuses them as before."""
    src = loader.ParquetChunkSource(str(stream_root / "wh"), "item",
                                    columns=["i_item_sk", "i_category"])
    assert src.column_meta()["i_category"][2] is not None
    bare = without_sidecar(stream_root / "wh", "item")
    with pytest.raises(loader.StreamUnsupported, match="string column"):
        loader.ParquetChunkSource(str(bare), "item",
                                  columns=["i_item_sk", "i_category"])


def test_attach_stream_source_validates(stream_root, catalog):
    src = loader.ParquetChunkSource(str(stream_root / "wh"),
                                    "store_sales",
                                    columns=["ss_item_sk", "ss_quantity"])
    with pytest.raises(KeyError):
        loader.attach_stream_source(catalog, "nope", src)
    with pytest.raises(ValueError, match="rows"):
        loader.attach_stream_source(catalog, "store_returns", src)


def test_chunked_execute_streams_from_parquet(stream_root, catalog):
    """With a registered ParquetChunkSource the chunked executor pulls
    rows from disk (io.scan.bytes moves) and still matches the oracle
    bit-identically, row order included."""
    src = loader.ParquetChunkSource(str(stream_root / "wh"),
                                    "store_sales",
                                    columns=["ss_item_sk", "ss_quantity"])
    loader.attach_stream_source(catalog, "store_sales", src)
    before = obs.counters_snapshot()
    try:
        got, exe = _chunked_rows(catalog, 2, Q_ROWS, depth=2)
        assert exe._chunk_info[0]
        assert got == _oracle_rows(catalog, Q_ROWS)
    finally:
        catalog.streams.pop("store_sales", None)
    d = obs.counter_delta(before)
    assert d.get("io.scan.bytes", 0) > 0


# -- prefetch ring ----------------------------------------------------------


def test_prefetch_depths_bit_identical(catalog):
    """Depth 0/1/2 on a 2-device mesh and depth 2 on a 1-device mesh all
    produce the same bytes in the same order; the ring actually engages
    (hits at depth 2, none at depth 0) and streams >= 3 launches."""
    for sql in (Q_AGG, Q_ROWS):
        oracle = _oracle_rows(catalog, sql)
        single, exe1 = _chunked_rows(catalog, 1, sql, depth=2)
        assert exe1._chunk_info[0]
        assert single == oracle
        for depth in (0, 1, 2):
            before = obs.counters_snapshot()
            got, exe = _chunked_rows(catalog, 2, sql, depth=depth)
            chunked, n_launches = exe._chunk_info[0], exe._chunk_info[1]
            assert chunked and n_launches >= 3
            assert got == oracle, f"depth={depth}: {sql[:48]}"
            d = obs.counter_delta(before)
            if depth == 0:
                assert d.get("io.prefetch.hit", 0) == 0
            else:
                assert d.get("io.prefetch.hit", 0) > 0
            assert d.get("engine.h2d.bytes", 0) > 0
            assert d.get("engine.stream.execute_s", 0) > 0


def test_prefetch_fault_degrades_but_stays_correct(catalog):
    faults.install("io.prefetch:transient:1.0:seedF:times=1")
    before = obs.counters_snapshot()
    try:
        got, exe = _chunked_rows(catalog, 2, Q_ROWS, depth=2)
    finally:
        faults.uninstall()
    assert exe._chunk_info[0]
    assert got == _oracle_rows(catalog, Q_ROWS)
    d = obs.counter_delta(before)
    assert d.get("io.prefetch.degraded", 0) >= 1
    assert d.get("faults.injected.io.prefetch.transient", 0) == 1


def test_scan_fault_degrades_but_stays_correct(catalog):
    faults.install("io.read:transient:1.0:seedR:times=1")
    before = obs.counters_snapshot()
    try:
        got, exe = _chunked_rows(catalog, 2, Q_ROWS, depth=2)
    finally:
        faults.uninstall()
    assert exe._chunk_info[0]
    assert got == _oracle_rows(catalog, Q_ROWS)
    d = obs.counter_delta(before)
    assert d.get("io.scan.degraded", 0) >= 1
    assert d.get("faults.injected.io.read.transient", 0) == 1


# -- session wiring ---------------------------------------------------------


def test_session_auto_chunk_rows(catalog, monkeypatch):
    """spmd_chunk_rows='auto' sizes the stream from the (pinned) device
    budget and engages chunking when the fact exceeds it."""
    from ndstpu.engine.session import Session

    monkeypatch.setenv("NDSTPU_HBM_BYTES", "200000")
    cpu = Session(catalog, backend="cpu")
    tpu = Session(catalog, backend="tpu", spmd_threshold=500,
                  spmd_chunk_rows="auto")
    sql = Q_AGG
    assert sorted(map(str, tpu.sql(sql).to_rows())) == \
        sorted(map(str, cpu.sql(sql).to_rows()))
    assert getattr(tpu, "_spmd_used", False)
    assert not getattr(tpu, "_spmd_errors", None)
    assert any(ent[1]._chunk_info[0] for ent in tpu._spmd_cache.values())


def test_session_stream_config_validation(catalog):
    from ndstpu.engine.session import Session
    for bad in (0, -5, True, "bogus", 3.5):
        with pytest.raises(ValueError):
            Session(catalog, spmd_chunk_rows=bad)
    with pytest.raises(ValueError):
        Session(catalog, spmd_prefetch_depth=-1)
    Session(catalog, spmd_chunk_rows="auto", spmd_prefetch_depth=0)


def test_nds311_chunk_fallthrough_warns_and_strict_raises(
        catalog, monkeypatch):
    """Chunking configured on a multi-device mesh + a plan that falls
    back to the single-chip path is no longer silent: NDS311 warning,
    counter, and an error under NDSTPU_SPMD_STRICT."""
    from ndstpu.engine.session import ChunkFallthroughError, Session

    # default shard threshold: every table at this SF broadcasts, so the
    # distributed executor refuses the plan and the session falls back
    sql = "select count(*) as n from item"
    sess = Session(catalog, backend="tpu-spmd", spmd_chunk_rows=1000)
    before = obs.counters_snapshot()
    with pytest.warns(UserWarning, match="NDS311"):
        out = sess.sql(sql)
    assert out.to_rows()[0][0] == catalog.get("item").num_rows
    assert obs.counter_delta(before).get(
        "engine.spmd.fallback.NDS311") == 1

    monkeypatch.setenv("NDSTPU_SPMD_STRICT", "1")
    strict = Session(catalog, backend="tpu-spmd", spmd_chunk_rows=1000)
    with pytest.raises(ChunkFallthroughError, match="NDS311"):
        strict.sql(sql)


def test_nds311_registered():
    from ndstpu.analysis import diagnostics
    assert diagnostics.CODES["NDS311"][0] == "warning"


# -- crash safety -----------------------------------------------------------


def test_power_sigkill_midstream_then_resume(stream_root, env, tmp_path):
    """SIGKILL the power CLI while the chunked prefetching engine is
    mid-stream; --resume must skip the journaled query and complete the
    rest with the same fingerprint."""
    props = tmp_path / "stream.properties"
    props.write_text("spmd.threshold_rows=500\n"
                     "spmd.chunk_rows=1000\n"
                     "spmd.prefetch_depth=2\n")
    time_log = tmp_path / "time.csv"
    cmd = ["python", "-m", "ndstpu.harness.power",
           str(stream_root / "streams" / "query_0.sql"),
           str(stream_root / "wh"), str(time_log),
           "--engine", "tpu", "--property_file", str(props),
           "--sub_queries", "query3,query42"]
    proc = subprocess.Popen(cmd, env=env, stdout=subprocess.DEVNULL,
                            stderr=subprocess.DEVNULL)
    journal = tmp_path / "time.csv.progress.jsonl"
    deadline = time.monotonic() + 180
    try:
        while time.monotonic() < deadline:
            if journal.exists() and "query3" in journal.read_text():
                break
            if proc.poll() is not None:
                break
            time.sleep(0.05)
    finally:
        proc.kill()      # SIGKILL: no atexit, no flush, no cleanup
        proc.wait()
    recs = [json.loads(line)
            for line in journal.read_text().splitlines()]
    assert any(r["query"] == "query3" for r in recs)

    r = subprocess.run(cmd + ["--resume"], check=True, env=env,
                       capture_output=True, text=True)
    assert "Skip query3 (resume: already completed)" in r.stdout
    sidecar = json.loads(
        (tmp_path / "time.csv.metrics.json").read_text())
    assert "query3" in sidecar["resumed"]
    assert "query42" in time_log.read_text()
