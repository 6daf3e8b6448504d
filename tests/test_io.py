"""IO layer tests: CSV ingest, transcode, loader round-trip, ACID tables."""

import os
import subprocess

import numpy as np
import pyarrow as pa
import pytest

from ndstpu import schema
from ndstpu.check import check_build
from ndstpu.engine import columnar
from ndstpu.io import acid, csvio, loader


@pytest.fixture(scope="module")
def dataset(tmp_path_factory):
    """Tiny generated dataset shared across IO tests."""
    out = tmp_path_factory.mktemp("data")
    tool = str(check_build())
    subprocess.run([tool, "-scale", "0.001", "-dir", str(out)], check=True)
    # driver layout: per-table dirs
    for t in schema.SOURCE_TABLE_NAMES:
        d = out / t
        d.mkdir()
        f = out / f"{t}_1_1.dat"
        if f.exists():
            f.rename(d / f.name)
    return out


@pytest.fixture(scope="module")
def warehouse(dataset, tmp_path_factory):
    out = tmp_path_factory.mktemp("wh")
    env = dict(os.environ, PYTHONPATH=os.getcwd())
    subprocess.run(
        ["python", "-m", "ndstpu.io.transcode",
         "--input_prefix", str(dataset),
         "--output_prefix", str(out),
         "--report_file", str(out / "load_report.txt")],
        check=True, env=env)
    return out


def test_csv_read_schema(dataset):
    s = schema.get_schemas()["store_sales"]
    at = csvio.read_table_dir(str(dataset), "store_sales", s)
    assert at.column_names == s.column_names
    assert at.num_rows > 0
    assert pa.types.is_decimal(at.schema.field("ss_net_paid").type)
    assert pa.types.is_int64(at.schema.field("ss_ticket_number").type)


def test_csv_nulls(dataset):
    s = schema.get_schemas()["store_sales"]
    at = csvio.read_table_dir(str(dataset), "store_sales", s)
    # ~2% of sold_date_sk are NULL by generator construction
    nulls = at.column("ss_sold_date_sk").null_count
    assert nulls > 0


def test_transcode_report(warehouse):
    text = (warehouse / "load_report.txt").read_text()
    assert "Load Test Time:" in text
    assert "RNGSEED used:" in text
    assert "Time to convert 'store_sales'" in text


def test_fact_partitioned_layout(warehouse):
    root = warehouse / "store_sales"
    parts = [p for p in os.listdir(root) if p.startswith("ss_sold_date_sk=")]
    assert len(parts) > 1
    # NULL sold dates (~2% by generator construction) land in the hive
    # default partition and must survive the round trip
    assert "ss_sold_date_sk=__HIVE_DEFAULT_PARTITION__" in parts


def test_loader_round_trip(dataset, warehouse):
    s = schema.get_schemas()["store_sales"]
    raw = csvio.read_table_dir(str(dataset), "store_sales", s)
    cat = loader.load_catalog(str(warehouse), ["store_sales", "date_dim"])
    t = cat.get("store_sales")
    assert t.num_rows == raw.num_rows
    assert t.column_names == s.column_names
    # decimal column is scaled int64
    c = t.column("ss_net_paid")
    assert c.ctype.kind == "decimal" and c.data.dtype == np.int64
    # sum of net_paid matches raw decimal sum
    raw_sum = sum(x.as_py() for x in raw.column("ss_net_paid") if x.is_valid)
    eng_sum = int(c.data[c.validity()].sum())
    assert float(raw_sum) == pytest.approx(eng_sum / 100, abs=0.01)


def test_dense_key_detection(warehouse):
    cat = loader.load_catalog(str(warehouse),
                              ["date_dim", "item", "customer"])
    assert cat.meta["item"].dense_key == "i_item_sk"
    assert cat.meta["item"].dense_min == 1
    assert cat.meta["date_dim"].dense_key == "d_date_sk"
    assert cat.meta["date_dim"].dense_min == 2415022


def test_string_dictionary_sorted(warehouse):
    cat = loader.load_catalog(str(warehouse), ["item"])
    d = cat.get("item").column("i_category").dictionary
    assert list(d) == sorted(d)


def test_avro_round_trip():
    import decimal as pydec

    from ndstpu.io import avroio
    at = pa.table({
        "i": pa.array([1, None, 3], type=pa.int32()),
        "l": pa.array([2 ** 60, None, -5], type=pa.int64()),
        "f": pa.array([1.5, None, float("nan")], type=pa.float64()),
        "s": pa.array(["a", None, "日本"], type=pa.string()),
        "d": pa.array([10957, None, 0], type=pa.int32()).cast(
            pa.date32()),
        "m": pa.array([pydec.Decimal("123.45"), None,
                       pydec.Decimal("-0.01")],
                      type=pa.decimal128(7, 2)),
    })
    import tempfile
    with tempfile.TemporaryDirectory() as td:
        p = os.path.join(td, "t.avro")
        avroio.write_table(at, p)
        got = avroio.read_table(p)
    assert got.schema.names == at.schema.names
    for name in at.schema.names:
        a = at.column(name).to_pylist()
        b = got.column(name).to_pylist()
        for va, vb in zip(a, b):
            if isinstance(va, float) and va != va:
                assert vb != vb
            else:
                assert va == vb, (name, va, vb)


def test_avro_warehouse_round_trip(dataset, tmp_path):
    """transcode --output_format avro and load the warehouse back."""
    env = dict(os.environ, PYTHONPATH=os.getcwd())
    out = tmp_path / "wh_avro"
    subprocess.run(
        ["python", "-m", "ndstpu.io.transcode",
         "--input_prefix", str(dataset), "--output_prefix", str(out),
         "--report_file", str(out / "load.txt"),
         "--output_format", "avro", "--tables", "item,store"],
        check=True, env=env, stdout=subprocess.DEVNULL)
    cat = loader.load_catalog(str(out), tables=["item", "store"])
    t = cat.get("item")
    assert t.num_rows > 0
    assert "i_item_sk" in t.column_names
    # agrees with the parquet path
    cat2_dir = tmp_path / "wh_pq"
    subprocess.run(
        ["python", "-m", "ndstpu.io.transcode",
         "--input_prefix", str(dataset), "--output_prefix", str(cat2_dir),
         "--report_file", str(cat2_dir / "load.txt"),
         "--tables", "item,store"],
        check=True, env=env, stdout=subprocess.DEVNULL)
    cat2 = loader.load_catalog(str(cat2_dir), tables=["item", "store"])
    assert sorted(map(str, cat.get("item").to_rows())) == \
        sorted(map(str, cat2.get("item").to_rows()))


def test_acid_create_append_delete_rollback(tmp_path):
    at = pa.table({"k": pa.array([1, 2, 3, 4], pa.int32()),
                   "v": pa.array([10.0, 20.0, 30.0, 40.0])})
    root = str(tmp_path / "t")
    acid.create_table(root, at)
    assert acid.read(root).num_rows == 4
    v0 = acid.current_version(root)

    acid.append(root, pa.table({"k": pa.array([5], pa.int32()),
                                "v": pa.array([50.0])}))
    assert acid.read(root).num_rows == 5

    ts_before_delete = acid.load_snapshot(root).timestamp
    n = acid.delete_rows(
        root, lambda t: np.asarray(t.column("k").to_numpy() % 2 == 0))
    assert n == 2
    assert sorted(acid.read(root).column("k").to_pylist()) == [1, 3, 5]

    # time travel: read the pre-delete version
    assert acid.read(root, version=v0).num_rows == 4
    acid.rollback_to_timestamp(root, ts_before_delete)
    assert acid.read(root).num_rows == 5


def test_columnar_concat_string_merge():
    a = columnar.Table({"s": columnar.Column.from_strings(["b", "a", None])})
    b = columnar.Table({"s": columnar.Column.from_strings(["c", "a"])})
    m = columnar.Table.concat([a, b])
    assert m.column("s").to_pylist() == ["b", "a", None, "c", "a"]
    assert list(m.column("s").dictionary) == ["a", "b", "c"]


@pytest.mark.parametrize("fmt", ["ndslake", "ndsdelta"])
def test_lake_formats_create_append_delete_rollback(tmp_path, fmt):
    """Both ACID formats satisfy the same contract through the lake
    facade (reference benchmarks Iceberg AND Delta: nds_power.py:107-121)."""
    from ndstpu.io import lake
    mod = lake.module_for(fmt)
    at = pa.table({"k": pa.array([1, 2, 3, 4], pa.int32()),
                   "v": pa.array([10.0, 20.0, 30.0, 40.0])})
    root = str(tmp_path / "t")
    lake.create_table(fmt, root, at)
    assert lake.detect(root) is mod
    assert lake.read(root).num_rows == 4
    v0 = mod.current_version(root)

    lake.append(root, pa.table({"k": pa.array([5], pa.int32()),
                                "v": pa.array([50.0])}))
    assert lake.read(root).num_rows == 5
    import time as _time
    ts_before_delete = _time.time()

    n = lake.delete_rows(
        root, lambda t: np.asarray(t.column("k").to_numpy() % 2 == 0))
    assert n == 2
    assert sorted(lake.read(root).column("k").to_pylist()) == [1, 3, 5]

    # time travel + rollback
    assert lake.read(root, version=v0).num_rows == 4
    lake.rollback_to_timestamp(root, ts_before_delete)
    assert lake.read(root).num_rows == 5
    # rollback is itself a new commit: rolling forward again still works
    lake.rollback_to_version(root, v0)
    assert lake.read(root).num_rows == 4


def test_ndsdelta_checkpoint_replay(tmp_path):
    """Enough commits to cross a checkpoint: state must replay from the
    checkpoint, and time travel before it must still work."""
    from ndstpu.io import deltalog
    root = str(tmp_path / "t")
    deltalog.create_table(root, pa.table({"k": pa.array([0], pa.int32())}))
    for i in range(1, 14):
        deltalog.append(root, pa.table({"k": pa.array([i], pa.int32())}))
    assert deltalog.current_version(root) == 13
    cp = os.path.join(root, "_delta_log", "_last_checkpoint")
    assert os.path.exists(cp)
    assert deltalog.read(root).num_rows == 14
    # time travel to a pre-checkpoint version
    assert deltalog.read(root, version=3).num_rows == 4
    n = deltalog.delete_rows(
        root, lambda t: np.asarray(t.column("k").to_numpy() < 5))
    assert n == 5 and deltalog.read(root).num_rows == 9


def _sample_arrow():
    import decimal as _dec
    return pa.table({
        "k": pa.array([1, 2, 3, 4], pa.int64()),
        "d": pa.array([_dec.Decimal("1.50"), _dec.Decimal("2.25"),
                       None, _dec.Decimal("-9.99")],
                      pa.decimal128(7, 2)),
        "s": pa.array(["a", "b", None, "d"], pa.string()),
    })


@pytest.mark.parametrize("fmt", ["ndslake", "ndsdelta"])
def test_delta_export_standard_protocol(tmp_path, fmt):
    """Exported tables carry a protocol-correct Delta log: protocol +
    metaData (Spark schemaString) + one add per file with real sizes,
    and the data round-trips row-for-row — including after a DELETE
    (ndslake's merge-on-read deletion vectors must materialize)."""
    import json as _json
    from ndstpu.io import delta_export, deltalog
    at = _sample_arrow()
    src = tmp_path / "t"
    if fmt == "ndslake":
        acid.create_table(str(src), at)
        acid.delete_rows(str(src), lambda t: np.asarray(
            [v == 2 for v in t.column("k").to_pylist()]))
    else:
        deltalog.create_table(str(src), at)
        deltalog.delete_rows(str(src), lambda t: np.asarray(
            [v == 2 for v in t.column("k").to_pylist()]))
    out = tmp_path / "delta"
    info = delta_export.export(str(src), str(out))
    assert info["rows"] == 3
    log = out / "_delta_log" / f"{0:020d}.json"
    actions = [_json.loads(ln) for ln in log.read_text().splitlines()]
    kinds = [next(iter(a)) for a in actions]
    assert kinds[0] == "commitInfo"
    assert "protocol" in kinds and "metaData" in kinds
    proto = next(a["protocol"] for a in actions if "protocol" in a)
    assert proto == {"minReaderVersion": 1, "minWriterVersion": 2}
    meta = next(a["metaData"] for a in actions if "metaData" in a)
    sch = _json.loads(meta["schemaString"])
    assert sch["type"] == "struct"
    assert {f["name"]: f["type"] for f in sch["fields"]} == {
        "k": "long", "d": "decimal(7,2)", "s": "string"}
    adds = [a["add"] for a in actions if "add" in a]
    assert adds, "no add actions"
    total = 0
    for a in adds:
        fp = out / a["path"]
        assert fp.exists() and a["size"] == os.path.getsize(fp)
        assert a["partitionValues"] == {}
        total += pa.parquet.read_metadata(fp).num_rows  # noqa: F401
    # read back via the add list exactly as a Delta reader would
    import pyarrow.parquet as pq
    got = pa.concat_tables([pq.read_table(out / a["path"]) for a in adds])
    assert got.num_rows == 3
    assert sorted(got.column("k").to_pylist()) == [1, 3, 4]


# ---- crash-consistent commit protocol (io/commit.py) -----------------------


@pytest.mark.parametrize("fmt", ["ndslake", "ndsdelta"])
def test_lake_two_interleaved_writers_conflict(tmp_path, fmt):
    """Two writers based on the same snapshot: the first commit wins,
    the second raises a typed retryable CommitConflict instead of
    silently last-writer-wins clobbering."""
    from ndstpu.faults import taxonomy
    from ndstpu.io import lake
    at = pa.table({"k": pa.array([1, 2, 3], pa.int64())})
    root = str(tmp_path / "t")
    lake.create_table(fmt, root, at)
    v0 = lake.current_version(root)

    # writer A commits against v0 and wins
    lake.append(root, pa.table({"k": pa.array([4], pa.int64())}),
                expected_version=v0)
    # writer B also based its write on v0 — stale, must conflict
    with pytest.raises(lake.CommitConflict) as ei:
        lake.append(root, pa.table({"k": pa.array([5], pa.int64())}),
                    expected_version=v0)
    assert ei.value.expected == v0
    # conflicts are transient in the fault taxonomy: reload + retry
    assert taxonomy.classify(ei.value) == "transient"
    # writer A's commit survived intact, B's never landed
    assert sorted(lake.read(root).column("k").to_pylist()) == [1, 2, 3, 4]
    # the retry pattern: rebase on the current version and re-commit
    lake.append(root, pa.table({"k": pa.array([5], pa.int64())}),
                expected_version=lake.current_version(root))
    assert sorted(lake.read(root).column("k").to_pylist()) == \
        [1, 2, 3, 4, 5]


@pytest.mark.parametrize("fmt", ["ndslake", "ndsdelta"])
def test_lake_delete_conflict_on_stale_expected(tmp_path, fmt):
    from ndstpu.io import lake
    at = pa.table({"k": pa.array([1, 2, 3, 4], pa.int64())})
    root = str(tmp_path / "t")
    lake.create_table(fmt, root, at)
    v0 = lake.current_version(root)
    lake.append(root, pa.table({"k": pa.array([9], pa.int64())}))
    with pytest.raises(lake.CommitConflict):
        lake.delete_rows(
            root,
            lambda t: np.asarray(t.column("k").to_numpy() % 2 == 0),
            expected_version=v0)
    # nothing was deleted by the conflicted writer
    assert sorted(lake.read(root).column("k").to_pylist()) == \
        [1, 2, 3, 4, 9]


@pytest.mark.parametrize("fmt", ["ndslake", "ndsdelta"])
def test_lake_pinned_read_during_append_and_delete(tmp_path, fmt):
    """A reader pinned to its admission-time version sees exactly that
    snapshot's rows while appends AND deletes commit underneath it."""
    from ndstpu.io import lake
    at = pa.table({"k": pa.array(list(range(10)), pa.int64())})
    root = str(tmp_path / "t")
    lake.create_table(fmt, root, at)
    pin = lake.current_version(root)

    lake.append(root, pa.table({"k": pa.array([100, 101], pa.int64())}))
    lake.delete_rows(
        root, lambda t: np.asarray(t.column("k").to_numpy() % 3 == 0))

    live = sorted(lake.read(root).column("k").to_pylist())
    assert live != list(range(10))  # the live view moved
    pinned = sorted(lake.read(root, version=pin).column("k").to_pylist())
    assert pinned == list(range(10)), \
        "pinned read leaked post-pin appends or deletes"


@pytest.mark.parametrize("fmt", ["ndslake", "ndsdelta"])
def test_lake_pinned_historical_read_after_many_commits(tmp_path, fmt):
    """Every historical version stays resolvable after N commits."""
    from ndstpu.io import lake
    root = str(tmp_path / "t")
    lake.create_table(
        fmt, root, pa.table({"k": pa.array([0], pa.int64())}))
    versions = [lake.current_version(root)]
    for i in range(1, 13):  # crosses the ndsdelta checkpoint at v10
        lake.append(root, pa.table({"k": pa.array([i], pa.int64())}))
        versions.append(lake.current_version(root))
    for n, v in enumerate(versions, start=1):
        got = sorted(lake.read(root, version=v).column("k").to_pylist())
        assert got == list(range(n)), f"version {v} unresolvable"


@pytest.mark.parametrize("fmt", ["ndslake", "ndsdelta"])
def test_lake_abort_to_version_retracts_history(tmp_path, fmt):
    """Crash-recovery retraction: versions above the target disappear
    and the next commit reuses the retracted numbering — unlike
    rollback_to_version, which publishes a NEW snapshot."""
    from ndstpu.io import lake
    at = pa.table({"k": pa.array([1, 2], pa.int64())})
    root = str(tmp_path / "t")
    lake.create_table(fmt, root, at)
    v0 = lake.current_version(root)
    lake.append(root, pa.table({"k": pa.array([3], pa.int64())}))
    lake.append(root, pa.table({"k": pa.array([4], pa.int64())}))
    v2 = lake.current_version(root)
    assert v2 > v0

    lake.abort_to_version(root, v0)
    assert lake.current_version(root) == v0
    assert sorted(lake.read(root).column("k").to_pylist()) == [1, 2]
    # retracted versions are gone, and numbering restarts where the
    # first aborted commit had been — the clean-run trajectory
    lake.append(root, pa.table({"k": pa.array([7], pa.int64())}))
    assert lake.current_version(root) == v0 + 1
    assert sorted(lake.read(root).column("k").to_pylist()) == [1, 2, 7]


def test_ndslake_gc_orphan_manifests(tmp_path):
    """A manifest written but never published to CURRENT (crash or
    injected fault mid-commit) is GC-able, restoring _next_version."""
    import json as _json

    root = str(tmp_path / "t")
    acid.create_table(root, pa.table({"k": pa.array([1], pa.int64())}))
    cur = acid.current_version(root)
    orphan = acid._snap_path(root, cur + 3)
    with open(orphan, "w") as f:
        _json.dump({"version": cur + 3, "timestamp": 0.0, "files": [],
                    "partition_col": None, "operation": "torn"}, f)
    assert acid._next_version(root) == cur + 4  # skewed by the orphan
    assert acid.gc_orphan_manifests(root) == [cur + 3]
    assert not os.path.exists(orphan)
    assert acid._next_version(root) == cur + 1
    # CURRENT was never touched
    assert acid.current_version(root) == cur


@pytest.mark.parametrize("fmt", ["ndslake", "ndsdelta"])
def test_lake_chunk_source_windows_and_deletes(tmp_path, fmt):
    """LakeChunkSource reads a pinned version across multi-file windows
    with deletion masks applied, ignoring post-pin commits."""
    from ndstpu.io import lake
    from ndstpu.io.loader import LakeChunkSource
    root = str(tmp_path / "t")
    lake.create_table(
        fmt, root,
        pa.table({"k": pa.array(list(range(6)), pa.int64()),
                  "v": pa.array([float(i) for i in range(6)])}))
    lake.append(root, pa.table({"k": pa.array([6, 7], pa.int64()),
                                "v": pa.array([6.0, 7.0])}))
    lake.delete_rows(
        root, lambda t: np.asarray(t.column("k").to_numpy() == 1))
    pin = lake.current_version(root)

    src = LakeChunkSource(root, columns=["k", "v"], version=pin)
    assert src.num_rows == 7  # 8 rows minus the deleted k=1
    ks = []
    for start in range(0, src.num_rows, 3):  # windows cross file edges
        payload = src.read(start, min(3, src.num_rows - start))
        vals, valid = payload["k"]
        assert valid.all()
        ks.extend(vals.tolist())
    # windows tile the pinned rows exactly once; global file order is
    # format-specific (ndsdelta's COW delete rewrites file lists)
    assert sorted(ks) == [0, 2, 3, 4, 5, 6, 7]

    # post-pin commits are invisible to the pinned source
    lake.append(root, pa.table({"k": pa.array([99], pa.int64()),
                                "v": pa.array([99.0])}))
    assert LakeChunkSource(root, columns=["k"],
                           version=pin).num_rows == 7
    fresh = LakeChunkSource(root, columns=["k"])
    assert fresh.num_rows == 8
    vals, _ = fresh.read(0, 8)["k"]
    assert sorted(vals.tolist()) == [0, 2, 3, 4, 5, 6, 7, 99]


# ---- global dictionary sidecars (io/gdict.py) ------------------------------


def test_transcode_builds_gdict_sidecars(warehouse):
    """Transcode writes a _GLOBAL_DICTS.json sidecar per string-bearing
    table; the loader encodes resident columns against it, so resident
    codes ARE the warehouse-wide code space."""
    from ndstpu.io import gdict
    assert gdict.has_sidecar(str(warehouse / "item"))
    gds = gdict.table_dicts(str(warehouse / "item"), "item")
    cat = loader.load_catalog(str(warehouse), ["item"])
    c = cat.get("item").column("i_category")
    assert c.gdict is not None
    assert list(c.dictionary) == list(gds["i_category"].values)
    d = gds["i_category"]
    assert list(d.values) == sorted(d.values)
    assert d.hash == gdict.content_hash(d.values)
    assert d.nbytes == sum(len(str(v).encode()) for v in d.values)


def test_table_without_sidecar_loads_with_per_call_dicts(
        warehouse, without_sidecar):
    from ndstpu.io import gdict
    bare = without_sidecar(warehouse, "item")
    assert gdict.table_dicts(str(bare / "item"), "item") == {}
    col = loader.load_catalog(str(bare), ["item"]).get(
        "item").column("i_category")
    assert col.gdict is None
    # the same strings, decoded through the per-call dictionary
    want = loader.load_catalog(str(warehouse), ["item"]).get(
        "item").column("i_category")
    assert col.to_pylist() == want.to_pylist()


def test_gdict_update_sidecar_append_only(tmp_path):
    """Growth produces a NEW sorted version; the value set only grows;
    re-running with the same values writes nothing new; pinned
    selection returns the version matching the pin."""
    import numpy as np

    from ndstpu.io import gdict
    td = str(tmp_path / "t")
    gdict.update_sidecar(td, "t", {"s": np.asarray(
        ["birch", "ash"], object)}, table_version=0)
    d0 = gdict.table_dicts(td, "t")["s"]
    assert list(d0.values) == ["ash", "birch"] and d0.version == 0

    # idempotent: same value set -> no new version
    gdict.update_sidecar(td, "t", {"s": np.asarray(
        ["ash", "birch"], object)}, table_version=1)
    assert gdict.table_dicts(td, "t")["s"].version == 0

    # growth: union, re-sorted, new version stamped with the commit
    gdict.update_sidecar(td, "t", {"s": np.asarray(
        ["cedar", "ash"], object)}, table_version=2)
    d2 = gdict.table_dicts(td, "t")["s"]
    assert list(d2.values) == ["ash", "birch", "cedar"]
    assert d2.version == 1 and d2.table_version == 2
    # snapshot-pinned readers keep their matching version
    dp = gdict.table_dicts(td, "t", pin_table_version=1)["s"]
    assert list(dp.values) == ["ash", "birch"] and dp.version == 0


def test_parquet_chunk_source_streams_strings(warehouse):
    """String tables stream chunk-wise: every chunk decodes against the
    frozen sidecar dictionary, so chunk codes agree with the resident
    load (the invariant that unlocked out-of-core string tables)."""
    import numpy as np

    cat = loader.load_catalog(str(warehouse), ["item"])
    resident = cat.get("item")
    src = loader.ParquetChunkSource(
        str(warehouse), "item", ["i_item_sk", "i_category"])
    assert src.num_rows == resident.num_rows
    meta = src.column_meta()
    assert list(meta["i_category"][2]) == \
        list(resident.column("i_category").dictionary)
    codes = []
    for start in range(0, src.num_rows, 7):
        vals, _ = src.read(start, min(7, src.num_rows - start))[
            "i_category"]
        codes.extend(vals.tolist())
    assert np.array_equal(
        np.asarray(codes), resident.column("i_category").data)


def test_parquet_chunk_source_rejects_strings_without_dicts(
        warehouse, without_sidecar):
    from ndstpu.io import gdict
    bare = without_sidecar(warehouse, "item")
    with pytest.raises(loader.StreamUnsupported) as ei:
        loader.ParquetChunkSource(str(bare), "item",
                                  ["i_item_sk", "i_category"])
    assert gdict.GDICT_FILE in str(ei.value)
    # its numeric columns stream as before
    src = loader.ParquetChunkSource(str(bare), "item", ["i_item_sk"])
    assert src.num_rows > 0
