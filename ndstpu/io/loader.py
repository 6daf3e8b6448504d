"""Warehouse loader: transcode output -> engine Tables (host or device).

Loads per-table warehouse directories (hive-partitioned parquet datasets,
single parquet/orc files, or ndslake ACID tables) into
:class:`ndstpu.engine.columnar.Table`, recording per-table key metadata the
engine exploits:

* dense surrogate keys — every dimension's primary key is `1..N` (or
  offset-dense like date_dim's Julian day sk), so FK->PK joins lower to a
  bounds-checked gather instead of a hash table (TPU-friendly).

This is the analog of the reference's table registration step
(nds_power.py:78-121 setup_tables / register_delta_tables), with Spark
TempViews replaced by an in-process catalog.
"""

from __future__ import annotations

import glob
import os
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np
import pyarrow as pa
import pyarrow.dataset as pads

from ndstpu import schema as nds_schema
from ndstpu.engine import columnar
from ndstpu.io import gdict, lake


@dataclass
class TableMeta:
    name: str
    num_rows: int
    # primary key column with dense values pk_min..pk_min+N-1, if detected
    dense_key: Optional[str] = None
    dense_min: int = 0


@dataclass
class Catalog:
    """Named engine tables + metadata, the engine's table registry."""

    tables: Dict[str, columnar.Table] = field(default_factory=dict)
    meta: Dict[str, TableMeta] = field(default_factory=dict)
    # per-table monotonic version, bumped on every (re)register — the
    # invalidation key for device-resident caches (id() reuse is not sound)
    versions: Dict[str, int] = field(default_factory=dict)
    # out-of-core scan sources (table -> ChunkSource): the distributed
    # chunked executor streams these tables' rows through the scan/decode
    # pool instead of slicing the resident copy (docs/ARCHITECTURE.md
    # "Streaming out-of-core pipeline")
    streams: Dict[str, "ChunkSource"] = field(default_factory=dict)

    def register(self, name: str, table: columnar.Table) -> None:
        self.tables[name] = table
        self.meta[name] = TableMeta(name, table.num_rows)
        self.versions[name] = self.versions.get(name, 0) + 1
        # re-registration replaces the data: a chunk source built over
        # the old rows must not keep serving them
        self.streams.pop(name, None)
        key = _primary_key_column(name, table)
        if key is not None:
            col = table.column(key)
            if col.valid is None and len(col.data):
                data = col.data
                lo = int(data.min())
                hi = int(data.max())
                if hi - lo + 1 == len(data) and _is_permutation(data, lo, hi):
                    self.meta[name].dense_key = key
                    self.meta[name].dense_min = lo

    def unregister(self, name: str) -> None:
        self.tables.pop(name, None)
        self.meta.pop(name, None)
        self.streams.pop(name, None)
        self.versions[name] = self.versions.get(name, 0) + 1

    def get(self, name: str) -> columnar.Table:
        return self.tables[name]

    def __contains__(self, name: str) -> bool:
        return name in self.tables


def _is_permutation(data: np.ndarray, lo: int, hi: int) -> bool:
    seen = np.zeros(hi - lo + 1, dtype=bool)
    seen[data - lo] = True
    return bool(seen.all())


_PK_OVERRIDES = {
    "date_dim": "d_date_sk",
    "time_dim": "t_time_sk",
}


def _primary_key_column(name: str, table: columnar.Table) -> Optional[str]:
    if name in _PK_OVERRIDES:
        return _PK_OVERRIDES[name]
    # convention: first column ending in _sk is the surrogate PK
    first = table.column_names[0] if table.column_names else None
    if first and first.endswith("_sk"):
        return first
    return None


def read_warehouse_table(warehouse: str, table: str,
                         columns: Optional[List[str]] = None) -> pa.Table:
    """Read one table from a transcoded warehouse, any supported layout."""
    root = os.path.join(warehouse, table)
    if lake.is_lake(root):
        return lake.read(root, columns=columns)
    singles = sorted(glob.glob(os.path.join(root, f"{table}*.parquet")))
    if singles:
        import pyarrow.parquet as pq
        parts = [pq.read_table(p, columns=columns) for p in singles]
        return pa.concat_tables(parts) if len(parts) > 1 else parts[0]
    for ext, fmt in (("orc", "orc"), ("avro", "avro"), ("csv", "csv"),
                     ("json", "json")):
        paths = sorted(glob.glob(os.path.join(root, f"{table}*.{ext}")))
        if paths:
            parts = []
            for p in paths:
                if fmt == "orc":
                    import pyarrow.orc as paorc
                    parts.append(paorc.read_table(p))
                elif fmt == "avro":
                    from ndstpu.io import avroio
                    parts.append(avroio.read_table(p))
                elif fmt == "csv":
                    import pyarrow.csv as pacsv
                    parts.append(pacsv.read_csv(
                        p, convert_options=pacsv.ConvertOptions(
                            strings_can_be_null=True)))
                else:
                    import pandas as pd
                    parts.append(
                        pa.Table.from_pandas(pd.read_json(p, lines=True)))
            t = pa.concat_tables(parts) if len(parts) > 1 else parts[0]
            return t.select(columns) if columns else t
    if os.path.isdir(root):
        # hive-partitioned parquet dataset
        dset = pads.dataset(root, format="parquet", partitioning="hive")
        at = dset.to_table(columns=columns)
        return at
    raise FileNotFoundError(f"table {table} not found under {warehouse}")


def _postprocess_partition_dtypes(table: str, at: pa.Table) -> pa.Table:
    """Hive partition keys come back as inferred ints; restore int32 for the
    *_date_sk partition columns so schemas round-trip."""
    part_col = nds_schema.TABLE_PARTITIONING.get(table)
    if part_col and part_col in at.column_names:
        idx = at.column_names.index(part_col)
        col = at.column(idx)
        if not pa.types.is_int32(col.type):
            at = at.set_column(idx, part_col, col.cast(pa.int32()))
    return at


def load_catalog(warehouse: str, tables: Optional[List[str]] = None,
                 use_decimal: bool = True,
                 max_workers: Optional[int] = None) -> Catalog:
    """Load a transcoded warehouse into an engine catalog.

    Per-table scan (pyarrow file reads) and decode (``from_arrow``
    dictionary encoding / decimal scaling) run on a bounded worker
    pool — both release the GIL, so tables load concurrently.
    ``max_workers`` defaults to ``NDSTPU_IO_WORKERS`` or 4; 1 restores
    the serial path.  Registration order stays the caller's table
    order regardless of completion order.
    """
    from ndstpu import obs
    if tables is None:
        tables = [t for t in nds_schema.SOURCE_TABLE_NAMES
                  if os.path.isdir(os.path.join(warehouse, t))]
    schemas = {**nds_schema.get_schemas(use_decimal),
               **nds_schema.get_maintenance_schemas(use_decimal)}

    def load_one(t: str) -> columnar.Table:
        at = read_warehouse_table(warehouse, t)
        at = _postprocess_partition_dtypes(t, at)
        sch = schemas.get(t)
        if sch is not None:
            # restore declared column order (partitioned reads reorder)
            order = [c.name for c in sch.columns
                     if c.name in at.column_names]
            at = at.select(order)
        # encode strings against the table's frozen global dictionary
        # sidecar (if present), so resident codes match what chunk
        # sources and other processes emit for the same warehouse
        gds = gdict.table_dicts(os.path.join(warehouse, t), t)
        return columnar.from_arrow(at, sch, gdicts=gds or None)

    if max_workers is None:
        max_workers = int(os.environ.get("NDSTPU_IO_WORKERS", "4"))
    cat = Catalog()
    with obs.span("load_catalog", cat="io", n_tables=len(tables),
                  workers=max_workers):
        if max_workers <= 1 or len(tables) <= 1:
            for t in tables:
                cat.register(t, load_one(t))
            return cat
        from concurrent.futures import ThreadPoolExecutor
        with ThreadPoolExecutor(
                max_workers=min(max_workers, len(tables)),
                thread_name_prefix="ndstpu-io") as pool:
            futs = {t: pool.submit(load_one, t) for t in tables}
            for t in tables:
                t0 = time.monotonic()
                done = futs[t].done()
                table = futs[t].result()
                if not done:
                    obs.inc("io.scan.wait_s", time.monotonic() - t0)
                cat.register(t, table)
    return cat


# ---------------------------------------------------------------------------
# Streaming out-of-core scan: chunk sources + read-ahead decode pool
# ---------------------------------------------------------------------------


class StreamUnsupported(RuntimeError):
    """A table/column shape the streaming scan cannot serve (the caller
    falls back to the resident path, never wedges)."""


def _string_stream_reject(table: str, col: str) -> StreamUnsupported:
    """Why a string column cannot stream, naming what changes the
    answer: streaming strings requires the table's frozen global
    dictionary (ndstpu/io/gdict.py) so every chunk emits codes in one
    shared code space."""
    return StreamUnsupported(
        f"string column {col} of {table}: per-chunk dictionaries do not "
        f"share a code space, and the table has no {gdict.GDICT_FILE} "
        f"sidecar covering it — re-transcode the warehouse to build "
        f"one (or run the table resident); scripts/dict_audit.py "
        f"(DICT_AUDIT.md) reports per-column coverage")


def _check_gdict_decode(t: columnar.Table, table: str) -> columnar.Table:
    """A decoded chunk must carry its strings in the frozen global code
    space; local-dictionary fallback (a value missing from the sidecar)
    would silently emit codes other chunks disagree with."""
    for n, c in t.columns.items():
        if c.ctype.kind == "string" and c.gdict is None:
            raise StreamUnsupported(
                f"string column {n} of {table}: chunk holds values "
                f"outside the frozen global dictionary (stale "
                f"{gdict.GDICT_FILE} sidecar — re-transcode the table "
                f"or check DICT_AUDIT.md coverage; the resident path "
                f"loads it with per-load dictionaries)")
    return t


#: one decoded chunk: column name -> (data, validity) numpy arrays,
#: exactly ``count`` rows each
ChunkPayload = Dict[str, Tuple[np.ndarray, np.ndarray]]


class ChunkSource:
    """Row-range reads of a table's column subset, decoded to the
    engine's numpy layout.  Implementations must be thread-safe for
    concurrent ``read`` calls (the scan pool issues them from worker
    threads)."""

    num_rows: int = 0
    table: str = ""
    columns: Sequence[str] = ()

    def column_meta(self) -> Dict[str, tuple]:
        """name -> (ctype, numpy dtype, dictionary-or-None), the static
        metadata the traced spine needs without touching row data."""
        raise NotImplementedError

    def read(self, start: int, count: int) -> ChunkPayload:
        raise NotImplementedError


class TableChunkSource(ChunkSource):
    """Scan source over a resident :class:`columnar.Table` — decode is
    a numpy slice.  The default source when no out-of-core stream is
    registered: the same pipeline (scan pool -> staging ring -> device)
    runs over it, so the streaming path has ONE shape regardless of
    where rows physically live."""

    def __init__(self, table: columnar.Table, name: str,
                 columns: Sequence[str]):
        self._t = table
        self.table = name
        self._cols = self.columns = list(columns)
        self.num_rows = table.num_rows

    def column_meta(self) -> Dict[str, tuple]:
        return {n: (self._t.column(n).ctype, self._t.column(n).data.dtype,
                    self._t.column(n).dictionary) for n in self._cols}

    def read(self, start: int, count: int) -> ChunkPayload:
        from ndstpu import faults
        faults.check("io.read", key=f"{self.table}@{start}")
        out: ChunkPayload = {}
        for n in self._cols:
            c = self._t.column(n)
            out[n] = (c.data[start:start + count],
                      c.validity()[start:start + count])
        return out


class ParquetChunkSource(ChunkSource):
    """True out-of-core scan source: row-range reads over a transcoded
    warehouse table's parquet files, row-group-aligned, decoded with
    the same ``from_arrow`` rules the resident loader uses.

    String columns stream when the table carries a global dictionary
    sidecar (ndstpu/io/gdict.py): every chunk decodes its strings
    against the frozen table-wide dictionary, so codes agree with the
    resident load and the traced spine's compile-time dictionary.
    Without a sidecar they are rejected (``StreamUnsupported``):
    per-chunk dictionary encodings would not share a code space.  Hive
    partition-key columns live in directory names, not the files, and
    are likewise rejected.
    """

    def __init__(self, warehouse: str, table: str,
                 columns: Optional[Sequence[str]] = None,
                 use_decimal: bool = True):
        import pyarrow.parquet as pq
        self._pq = pq
        self.table = table
        root = os.path.join(warehouse, table)
        if lake.is_lake(root):
            # ndslake logs carry row-level deletes; raw file enumeration
            # would resurrect them
            raise StreamUnsupported(
                f"table {table} is an ndslake ACID table; streaming scan "
                f"needs a plain parquet layout")
        paths = sorted(glob.glob(os.path.join(root, "**", "*.parquet"),
                                 recursive=True))
        if not paths:
            raise StreamUnsupported(
                f"no parquet files for table {table} under {warehouse}")
        schemas = {**nds_schema.get_schemas(use_decimal),
                   **nds_schema.get_maintenance_schemas(use_decimal)}
        self._schema = schemas.get(table)
        file_cols = set(pq.ParquetFile(paths[0]).schema_arrow.names)
        if columns is None:
            columns = [c for c in file_cols]
        missing = [c for c in columns if c not in file_cols]
        if missing:
            raise StreamUnsupported(
                f"columns {missing} not in {table} parquet files "
                f"(hive partition keys cannot stream)")
        self._cols = self.columns = list(columns)
        self._gdicts = gdict.table_dicts(root, table)
        if self._schema is not None:
            for c in self._cols:
                try:
                    kind = self._schema.column(c).dtype.kind
                except KeyError:
                    continue
                if kind == "string" and c not in self._gdicts:
                    raise _string_stream_reject(table, c)
        # global row index: (path, row_group, global_start, n_rows)
        self._groups: List[tuple] = []
        total = 0
        for p in paths:
            md = pq.ParquetFile(p).metadata
            for g in range(md.num_row_groups):
                n = md.row_group(g).num_rows
                self._groups.append((p, g, total, n))
                total += n
        self.num_rows = total
        self._meta: Optional[Dict[str, tuple]] = None

    def column_meta(self) -> Dict[str, tuple]:
        if self._meta is None:
            t = self._decode(*self._groups[0][:2])
            meta = {}
            for n in self._cols:
                c = t.column(n)
                if c.ctype.kind == "string" and n not in self._gdicts:
                    raise _string_stream_reject(self.table, n)
                meta[n] = (c.ctype, c.data.dtype,
                           self._gdicts[n].values
                           if c.ctype.kind == "string" else None)
            self._meta = meta
        return self._meta

    def _decode(self, path: str, group: int) -> columnar.Table:
        at = self._pq.ParquetFile(path).read_row_group(
            group, columns=self._cols)
        t = columnar.from_arrow(at.select(self._cols), self._schema,
                                gdicts=self._gdicts or None)
        return _check_gdict_decode(t, self.table)

    def read(self, start: int, count: int) -> ChunkPayload:
        from ndstpu import faults, obs
        faults.check("io.read", key=f"{self.table}@{start}")
        end = min(start + count, self.num_rows)
        pieces: List[columnar.Table] = []
        nbytes = 0
        for path, g, g_start, g_n in self._groups:
            if g_start + g_n <= start or g_start >= end:
                continue
            t = self._decode(path, g)
            lo = max(start - g_start, 0)
            hi = min(end - g_start, g_n)
            pieces.append(columnar.Table({
                n: columnar.Column(
                    c.data[lo:hi], c.ctype,
                    None if c.valid is None else c.valid[lo:hi],
                    c.dictionary)
                for n, c in t.columns.items()}))
        out: ChunkPayload = {}
        for n in self._cols:
            cols = [p.column(n) for p in pieces]
            data = np.concatenate([c.data for c in cols]) if cols \
                else np.empty(0, dtype=self.column_meta()[n][1])
            valid = np.concatenate([c.validity() for c in cols]) if cols \
                else np.empty(0, dtype=bool)
            nbytes += data.nbytes + valid.nbytes
            out[n] = (data, valid)
        obs.inc("io.scan.bytes", nbytes)
        return out


class LakeChunkSource(ChunkSource):
    """Snapshot-pinned out-of-core scan over an ACID lake table
    (``ndslake`` or ``ndsdelta``).

    Where :class:`ParquetChunkSource` refuses lake tables outright,
    this source reads a PINNED snapshot version (default: CURRENT at
    construction): the data-file list comes from that version's
    manifest/log replay, and ndslake deletion vectors are applied as
    keep-masks at scan time — so appends and deletes committed *after*
    the pin land in snapshots this source never consults.  This is the
    chunk-source half of snapshot-pinned reads (docs/ARCHITECTURE.md):
    an in-flight streaming query keeps scanning its admission-time
    version while ingest advances the table underneath it.

    File-granular rather than row-group-granular: lake data files are
    micro-batch sized (one per refresh-function commit), so a read
    decodes each overlapping file, masks its deleted rows, and slices
    the requested live-row window.  String columns stream against the
    global-dictionary sidecar version matching the PIN (gdict entries
    are stamped with the lake version that introduced them), so a
    pinned reader decodes with the dictionary its snapshot was
    committed under even while ingest grows the dict; without sidecar
    coverage they are rejected like ParquetChunkSource.
    """

    def __init__(self, table_dir: str, table: Optional[str] = None,
                 columns: Optional[Sequence[str]] = None,
                 version: Optional[int] = None,
                 use_decimal: bool = True):
        import pyarrow.parquet as pq
        self._pq = pq
        self._dir = table_dir
        self.table = table or os.path.basename(
            os.path.normpath(table_dir))
        mod = lake.detect(table_dir)
        if mod is None:
            raise StreamUnsupported(
                f"{table_dir} is not an ACID lake table")
        from ndstpu.io import acid as _acid
        self.version = mod.current_version(table_dir) \
            if version is None else version
        if mod is _acid:
            snap = _acid.load_snapshot(table_dir, self.version)
            file_metas = [(fm["path"], fm.get("deletes"))
                          for fm in snap.files]
        else:
            st = mod._replay(table_dir, self.version)
            # ndsdelta deletes are copy-on-write: no mask needed
            file_metas = [(fm["path"], None)
                          for fm in st.files.values()]
        schemas = {**nds_schema.get_schemas(use_decimal),
                   **nds_schema.get_maintenance_schemas(use_decimal)}
        self._schema = schemas.get(self.table)
        # global live-row index: (abs path, keep-mask-or-None,
        # global_start, live_rows)
        self._files: List[tuple] = []
        total = 0
        first_cols: Optional[List[str]] = None
        for rel, drel in file_metas:
            fp = os.path.join(table_dir, rel)
            n = pq.ParquetFile(fp).metadata.num_rows
            if first_cols is None:
                first_cols = list(pq.ParquetFile(fp).schema_arrow.names)
            keep = None
            live = n
            if drel:
                dels = np.load(os.path.join(table_dir, drel))
                keep = np.ones(n, dtype=bool)
                keep[dels] = False
                live = int(keep.sum())
            if live:
                self._files.append((fp, keep, total, live))
                total += live
        self.num_rows = total
        if columns is None:
            columns = list(first_cols or [])
        missing = [c for c in columns if c not in (first_cols or [])]
        if missing:
            raise StreamUnsupported(
                f"columns {missing} not in {self.table} data files")
        self._cols = self.columns = list(columns)
        self._gdicts = gdict.table_dicts(
            table_dir, self.table, pin_table_version=self.version)
        if self._schema is not None:
            for c in self._cols:
                try:
                    kind = self._schema.column(c).dtype.kind
                except KeyError:
                    continue
                if kind == "string" and c not in self._gdicts:
                    raise _string_stream_reject(self.table, c)
        self._meta: Optional[Dict[str, tuple]] = None

    def column_meta(self) -> Dict[str, tuple]:
        if self._meta is None:
            if not self._files:
                raise StreamUnsupported(
                    f"pinned snapshot v{self.version} of {self.table} "
                    f"has no live rows to derive column metadata from")
            t = self._decode(*self._files[0][:2])
            meta = {}
            for n in self._cols:
                c = t.column(n)
                if c.ctype.kind == "string" and n not in self._gdicts:
                    raise _string_stream_reject(self.table, n)
                meta[n] = (c.ctype, c.data.dtype,
                           self._gdicts[n].values
                           if c.ctype.kind == "string" else None)
            self._meta = meta
        return self._meta

    def _decode(self, path: str,
                keep: Optional[np.ndarray]) -> columnar.Table:
        at = self._pq.read_table(path, columns=self._cols)
        t = columnar.from_arrow(at.select(self._cols), self._schema,
                                gdicts=self._gdicts or None)
        _check_gdict_decode(t, self.table)
        if keep is not None:
            t = t.filter(keep)
        return t

    def read(self, start: int, count: int) -> ChunkPayload:
        from ndstpu import faults, obs
        faults.check("io.read", key=f"{self.table}@{start}")
        end = min(start + count, self.num_rows)
        pieces: List[columnar.Table] = []
        nbytes = 0
        for fp, keep, g_start, g_live in self._files:
            if g_start + g_live <= start or g_start >= end:
                continue
            t = self._decode(fp, keep)
            lo = max(start - g_start, 0)
            hi = min(end - g_start, g_live)
            pieces.append(columnar.Table({
                n: columnar.Column(
                    c.data[lo:hi], c.ctype,
                    None if c.valid is None else c.valid[lo:hi],
                    c.dictionary)
                for n, c in t.columns.items()}))
        out: ChunkPayload = {}
        for n in self._cols:
            cols = [p.column(n) for p in pieces]
            data = np.concatenate([c.data for c in cols]) if cols \
                else np.empty(0, dtype=self.column_meta()[n][1])
            valid = np.concatenate([c.validity() for c in cols]) if cols \
                else np.empty(0, dtype=bool)
            nbytes += data.nbytes + valid.nbytes
            out[n] = (data, valid)
        obs.inc("io.scan.bytes", nbytes)
        return out


class ChunkScanPool:
    """Bounded read-ahead scan/decode pool in front of the executor.

    Workers read + decode the next ``depth`` chunks (in consumption
    order) while the executor computes on the current one; ``get``
    blocks only when the pipeline is behind, and that block time is
    the honest ``io.scan.wait_s`` evidence for the overlap claim.
    A failing worker read degrades the pool to synchronous streaming
    (``io.scan.degraded``) instead of wedging the run — the PR-5
    ``io.read`` fault site fires inside ``ChunkSource.read``.

    Per-chunk :class:`ndstpu.engine.latch.KeyedLatch` keeps a sync
    fallback and a late worker from decoding the same chunk twice.
    """

    def __init__(self, read_fn: Callable[[int], ChunkPayload],
                 starts: Sequence[int], workers: int = 2,
                 depth: int = 2):
        import threading

        from ndstpu.engine.latch import KeyedLatch
        self._read = read_fn
        self._starts = list(starts)
        self._depth = max(int(depth), 0)
        self._workers = max(int(workers), 1)
        self._futs: Dict[int, object] = {}
        self._next = 0          # index into _starts not yet scheduled
        self._pool = None
        self._degraded = False
        self._latch = KeyedLatch()
        # get() is called from the executor AND the H2D staging thread
        # (sync fallbacks vs background staging) — scheduling
        # bookkeeping must not race
        self._sched_lock = threading.Lock()

    def _ensure_pool(self):
        if self._pool is None:
            from concurrent.futures import ThreadPoolExecutor
            self._pool = ThreadPoolExecutor(
                max_workers=self._workers,
                thread_name_prefix="ndstpu-scan")
        return self._pool

    def _guarded_read(self, start: int) -> ChunkPayload:
        with self._latch.holding(start):
            return self._read(start)

    def start_ahead(self) -> None:
        """Kick the read-ahead window before the first ``get`` — called
        at pipeline build so compile time hides the cold reads."""
        self._schedule_ahead(-1)

    def reset(self, next_idx: int = 0) -> None:
        """Rewind the read-ahead window for another pass over the same
        chunk sequence (repeat execution of a cached chunked query).
        A degraded pool stays degraded — the source already failed."""
        with self._sched_lock:
            for fut in self._futs.values():
                fut.cancel()
            self._futs.clear()
            self._next = max(int(next_idx), 0)
        self._schedule_ahead(next_idx - 1)

    def _schedule_ahead(self, upto_idx: int) -> None:
        if self._degraded or self._depth == 0:
            return
        with self._sched_lock:
            limit = min(upto_idx + 1 + self._depth, len(self._starts))
            while self._next < limit:
                s = self._starts[self._next]
                self._futs[s] = self._ensure_pool().submit(
                    self._guarded_read, s)
                self._next += 1

    @staticmethod
    def _wait_counter() -> str:
        """Scan blocking on the H2D staging thread is latency the ring
        absorbs, not executor stall — attribute it separately so
        ``io.scan.wait_s`` stays the honest overlap-claim numerator."""
        import threading
        if threading.current_thread().name.startswith("ndstpu-h2d"):
            return "io.scan.wait_bg_s"
        return "io.scan.wait_s"

    def get(self, start: int) -> ChunkPayload:
        from ndstpu import obs
        try:
            idx = self._starts.index(start)
            with self._sched_lock:
                self._next = max(self._next, idx)
            self._schedule_ahead(idx)
        except ValueError:
            idx = None   # off-schedule read: serve synchronously
        with self._sched_lock:
            fut = self._futs.pop(start, None)
        if fut is not None:
            obs.inc("io.scan.ahead.hit" if fut.done()
                    else "io.scan.ahead.miss")
            t0 = time.monotonic()
            try:
                payload = fut.result()
                obs.inc(self._wait_counter(), time.monotonic() - t0)
                if idx is not None:
                    self._schedule_ahead(idx + 1)
                return payload
            except Exception as e:  # noqa: BLE001 — degrade, don't wedge
                self._degrade(e)
        else:
            obs.inc("io.scan.ahead.miss")
        t0 = time.monotonic()
        try:
            return self._guarded_read(start)
        finally:
            obs.inc(self._wait_counter(), time.monotonic() - t0)

    def _degrade(self, exc: Exception) -> None:
        from ndstpu import obs
        if not self._degraded:
            self._degraded = True
            obs.inc("io.scan.degraded")
            obs.annotate(io_scan_degraded=f"{type(exc).__name__}: {exc}")
        with self._sched_lock:
            for fut in self._futs.values():
                fut.cancel()
            self._futs.clear()

    def close(self) -> None:
        with self._sched_lock:
            for fut in self._futs.values():
                fut.cancel()
            self._futs.clear()
        if self._pool is not None:
            self._pool.shutdown(wait=False)
            self._pool = None


def attach_stream_source(catalog: Catalog, name: str,
                         source: ChunkSource) -> None:
    """Register an out-of-core scan source for a catalog table.  The
    chunked SPMD executor streams this table's rows from the source;
    every other path keeps using the resident copy."""
    if name not in catalog.tables:
        raise KeyError(f"table {name} not in catalog")
    if source.num_rows != catalog.get(name).num_rows:
        raise ValueError(
            f"stream source rows ({source.num_rows}) != resident rows "
            f"({catalog.get(name).num_rows}) for {name}")
    # string chunks must decode into the RESIDENT code space: the traced
    # spine bakes the resident dictionary in as a compile-time constant
    resident = catalog.get(name)
    if any(col in resident.columns
           and resident.column(col).ctype.kind == "string"
           for col in source.columns):
        for col, (ct, _dt, d) in source.column_meta().items():
            if ct.kind != "string" or col not in resident.columns:
                continue
            rd = resident.column(col).dictionary
            if d is None or rd is None or not np.array_equal(
                    np.asarray(d, dtype=object),
                    np.asarray(rd, dtype=object)):
                raise ValueError(
                    f"stream source dictionary for {name}.{col} does "
                    f"not match the resident dictionary — codes would "
                    f"disagree across chunks (was the "
                    f"{gdict.GDICT_FILE} sidecar rebuilt after the "
                    f"catalog loaded?)")
    streams = getattr(catalog, "streams", None)
    if streams is None:       # catalogs unpickled from older snapshots
        streams = catalog.streams = {}
    streams[name] = source


def raw_table_paths(data_dir: str, table: str) -> List[str]:
    return sorted(glob.glob(os.path.join(data_dir, table, "*.dat")))
