"""JAX/XLA execution backend — the TPU path.

Executes the same logical plans as ndstpu.engine.physical, but on device
arrays with XLA-friendly static shapes (cf. reference execution engine:
Spark SQL + spark-rapids GPU plugin, nds/power_run_gpu.template:23-40).

Design (TPU-first, not a Spark translation):

* **Static capacities + alive mask.** Every table is padded to a
  power-of-two *size class*; a boolean ``alive`` vector marks real rows.
  Filters only AND the mask (no data movement); compaction happens lazily
  at the few points that need it (LIMIT, join sizing).  Data-dependent
  output sizes (join fan-out) sync one scalar to host and pick a size
  class, so XLA recompiles per size class, not per row count.

* **Pure functional operators.** Each operator is a pure function of jnp
  arrays, so any sync-free subtree can be traced under ``jax.jit`` (the
  graft entry point jits a whole query pipeline this way).

* **Sort-based relational kernels.** Group-by = lexicographic sort →
  adjacent-difference dense group ids → ``segment_sum``/min/max (exact
  int64 for decimals).  Equi-join = dense-rank both sides jointly,
  mixed-radix composite key, sort build side, two-sided
  ``searchsorted``, ragged expansion against a host-sized output.

* **Strings never touch the device.**  String columns are int32 codes
  into per-column *sorted* dictionaries; LIKE/substr/upper/… are computed
  once per dictionary entry on host (O(|dict|)) and become code-indexed
  lookup-table gathers on device (O(rows)).  Cross-dictionary equality
  goes through host-built translation tables.

* **Exact decimals.** decimal(p,s) stays scale-shifted int64 on device;
  sums are exact int64 segment sums (validation bar: nds_validate.py
  epsilon semantics).

Nodes/exprs without a device lowering fall back per-subtree to the numpy
reference interpreter (children still run on device; results are pulled
to host once).
"""

from __future__ import annotations

import os
import contextlib
import dataclasses
import math
import re
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp  # noqa: E402

from ndstpu import obs  # noqa: E402
from ndstpu.obs import host as obs_host  # noqa: E402
# the declarative supported-op registry is the single source of truth
# shared with the static analyzer and scripts/spmd_coverage.py — keep
# capability checks here pointing at it so the two can't drift
from ndstpu.analysis import lowering as lowreg  # noqa: E402
from ndstpu.engine import (  # noqa: E402
    columnar, expr as ex, optimizer, physical, plan as lp)
from ndstpu.engine.columnar import (  # noqa: E402
    BOOL,
    DATE,
    FLOAT64,
    INT32,
    INT64,
    STRING,
    Column,
    DType,
    Table,
    decimal,
)

# Sentinels (int64 key space)
_NULL_KEY = np.int64(-(2 ** 62))      # NULL group/join key
_DEAD_KEY = np.int64(2 ** 62)         # padding / filtered-out rows
# int32 key space (narrow keys: v5e has no native int64 ALU — the x64
# rewrite emulates every s64 op as s32 pairs, so keys whose domain fits
# int32 cut the VPU work of sorts/compares in half or better)
_NULL32 = np.int32(-(2 ** 30))
_DEAD32 = np.int32(2 ** 30)
_ORD_DEAD32 = np.int32(2 ** 30 + 1)   # order keys: dead strictly last
_NARROW_LIM = 2 ** 30                 # |value| bound for int32 keys
_MIN_CAPACITY = 256
# survivor compaction (_survivor_positions): one element gathered by the
# binary search costs this many updates of the scatter.  TPU v5 lite,
# 4 Mi rows (PR 26's chip run): the int32 scatter takes 21.1 ms whatever
# survives (10.8 at 2 Mi, 5.8 at 1 Mi rows), the search 1.5 + 7.2 ns x
# cap x 22 steps: 6.7 ms for 32 Ki survivors, 11.9 for 64 Ki, 22.4 for
# 128 Ki, 43.4 for 256 Ki, 333 for 2 Mi; at 1 Mi rows 1.5 ms for 4 Ki,
# 38.9 for 256 Ki.  (jnp.nonzero(size=) counts in int64 under x64,
# emulated on this chip: 257-291 ms at 4 Mi rows, 65-73 at 1 Mi.)
_SEARCH_COMPACT_COST = 1.5
# lookup join by comparing each probe key with every alive build key
# (_compare_cheaper): one (probe key, build key) pair costs this many
# elements of the probe-sized gather it replaces.  TPU v5 lite (PR 29's
# chip run): the keycmp kernel takes 0.59 / 1.11 / 2.13 / 4.21 / 8.30 /
# 16.5 ms for 256 / 512 / 1024 / 2048 / 4096 / 8192 keys at 4 Mi rows
# and 0.28 / 0.31 / 0.58 / 1.09 / 2.12 / 4.18 ms at 1 Mi in blocks of 512
# rows (0.52 and 3.72 ms at 256 and 2048 keys in the blocks it has now;
# 0.45 ms at 256 keys inside a replay): 0.48 ps a pair.  The same
# compare as one XLA reduce fusion: 0.94-1.2 ps up to 1024 keys, 2.3 ps
# from 2048 on.  A gather of 4 Mi elements: 36.1 ms from 18 001, 73 050
# or 1 920 801 slots (8.6 ns an element; 9.1 ms at 1 Mi rows), 39-41 ms
# from 128-301 slots.  (From 64 slots or fewer XLA selects instead:
# 0.27 ms, under the kernel's 0.45-0.52 at 256 keys.  Not modelled:
# sending query96's and query25's 13-slot `store` lookups back to it
# is worth 0.4 ms each, 0.2 % of a pass.)
_COMPARE_PAIR_COST = 5.6e-5
# the kernel keeps keys and rows in scalar memory (1 MB a core)
_COMPARE_MAX_KEYS = 32768
# where a chain of lookup joins compacts (_defer_cheaper): behind a
# compaction every column is a lazy view, and a key column read there
# gathers its data (one element a row) and its validity, a pred gather
# that costs this many int32 ones.  TPU v5 lite, query7 at SF1 (ledger,
# PR 30; builder's trace, PR 29): store_sales' key gathered at 2 Mi rows
# from its 4 Mi base takes 18.0 ms for the data and 38.8 for the
# validity, behind a compaction scatter of 19.3 ms (21.1 at 4 Mi rows
# whatever survives, _SEARCH_COMPACT_COST) -- 76 ms so that a compare
# with 512 alive keys runs over 2 Mi rows instead of 4 Mi: 0.45 ms
# instead of 1.1.
_PRED_GATHER_COST = 2
# "compare" joins are counted under "lookup" too (they are lookups)
_JOIN_PATHS = ("lookup", "expand", "sort", "compare", "deferred")
# operators of a traced program by KIND, beside the joins' paths: semi
# covers anti and null-aware anti; residual counts those semi / anti /
# mark joins that expand their key matches to test a residual predicate
# (_residual_hits); setop is INTERSECT / EXCEPT; agg_sort a keyed
# aggregate without a linearised key (_direct_group_ids gave None);
# exists_extremes the join of two per-key min / max aggregates that
# optimizer.exists_by_extremes put in place of an inner join's pairs;
# window_rank a rank / dense_rank / row_number window, window_running an
# aggregate window with ORDER BY (_running_window), window_whole one over
# a whole partition; agg_wide a keyed aggregate on a linearised key whose
# domain exceeds _PALLAS_SEGS_MAX, with a sum / avg column (those take
# XLA's int64 scatter, not the segsum kernel); memo_shared a maximal
# subtree the program took from an identical earlier one instead of
# running it (a memo hit in execute: a second use of a CTE, or a second
# read of a segment _cut_segments folded two occurrences into)
_OP_KINDS = ("join_semi", "join_mark", "join_residual", "join_full",
             "setop", "agg_sort", "exists_extremes", "window_rank",
             "window_running", "window_whole", "agg_wide", "memo_shared")
# group-by by linearized key (_direct_group_ids): the most slots of a
# composite key domain; a larger one takes the sort path.  1 << 16 left
# q2's pivoted (d_week_seq x d_day_name) composite key (~83k slots) --
# and q59's (week x store x day, ~1.17M) -- on the SORT path: a full
# multi-key sort of the 2-3M-row fact spine that costs more than the
# masked scatters the pivot removed.  Slot buffers are domain-sized
# (1 << 21 x 8 B = 16 MB per reduction, freed per aggregate), trivial
# next to the row data; sparse scatter output stays cheaper than
# sorting millions of rows.  (No chip reading on either side of it yet:
# no cell groups by a domain near the cap.)
_GROUPBY_DOMAIN_CAP = 1 << 21
# direct-addressed join (_lut_span): the most slots of the key domain;
# a larger one takes the combined sort.  The count and start tables of
# that many slots live in HBM (2 x 4 B x slots; 1 << 25 -> 256 MB peak,
# freed per join).  query25's store_sales x store_returns on three keys
# is over it: 78 ms a pass on the sort path (PR 29's chip run).
_JOIN_LUT_CAP = 1 << 25


def size_class(n: int) -> int:
    """Smallest power-of-two capacity >= n (bounded recompilation)."""
    return max(_MIN_CAPACITY, 1 << max(0, (int(n) - 1)).bit_length())


_JNP_DTYPES = {
    "int32": jnp.int32,
    "int64": jnp.int64,
    "float64": jnp.float64,
    "decimal": jnp.int64,
    "date": jnp.int32,
    "string": jnp.int32,
    "bool": jnp.bool_,
}


def jnp_dtype(ct: DType):
    return _JNP_DTYPES[ct.kind]


@dataclasses.dataclass
class _View:
    """Shared row indirection for lazily-gathered columns: ``idx`` maps
    the current capacity into a BASE column's capacity (always one
    level — compositions fold into a single gather), ``mask`` is an
    accumulated validity-kill at the current capacity (or None)."""

    idx: jnp.ndarray
    mask: Optional[jnp.ndarray] = None


class DCol:
    """Device column: padded data + validity (meaningful where alive).

    Either materialized (``data``/``valid`` arrays) or a lazy view over
    a base column (``src_data``/``src_valid`` + shared :class:`_View`).
    Lazy columns materialize on first ``.data``/``.valid`` access with
    ONE gather from the base: eager join expansion re-gathered every
    column of both sides at every join of a multi-join pipeline."""

    __slots__ = ("_data", "_valid", "ctype", "dictionary", "bounds",
                 "src_data", "src_valid", "view")

    def __init__(self, data, valid, ctype: DType,
                 dictionary: Optional[np.ndarray] = None,
                 bounds: Optional[Tuple[int, int]] = None):
        self._data = data
        self._valid = valid
        self.ctype = ctype
        # host-side, sorted dictionary for string columns
        self.dictionary = dictionary
        # host-side static (lo, hi) over the column's valid values, set
        # at upload and preserved by row-subset ops (gather/filter);
        # lets group-by linearize small integer key domains without
        # sorting.  Invalidation rides the same contract as
        # `dictionary`: data changes bump the catalog version, which
        # forces re-upload + re-trace.
        self.bounds = bounds
        self.src_data = None
        self.src_valid = None
        self.view = None

    @classmethod
    def lazy(cls, src_data, src_valid, view: _View, ctype: DType,
             dictionary=None, bounds=None) -> "DCol":
        c = cls(None, None, ctype, dictionary, bounds)
        c.src_data = src_data
        c.src_valid = src_valid
        c.view = view
        return c

    @property
    def data(self):
        if self._data is None:
            self._data = self.src_data[self.view.idx]
        return self._data

    @property
    def valid(self):
        if self._valid is None:
            v = self.view.mask
            if self.src_valid is not None:
                sv = self.src_valid[self.view.idx]
                v = sv if v is None else (sv & v)
            if v is None:
                v = jnp.ones(self.view.idx.shape[0], bool)
            self._valid = v
        return self._valid

    @property
    def capacity(self) -> int:
        if self._data is not None:
            return int(self._data.shape[0])
        return int(self.view.idx.shape[0])


def _select_cols(cols_a: Dict[str, DCol], cols_b: Dict[str, DCol],
                 idx_a: jnp.ndarray, idx_b: jnp.ndarray,
                 pick_a: jnp.ndarray,
                 extra_mask: Optional[jnp.ndarray] = None
                 ) -> Dict[str, DCol]:
    """Two-source row select: out[n][p] = a[n][idx_a[p]] if pick_a[p]
    else b[n][idx_b[p]].  When both columns resolve to the SAME base
    array (a is a lazy view of b's source — the left-join shape), the
    select collapses to ONE combined index and stays lazy; otherwise
    both sides materialize and combine with `where`."""
    memo: Dict[tuple, _View] = {}
    out: Dict[str, DCol] = {}
    ones_a = None
    for n in cols_a:
        a, b = cols_a[n], cols_b[n]
        base_a = a.src_data if a.view is not None else a._data
        base_b = b.src_data if b.view is not None else b._data
        sv_a = a.src_valid if a.view is not None else a._valid
        sv_b = b.src_valid if b.view is not None else b._valid
        # collapsing to one lazy view uses side a's src_valid for rows
        # picked from b — only sound when the VALIDITY bases match too
        # (a shared data buffer with different validity, e.g. a cast
        # built as DCol(c.data, c.valid & ok), must not collapse)
        if base_a is base_b and sv_a is sv_b:
            key = (id(a.view), id(b.view))
            v2 = memo.get(key)
            if v2 is None:
                ia = a.view.idx[idx_a] if a.view is not None else idx_a
                ib = b.view.idx[idx_b] if b.view is not None else idx_b
                nidx = jnp.where(pick_a, ia, ib)
                ma = a.view.mask[idx_a] \
                    if a.view is not None and a.view.mask is not None \
                    else None
                mb = b.view.mask[idx_b] \
                    if b.view is not None and b.view.mask is not None \
                    else None
                if ma is None and mb is None:
                    nmask = None
                else:
                    if ones_a is None:
                        ones_a = jnp.ones(pick_a.shape[0], bool)
                    nmask = jnp.where(pick_a,
                                      ma if ma is not None else ones_a,
                                      mb if mb is not None else ones_a)
                if extra_mask is not None:
                    nmask = extra_mask if nmask is None else \
                        (nmask & extra_mask)
                v2 = memo[key] = _View(nidx, nmask)
            sv = a.src_valid if a.view is not None else a._valid
            out[n] = DCol.lazy(base_a, sv, v2, a.ctype, a.dictionary,
                               _union_bounds(a.bounds, b.bounds))
        else:
            data = jnp.where(pick_a, a.data[idx_a], b.data[idx_b])
            valid = jnp.where(pick_a, a.valid[idx_a], b.valid[idx_b])
            if extra_mask is not None:
                valid = valid & extra_mask
            out[n] = DCol(data, valid, a.ctype, a.dictionary,
                          _union_bounds(a.bounds, b.bounds))
    return out


def _union_bounds(a: Optional[Tuple[int, int]],
                  b: Optional[Tuple[int, int]]):
    if a is None or b is None:
        return None
    return (min(a[0], b[0]), max(a[1], b[1]))


def _gather_cols(cols: Dict[str, DCol], idx: jnp.ndarray,
                 extra_mask: Optional[jnp.ndarray] = None
                 ) -> Dict[str, DCol]:
    """Lazily gather every column by ``idx``: columns sharing a view
    compose index/mask ONCE; materialized sources just wrap.  With
    ``extra_mask`` the gathered validity is additionally ANDed (at the
    output capacity)."""
    ident = _View(idx, extra_mask)
    memo: Dict[int, _View] = {}
    out: Dict[str, DCol] = {}
    for n, c in cols.items():
        if c.view is None:
            out[n] = DCol.lazy(c._data, c._valid, ident, c.ctype,
                               c.dictionary, c.bounds)
            continue
        v2 = memo.get(id(c.view))
        if v2 is None:
            nidx = c.view.idx[idx]
            nmask = c.view.mask[idx] if c.view.mask is not None else None
            if extra_mask is not None:
                nmask = extra_mask if nmask is None else \
                    (nmask & extra_mask)
            v2 = memo[id(c.view)] = _View(nidx, nmask)
        out[n] = DCol.lazy(c.src_data, c.src_valid, v2, c.ctype,
                           c.dictionary, c.bounds)
    return out


@dataclasses.dataclass
class DTable:
    """Device table: named columns + alive mask, all of one capacity.

    ``pending`` is set on what an inner lookup join returns when it left
    its survivors where the probe had them: the survivors' size class
    (static, an upper bound on ``sum(alive)``), to which whatever needs
    dense rows compacts them (JaxExecutor._settle).  Only
    ``execute(settled=False)`` hands such a table out."""

    columns: Dict[str, DCol]
    alive: jnp.ndarray
    pending: Optional[int] = None

    @property
    def capacity(self) -> int:
        return int(self.alive.shape[0])

    @property
    def column_names(self) -> List[str]:
        return list(self.columns)

    def column(self, name: str) -> DCol:
        return self.columns[name]

    def select(self, names: Sequence[str]) -> "DTable":
        return DTable({n: self.columns[n] for n in names}, self.alive,
                      self.pending)

    def gather(self, idx: jnp.ndarray, alive: jnp.ndarray) -> "DTable":
        return DTable(_gather_cols(self.columns, idx), alive)


# ---------------------------------------------------------------------------
# host <-> device conversion
# ---------------------------------------------------------------------------


def _pad(arr: np.ndarray, cap: int, fill=0) -> np.ndarray:
    if len(arr) == cap:
        return arr
    out = np.full(cap, fill, dtype=arr.dtype)
    out[:len(arr)] = arr
    return out


import contextlib  # noqa: E402


def default_platform() -> str:
    """Platform of the device compiled replay programs run on."""
    return jax.devices()[0].platform


def host_cpu_device():
    """The host CPU jax device, if one is registered alongside an
    accelerator platform (None when CPU already is the default)."""
    try:
        dev = jax.devices("cpu")[0]
    except Exception:
        return None
    return dev if jax.devices()[0] != dev else None


def host_compute():
    """Context manager pinning uncommitted jax computation to the host
    CPU backend.  The eager/discovery path runs under it, op by op on
    the host; only compiled replay programs run on the accelerator.
    When the CPU backend is not registered (``JAX_PLATFORMS=tpu``)
    :func:`host_cpu_device` returns None and discovery dispatches op by
    op on the chip instead."""
    dev = host_cpu_device()
    return jax.default_device(dev) if dev is not None else \
        contextlib.nullcontext()


def to_device(t: Table, cap: Optional[int] = None) -> DTable:
    n = t.num_rows
    cap = cap or size_class(n)
    cols: Dict[str, DCol] = {}
    for name, c in t.columns.items():
        host = np.asarray(c.data)
        data = jnp.asarray(_pad(host, cap))
        valid = jnp.asarray(_pad(c.validity(), cap, False))
        bounds = None
        if c.ctype.kind in ("int32", "int64", "date", "decimal") and n > 0:
            hv = host[c.validity()[:n]] if c.valid is not None else host[:n]
            if len(hv):
                bounds = (int(hv.min()), int(hv.max()))
        cols[name] = DCol(data, valid, c.ctype, c.dictionary, bounds)
    alive = jnp.asarray(_pad(np.ones(n, dtype=bool), cap, False))
    return DTable(cols, alive)


def to_host(dt: DTable) -> Table:
    alive = np.asarray(dt.alive)
    cols: Dict[str, Column] = {}
    for name, c in dt.columns.items():
        data = np.asarray(c.data)[alive]
        valid = np.asarray(c.valid)[alive]
        cols[name] = Column(data, c.ctype,
                            None if valid.all() else valid, c.dictionary)
    return Table(cols)


# ---------------------------------------------------------------------------
# streaming H2D prefetch ring (out-of-core chunked execution)
# ---------------------------------------------------------------------------


class ChunkPrefetcher:
    """Double-buffered host->HBM staging ring for the out-of-core
    streaming executor (docs/ARCHITECTURE.md "Streaming out-of-core
    pipeline").

    ``get(i)`` returns chunk ``i``'s staged device arguments; while the
    caller's compiled launch computes on them, a single background
    thread runs ``stage_fn`` (scan-pool read + ``jax.device_put``) for
    chunks ``i+1 .. i+depth``, so the next launch starts without
    waiting on the transfer.  ``depth=0`` is fully synchronous — the
    ring degenerates to the pre-streaming behavior, which is also the
    degraded mode when a background stage fails (the PR-5 ``io.prefetch``
    fault site fires inside the staging path): the stream slows down,
    it never wedges or drops a chunk.

    Counters: ``io.prefetch.hit`` (chunk staged ahead and ready at
    ``get``), ``io.prefetch.miss`` (staged synchronously or still in
    flight), ``engine.h2d.overlap_s`` (wall spent staging in the
    background — transfer time hidden behind compute); ``stage_fn``
    itself accounts ``engine.h2d.bytes``.
    """

    def __init__(self, stage_fn, n_chunks: int, depth: int = 2):
        self._stage = stage_fn
        self._n = int(n_chunks)
        self._depth = max(int(depth), 0)
        self._futs: Dict[int, object] = {}
        self._pool = None
        self._degraded = False
        # eager start: stage chunk 0's window now so whole-query
        # compile time hides the ring warmup
        self._schedule_ahead(-1)

    def reset(self, next_i: int = 0) -> None:
        """Rewind the ring for another pass over the same chunks (the
        repeat-execution path of a cached chunked query), pre-staging
        from chunk ``next_i`` (chunk 0's device args usually persist
        from the first pass)."""
        for fut in self._futs.values():
            fut.cancel()
        self._futs.clear()
        if not self._degraded:
            self._schedule_ahead(next_i - 1)

    def _ensure_pool(self):
        if self._pool is None:
            from concurrent.futures import ThreadPoolExecutor
            # one thread: H2D staging is serialized by the transfer
            # engine anyway, and a single writer keeps the host staging
            # buffers single-producer
            self._pool = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="ndstpu-h2d")
        return self._pool

    def _stage_bg(self, i: int):
        from ndstpu import faults
        faults.check("io.prefetch", key=str(i))
        t0 = time.monotonic()
        try:
            return self._stage(i)
        finally:
            obs.inc("engine.h2d.overlap_s", time.monotonic() - t0)

    def _schedule_ahead(self, i: int) -> None:
        if self._degraded or self._depth == 0:
            return
        for j in range(i + 1, min(i + 1 + self._depth, self._n)):
            if j not in self._futs:
                self._futs[j] = self._ensure_pool().submit(
                    self._stage_bg, j)

    def get(self, i: int):
        fut = self._futs.pop(i, None)
        if fut is not None:
            done = fut.done()
            obs.inc("io.prefetch.hit" if done else "io.prefetch.miss")
            t0 = time.monotonic()
            try:
                args = fut.result()
                if not done:   # ring behind compute: visible stall
                    obs.inc("engine.h2d.wait_s", time.monotonic() - t0)
                self._schedule_ahead(i)
                return args
            except Exception as e:  # noqa: BLE001 — degrade, don't wedge
                self._degrade(e)
        else:
            obs.inc("io.prefetch.miss")
            self._schedule_ahead(i)
        return self._stage(i)

    def _degrade(self, exc: Exception) -> None:
        if not self._degraded:
            self._degraded = True
            obs.inc("io.prefetch.degraded")
            obs.annotate(
                io_prefetch_degraded=f"{type(exc).__name__}: {exc}")
        for fut in self._futs.values():
            fut.cancel()
        self._futs.clear()

    def close(self) -> None:
        for fut in self._futs.values():
            fut.cancel()
        self._futs.clear()
        if self._pool is not None:
            self._pool.shutdown(wait=False)
            self._pool = None


# ---------------------------------------------------------------------------
# jnp expression evaluation (device mirror of ex.Evaluator)
# ---------------------------------------------------------------------------


def _table_content_fp(t) -> str:
    """Content hash of a columnar.Table: column names, ctypes,
    dictionaries, and a crc over data+validity bytes.  Process-stable —
    id()-keyed fingerprints made persisted compile records unmatchable
    across processes (and pinned key stability on object lifetime).
    Memoized on the Table (immutable once inlined): _plan_fp runs at
    every memo node, and re-CRCing a large inline table per node would
    turn an O(1) lookup into O(bytes)."""
    # memo token guards against mutation after first fingerprinting: a
    # stale fp would silently key segment reuse and persisted compile
    # records, so the memo is only honored while the table still holds
    # the SAME column objects (identity, with strong refs held — bare
    # id()s could be recycled after GC) and row count
    token = (t.num_rows, tuple(t.columns.values()))
    cached = getattr(t, "_content_fp", None)
    if cached is not None and cached[0][0] == token[0] and \
            len(cached[0][1]) == len(token[1]) and \
            all(a is b for a, b in zip(cached[0][1], token[1])):
        return cached[1]
    import zlib
    parts = []
    for name in t.column_names:
        c = t.columns[name]
        data = np.ascontiguousarray(np.asarray(c.data))
        crc = zlib.crc32(data.tobytes())
        if c.valid is not None:
            crc = zlib.crc32(np.ascontiguousarray(c.valid).tobytes(), crc)
        if c.dictionary is not None:
            # length-prefix each entry: ['ab','c'] must not collide
            # with ['a','bc'] under bare concatenation
            crc = zlib.crc32(str(len(c.dictionary)).encode(), crc)
            for s in c.dictionary:
                b = str(s).encode()
                crc = zlib.crc32(f"{len(b)}:".encode() + b, crc)
        parts.append(f"{name}:{c.ctype!r}:{data.dtype}{data.shape}:{crc}")
    fp = f"T({t.num_rows};" + ";".join(parts) + ")"
    try:
        t._content_fp = (token, fp)
    except (AttributeError, TypeError):
        pass  # slotted/frozen table: recompute next time
    return fp


def _plan_fp(o, out: Optional[list] = None) -> Optional[str]:
    """Structural fingerprint of a plan/expression tree.

    Unlike ``repr``, covers EVERY dataclass field (Scan's repr hides its
    pruned columns and pushed-down predicate; Literal's hides its ctype).
    Every leaf is fingerprinted by CONTENT (inline tables by column
    crc via _table_content_fp), never by id()/default repr — the
    fingerprint must be stable across processes because it keys
    persisted compile records and the replay programs' argument names
    (which feed the XLA persistent-cache key)."""
    top = out is None
    if top:
        out = []
    if isinstance(o, lp.InlineTable):
        out.append(f"IT{_table_content_fp(o.table)}")
    elif dataclasses.is_dataclass(o) and not isinstance(o, type):
        out.append(type(o).__name__)
        out.append("(")
        for f in dataclasses.fields(o):
            _plan_fp(getattr(o, f.name), out)
            out.append(",")
        out.append(")")
    elif isinstance(o, (list, tuple)):
        out.append("[")
        for x in o:
            _plan_fp(x, out)
            out.append(",")
        out.append("]")
    elif isinstance(o, np.ndarray):
        # repr() elides long arrays ("...") — fingerprint the bytes
        import zlib
        out.append(f"ND{o.dtype}{o.shape}{zlib.crc32(o.tobytes())}")
    else:
        r = repr(o)
        # default object repr ("<X object at 0x...>") embeds a
        # process-local address; a fingerprint built from it can never
        # match across processes and would silently disable record
        # reuse.  Anchored to the default-repr shape — a bare
        # " at 0x" substring check would false-positive on ordinary
        # string literals in predicates.
        import re as _re
        if _re.search(r"<[^<>]* at 0x[0-9a-fA-F]+>", r):
            raise TypeError(
                f"_plan_fp: {type(o).__name__} has no content-based "
                f"repr; add an explicit fingerprint branch")
        out.append(r)
    if top:
        return "".join(out)
    return None


class Unsupported(Exception):
    """Raised at build time when an expr/plan has no device lowering.

    ``code`` is the static-analyzer diagnostic (NDS2xx, see
    ndstpu/analysis/diagnostics.py) that predicts this raise site, so a
    runtime fallback can say WHY in the tracer sidecar and run ledger.
    Data-dependent guards the analyzer cannot see statically (rank
    pairing capacity, distinct column type) stay uncoded."""

    def __init__(self, msg: str, code: Optional[str] = None):
        super().__init__(msg)
        self.code = code


def _civil_from_days(days: jnp.ndarray):
    """days since 1970-01-01 -> (year, month, day), integer math only
    (int32 throughout: safe while |days| + 719468 < 2^31, i.e. any
    date the engine can represent)."""
    z = days.astype(jnp.int32) + 719468
    era = jnp.floor_divide(z, 146097)
    doe = z - era * 146097
    yoe = jnp.floor_divide(
        doe - doe // 1460 + doe // 36524 - doe // 146096, 365)
    y = yoe + era * 400
    doy = doe - (365 * yoe + yoe // 4 - yoe // 100)
    mp = jnp.floor_divide(5 * doy + 2, 153)
    d = doy - jnp.floor_divide(153 * mp + 2, 5) + 1
    m = mp + jnp.where(mp < 10, 3, -9)
    year = y + (m <= 2)
    return year.astype(jnp.int32), m.astype(jnp.int32), d.astype(jnp.int32)


def _like_to_regex(pattern: str) -> str:
    out = []
    for ch in pattern:
        if ch == "%":
            out.append(".*")
        elif ch == "_":
            out.append(".")
        else:
            out.append(re.escape(ch))
    return "".join(out)


def _dict_lookup_bool(c: DCol, fn) -> jnp.ndarray:
    """Host predicate per dictionary entry -> device bool gather."""
    hits = np.array([bool(fn(str(x))) for x in c.dictionary], dtype=bool)
    table = jnp.asarray(np.concatenate([hits, [False]]))  # -1 -> False
    return table[c.data]


def _dict_remap(c: DCol, fn) -> DCol:
    """Host string->string map per dictionary entry -> new dict + gather."""
    vals = [fn(str(x)) for x in c.dictionary]
    uniq = np.unique(np.asarray(vals, dtype=str)) if vals else \
        np.empty(0, dtype=str)
    remap = (np.searchsorted(uniq, np.asarray(vals, dtype=str))
             .astype(np.int32) if vals else np.empty(0, np.int32))
    table = jnp.asarray(np.concatenate([remap, [-1]]).astype(np.int32))
    return DCol(table[c.data], c.valid, STRING, uniq.astype(object))


def _translate(c: DCol, merged: np.ndarray) -> jnp.ndarray:
    """Device codes of `c` re-expressed in `merged` dictionary order.
    Unmatched/-1 codes become -2 (never equal to a valid code)."""
    if c.dictionary is None or len(c.dictionary) == 0:
        return jnp.full(c.data.shape, -2, jnp.int32)
    pos = np.searchsorted(merged, c.dictionary.astype(str))
    posc = np.clip(pos, 0, max(len(merged) - 1, 0))
    hit = merged[posc] == c.dictionary.astype(str) if len(merged) else \
        np.zeros(len(c.dictionary), dtype=bool)
    mapping = np.where(hit, posc, -2).astype(np.int32)
    table = jnp.asarray(np.concatenate([mapping, [-2]]).astype(np.int32))
    return table[c.data]


def _merged_dict(cols: Sequence[DCol]) -> np.ndarray:
    parts = [c.dictionary.astype(str) for c in cols
             if c.dictionary is not None and len(c.dictionary)]
    if not parts:
        return np.empty(0, dtype=str)
    return np.unique(np.concatenate(parts))


# ---------------------------------------------------------------------------
# runtime parameter binding (canonical plans — analysis/canon.py)
# ---------------------------------------------------------------------------
#
# Canonicalized plans carry ex.Param / ex.InParam where the SQL text had
# literals; the concrete values travel OUTSIDE the plan as an
# ex.ParamBinding, so one traced program serves every rendering of a
# template.  Scalars become broadcast columns (no point bounds — bounds
# would bake the value back into the traced program); string parameters
# become host-computed hit tables over the operand's dictionary, exactly
# like literal string predicates, except the table is a replay ARGUMENT
# instead of a traced constant.  During discovery every table/vector
# materialization is recorded into the program's ``param_spec`` so the
# replay argument subtree can be rebuilt for any later binding; the
# jitted replay pops the spec positionally, mirroring the size-plan
# record discipline.

_ACTIVE_PARAMS = threading.local()


def _active_params() -> Optional["_ParamCtx"]:
    return getattr(_ACTIVE_PARAMS, "ctx", None)


def _param_scalar_np(value, ctype: DType):
    """Host conversion of one bound scalar to its device representation
    (mirrors JEval._lit dtype choices, minus the point bounds)."""
    if ctype.kind == "bool":
        return np.bool_(value)
    if ctype.kind == "decimal":
        v = value * 10 ** ctype.scale if isinstance(value, int) \
            else round(value * 10 ** ctype.scale)
        return np.int64(v)
    if ctype.kind == "float64":
        return np.float64(value)
    if ctype.kind in ("int32", "date"):
        return np.int32(value)
    if ctype.kind == "int64":
        return np.int64(value)
    raise Unsupported(f"parameter scalar {ctype.kind}", code="NDS201")


_PDICT_OPS = {
    "=": lambda a, b: a == b, "<>": lambda a, b: a != b,
    "<": lambda a, b: a < b, "<=": lambda a, b: a <= b,
    ">": lambda a, b: a > b, ">=": lambda a, b: a >= b,
}


def _pdict_hits(value, op: str, swapped: bool, dictionary) -> np.ndarray:
    """Hit table over a sorted string dictionary for one bound string
    value (or IN tuple): len(dict)+1 bools, last entry False so the -1
    NULL code gathers False (cf. _dict_lookup_bool).  Host python string
    comparison matches np.unique's lexicographic dictionary order, so
    ordered operators agree with the merged-dict literal path."""
    if op == "in":
        vals = set(str(v) for v in value)
        hits = [str(x) in vals for x in dictionary]
    else:
        fn = _PDICT_OPS[op]
        v = str(value)
        hits = [fn(v, str(x)) if swapped else fn(str(x), v)
                for x in dictionary]
    return np.asarray(hits + [False], dtype=bool)


def _gcode_np(value, dictionary) -> np.int32:
    """Bind-time dictionary-code lookup for a scalar string parameter:
    the code of ``value`` in the frozen sorted dictionary, or the miss
    sentinel ``len(dictionary)`` — outside every real code AND distinct
    from the -1/-2 NULL/translate-miss codes that appear in DATA, so
    ``= miss`` is never true and ``<> miss`` holds for every present
    row."""
    from ndstpu import obs
    obs.inc("engine.dict.lookups")
    v = str(value)
    n = len(dictionary)
    if n:
        pos = int(np.searchsorted(
            np.asarray(dictionary).astype(str), v))
        if pos < n and str(dictionary[pos]) == v:
            return np.int32(pos)
    obs.inc("engine.dict.misses")
    return np.int32(n)


def _pvec_np(values, ctype: DType) -> np.ndarray:
    """Coerced device-representation vector for a bound IN-list over a
    numeric/date operand (mirrors JEval._in_list's literal path: decimal
    values arrive scale-shifted from coerce_in_values)."""
    vals, _had_null = ex.coerce_in_values(ctype, values)
    if ctype.kind == "float64":
        return np.array(vals, dtype=np.float64)
    return np.array(vals, dtype=np.int64)


class _ParamCtx:
    """One execution's bound parameters.

    mode ``concrete``: ``values`` holds python literals; hit tables and
    vectors are computed on host directly (and appended to ``spec`` when
    ``record`` is set, i.e. during discovery).  mode ``trace``: inside
    the jitted replay — scalars/tables/vectors are read from the traced
    ``"\\x00params"`` argument subtree; non-scalar entries pop ``spec``
    positionally, exactly like the size-plan record."""

    def __init__(self, values, mode: str, spec: Optional[list] = None,
                 traced: Optional[dict] = None, record: bool = False):
        self.values = values
        self.mode = mode
        self.spec = spec if spec is not None else []
        self.pos = 0
        self.traced = traced if traced is not None else {}
        self.record = record

    def _pop(self, kind: str) -> int:
        j = self.pos
        self.pos += 1
        if j >= len(self.spec) or self.spec[j][0] != kind:
            raise RuntimeError(f"param-spec drift (expected {kind})")
        return j

    def scalar(self, slot: int, ctype: DType, cap: int) -> DCol:
        if self.mode == "trace":
            v = self.traced[f"s{slot}"]
        else:
            v = _param_scalar_np(self.values[slot], ctype)
        data = jnp.broadcast_to(jnp.asarray(v), (cap,))
        return DCol(data, jnp.ones(cap, bool), ctype)

    def str_table(self, slot: int, op: str, swapped: bool,
                  dictionary) -> jnp.ndarray:
        if self.mode == "trace":
            return self.traced[f"d{self._pop('pdict')}"]
        if self.record:
            self.spec.append(("pdict", slot, op, swapped,
                              np.asarray(dictionary, dtype=object)))
        return jnp.asarray(
            _pdict_hits(self.values[slot], op, swapped, dictionary))

    def num_vec(self, slot: int, ctype: DType) -> jnp.ndarray:
        if self.mode == "trace":
            return self.traced[f"v{self._pop('pvec')}"]
        if self.record:
            self.spec.append(("pvec", slot, ctype))
        return jnp.asarray(_pvec_np(self.values[slot], ctype))

    def str_code(self, slot: int, dictionary) -> jnp.ndarray:
        """Scalar dict-code string parameter (=/<> against a frozen
        global dictionary): one traced int32 instead of a len(dict)+1
        hit table per binding."""
        if self.mode == "trace":
            return self.traced[f"g{self._pop('gcode')}"]
        if self.record:
            self.spec.append(("gcode", slot,
                              np.asarray(dictionary, dtype=object)))
        return jnp.asarray(_gcode_np(self.values[slot], dictionary))


@contextlib.contextmanager
def _params_bound(ctx: Optional[_ParamCtx]):
    """Install a parameter context for the device evaluator AND — when
    concrete values are present — the numpy fallback path
    (ex.bound_params) for the dynamic extent."""
    prev = getattr(_ACTIVE_PARAMS, "ctx", None)
    _ACTIVE_PARAMS.ctx = ctx
    try:
        if ctx is not None and ctx.values is not None:
            with ex.bound_params(ctx.values):
                yield
        else:
            yield
    finally:
        _ACTIVE_PARAMS.ctx = prev


def _param_args_np(spec, binding: Optional[ex.ParamBinding]) -> dict:
    """Host argument subtree (the ``"\\x00params"`` replay input) for one
    program under one binding: every bindable scalar slot plus one hit
    table / coerced vector per recorded spec entry."""
    out = {}
    if binding is None:
        return out
    for slot, ctype in binding.scalars:
        out[f"s{slot}"] = _param_scalar_np(binding.values[slot], ctype)
    for j, ent in enumerate(spec or ()):
        if ent[0] == "pdict":
            _tag, slot, op, swapped, dic = ent
            out[f"d{j}"] = _pdict_hits(binding.values[slot], op,
                                       swapped, dic)
        elif ent[0] == "gcode":
            _tag, slot, dic = ent
            out[f"g{j}"] = _gcode_np(binding.values[slot], dic)
        else:
            _tag, slot, ctype = ent
            out[f"v{j}"] = _pvec_np(binding.values[slot], ctype)
    return out


class JEval:
    """Evaluates an Expr over a DTable with jnp ops (traceable)."""

    _CMP = {"=", "<>", "<", "<=", ">", ">="}
    _ARITH = {"+", "-", "*", "/", "%"}

    def __init__(self, table: DTable):
        self.t = table
        self.cap = table.capacity

    # -- helpers -------------------------------------------------------------

    def _lit(self, value, ctype: Optional[DType]) -> DCol:
        cap = self.cap
        if value is None:
            ct = ctype or INT32
            return DCol(jnp.zeros(cap, jnp_dtype(ct)),
                        jnp.zeros(cap, bool), ct,
                        np.empty(0, object) if ct.kind == "string" else None)
        valid = jnp.ones(cap, bool)
        if isinstance(value, bool):
            return DCol(jnp.full(cap, value, jnp.bool_), valid, BOOL)
        if isinstance(value, int):
            # point bounds: every valid row is exactly this value —
            # lets Case-of-literals keys (the fusion pass's bucket id)
            # stay on the small-domain group-by/bitmap paths
            ct = ctype or (INT64 if abs(value) > 2 ** 31 - 1 else INT32)
            if ct.kind == "decimal":
                v = value * 10 ** ct.scale
                return DCol(jnp.full(cap, v, jnp.int64),
                            valid, ct, bounds=(v, v))
            return DCol(jnp.full(cap, value, jnp_dtype(ct)), valid, ct,
                        bounds=(int(value), int(value)))
        if isinstance(value, float):
            if ctype and ctype.kind == "decimal":
                v = round(value * 10 ** ctype.scale)
                return DCol(jnp.full(cap, v, jnp.int64),
                            valid, ctype, bounds=(v, v))
            return DCol(jnp.full(cap, value, jnp.float64), valid, FLOAT64)
        if isinstance(value, str):
            d = np.array([value], dtype=object)
            return DCol(jnp.zeros(cap, jnp.int32), valid, STRING, d)
        raise Unsupported(f"literal {value!r}", code="NDS201")

    def cast(self, c: DCol, target: DType) -> DCol:
        k, tk = c.ctype.kind, target.kind
        if k == tk and (tk != "decimal" or c.ctype.scale == target.scale):
            if tk != "decimal":
                return c
            if target.precision < c.ctype.precision:
                # Spark non-ANSI overflow: out-of-precision values -> NULL
                limit = 10 ** target.precision
                ok = jnp.abs(c.data) < limit
                b = (-(limit - 1), limit - 1)
                if c.bounds is not None:
                    b = (max(b[0], c.bounds[0]), min(b[1], c.bounds[1]))
                return DCol(c.data, c.valid & ok, target, c.dictionary,
                            bounds=b if b[0] <= b[1] else None)
            return DCol(c.data, c.valid, target, c.dictionary,
                        bounds=c.bounds)
        if tk == "float64":
            if k == "decimal":
                data = c.data.astype(jnp.float64) / (10 ** c.ctype.scale)
            elif k == "string":
                data, valid = self._string_parse_float(c)
                return DCol(data, valid, FLOAT64)
            else:
                data = c.data.astype(jnp.float64)
            return DCol(data, c.valid, FLOAT64)
        if tk == "decimal":
            scale = 10 ** target.scale
            bounds = None
            if k == "decimal":
                shift = target.scale - c.ctype.scale
                if shift >= 0:
                    data = c.data * (10 ** shift)
                    if c.bounds is not None:
                        m = 10 ** shift
                        bounds = (c.bounds[0] * m, c.bounds[1] * m)
                else:
                    d = 10 ** (-shift)
                    sign = jnp.sign(c.data)
                    data = sign * ((jnp.abs(c.data) + d // 2) // d)
                    if c.bounds is not None:
                        # round-half-away-from-zero is monotonic
                        def _rd(v: int) -> int:
                            s = -1 if v < 0 else 1
                            return s * ((abs(v) + d // 2) // d)
                        bounds = (_rd(c.bounds[0]), _rd(c.bounds[1]))
            elif k == "float64":
                x = c.data * scale
                data = (jnp.floor(jnp.abs(x) + 0.5) *
                        jnp.sign(x)).astype(jnp.int64)
            elif k == "string":
                f, valid = self._string_parse_float(c)
                x = f * scale
                data = (jnp.floor(jnp.abs(x) + 0.5) *
                        jnp.sign(x)).astype(jnp.int64)
                return DCol(data, valid, target)
            else:
                data = c.data.astype(jnp.int64) * scale
                if k in ("int32", "int64") and c.bounds is not None:
                    bounds = (c.bounds[0] * scale, c.bounds[1] * scale)
                elif k == "bool":
                    bounds = (0, scale)
            return DCol(data.astype(jnp.int64), c.valid, target,
                        bounds=bounds)
        if tk in ("int32", "int64"):
            dt = jnp.int64 if tk == "int64" else jnp.int32
            bounds = None
            if k == "decimal":
                data = jnp.trunc(
                    c.data / (10 ** c.ctype.scale)).astype(dt)
                if c.bounds is not None and \
                        max(abs(c.bounds[0]), abs(c.bounds[1])) < (1 << 53):
                    # the data path divides in float64; below 2^53 the
                    # scaled value is exact and trunc(fl(v/s)) == v//s
                    # (an up-crossing needs s-r <= hi*2^-53 < 1, and
                    # exact multiples divide exactly), so exact-integer
                    # bounds match the computed values.  At or above
                    # 2^53 they can disagree -> no bounds (sort path).
                    s = 10 ** c.ctype.scale
                    # trunc-toward-zero is monotonic
                    def _tr(v: int) -> int:
                        return -((-v) // s) if v < 0 else v // s
                    bounds = (_tr(c.bounds[0]), _tr(c.bounds[1]))
            elif k == "string":
                f, valid = self._string_parse_float(c)
                return DCol(f.astype(dt), valid, target)
            else:
                data = c.data.astype(dt)
                if k in ("int32", "int64") and c.bounds is not None:
                    bounds = c.bounds
                elif k == "bool":
                    bounds = (0, 1)
            if bounds is not None and tk == "int32" and not (
                    -(1 << 31) <= bounds[0] and bounds[1] < (1 << 31)):
                # narrowing may wrap valid values; no safe bounds
                bounds = None
            return DCol(data, c.valid, target, bounds=bounds)
        if tk == "date":
            if k == "string":
                return self._string_parse_date(c)
            return DCol(c.data.astype(jnp.int32), c.valid, DATE)
        if tk == "bool":
            return DCol(c.data.astype(jnp.bool_), c.valid, BOOL)
        raise Unsupported(f"cast {c.ctype} -> {target}", code="NDS204")

    def _string_parse_float(self, c: DCol):
        vals = np.zeros(len(c.dictionary) + 1, dtype=np.float64)
        ok = np.zeros(len(c.dictionary) + 1, dtype=bool)
        for i, s in enumerate(c.dictionary):
            try:
                vals[i] = float(str(s))
                ok[i] = True
            except ValueError:
                pass
        data = jnp.asarray(vals)[c.data]
        valid = c.valid & jnp.asarray(ok)[c.data]
        return data, valid

    def _string_parse_date(self, c: DCol) -> DCol:
        base = np.datetime64("1970-01-01")
        vals = np.zeros(len(c.dictionary) + 1, dtype=np.int32)
        ok = np.zeros(len(c.dictionary) + 1, dtype=bool)
        for i, s in enumerate(c.dictionary):
            try:
                vals[i] = columnar.parse_date_days(str(s))
                ok[i] = True
            except ValueError:
                pass
        data = jnp.asarray(vals)[c.data]
        valid = c.valid & jnp.asarray(ok)[c.data]
        return DCol(data, valid, DATE)

    # -- entry ---------------------------------------------------------------

    def eval(self, e: ex.Expr) -> DCol:
        if isinstance(e, ex.ColumnRef):
            return self.t.column(e.name)
        if isinstance(e, ex.Literal):
            return self._lit(e.value, e.ctype)
        if isinstance(e, ex.Cast):
            return self.cast(self.eval(e.operand), e.target)
        if isinstance(e, ex.BinOp):
            return self._binop(e)
        if isinstance(e, ex.UnaryOp):
            return self._unary(e)
        if isinstance(e, ex.Case):
            return self._case(e)
        if isinstance(e, ex.Func):
            return self._func(e)
        if isinstance(e, ex.InList):
            return self._in_list(e)
        if isinstance(e, ex.Param):
            return self._param(e)
        if isinstance(e, ex.InParam):
            return self._in_param(e)
        raise Unsupported(f"expr {type(e).__name__}", code="NDS201")

    # -- operators -----------------------------------------------------------

    def _binop(self, e: ex.BinOp) -> DCol:
        op = e.op
        if op in ("and", "or"):
            lc, rc = self.eval(e.left), self.eval(e.right)
            ld = lc.data.astype(bool) & lc.valid
            rd = rc.data.astype(bool) & rc.valid
            if op == "and":
                data = ld & rd
                definite_false = (~lc.data.astype(bool) & lc.valid) | \
                                 (~rc.data.astype(bool) & rc.valid)
                valid = (lc.valid & rc.valid) | definite_false
            else:
                data = ld | rd
                valid = (lc.valid & rc.valid) | ld | rd
            return DCol(data, valid, BOOL)
        if op in self._CMP:
            pc = self._param_compare(e, op)
            if pc is not None:
                return pc
        lc, rc = self.eval(e.left), self.eval(e.right)
        if op in self._CMP:
            return self._compare(op, lc, rc)
        if op in self._ARITH:
            return self._arith(op, lc, rc)
        if op == "||":
            return self._concat_pair(lc, rc)
        raise Unsupported(f"binop {op}", code="NDS202")

    def _align_compare(self, lc: DCol, rc: DCol):
        lk, rk = lc.ctype.kind, rc.ctype.kind
        if lk == "string" and rk == "string":
            if lc.dictionary is not None and rc.dictionary is not None and \
                    len(lc.dictionary) == len(rc.dictionary) and \
                    np.array_equal(lc.dictionary, rc.dictionary):
                return lc.data, rc.data
            merged = _merged_dict([lc, rc])
            return _translate(lc, merged), _translate(rc, merged)
        if lk == "decimal" or rk == "decimal":
            if "float64" in (lk, rk):
                return (self.cast(lc, FLOAT64).data,
                        self.cast(rc, FLOAT64).data)
            s = max(lc.ctype.scale if lk == "decimal" else 0,
                    rc.ctype.scale if rk == "decimal" else 0)
            tgt = decimal(38, s)
            return self.cast(lc, tgt).data, self.cast(rc, tgt).data
        if lk == "float64" or rk == "float64":
            return (self.cast(lc, FLOAT64).data,
                    self.cast(rc, FLOAT64).data)
        return lc.data, rc.data

    def _compare(self, op: str, lc: DCol, rc: DCol) -> DCol:
        # implicit string->date coercion (Spark semantics), mirroring
        # ex.Evaluator._compare so both backends stay bit-identical:
        # without it a bare `d_date >= '2002-4-01'` compared date days
        # against the literal's dictionary code
        if lc.ctype.kind == "date" and rc.ctype.kind == "string":
            rc = self._string_to_date(rc)
        elif rc.ctype.kind == "date" and lc.ctype.kind == "string":
            lc = self._string_to_date(lc)
        ld, rd = self._align_compare(lc, rc)
        data = {"=": lambda: ld == rd, "<>": lambda: ld != rd,
                "<": lambda: ld < rd, "<=": lambda: ld <= rd,
                ">": lambda: ld > rd, ">=": lambda: ld >= rd}[op]()
        return DCol(data, lc.valid & rc.valid, BOOL)

    def _string_to_date(self, c: DCol) -> DCol:
        """Parse string codes as dates through a host-parsed dictionary
        table; unparseable entries and negative codes become NULL
        (same table as ex.string_to_date_column)."""
        days, ok = ex.parse_dictionary_days(c.dictionary)
        if not len(days):
            return DCol(jnp.zeros(self.cap, jnp.int32),
                        jnp.zeros(self.cap, bool), DATE)
        codes_ok = c.data >= 0
        idx = jnp.clip(c.data, 0, len(days) - 1)
        out = jnp.where(codes_ok, jnp.asarray(days)[idx], jnp.int32(0))
        valid = c.valid & codes_ok & jnp.asarray(ok)[idx]
        return DCol(out, valid, DATE)

    def _arith(self, op: str, lc: DCol, rc: DCol) -> DCol:
        lk, rk = lc.ctype.kind, rc.ctype.kind
        valid = lc.valid & rc.valid
        if lk == "date" and rk in ("int32", "int64"):
            delta = rc.data.astype(jnp.int32)
            data = lc.data + (delta if op == "+" else -delta)
            return DCol(data, valid, DATE)
        if op == "/":
            ld = self.cast(lc, FLOAT64).data
            rd = self.cast(rc, FLOAT64).data
            safe = jnp.where(rd == 0, 1.0, rd)
            return DCol(ld / safe, valid & (rd != 0), FLOAT64)
        if lk == "decimal" or rk == "decimal":
            if "float64" in (lk, rk):
                ld = self.cast(lc, FLOAT64).data
                rd = self.cast(rc, FLOAT64).data
                data = {"+": ld + rd, "-": ld - rd, "*": ld * rd,
                        "%": jnp.mod(ld, jnp.where(rd == 0, 1, rd))}[op]
                return DCol(data, valid, FLOAT64)
            ls = lc.ctype.scale if lk == "decimal" else 0
            rs = rc.ctype.scale if rk == "decimal" else 0
            if op == "*":
                data = lc.data.astype(jnp.int64) * rc.data.astype(jnp.int64)
                return DCol(data, valid, decimal(38, ls + rs))
            s = max(ls, rs)
            ld = lc.data.astype(jnp.int64) * (10 ** (s - ls))
            rd = rc.data.astype(jnp.int64) * (10 ** (s - rs))
            if op == "%":
                safe = jnp.where(rd == 0, 1, rd)
                return DCol(jnp.mod(ld, safe), valid & (rd != 0),
                            decimal(38, s))
            data = ld + rd if op == "+" else ld - rd
            return DCol(data, valid, decimal(38, s))
        tgt = ex.common_type(lc.ctype, rc.ctype)
        ld = self.cast(lc, tgt).data
        rd = self.cast(rc, tgt).data
        if op == "%":
            safe = jnp.where(rd == 0, 1, rd)
            return DCol(jnp.mod(ld, safe), valid & (rd != 0), tgt)
        data = {"+": ld + rd, "-": ld - rd, "*": ld * rd}[op]
        return DCol(data, valid, tgt)

    def _unary(self, e: ex.UnaryOp) -> DCol:
        c = self.eval(e.operand)
        if e.op == "not":
            return DCol(~c.data.astype(bool), c.valid, BOOL)
        if e.op == "neg":
            return DCol(-c.data, c.valid, c.ctype)
        if e.op == "isnull":
            return DCol(~c.valid, jnp.ones(self.cap, bool), BOOL)
        if e.op == "isnotnull":
            return DCol(c.valid, jnp.ones(self.cap, bool), BOOL)
        raise Unsupported(f"unary {e.op}", code="NDS203")

    def _case(self, e: ex.Case) -> DCol:
        conds, vals = [], []
        for cond, val in e.whens:
            cc = self.eval(cond)
            conds.append(cc.data.astype(bool) & cc.valid)
            vals.append(self.eval(val))
        default = self.eval(e.default) if e.default is not None else None
        cands = vals + ([default] if default is not None else [])
        tgt = cands[0].ctype
        for c in cands[1:]:
            if ex.is_numeric(c.ctype) and ex.is_numeric(tgt):
                tgt = ex.common_type(tgt, c.ctype)
            elif c.ctype.kind != tgt.kind:
                tgt = c.ctype if tgt.kind == "int32" else tgt
        if tgt.kind == "string":
            # all-branch merged dictionary, then code selection on device
            scols = [self.cast(v, STRING) for v in vals]
            sdef = self.cast(default, STRING) if default is not None else None
            allc = scols + ([sdef] if sdef is not None else [])
            merged = _merged_dict(allc)
            data = jnp.full(self.cap, -2, jnp.int32)
            valid = jnp.zeros(self.cap, bool)
            taken = jnp.zeros(self.cap, bool)
            for cond, vc in zip(conds, scols):
                sel = cond & ~taken
                data = jnp.where(sel, _translate(vc, merged), data)
                valid = jnp.where(sel, vc.valid, valid)
                taken = taken | cond
            if sdef is not None:
                data = jnp.where(taken, data, _translate(sdef, merged))
                valid = jnp.where(taken, valid, sdef.valid)
            data = jnp.where(valid, data, -1)
            return DCol(data, valid, STRING, merged.astype(object))
        data = jnp.zeros(self.cap, jnp_dtype(tgt))
        valid = jnp.zeros(self.cap, bool)
        taken = jnp.zeros(self.cap, bool)
        branch_bounds = []
        for cond, val in zip(conds, vals):
            vc = self.cast(val, tgt)
            sel = cond & ~taken
            data = jnp.where(sel, vc.data, data)
            valid = jnp.where(sel, vc.valid, valid)
            taken = taken | cond
            branch_bounds.append(vc.bounds)
        if default is not None:
            dc = self.cast(default, tgt)
            data = jnp.where(taken, data, dc.data)
            valid = jnp.where(taken, valid, dc.valid)
            # a NULL-literal default contributes no VALID rows, so it
            # cannot widen the bounds of the output's valid values
            if not (isinstance(e.default, ex.Literal)
                    and e.default.value is None):
                branch_bounds.append(dc.bounds)
        bounds = None
        if tgt.kind in ("int32", "int64", "decimal") and branch_bounds \
                and all(b is not None for b in branch_bounds):
            # every valid output row carries some branch's valid value,
            # so the union of branch bounds bounds the output
            bounds = (min(b[0] for b in branch_bounds),
                      max(b[1] for b in branch_bounds))
        return DCol(data.astype(jnp_dtype(tgt)), valid, tgt,
                    bounds=bounds)

    def _in_list(self, e: ex.InList) -> DCol:
        c = self.eval(e.operand)
        had_null = False
        if c.ctype.kind == "string":
            vals = set(str(v) for v in e.values)
            data = _dict_lookup_bool(c, lambda s: s in vals)
        elif c.ctype.kind == "decimal":
            vals, had_null = ex.coerce_in_values(c.ctype, e.values)
            data = jnp.isin(c.data, jnp.asarray(
                np.array(vals, dtype=np.int64))) if vals else \
                jnp.zeros(c.capacity, bool)
        else:
            vals, had_null = ex.coerce_in_values(c.ctype, e.values)
            if not vals:
                data = jnp.zeros(c.capacity, bool)
            else:
                arr = np.asarray(vals)
                if arr.dtype == object or arr.dtype.kind in "US":
                    raise Unsupported(f"IN-list literals {arr.dtype} for "
                                      f"{c.ctype.kind} column",
                                      code="NDS212")
                data = jnp.isin(c.data, jnp.asarray(arr))
        if e.negated:
            # x NOT IN (..., NULL) is never TRUE (NULL semantics)
            data = jnp.zeros_like(data) if had_null else ~data
        return DCol(data, c.valid, BOOL)

    # -- bound parameters (canonical plans) ----------------------------------

    def _param(self, e: ex.Param) -> DCol:
        ctx = _active_params()
        if ctx is None or e.shape:
            raise Unsupported(f"unbound parameter S{e.slot}",
                              code="NDS201")
        if e.ctype.kind == "string":
            # string scalars only bind through the dictionary-compare /
            # IN intercepts; reaching generic eval means the
            # canonicalizer lifted a string the device cannot broadcast
            raise Unsupported("string parameter outside dictionary "
                              "context", code="NDS206")
        return ctx.scalar(e.slot, e.ctype, self.cap)

    def _param_compare(self, e: ex.BinOp, op: str) -> Optional[DCol]:
        """String-parameter comparison: host hit table over the other
        side's dictionary (the parametric twin of the literal-string
        merged-dict path)."""
        ctx = _active_params()
        if ctx is None:
            return None
        for par, other, swapped in ((e.right, e.left, False),
                                    (e.left, e.right, True)):
            if isinstance(par, ex.Param) and not par.shape and \
                    par.ctype.kind == "string":
                oc = self.eval(other)
                if oc.ctype.kind != "string" or oc.dictionary is None:
                    raise Unsupported("string parameter vs non-dictionary"
                                      " operand", code="NDS206")
                if op in ("=", "<>"):
                    # scalar dict-code param: the bound value resolves
                    # to one dictionary code on the host (miss ->
                    # len(dict) sentinel), so equality runs on raw codes
                    # and every binding replays one traced scalar
                    code = ctx.str_code(par.slot, oc.dictionary)
                    eq = oc.data == code
                    return DCol(eq if op == "=" else ~eq, oc.valid, BOOL)
                table = ctx.str_table(par.slot, op, swapped,
                                      oc.dictionary)
                return DCol(table[oc.data], oc.valid, BOOL)
        return None

    def _in_param(self, e: ex.InParam) -> DCol:
        ctx = _active_params()
        if ctx is None:
            raise Unsupported(f"unbound parameter P{e.slot}",
                              code="NDS201")
        c = self.eval(e.operand)
        if c.ctype.kind == "string":
            if c.dictionary is None:
                raise Unsupported("IN parameter on non-dictionary "
                                  "string", code="NDS206")
            table = ctx.str_table(e.slot, "in", False, c.dictionary)
            data = table[c.data]
        else:
            data = jnp.isin(c.data, ctx.num_vec(e.slot, c.ctype))
        if e.negated:
            # the canonicalizer only lifts NULL-free IN-lists, so plain
            # complement is exact (no three-valued NOT IN hazard)
            data = ~data
        return DCol(data, c.valid, BOOL)

    def _concat_pair(self, a: DCol, b: DCol) -> DCol:
        """String concatenation on dictionary codes.  One-sided literal:
        host remap of the other side's dictionary.  Dict x dict: host
        cross-product dictionary (guarded against blowup) + device pair
        codes.  NULL || x is NULL (SQL semantics)."""
        if a.ctype.kind != "string" or b.ctype.kind != "string":
            raise Unsupported("|| on non-string operands", code="NDS206")
        da = a.dictionary if a.dictionary is not None else np.empty(0, object)
        db = b.dictionary if b.dictionary is not None else np.empty(0, object)
        na, nb = len(da), len(db)
        valid = a.valid & b.valid & (a.data >= 0) & (b.data >= 0)
        if na == 0 or nb == 0:  # one side all-NULL
            return DCol(jnp.full(self.cap, -1, jnp.int32),
                        jnp.zeros(self.cap, bool), STRING,
                        np.empty(0, object))
        def encode(vals: np.ndarray):
            uniq, remap = np.unique(vals, return_inverse=True)
            table = jnp.asarray(np.concatenate(
                [remap.astype(np.int64), [-1]]).astype(np.int32))
            return uniq.astype(object), table

        if na == 1 or nb == 1:
            if nb == 1:
                base, vals = a, np.char.add(da.astype(str),
                                            str(db[0]))
            else:
                base, vals = b, np.char.add(str(da[0]),
                                            db.astype(str))
            uniq, table = encode(vals)
            data = jnp.where(valid, table[base.data], -1)
            return DCol(data, valid, STRING, uniq)
        if na * nb > (1 << 20):
            raise Unsupported("|| dictionary cross-product too large",
                              code="NDS213")
        uniq, table = encode(np.char.add(np.repeat(da.astype(str), nb),
                                         np.tile(db.astype(str), na)))
        pair = jnp.where(valid, a.data * nb + b.data, na * nb)
        return DCol(table[pair], valid, STRING, uniq)

    # -- functions -----------------------------------------------------------

    def _func(self, e: ex.Func) -> DCol:
        name = e.name
        if name == "concat":
            cols = [self.eval(a) for a in e.args]
            out = cols[0]
            for c in cols[1:]:
                out = self._concat_pair(out, c)
            return out
        if name == "coalesce":
            cols = [self.eval(a) for a in e.args]
            tgt = ex.coalesce_common_type(e.args,
                                          [c.ctype for c in cols])
            if tgt.kind == "string":
                scols = [self.cast(c, STRING) for c in cols]
                merged = _merged_dict(scols)
                data = jnp.full(self.cap, -1, jnp.int32)
                valid = jnp.zeros(self.cap, bool)
                for c in scols:
                    take = ~valid & c.valid
                    data = jnp.where(take, _translate(c, merged), data)
                    valid = valid | c.valid
                return DCol(data, valid, STRING, merged.astype(object))
            data = jnp.zeros(self.cap, jnp_dtype(tgt))
            valid = jnp.zeros(self.cap, bool)
            for c in cols:
                cc = self.cast(c, tgt)
                take = ~valid & cc.valid
                data = jnp.where(take, cc.data, data)
                valid = valid | cc.valid
            return DCol(data.astype(jnp_dtype(tgt)), valid, tgt)
        if name == "like":
            c = self.eval(e.args[0])
            rx = re.compile(_like_to_regex(e.args[1].value), re.S)
            data = _dict_lookup_bool(
                c, lambda s: rx.fullmatch(s) is not None)
            return DCol(data, c.valid, BOOL)
        if name in ("substr", "substring"):
            c = self.eval(e.args[0])
            start = int(e.args[1].value)
            length = int(e.args[2].value) if len(e.args) > 2 else None

            def sub(s: str) -> str:
                i = start - 1 if start > 0 else len(s) + start
                return s[i:i + length] if length is not None else s[i:]
            out = _dict_remap(self.cast(c, STRING) if c.ctype.kind != "string"
                              else c, sub)
            return DCol(out.data, c.valid, STRING, out.dictionary)
        if name == "upper":
            c = self._as_string(e.args[0])
            out = _dict_remap(c, str.upper)
            return DCol(out.data, c.valid, STRING, out.dictionary)
        if name == "lower":
            c = self._as_string(e.args[0])
            out = _dict_remap(c, str.lower)
            return DCol(out.data, c.valid, STRING, out.dictionary)
        if name == "trim":
            c = self._as_string(e.args[0])
            out = _dict_remap(c, str.strip)
            return DCol(out.data, c.valid, STRING, out.dictionary)
        if name == "length":
            c = self._as_string(e.args[0])
            lens = np.array([len(str(x)) for x in c.dictionary] + [0],
                            dtype=np.int32)
            return DCol(jnp.asarray(lens)[c.data], c.valid, INT32)
        if name == "abs":
            c = self.eval(e.args[0])
            return DCol(jnp.abs(c.data), c.valid, c.ctype)
        if name == "round":
            c = self.eval(e.args[0])
            nd = int(e.args[1].value) if len(e.args) > 1 else 0
            if c.ctype.kind == "decimal":
                if nd >= c.ctype.scale:
                    return c
                return self.cast(c, decimal(c.ctype.precision, nd))
            m = 10.0 ** nd
            data = jnp.floor(jnp.abs(c.data) * m + 0.5) / m * \
                jnp.sign(c.data)
            return DCol(data, c.valid, FLOAT64)
        if name == "floor":
            c = self.cast(self.eval(e.args[0]), FLOAT64)
            return DCol(jnp.floor(c.data), c.valid, FLOAT64)
        if name == "ceil":
            c = self.cast(self.eval(e.args[0]), FLOAT64)
            return DCol(jnp.ceil(c.data), c.valid, FLOAT64)
        if name == "sqrt":
            c = self.cast(self.eval(e.args[0]), FLOAT64)
            return DCol(jnp.sqrt(jnp.maximum(c.data, 0)), c.valid, FLOAT64)
        if name in ("year", "month", "day"):
            c = self.eval(e.args[0])
            y, m, d = _civil_from_days(c.data)
            return DCol({"year": y, "month": m, "day": d}[name],
                        c.valid, INT32)
        if name == "nullif":
            a = self.eval(e.args[0])
            b = self.eval(e.args[1])
            eqc = self._compare("=", a, b)
            eq = eqc.data & eqc.valid
            return DCol(a.data, a.valid & ~eq, a.ctype, a.dictionary)
        raise Unsupported(f"function {name}", code="NDS205")

    def _as_string(self, arg: ex.Expr) -> DCol:
        c = self.eval(arg)
        if c.ctype.kind != "string":
            raise Unsupported("cast-to-string on device", code="NDS206")
        return c

    def predicate(self, e: ex.Expr) -> jnp.ndarray:
        c = self.eval(e)
        return c.data.astype(bool) & c.valid & self.t.alive


# ---------------------------------------------------------------------------
# relational kernels (pure jnp, traceable)
# ---------------------------------------------------------------------------


def _minmax_vals(data: jnp.ndarray, valid: jnp.ndarray, kind: str,
                 is_min: bool) -> jnp.ndarray:
    """Reduction input for min/max in the data's NATIVE dtype: invalid
    rows filled with the dtype's own extremum (the reduction identity).
    Bool widens to int32 (no iinfo for bool)."""
    if kind == "bool":
        data = data.astype(jnp.int32)
    info = jnp.iinfo(data.dtype)
    sent = data.dtype.type(info.max if is_min else info.min)
    return jnp.where(valid, data, sent)


def _sum_input(data: jnp.ndarray, valid: jnp.ndarray, kind: str):
    """Summation input under the TPU precision rule: decimal/int sums
    stay exact int64 (s64 is exactly emulated on TPU via s32 pairs);
    float sums are float64 (which TPU hardware computes at f32
    precision — acceptable only for genuinely-float data)."""
    if kind in ("decimal", "int32", "int64"):
        return jnp.where(valid, data.astype(jnp.int64), jnp.int64(0))
    return jnp.where(valid, data.astype(jnp.float64), 0.0)


def _key_i64(c: DCol, alive: jnp.ndarray,
             peer: Optional[DCol] = None) -> jnp.ndarray:
    """Column -> int64 key with NULL/dead sentinels (grouping/join space).
    For strings, translates into a dictionary merged with `peer` when
    dictionaries differ."""
    if c.ctype.kind == "string":
        if peer is not None and peer.ctype.kind == "string" and not (
                c.dictionary is not None and peer.dictionary is not None and
                len(c.dictionary) == len(peer.dictionary) and
                np.array_equal(c.dictionary, peer.dictionary)):
            merged = _merged_dict([c, peer])
            data = _translate(c, merged).astype(jnp.int64)
        else:
            data = c.data.astype(jnp.int64)
    elif c.ctype.kind == "float64":
        # float64 keys STAY float64: consumers only sort and compare, and
        # the TPU X64-rewrite pass has no lowering for f64<->s64
        # bitcast-convert (a bit-pattern encoding crashes the TPU
        # compiler outright).  IEEE gives SQL semantics for free
        # (-0.0 == 0.0); NaNs fold to +inf so they group/join as one
        # value; the sentinel magnitudes (2^62) are exactly representable
        # and far outside any decimal-derived data domain.
        data = c.data.astype(jnp.float64)
        # NaNs fold to DBL_MAX (one NaN group, +inf stays distinct;
        # only a literal DBL_MAX in the data could collide)
        data = jnp.where(jnp.isnan(data),
                         jnp.finfo(jnp.float64).max, data)
        data = jnp.where(c.valid, data, jnp.float64(_NULL_KEY))
        return jnp.where(alive, data, jnp.float64(_DEAD_KEY))
    else:
        data = c.data.astype(jnp.int64)
    data = jnp.where(c.valid, data, _NULL_KEY)
    return jnp.where(alive, data, _DEAD_KEY)


def _lexsort_order(keys: List[jnp.ndarray]) -> jnp.ndarray:
    """Stable argsort by multiple keys; keys[0] is the primary.

    ONE variadic ``lax.sort`` (num_keys=len(keys)) with an int32 iota
    payload — not a chain of per-key argsorts: a single sort HLO on TPU
    costs roughly one sort regardless of key count, and the int32
    permutation avoids x64's default int64 index arrays."""
    n = keys[0].shape[0]
    iota = jax.lax.iota(jnp.int32, n)
    return jax.lax.sort(tuple(keys) + (iota,), num_keys=len(keys),
                        is_stable=True)[-1]


def _inv_permute(order: jnp.ndarray, vals: jnp.ndarray) -> jnp.ndarray:
    """out[order[i]] = vals[i] for a permutation `order`: a pair-sort
    keyed by the permutation instead of a scatter."""
    return jax.lax.sort((order, vals), num_keys=1, is_stable=True)[1]


def _group_ids(keys: List[jnp.ndarray]) -> Tuple[jnp.ndarray, jnp.ndarray,
                                                 jnp.ndarray]:
    """Dense group ids via ONE variadic sort: (gid int32, order int32,
    newgrp).  Sorted key columns come straight out of the sort — no
    per-key re-gather."""
    n = keys[0].shape[0]
    iota = jax.lax.iota(jnp.int32, n)
    res = jax.lax.sort(tuple(keys) + (iota,), num_keys=len(keys),
                       is_stable=True)
    order = res[-1]
    diff = jnp.zeros(n, bool).at[0].set(True)
    for ks in res[:-1]:
        diff = diff.at[1:].set(diff[1:] | (ks[1:] != ks[:-1]))
    gid_sorted = jnp.cumsum(diff.astype(jnp.int32)) - 1
    gid = _inv_permute(order, gid_sorted)
    return gid, order, diff


def _dense_rank_pair(a: jnp.ndarray, b: jnp.ndarray):
    """Joint dense rank of two arrays (values aligned across both).
    Ranks are int32 (row counts are always < 2^31)."""
    both = jnp.concatenate([a, b])
    n = both.shape[0]
    iota = jax.lax.iota(jnp.int32, n)
    s, order = jax.lax.sort((both, iota), num_keys=1, is_stable=True)
    diff = jnp.zeros(n, jnp.int32).at[1:].set(
        (s[1:] != s[:-1]).astype(jnp.int32))
    rank_sorted = jnp.cumsum(diff)
    ranks = _inv_permute(order, rank_sorted)
    return ranks[:a.shape[0]], ranks[a.shape[0]:]


def _narrow_span(c: DCol) -> Optional[Tuple[int, int]]:
    """(lo, hi) when every valid value of ``c`` fits the int32 key
    space (|v| < 2^30), else None.  Strings qualify via dictionary
    size (codes are 0..len-1); int-like kinds need static bounds."""
    if c.ctype.kind == "string":
        nd = 0 if c.dictionary is None else len(c.dictionary)
        return (0, max(nd - 1, 0)) if nd < _NARROW_LIM else None
    if c.ctype.kind in ("int32", "int64", "date", "decimal") and \
            c.bounds is not None:
        lo, hi = c.bounds
        if -_NARROW_LIM < lo and hi < _NARROW_LIM:
            return (int(lo), int(hi))
    return None


def _key_col(c: DCol, alive: jnp.ndarray) -> jnp.ndarray:
    """Single-table grouping/sort key in the narrowest dtype: int32
    with int32 sentinels when the value domain fits, else the int64
    (or float64) encoding of :func:`_key_i64`."""
    if c.ctype.kind == "float64":
        return _key_i64(c, alive)
    if _narrow_span(c) is not None:
        data = c.data.astype(jnp.int32)
        data = jnp.where(c.valid, data, _NULL32)
        return jnp.where(alive, data, _DEAD32)
    return _key_i64(c, alive)


# ---------------------------------------------------------------------------
# Executor
# ---------------------------------------------------------------------------


class JaxExecutor:
    """Plan executor on the JAX backend, with per-subtree numpy fallback.

    Three modes share one operator implementation:

    * ``eager``    — ops dispatch immediately (correctness path).
    * ``discover`` — like eager, but records every data-dependent decision
      (output capacities at join/compact sync points, null-aware branch
      bools, resolved subquery literals) into a *size plan*.
    * ``replay``   — re-runs the plan under ``jax.jit`` tracing: recorded
      capacities become static shapes, recorded branches drive control
      flow, and each decision contributes a traced ``ok`` guard; the
      whole query becomes ONE XLA program (critical on real TPUs, where
      eager dispatch costs a host round-trip per primitive).

    If the guards fail at runtime (data changed enough to overflow a
    size class), the caller re-discovers and recompiles.
    """

    def __init__(self, catalog):
        self.catalog = catalog
        self.np_exec = physical.Executor(catalog)
        self._device_cache: Dict[str, Tuple[int, DTable]] = {}
        self._accel_cache: Dict[str, Tuple[int, object]] = {}
        self._subq_cache: Dict[int, ex.Expr] = {}
        self.mode = "eager"
        self._rec: Optional[list] = None   # size plan being written/read
        self._pos = 0
        self._oks: Optional[list] = None   # traced guard bools (replay)
        self._trace_tables: Optional[Dict[str, DTable]] = None
        self._used_fallback = False
        self._fallback_codes: List[str] = []
        # compiled-query cache: plan identity -> _CompiledPlan
        self._compiled: Dict[int, "_CompiledPlan"] = {}
        # segmented compilation: fingerprint -> segment _CompiledPlan,
        # shared across queries; eager segment results for the plan
        # currently being discovered / eager-executed
        self._seg_compiled: Dict[str, "_CompiledPlan"] = {}
        self._seg_tables: Dict[str, DTable] = {}
        # ONE configuration, the one the benchmark's cells run: no
        # environment variable selects a path of this executor.  The
        # thresholds between paths are module constants beside their
        # chip readings (_GROUPBY_DOMAIN_CAP, _JOIN_LUT_CAP,
        # _SEARCH_COMPACT_COST, _COMPARE_PAIR_COST); a Pallas kernel
        # engages by one rule (_pallas_kernel).
        # compile+run the jitted replay at the end of discovery so
        # steady-state executions never pay a trace/compile (opt out
        # with NDSTPU_WARM_REPLAY=0: WHEN the replay program compiles,
        # not which program)
        self.warm_replay = os.environ.get(
            "NDSTPU_WARM_REPLAY", "1") != "0"
        # introspection counters: tests assert steady-state executions
        # re-run NO discovery and build NO new jitted programs
        self.n_discoveries = 0
        self.n_jit_builds = 0
        # Thread-safety (inproc throughput scheduler): the executor
        # keeps per-query mutable state (mode/_rec/_pos, subquery
        # memos, eager segment tables), so query execution is
        # serialized under _exec_lock (RLock: replay of a demoted
        # segment re-enters execute_to_host).  _key_latch adds per-key
        # "discover once, others wait": a second stream arriving for a
        # text mid-compile blocks on the key, then hits _compiled.
        from ndstpu.engine.latch import KeyedLatch
        self._exec_lock = threading.RLock()
        self._key_latch = KeyedLatch()
        # plan node -> pre-order id, while a replay program is traced
        self._scope_ids: Dict[int, int] = {}
        # equi-join operators by the path each took, since the replay
        # program being traced began (-> _CompiledPlan.join_paths)
        self._join_paths: Dict[str, int] = dict.fromkeys(_JOIN_PATHS, 0)
        # ... and its operators by kind (-> _CompiledPlan.op_kinds)
        self._op_kinds: Dict[str, int] = dict.fromkeys(_OP_KINDS, 0)
        # eager bounds diagnostic: plain (non-compiling) executors keep
        # it always on — they have no discovery phase to front-load the
        # check into; CompilingExecutor narrows it to discovery
        self._in_discovery = True

    # -- public --------------------------------------------------------------

    def execute_to_host(self, p: lp.Plan) -> Table:
        with self._exec_lock:
            # per-query subquery memo: expr ids are only stable within
            # one plan
            self._subq_cache = {}
            self._tree_cache = {}
            self.np_exec = physical.Executor(self.catalog)
            self.mode = "eager"
            with host_compute():
                return to_host(self.execute(p))

    # -- sync-point abstraction ----------------------------------------------

    def _capacity_for(self, count) -> Tuple[int, jnp.ndarray]:
        """Size-class a data-dependent output count.

        eager/discover: host-sync the count, compute the size class
        (discover records it).  replay: pop the recorded capacity (static)
        and guard ``count <= cap``; the traced count still drives alive
        masks, so results stay exact as long as the guard holds."""
        if self.mode == "replay":
            tag, cap = self._rec[self._pos]
            self._pos += 1
            if tag != "cap":
                raise RuntimeError("size-plan drift (expected cap)")
            self._oks.append(count <= cap)
            return cap, count
        n = int(count)
        cap = size_class(max(n, 1))
        if self.mode == "discover":
            self._rec.append(("cap", cap))
        return cap, count

    def _branch_bool(self, flag) -> bool:
        """Host-sync a branch decision (replay: recorded + guarded)."""
        if self.mode == "replay":
            tag, val = self._rec[self._pos]
            self._pos += 1
            if tag != "bool":
                raise RuntimeError("size-plan drift (expected bool)")
            self._oks.append(jnp.asarray(flag) == val)
            return val
        b = bool(flag)
        if self.mode == "discover":
            self._rec.append(("bool", b))
        return b

    # expensive nodes worth structural-dedup: repeated CTE instances are
    # deep-copied by the planner (copy_plan) so identity can't match, but
    # instances the optimizer left identical (same pushed-down filters /
    # pruned columns) fingerprint equal and execute ONCE per query.
    # Deterministic given the plan tree, so discover and replay hit the
    # memo at the same points and the size-plan record stays aligned.
    # A DeviceResult stands for its segment's program: a second read of
    # one is a folded occurrence, counted with the other hits.
    _MEMO_NODES = (lp.Join, lp.Aggregate, lp.SetOp, lp.Window,
                   lp.Distinct, lp.Sort, lp.DeviceResult)

    def execute(self, p: lp.Plan, settled: bool = True) -> DTable:
        """The node's table, with dense rows: a lookup join's pending
        compaction (DTable.pending) is settled here unless the caller
        can take the rows where they lie (``settled=False``: the probe
        side of _exec_join).  The memo keeps the settled form once one
        was asked for, so a node used twice compacts once."""
        key = None
        if isinstance(p, self._MEMO_NODES):
            try:
                key = _plan_fp(p)
            except TypeError:
                # un-fingerprintable leaf (no content-based repr):
                # skip memoization rather than fail the query
                pass
        out = None
        if key is not None:
            cache = getattr(self, "_tree_cache", None)
            if cache is None:
                cache = self._tree_cache = {}
            out = cache.get(key)
        if out is None:
            out = self._execute_node(p)
        else:
            self._op_kinds["memo_shared"] += 1
        if settled and out.pending is not None:
            out = self._settle(out)
        if key is not None:
            cache[key] = out
        return out

    def _execute_node(self, p: lp.Plan) -> DTable:
        name = "_exec_" + type(p).__name__.lower()
        m = getattr(self, name, None)
        if m is None:
            return self._fallback(p)
        try:
            if self.mode == "replay":
                # tracing the replay program: name the operator's HLO
                # after its kind and pre-order plan-node id (metadata
                # only)
                with jax.named_scope(self._scope_name(p)):
                    return m(p)
            return m(p)
        except Unsupported as u:
            return self._fallback(p, code=u.code)

    def _scope_name(self, p: lp.Plan) -> str:
        i = self._scope_ids.get(id(p))
        kind = type(p).__name__
        return kind if i is None else f"{kind}_{i}"

    # -- fallback ------------------------------------------------------------

    def _fallback(self, p: lp.Plan, code: Optional[str] = None) -> DTable:
        """Run this node on the numpy interpreter; children still execute on
        the device path and are pulled to host once.  ``code`` is the
        NDS2xx diagnostic of the Unsupported that sent us here; it is
        counted and annotated onto the enclosing query span so sidecar
        and ledger rows record why the query fell back."""
        if self.mode == "replay":
            raise RuntimeError(
                f"fallback for {type(p).__name__} during replay — "
                "discovery should have marked this plan non-compilable")
        self._used_fallback = True
        tag = f"{code or 'uncoded'}:{type(p).__name__}"
        if tag not in self._fallback_codes:
            self._fallback_codes.append(tag)
        obs.inc(f"engine.fallback.{code or 'uncoded'}")
        obs.annotate(fallback_codes=",".join(sorted(self._fallback_codes)))
        repl = self._replace_children_with_host(p)
        host = self.np_exec.execute(repl)
        return to_device(host)

    def _replace_children_with_host(self, p: lp.Plan) -> lp.Plan:
        def host_child(c: lp.Plan) -> lp.Plan:
            return lp.InlineTable(to_host(self.execute(c)))

        if isinstance(p, (lp.Filter, lp.Project, lp.Limit, lp.Distinct,
                          lp.Window, lp.Sort, lp.Aggregate,
                          lp.SubqueryAlias)):
            q = lp.copy_plan(p)
            q.child = host_child(p.child)
            return q
        if isinstance(p, lp.Join):
            q = lp.copy_plan(p)
            q.left = host_child(p.left)
            q.right = host_child(p.right)
            return q
        if isinstance(p, lp.SetOp):
            q = lp.copy_plan(p)
            q.left = host_child(p.left)
            q.right = host_child(p.right)
            return q
        return p

    # -- subqueries ----------------------------------------------------------

    def _resolve_subqueries(self, e: ex.Expr) -> ex.Expr:
        if isinstance(e, ex.SubqueryExpr):
            if id(e) in self._subq_cache:
                return self._subq_cache[id(e)]
            if self.mode == "replay":
                # subquery results were resolved during discovery and are
                # part of the size plan (guarded by catalog versions)
                tag, out = self._rec[self._pos]
                self._pos += 1
                if tag != "subq":
                    raise RuntimeError("size-plan drift (expected subq)")
                self._subq_cache[id(e)] = out
                return out
            # the sub-plan executes eagerly even during discovery so its
            # own sync points never leak into the main plan's size plan
            # (replay skips the sub-plan entirely — a fallback inside it
            # doesn't make the main plan non-compilable either)
            outer = self.mode
            outer_fallback = self._used_fallback
            # isolate the subtree memo: a main-plan subtree must never
            # hit a DTable cached during subquery resolution — replay
            # skips subqueries entirely, so such a hit would desync the
            # size-plan record positions between discover and replay
            outer_tree = getattr(self, "_tree_cache", None)
            self._tree_cache = {}
            self.mode = "eager"
            try:
                t = to_host(self.execute(e.plan))
                col = t.columns[t.column_names[0]]
                if e.kind == "scalar":
                    if t.num_rows == 0:
                        out = ex.Literal(None, col.ctype)
                    else:
                        vals = col.to_pylist()
                        if len(vals) > 1:
                            raise RuntimeError(
                                "scalar subquery returned >1 row")
                        out = ex.Literal(vals[0], col.ctype)
                elif e.kind == "in":
                    pyvals = col.to_pylist()
                    has_null = any(v is None for v in pyvals)
                    vals = tuple(v for v in pyvals if v is not None)
                    if e.negated and has_null:
                        out = ex.Literal(False)
                    else:
                        out = ex.InList(
                            self._resolve_subqueries(e.operand), vals,
                            e.negated)
                else:
                    raise Unsupported(f"subquery kind {e.kind}", code="NDS211")
            finally:
                self.mode = outer
                self._used_fallback = outer_fallback
                self._tree_cache = outer_tree if outer_tree is not None \
                    else {}
            if self.mode == "discover":
                self._rec.append(("subq", out))
            self._subq_cache[id(e)] = out
            return out
        if isinstance(e, ex.BinOp):
            return ex.BinOp(e.op, self._resolve_subqueries(e.left),
                            self._resolve_subqueries(e.right))
        if isinstance(e, ex.UnaryOp):
            return ex.UnaryOp(e.op, self._resolve_subqueries(e.operand))
        if isinstance(e, ex.Cast):
            return ex.Cast(self._resolve_subqueries(e.operand), e.target)
        if isinstance(e, ex.Func):
            return ex.Func(e.name, tuple(self._resolve_subqueries(a)
                                         for a in e.args))
        if isinstance(e, ex.Case):
            return ex.Case(
                tuple((self._resolve_subqueries(c),
                       self._resolve_subqueries(v)) for c, v in e.whens),
                self._resolve_subqueries(e.default)
                if e.default is not None else None)
        if isinstance(e, ex.InList):
            return ex.InList(self._resolve_subqueries(e.operand), e.values,
                             e.negated)
        if isinstance(e, ex.InParam):
            return ex.InParam(self._resolve_subqueries(e.operand), e.slot,
                              e.n, e.negated)
        return e

    # -- leaves --------------------------------------------------------------

    def _table_device(self, name: str) -> DTable:
        host = self.catalog.get(name)
        version = getattr(self.catalog, "versions", {}).get(name)
        cached = self._device_cache.get(name)
        if cached is not None and cached[0] == version and \
                version is not None:
            obs.inc("engine.cache.device.hit")
            return cached[1]
        obs.inc("engine.cache.device.miss")
        # always materialize on the HOST backend: this cache feeds
        # eager/discovery and replay metadata; pinning a second full
        # copy of every table in accelerator HBM (alongside the
        # per-column replay buffers) starved the device at SF1
        with host_compute():
            dt = to_device(host)
        self._device_cache[name] = (version, dt)
        return dt

    def _exec_scan(self, p: lp.Scan) -> DTable:
        if self.mode == "replay":
            dt = self._trace_tables[p.table]
        else:
            dt = self._table_device(p.table)
        if p.columns is not None:
            cols = list(p.columns) or dt.column_names[:1]
            dt = dt.select(cols)
        if p.predicate is not None:
            pred = self._resolve_subqueries(p.predicate)
            mask = JEval(dt).predicate(pred)
            dt = DTable(dt.columns, dt.alive & mask)
        return dt

    def _exec_inlinetable(self, p: lp.InlineTable) -> DTable:
        return to_device(p.table)

    def _exec_deviceresult(self, p: lp.DeviceResult) -> DTable:
        """Separately-compiled segment result (segmented compilation):
        replay reads the parent program's argument; eager/discover read
        the eager segment tables staged by the orchestrator."""
        if self.mode == "replay":
            return self._trace_tables[_seg_argname(p.key)]
        return self._seg_tables[p.key]

    def _exec_subqueryalias(self, p: lp.SubqueryAlias) -> DTable:
        dt = self.execute(p.child)
        if p.column_aliases:
            dt = DTable(dict(zip(p.column_aliases, dt.columns.values())),
                        dt.alive)
        return dt

    # -- row ops -------------------------------------------------------------

    def _exec_filter(self, p: lp.Filter) -> DTable:
        dt = self.execute(p.child)
        cond = self._resolve_subqueries(p.condition)
        mask = JEval(dt).predicate(cond)
        return DTable(dt.columns, dt.alive & mask)

    def _exec_project(self, p: lp.Project) -> DTable:
        dt = self.execute(p.child)
        evl = JEval(dt)
        cols = {}
        for name, e in p.exprs:
            cols[name] = evl.eval(self._resolve_subqueries(e))
        return DTable(cols, dt.alive)

    def _exec_limit(self, p: lp.Limit) -> DTable:
        dt = self.compact(self.execute(p.child))
        cap = dt.capacity
        keep = jax.lax.iota(jnp.int32, cap) < min(p.n, cap)
        return DTable(dt.columns, dt.alive & keep)

    def compact(self, dt: DTable) -> DTable:
        """Scatter alive rows to the front (order-preserving); one
        sync point for the new capacity."""
        return self._compact_to(dt, *self._capacity_for(jnp.sum(dt.alive)))

    def _settle(self, dt: DTable) -> DTable:
        """The compaction a lookup join left pending, now: the alive
        rows at the front of the survivors' size class.  The count is
        taken here, so a row-wise mask since the join cannot have made
        it stale; the class was recorded and guarded by the join."""
        return self._compact_to(dt, dt.pending, jnp.sum(dt.alive))

    def _compact_to(self, dt: DTable, cap: int, n_alive) -> DTable:
        """The ``n_alive`` alive rows of ``dt`` at the front of ``cap``."""
        idx_src = self._survivor_positions(dt.alive, cap)
        alive = jax.lax.iota(jnp.int32, cap) < \
            jnp.asarray(n_alive).astype(jnp.int32)
        return DTable(_gather_cols(dt.columns, idx_src, alive), alive)

    # -- sort ----------------------------------------------------------------

    def _order_key(self, evl: JEval, c: DCol, asc: bool,
                   nulls_first: Optional[bool]) -> jnp.ndarray:
        if nulls_first is None:
            nulls_first = asc
        alive = evl.t.alive
        if c.ctype.kind == "float64":
            data = c.data.astype(jnp.float64)
            key = data if asc else -data
            key = jnp.where(c.valid, key,
                            -jnp.inf if nulls_first else jnp.inf)
            # dead rows strictly last
            return jnp.where(alive, key, jnp.inf)
        if _narrow_span(c) is not None:
            # int32 order key (dictionary codes already collate — the
            # dictionaries are sorted)
            data = c.data.astype(jnp.int32)
            key = data if asc else -data
            key = jnp.where(c.valid, key,
                            _NULL32 if nulls_first else -_NULL32)
            return jnp.where(alive, key, _ORD_DEAD32)
        data = c.data.astype(jnp.int64)
        key = data if asc else -data
        key = jnp.where(c.valid, key,
                        _NULL_KEY if nulls_first else -_NULL_KEY)
        return jnp.where(alive, key, _DEAD_KEY)

    def _exec_sort(self, p: lp.Sort) -> DTable:
        dt = self.execute(p.child)
        evl = JEval(dt)
        keys = []
        for entry in p.keys:
            e, asc = entry[0], entry[1]
            nf = entry[2] if len(entry) > 2 else None
            keys.append(self._order_key(
                evl, evl.eval(self._resolve_subqueries(e)), asc, nf))
        order = _lexsort_order(keys)
        return dt.gather(order, dt.alive[order])

    # -- aggregate -----------------------------------------------------------

    def _exec_aggregate(self, p: lp.Aggregate) -> DTable:
        for _, e in p.aggs:
            self._check_agg_supported(e)
        dt = self.execute(p.child)
        if p.grouping_sets is None:
            return self._aggregate_once(dt, p, None)
        parts = self._grouping_sets_partials(dt, p)
        if parts is None:
            # non-decomposable aggregates (distinct, stddev, ...):
            # per-set full passes over the child
            parts = [self._aggregate_once(dt, p, subset)
                     for subset in p.grouping_sets]
        cols: Dict[str, DCol] = {}
        for n in parts[0].column_names:
            cs = [t.columns[n] for t in parts]
            bounds = None
            if all(c.bounds is not None for c in cs):
                bounds = (min(c.bounds[0] for c in cs),
                          max(c.bounds[1] for c in cs))
            cols[n] = DCol(jnp.concatenate([c.data for c in cs]),
                           jnp.concatenate([c.valid for c in cs]),
                           cs[0].ctype, cs[0].dictionary, bounds)
        return DTable(cols, jnp.concatenate([t.alive for t in parts]))

    _GS_COMBINABLE = lowreg.GS_COMBINABLE_AGGS

    def _grouping_sets_partials(self, dt: DTable,
                                p: lp.Aggregate) -> Optional[list]:
        """Grouping sets via decomposable partials.

        ONE finest-grain aggregation over the (large) child, then
        per-set re-aggregation of the tiny compacted partial table —
        the single-chip analog of dplan's distributed partial
        recombine (dplan.py _agg_partials/_combine_partials).  Before
        this, q22's 5-set ROLLUP paid 5 full-capacity sort+segment
        passes over inventory; now it pays one, plus 5 passes over
        ~#items rows.  Returns None when an aggregate is not
        decomposable (distinct, stddev) or an agg expression contains
        nodes the rewrite can't walk — the caller falls back to
        per-set full passes.
        """
        # dedup key is _plan_fp, NOT repr: AggExpr.__repr__ delegates to
        # arg reprs and Literal's repr hides its ctype, so two agg
        # expressions differing only in literal type would collide and
        # share one partial column.  NOTE the two-stage sum reorders
        # float64 summation vs the per-set direct path; the differential
        # harness epsilon (1e-5 relative) covers that drift.
        leaves: Dict[str, ex.AggExpr] = {}
        for _name, e in p.aggs:
            for node in e.walk():
                if isinstance(node, ex.AggExpr):
                    if node.distinct or \
                            node.func not in self._GS_COMBINABLE:
                        return None
                    leaves.setdefault(_plan_fp(node), node)
        # finest-grain partials: sum+count for sum/avg, the func itself
        # for count/min/max (counts recombine by sum, min/max by
        # min/max; sum-of-sums preserves NULL-iff-no-valid-rows because
        # a cnt=0 finest partial is itself NULL)
        fine_aggs: List[tuple] = []
        combine: Dict[str, ex.Expr] = {}
        for i, (rkey, a) in enumerate(leaves.items()):
            if a.func in ("sum", "avg"):
                sname = f"__gs{i}s"
                fine_aggs.append((sname, ex.AggExpr("sum", a.arg)))
                if a.func == "sum":
                    combine[rkey] = ex.AggExpr(
                        "sum", ex.ColumnRef(sname))
                else:
                    cname = f"__gs{i}c"
                    fine_aggs.append(
                        (cname, ex.AggExpr("count", a.arg)))
                    # avg = total sum / total count; Cast(decimal ->
                    # float64) descales exactly like _agg_column's avg
                    combine[rkey] = ex.BinOp(
                        "/",
                        ex.Cast(ex.AggExpr("sum", ex.ColumnRef(sname)),
                                FLOAT64),
                        ex.Cast(ex.AggExpr("sum", ex.ColumnRef(cname)),
                                FLOAT64))
            elif a.func == "count":
                cname = f"__gs{i}c"
                fine_aggs.append((cname, ex.AggExpr("count", a.arg)))
                combine[rkey] = ex.AggExpr("sum", ex.ColumnRef(cname))
            else:  # min / max
                mname = f"__gs{i}m"
                fine_aggs.append((mname, ex.AggExpr(a.func, a.arg)))
                combine[rkey] = ex.AggExpr(a.func, ex.ColumnRef(mname))

        def rebuild(node: ex.Expr) -> ex.Expr:
            if isinstance(node, ex.AggExpr):
                return combine[_plan_fp(node)]
            if isinstance(node, ex.BinOp):
                return ex.BinOp(node.op, rebuild(node.left),
                                rebuild(node.right))
            if isinstance(node, ex.Cast):
                return ex.Cast(rebuild(node.operand), node.target)
            if isinstance(node, ex.Func):
                if node.name == "grouping":
                    return node  # static per set; _grouping_ctx resolves
                return ex.Func(node.name,
                               tuple(rebuild(x) for x in node.args))
            if isinstance(node, ex.Case):
                return ex.Case(
                    tuple((rebuild(c), rebuild(v))
                          for c, v in node.whens),
                    rebuild(node.default)
                    if node.default is not None else None)
            if isinstance(node, (ex.Literal, ex.Param)):
                return node
            raise Unsupported(
                f"grouping-sets rewrite: {type(node).__name__}")

        try:
            set_aggs = [(name, rebuild(e)) for name, e in p.aggs]
        except Unsupported:
            return None
        p_fine = lp.Aggregate(p.child, p.group_by, fine_aggs, None)
        ft = self.compact(self._aggregate_once(dt, p_fine, None))
        set_group_by = [(n, ex.ColumnRef(n)) for n, _ in p.group_by]
        p_set = lp.Aggregate(p.child, set_group_by, set_aggs, None)
        return [self._aggregate_once(ft, p_set, subset)
                for subset in p.grouping_sets]

    def _aggregate_once(self, dt: DTable, p: lp.Aggregate,
                        subset: Optional[List[int]]) -> DTable:
        evl = JEval(dt)
        cap = dt.capacity
        key_cols = []
        for i, (name, e) in enumerate(p.group_by):
            c = evl.eval(self._resolve_subqueries(e))
            if subset is not None and i not in subset:
                # excluded key in this grouping set -> all NULL (rollup)
                c = DCol(jnp.zeros_like(c.data), jnp.zeros(cap, bool),
                         c.ctype, c.dictionary)
            key_cols.append((name, c))
        self._grouping_ctx = ([n for n, _ in p.group_by], subset)
        direct = self._direct_group_ids(key_cols, dt.alive) \
            if key_cols else None
        if direct is not None:
            gid, ngseg, out_alive, out_cols, order = direct
            if ngseg > self._PALLAS_SEGS_MAX and any(
                    isinstance(n, ex.AggExpr) and n.func in ("sum", "avg")
                    and not n.distinct
                    for _, e in p.aggs for n in e.walk()):
                self._op_kinds["agg_wide"] += 1
        elif key_cols:
            self._op_kinds["agg_sort"] += 1
            keys = [_key_col(c, dt.alive) for _, c in key_cols]
            gid, order, newgrp = _group_ids(keys)
            ngseg = cap
            # representative (first-in-sorted-order) row per group
            first_pos = jnp.full(cap, cap, jnp.int32).at[
                (jnp.cumsum(newgrp.astype(jnp.int32)) - 1)].min(
                jax.lax.iota(jnp.int32, cap))
            rep = order[jnp.clip(first_pos, 0, cap - 1)]
            galive = jax.ops.segment_sum(
                dt.alive.astype(jnp.int32), gid, num_segments=ngseg) > 0
            # group table alive mask: one slot per distinct gid
            n_groups_mask = jnp.zeros(cap, bool).at[gid].set(True)
            out_alive = n_groups_mask & galive
            # lazy: the final output compaction composes these rep
            # gathers down to the compacted capacity (8 string group
            # keys at 4M cost ~0.5 s in eager gathers otherwise)
            out_cols = _gather_cols(dict(key_cols), rep, out_alive)
        else:
            # keyless (scalar) aggregate: TWO segments (alive row 0,
            # dead row 1).  The old path used ngseg=cap — a cap-sized
            # scatter target per aggregate — and eagerly lexsorted the
            # whole capacity by a 0/1 key; q28's six scalar-agg
            # branches paid six full sorts for nothing.  The sort is
            # now lazy (only a float df64 sum needs gid-contiguous
            # order) and reductions land in 2 slots.
            gid = jnp.where(dt.alive, 0, 1).astype(jnp.int32)
            ngseg = 2
            out_alive = jnp.asarray([True, False])
            out_cols = {}
            memo_o: Dict[str, object] = {}

            def order(memo=memo_o, g=gid):
                if "o" not in memo:
                    memo["o"] = _lexsort_order([g])
                return memo["o"]
        # gid-sorted row order rides alongside gid: float sums use the
        # compensated segmented scan (ndstpu.engine.df64).  Passed as a
        # parameter, NOT instance state — _resolve_subqueries may run a
        # nested aggregate mid-loop and would clobber it.
        for name, e in p.aggs:
            out_cols[name] = self._eval_agg(
                dt, evl, self._resolve_subqueries(e), gid, ngseg, out_alive,
                order, dense=direct is not None)
        return DTable(out_cols, out_alive)

    def _direct_group_ids(self, key_cols, alive):
        """Linearized group ids for small host-known key domains.

        When every group key is dictionary-coded or carries static
        bounds, the (keys) tuple maps bijectively to a mixed-radix index
        over ``domain = prod(span_i + 1)`` slots (+1 = a NULL slot per
        key), so dense group ids need NO sort, segment reductions run
        over ``domain`` slots instead of the row capacity, and the one-
        hot MXU kernels apply.  Returns None when ineligible; then the
        sort-based path runs.  (Sort path analog of Spark's hash vs
        sort aggregate choice; reference picks per-plan the same way.)
        """
        parts = []
        domain = 1
        for _name, c in key_cols:
            if c.dictionary is not None and c.ctype.kind == "string":
                lo, span = 0, len(c.dictionary)
            elif c.bounds is not None and c.ctype.kind in (
                    "int32", "int64", "date", "decimal"):
                lo, hi = c.bounds
                span = hi - lo + 1
            else:
                return None
            if span <= 0:
                return None
            domain *= span + 1
            if domain > _GROUPBY_DOMAIN_CAP or domain >= 2 ** 31 - 1:
                return None
            parts.append((c, lo, span))
        cap = int(alive.shape[0])
        # the domain cap keeps the mixed-radix gid well inside int32
        gid = jnp.zeros(cap, jnp.int32)
        # bounds-invariant guard: a valid value outside its static
        # bounds means a DCol constructor copied bounds across a
        # value-changing transform — route the row to the trash slot
        # (visibly dropped) instead of silently merging it into the
        # boundary group
        row_ok = jnp.ones(cap, bool)
        for c, lo, span in parts:
            if -(2 ** 31) < lo and lo + span - 1 < 2 ** 31 and \
                    c.data.dtype == jnp.int32:
                raw = c.data - np.int32(lo)
                row_ok = row_ok & (~c.valid | ((raw >= 0) & (raw < span)))
                idx = jnp.clip(raw, 0, span - 1)
            else:
                raw64 = c.data.astype(jnp.int64) - lo
                row_ok = row_ok & (~c.valid |
                                   ((raw64 >= 0) & (raw64 < span)))
                idx = jnp.clip(raw64, 0, span - 1).astype(jnp.int32)
            idx = jnp.where(c.valid, idx, span)     # NULL slot per key
            gid = gid * (span + 1) + idx
        # dead / bounds-violating rows -> trash slot
        bad = alive & ~row_ok
        if self.mode == "replay":
            # a violation means upstream bounds propagation broke: fail
            # the replay guard so the query rediscovers (and the eager
            # pass below warns) instead of silently dropping rows
            self._oks.append(~jnp.any(bad))
        elif self._in_discovery:
            # the bool() forces a blocking device sync — pay it during
            # discovery (which covers demoted-to-eager subtrees too:
            # every query's FIRST execution passes through
            # _discover_plan, so bugs surface then), not on every
            # steady-state demoted eager aggregate.
            if bool(jnp.any(bad)):
                import warnings
                warnings.warn(
                    f"group-by bounds invariant violated: "
                    f"{int(jnp.sum(bad))} valid rows fell outside static "
                    f"key bounds and were dropped (upstream bounds-"
                    f"propagation bug)", stacklevel=2)
        gid = jnp.where(alive & row_ok, gid, domain)
        ngseg = domain + 1
        counts = jax.ops.segment_sum(alive.astype(jnp.int32), gid,
                                     num_segments=ngseg)
        out_alive = (counts > 0).at[domain].set(False)
        # reconstruct key values from the slot index (bijective mapping)
        rem = jnp.arange(ngseg)
        idxs = []
        for c, lo, span in reversed(parts):
            idxs.append(rem % (span + 1))
            rem = rem // (span + 1)
        idxs.reverse()
        out_cols: Dict[str, DCol] = {}
        for (name, c), (c2, lo, span), idx in zip(key_cols, parts, idxs):
            vout = (idx != span) & out_alive
            data = (lo + jnp.clip(idx, 0, span - 1)).astype(c.data.dtype)
            out_cols[name] = DCol(data, vout, c.ctype, c.dictionary,
                                  (lo, lo + span - 1))
        # float sums need a gid-contiguous row order (df64 compensated
        # scan); computed lazily — the common decimal/int case skips it
        memo = {}

        def order_thunk():
            if "o" not in memo:
                memo["o"] = _lexsort_order([gid])
            return memo["o"]

        return gid, ngseg, out_alive, out_cols, order_thunk

    def _check_agg_supported(self, e: ex.Expr):
        for node in e.walk():
            if isinstance(node, ex.AggExpr):
                if node.distinct and \
                        node.func not in lowreg.DISTINCT_AGG_FUNCS:
                    raise Unsupported(
                        f"distinct aggregate {node.func} on device",
                        code="NDS207")
                if node.func not in lowreg.SUPPORTED_AGG_FUNCS:
                    raise Unsupported(f"aggregate {node.func}",
                                      code="NDS207")

    def _eval_agg(self, dt: DTable, evl: JEval, e: ex.Expr, gid, ngseg,
                  out_alive, order, dense: bool = False) -> DCol:
        if isinstance(e, ex.AggExpr):
            return self._agg_column(dt, evl, e, gid, ngseg, out_alive,
                                    order, dense)
        if isinstance(e, ex.Func) and e.name == "grouping":
            # grouping(key) = 0 when the key participates in this grouping
            # set, 1 when rolled up (Spark semantics)
            names, subset = self._grouping_ctx
            arg = e.args[0]
            idx = names.index(arg.name) if isinstance(
                arg, ex.ColumnRef) and arg.name in names else -1
            active = subset is None or idx in subset
            return DCol(jnp.full(ngseg, 0 if active else 1, jnp.int32),
                        jnp.ones(ngseg, bool), INT32)
        if isinstance(e, (ex.BinOp, ex.Cast, ex.Func, ex.Case, ex.Literal,
                          ex.Param)):
            # expression over aggregates: evaluate leaves then combine on
            # the group-capacity table
            sub_cols: Dict[str, DCol] = {}
            counter = [0]

            def lower(node: ex.Expr) -> ex.Expr:
                if isinstance(node, ex.AggExpr):
                    name = f"__agg{counter[0]}"
                    counter[0] += 1
                    sub_cols[name] = self._agg_column(
                        dt, evl, node, gid, ngseg, out_alive, order,
                        dense)
                    return ex.ColumnRef(name)
                if isinstance(node, ex.Func) and node.name == "grouping":
                    name = f"__agg{counter[0]}"
                    counter[0] += 1
                    sub_cols[name] = self._eval_agg(
                        dt, evl, node, gid, ngseg, out_alive, order,
                        dense)
                    return ex.ColumnRef(name)
                if isinstance(node, ex.BinOp):
                    return ex.BinOp(node.op, lower(node.left),
                                    lower(node.right))
                if isinstance(node, ex.Cast):
                    return ex.Cast(lower(node.operand), node.target)
                if isinstance(node, ex.Func):
                    return ex.Func(node.name,
                                   tuple(lower(a) for a in node.args))
                if isinstance(node, ex.Case):
                    return ex.Case(
                        tuple((lower(c), lower(v)) for c, v in node.whens),
                        lower(node.default)
                        if node.default is not None else None)
                return node

            lowered = lower(e)
            gtable = DTable(sub_cols, out_alive) if sub_cols else DTable(
                {"__x": DCol(jnp.zeros(ngseg, jnp.int32),
                             jnp.ones(ngseg, bool), INT32)}, out_alive)
            return JEval(gtable).eval(lowered)
        raise Unsupported(f"aggregate output {type(e).__name__}",
                          code="NDS208")

    def _scan_levels(self, gid, order) -> int:
        """Recorded bound on the compensated scan's doubling steps: the
        longest same-gid run (in sorted order), size-classed through
        ``_capacity_for`` so replay gets a STATIC level count plus a
        data-changed guard.  Typical group-bys need 8 levels, not the
        log2(capacity)=22+ an unconditional full scan pays."""
        gs = gid[order]
        n = int(gs.shape[0])
        pos = jax.lax.iota(jnp.int32, n)
        newrun = jnp.ones(n, bool).at[1:].set(gs[1:] != gs[:-1])
        runstart = jax.lax.cummax(jnp.where(newrun, pos, 0))
        cap, _ = self._capacity_for(jnp.max(pos - runstart) + 1)
        return max(0, int(cap).bit_length() - 1)

    def _segment_sum_typed(self, vals, gid, ngseg, kind: str, order):
        """int/decimal sums stay exact s64 segment_sum; float sums use
        the compensated segmented scan (TPU computes f64 at f32
        precision — ndstpu.engine.df64).  `order` may be a lazy thunk
        (direct group-id path computes the sort only when floats need
        it)."""
        if kind in ("decimal", "int32", "int64"):
            return jax.ops.segment_sum(vals, gid, num_segments=ngseg)
        from ndstpu.engine import df64
        if callable(order):
            order = order()
        levels = self._scan_levels(gid, order)
        return df64.segment_sum_compensated(vals, gid, ngseg, order,
                                            levels)

    def _segment_sum_float_pair(self, x1, x2, gid, ngseg, order):
        """Two compensated float segment sums sharing ONE scan (one
        sort-order gather, one doubled-carry scan — half the HLO of two
        independent scans; q39's stddev moments are the hot caller)."""
        from ndstpu.engine import df64
        if callable(order):
            order = order()
        levels = self._scan_levels(gid, order)
        return df64.segment_sum_compensated2(x1, x2, gid, ngseg, order,
                                             levels)

    def _pallas_kernel(self) -> Optional[Dict[str, bool]]:
        """The one rule for a Pallas kernel (segsum, keycmp) in a
        program: it is part of a TRACED REPLAY program and of nothing
        else -- None in eager execution and discovery, which take the
        plain jnp form of the same result (op by op on the host there:
        the interpreter over a power-run-sized grid, or an unfused
        [K, n] compare, is far slower than XLA's scatter); the choice
        adds no entry to the size plan, so discovery on the plain form
        and replay on the kernel stay record-consistent.  Else the
        kernel's call options: Mosaic lowers it on a TPU, the
        interpreter runs it where the default platform is the CPU
        (tests, rehearsals)."""
        if self.mode != "replay":
            return None
        return {"interpret": default_platform() == "cpu"}

    # one-hot MXU segment sums stay exact while every |value| < 2^41
    # (ndstpu.ops.segsum bias bound) and rows fit the int32 accumulator
    _PALLAS_ROWS_MAX = (2 ** 31 - 1) // 255
    # one-hot work grows with rows x segs, so the kernel's margin over
    # the scatter shrinks as segments grow; 32k keeps the whole SF1
    # item domain on the kernel
    _PALLAS_SEGS_MAX = 32768

    def _pallas_sum_ok(self, c: DCol, ngseg: int) -> bool:
        if ngseg > self._PALLAS_SEGS_MAX or \
                c.data.shape[0] > self._PALLAS_ROWS_MAX:
            return False
        if c.ctype.kind == "int32":
            return True
        if c.ctype.kind == "decimal":
            return c.ctype.precision <= 12      # |v| < 10^12 < 2^41
        if c.ctype.kind == "int64":
            return c.bounds is not None and \
                max(abs(c.bounds[0]), abs(c.bounds[1])) < (1 << 41)
        return False

    def _agg_column(self, dt: DTable, evl: JEval, a: ex.AggExpr, gid, ngseg,
                    out_alive, order, dense: bool = False) -> DCol:
        """``dense``: ``gid`` came from _direct_group_ids (``ngseg`` is
        the key domain, not the row capacity)."""
        func = a.func
        alive = dt.alive
        if a.distinct and func in ("count", "sum", "avg") and \
                not isinstance(a.arg, ex.Star):
            # distinct is a no-op for min/max; for count/sum/avg dedup
            # (group, value) pairs sort-side first
            return self._agg_distinct(dt, evl, a, gid, ngseg)
        if isinstance(a.arg, ex.Star):
            # count in int32 (row capacities are < 2^31); widen only the
            # group-capacity output to the INT64 result contract
            counts = jax.ops.segment_sum(alive.astype(jnp.int32), gid,
                                         num_segments=ngseg)
            return DCol(counts.astype(jnp.int64), jnp.ones(ngseg, bool),
                        INT64)
        c = evl.eval(a.arg)
        valid = c.valid & alive
        kernel = self._pallas_kernel() if dense and func in (
            "sum", "avg") and self._pallas_sum_ok(c, ngseg) else None
        if kernel is not None:
            # exact int64 sums + counts in one one-hot MXU kernel pass
            # (v5e has no native int64 ALU: XLA's int64 scatter-add is
            # emulated on the VPU; kernel against scatter not timed)
            from ndstpu.ops import segsum
            # ticks at trace time, like the exchange.* counters: proof
            # that a compiled program really contains the kernel
            obs.inc("engine.pallas.segsum_calls")
            sums, cnts = segsum.segment_sum_decimal(
                c.data.astype(jnp.int64), gid, valid, ngseg, **kernel)
            if func == "sum":
                if c.ctype.kind == "decimal":
                    return DCol(sums, cnts > 0, decimal(38, c.ctype.scale))
                return DCol(sums, cnts > 0, INT64)
            data = sums.astype(jnp.float64) / jnp.maximum(cnts, 1)
            if c.ctype.kind == "decimal":
                data = data / (10 ** c.ctype.scale)
            return DCol(data, cnts > 0, FLOAT64)
        if func == "count":
            counts = jax.ops.segment_sum(valid.astype(jnp.int32), gid,
                                         num_segments=ngseg)
            return DCol(counts.astype(jnp.int64), jnp.ones(ngseg, bool),
                        INT64)
        got = jax.ops.segment_sum(valid.astype(jnp.int32), gid,
                                  num_segments=ngseg) > 0
        if func == "sum":
            sums = self._segment_sum_typed(
                _sum_input(c.data, valid, c.ctype.kind), gid, ngseg,
                c.ctype.kind, order)
            if c.ctype.kind == "decimal":
                return DCol(sums, got, decimal(38, c.ctype.scale))
            if c.ctype.kind in ("int32", "int64"):
                return DCol(sums, got, INT64)
            return DCol(sums, got, FLOAT64)
        if func == "avg":
            cnts = jax.ops.segment_sum(valid.astype(jnp.int32), gid,
                                       num_segments=ngseg)
            sums = self._segment_sum_typed(
                _sum_input(c.data, valid, c.ctype.kind), gid, ngseg,
                c.ctype.kind, order)
            data = sums.astype(jnp.float64) / jnp.maximum(cnts, 1)
            if c.ctype.kind == "decimal":
                data = data / (10 ** c.ctype.scale)
            return DCol(data, cnts > 0, FLOAT64)
        if func in ("min", "max"):
            if c.ctype.kind == "float64":
                init = jnp.inf if func == "min" else -jnp.inf
                vals = jnp.where(valid, c.data, init)
                seg = (jax.ops.segment_min if func == "min"
                       else jax.ops.segment_max)
                out = seg(vals, gid, num_segments=ngseg)
                return DCol(out, got, c.ctype)
            vals = _minmax_vals(c.data, valid, c.ctype.kind,
                                func == "min")
            seg = (jax.ops.segment_min if func == "min"
                   else jax.ops.segment_max)
            out = seg(vals, gid, num_segments=ngseg)
            return DCol(out.astype(c.data.dtype), got, c.ctype,
                        c.dictionary, c.bounds)
        if func in ("stddev_samp", "var_samp", "stddev", "variance"):
            # shifted two-pass moments (see physical.py analog): center
            # by the group mean so E[x^2]-E[x]^2 cancellation cannot eat
            # the variance when mean >> stddev; the (sum d)^2/n term
            # corrects the mean's own rounding.  d1/d2 ride ONE
            # compensated scan (df64 pair carry) instead of two.
            x = evl.cast(c, FLOAT64).data
            xv = jnp.where(valid, x, 0.0)
            cnt = jax.ops.segment_sum(valid.astype(jnp.int32), gid,
                                      num_segments=ngseg)
            s1 = self._segment_sum_typed(xv, gid, ngseg, "float64", order)
            mean = s1 / jnp.maximum(cnt, 1)
            d = jnp.where(valid, x - mean[gid], 0.0)
            d1, d2 = self._segment_sum_float_pair(d, d * d, gid, ngseg,
                                                  order)
            ok = cnt > 1
            denom = jnp.where(ok, cnt - 1, 1)
            var = jnp.maximum(
                d2 - jnp.where(cnt > 0, d1 * d1 / jnp.maximum(cnt, 1), 0.0),
                0.0) / denom
            data = var if func in ("var_samp", "variance") else jnp.sqrt(var)
            return DCol(data, ok, FLOAT64)
        raise Unsupported(f"aggregate {func}", code="NDS207")

    # presence-bitmap distinct: ngseg x domain slots; 1<<22 int32 slots
    # = 16 MB peak, freed per aggregate
    _DISTINCT_BITMAP_SLOTS = 1 << 22

    def _agg_distinct(self, dt: DTable, evl: JEval, a: ex.AggExpr,
                      gid, ngseg) -> DCol:
        """count/sum/avg(DISTINCT x).

        Small-domain int/decimal columns (static bounds) use a
        presence BITMAP: scatter 1s into (segment, value-lo) slots and
        reduce rows of the dense (ngseg, domain) array — no sort.
        q28's six count(distinct ss_list_price) branches each paid a
        full-capacity 2-key sort over store_sales this replaces.  The
        branch choice derives ONLY from static metadata (ctype, bounds,
        ngseg), so discovery and replay always agree; replay guards
        values escaping the recorded bounds via the ok-mask like the
        group-by linearizer.  Everything else keeps the sort path:
        sort (group, value), keep the first row of each distinct pair,
        segment-combine as usual."""
        func = a.func
        c = evl.eval(a.arg)
        valid = c.valid & dt.alive
        if c.ctype.kind in ("decimal", "int32", "int64") and \
                c.bounds is not None:
            lo, hi = c.bounds
            domain = int(hi - lo + 1)
            if 0 < domain and ngseg * domain <= self._DISTINCT_BITMAP_SLOTS:
                return self._agg_distinct_bitmap(
                    c, valid, gid, ngseg, lo, domain, func)
        vkey = _key_col(c, dt.alive)
        order = _lexsort_order([gid, vkey])
        gid_s = gid[order]
        vkey_s = vkey[order]
        cap = dt.capacity
        first = jnp.ones(cap, bool).at[1:].set(
            (gid_s[1:] != gid_s[:-1]) | (vkey_s[1:] != vkey_s[:-1]))
        uniq = first & valid[order]
        cnts = jax.ops.segment_sum(uniq.astype(jnp.int32), gid_s,
                                   num_segments=ngseg)
        if func == "count":
            return DCol(cnts.astype(jnp.int64), jnp.ones(ngseg, bool),
                        INT64)
        got = cnts > 0
        data_s = c.data[order]
        if c.ctype.kind in ("decimal", "int32", "int64"):
            vals = jnp.where(uniq, data_s.astype(jnp.int64), 0)
            sums = jax.ops.segment_sum(vals, gid_s, num_segments=ngseg)
            if func == "sum":
                if c.ctype.kind == "decimal":
                    return DCol(sums, got, decimal(38, c.ctype.scale))
                return DCol(sums, got, INT64)
            mean = sums.astype(jnp.float64) / jnp.maximum(cnts, 1)
            if c.ctype.kind == "decimal":
                mean = mean / (10 ** c.ctype.scale)
            return DCol(mean, got, FLOAT64)
        vals = jnp.where(uniq, data_s.astype(jnp.float64), 0.0)
        sums = jax.ops.segment_sum(vals, gid_s, num_segments=ngseg)
        if func == "sum":
            return DCol(sums, got, FLOAT64)
        return DCol(sums / jnp.maximum(cnts, 1), got, FLOAT64)

    def _agg_distinct_bitmap(self, c: DCol, valid, gid, ngseg: int,
                             lo: int, domain: int, func: str) -> DCol:
        raw = c.data.astype(jnp.int64) - lo
        in_dom = (raw >= 0) & (raw < domain)
        use = valid & in_dom
        if self.mode == "replay":
            # a valid value outside the recorded bounds means the data
            # changed under this size class: fail the guard, rediscover
            self._oks.append(~jnp.any(valid & ~in_dom))
        idx = gid.astype(jnp.int64) * domain + jnp.clip(raw, 0, domain - 1)
        idx = jnp.where(use, idx, ngseg * domain)  # trash slot
        seen = jnp.zeros(ngseg * domain + 1, jnp.int32).at[idx].max(
            use.astype(jnp.int32))
        seen2 = seen[:-1].reshape(ngseg, domain)
        cnts = seen2.sum(axis=1).astype(jnp.int64)
        if func == "count":
            return DCol(cnts, jnp.ones(ngseg, bool), INT64)
        got = cnts > 0
        slot_vals = lo + jnp.arange(domain, dtype=jnp.int64)
        sums = (seen2.astype(jnp.int64) * slot_vals[None, :]).sum(axis=1)
        if func == "sum":
            if c.ctype.kind == "decimal":
                return DCol(sums, got, decimal(38, c.ctype.scale))
            return DCol(sums, got, INT64)
        mean = sums.astype(jnp.float64) / jnp.maximum(cnts, 1)
        if c.ctype.kind == "decimal":
            mean = mean / (10 ** c.ctype.scale)
        return DCol(mean, got, FLOAT64)

    # -- window --------------------------------------------------------------

    def _exec_window(self, p: lp.Window) -> DTable:
        dt = self.execute(p.child)
        out = dict(dt.columns)
        for name, e in p.exprs:
            if not isinstance(e, ex.WindowExpr):
                raise Unsupported("non-window expr in Window node",
                                  code="NDS209")
            out[name] = self._window_column(dt, e)
        return DTable(out, dt.alive)

    def _window_column(self, dt: DTable, w: ex.WindowExpr) -> DCol:
        cap = dt.capacity
        evl = JEval(dt)
        if w.partition_by:
            pcols = [evl.eval(self._resolve_subqueries(e))
                     for e in w.partition_by]
            pkeys = [_key_col(c, dt.alive) for c in pcols]
        else:
            pkeys = [jnp.where(dt.alive, 0, 1).astype(jnp.int32)]
        pid, _, _ = _group_ids(pkeys)
        okeys = []
        for e, asc in w.order_by:
            c = evl.eval(self._resolve_subqueries(e))
            okeys.append(self._order_key(evl, c, asc, None))
        if w.func in ("row_number", "rank", "dense_rank"):
            self._op_kinds["window_rank"] += 1
            order = _lexsort_order([pid] + okeys)
            idx = jax.lax.iota(jnp.int32, cap)
            pid_s = pid[order]
            newpart = jnp.ones(cap, bool)
            if cap > 1:
                newpart = newpart.at[1:].set(pid_s[1:] != pid_s[:-1])
            part_start = jax.lax.cummax(jnp.where(newpart, idx, 0))
            pos_in_part = idx - part_start
            inv = jnp.zeros(cap, jnp.int32).at[order].set(idx)
            if w.func == "row_number":
                return DCol((pos_in_part + 1)[inv].astype(jnp.int64),
                            jnp.ones(cap, bool), INT64)
            tie = jnp.zeros(cap, bool)
            if cap > 1:
                t = jnp.ones(cap - 1, bool)
                for k in okeys:
                    ks = k[order]
                    t = t & (ks[1:] == ks[:-1])
                tie = tie.at[1:].set(t & ~newpart[1:])
            if w.func == "rank":
                last_nontie = jax.lax.cummax(jnp.where(~tie, idx, 0))
                ranks = pos_in_part[last_nontie] + 1
            else:
                incr = jnp.where(newpart, 0, (~tie).astype(jnp.int32))
                csum = jnp.cumsum(incr)
                base = jax.lax.cummax(jnp.where(newpart, csum, 0))
                ranks = csum - base + 1
            return DCol(ranks[inv].astype(jnp.int64),
                        jnp.ones(cap, bool), INT64)
        # aggregate window: whole partition without ORDER BY; with ORDER BY
        # a running UNBOUNDED PRECEDING..CURRENT ROW frame (Spark default
        # RANGE — peers share the run value; explicit ROWS = per-row)
        if w.order_by:
            self._op_kinds["window_running"] += 1
            return self._running_window(dt, evl, w, pid, okeys)
        self._op_kinds["window_whole"] += 1
        gid = pid
        if w.func == "count" and (w.arg is None or
                                  isinstance(w.arg, ex.Star)):
            cnt = jax.ops.segment_sum(dt.alive.astype(jnp.int32), gid,
                                      num_segments=cap)
            return DCol(cnt[gid].astype(jnp.int64), jnp.ones(cap, bool),
                        INT64)
        arg = evl.eval(self._resolve_subqueries(w.arg))
        valid = arg.valid & dt.alive
        cnts = jax.ops.segment_sum(valid.astype(jnp.int32), gid,
                                   num_segments=cap)
        got = (cnts > 0)[gid]
        if w.func == "count":
            return DCol(cnts[gid].astype(jnp.int64),
                        jnp.ones(cap, bool), INT64)
        if w.func == "sum":
            tot = jax.ops.segment_sum(
                _sum_input(arg.data, valid, arg.ctype.kind), gid,
                num_segments=cap)
            if arg.ctype.kind == "decimal":
                return DCol(tot[gid], got, decimal(38, arg.ctype.scale))
            if arg.ctype.kind in ("int32", "int64"):
                return DCol(tot[gid], got, INT64)
            return DCol(tot[gid], got, FLOAT64)
        if w.func == "avg":
            tot = jax.ops.segment_sum(
                _sum_input(arg.data, valid, arg.ctype.kind), gid,
                num_segments=cap)
            mean = tot.astype(jnp.float64) / jnp.maximum(cnts, 1)
            if arg.ctype.kind == "decimal":
                mean = mean / (10 ** arg.ctype.scale)
            return DCol(mean[gid], got, FLOAT64)
        if w.func in ("min", "max"):
            if arg.ctype.kind == "float64":
                init = jnp.inf if w.func == "min" else -jnp.inf
                vals = jnp.where(valid, arg.data, init)
                seg = (jax.ops.segment_min if w.func == "min"
                       else jax.ops.segment_max)
                return DCol(seg(vals, gid, num_segments=cap)[gid], got,
                            arg.ctype)
            vals = _minmax_vals(arg.data, valid, arg.ctype.kind,
                                w.func == "min")
            seg = (jax.ops.segment_min if w.func == "min"
                   else jax.ops.segment_max)
            out = seg(vals, gid, num_segments=cap)[gid]
            return DCol(out.astype(arg.data.dtype), got, arg.ctype,
                        arg.dictionary)
        raise Unsupported(f"window {w.func}", code="NDS209")

    def _running_window(self, dt: DTable, evl: JEval, w: ex.WindowExpr,
                        pid, okeys: List[jnp.ndarray]) -> DCol:
        """UNBOUNDED PRECEDING..CURRENT ROW running aggregate on device
        (q51 shape; numpy analog: physical.Executor._running_window).
        Sort by (partition, order keys), segmented cumulative combine,
        peers share the end-of-tie-run value under RANGE frames."""
        cap = dt.capacity
        idx = jax.lax.iota(jnp.int32, cap)
        order = _lexsort_order([pid] + okeys)
        inv = jnp.zeros(cap, jnp.int32).at[order].set(idx)
        pid_s = pid[order]
        newpart = jnp.ones(cap, bool).at[1:].set(pid_s[1:] != pid_s[:-1])
        pstart = jax.lax.cummax(jnp.where(newpart, idx, 0))
        if w.frame != "rows":
            t = jnp.ones(cap - 1, bool)
            for k in okeys:
                ks = k[order]
                t = t & (ks[1:] == ks[:-1])
            tie = jnp.zeros(cap, bool).at[1:].set(t & ~newpart[1:])
            end_marker = jnp.ones(cap, bool).at[:-1].set(~tie[1:])
            run_end = jax.lax.cummin(jnp.where(end_marker, idx, cap),
                                     reverse=True)
        else:
            run_end = idx

        def seg_cumsum(x):
            cs = jnp.cumsum(x)
            base = jnp.where(pstart > 0, cs[jnp.maximum(pstart - 1, 0)], 0)
            return cs - base

        alive_s = dt.alive[order]
        if w.arg is None or isinstance(w.arg, ex.Star):  # count(*)
            run = seg_cumsum(alive_s.astype(jnp.int32))[run_end]
            return DCol(run[inv].astype(jnp.int64),
                        jnp.ones(cap, bool), INT64)
        arg = evl.eval(self._resolve_subqueries(w.arg))
        valid_s = (arg.valid & dt.alive)[order]
        data_s = arg.data[order]
        rcnt = seg_cumsum(valid_s.astype(jnp.int32))[run_end]
        got = (rcnt > 0)[inv]
        if w.func == "count":
            return DCol(rcnt[inv].astype(jnp.int64),
                        jnp.ones(cap, bool), INT64)
        if w.func in ("sum", "avg"):
            run = seg_cumsum(
                _sum_input(data_s, valid_s, arg.ctype.kind))[run_end]
            if w.func == "sum":
                if arg.ctype.kind == "decimal":
                    return DCol(run[inv], got,
                                decimal(38, arg.ctype.scale))
                if arg.ctype.kind in ("int32", "int64"):
                    return DCol(run[inv], got, INT64)
                return DCol(run[inv], got, FLOAT64)
            mean = run.astype(jnp.float64)
            if arg.ctype.kind == "decimal":
                mean = mean / (10 ** arg.ctype.scale)
            return DCol((mean / jnp.maximum(rcnt, 1))[inv], got, FLOAT64)
        if w.func in ("min", "max"):
            is_min = w.func == "min"
            opfn = jnp.minimum if is_min else jnp.maximum
            if arg.ctype.kind == "float64":
                sent = jnp.inf if is_min else -jnp.inf
                x = jnp.where(valid_s, data_s, sent)
            else:
                x = _minmax_vals(data_s, valid_s, arg.ctype.kind, is_min)
                sent = x.dtype.type(
                    jnp.iinfo(x.dtype).max if is_min
                    else jnp.iinfo(x.dtype).min)
            # doubling prefix scan clipped at partition starts
            out = x
            shift = 1
            while shift < cap:
                cand = jnp.concatenate(
                    [jnp.full(shift, sent, out.dtype), out[:-shift]])
                take = (idx - shift) >= pstart
                out = jnp.where(take, opfn(out, cand), out)
                shift *= 2
            out = out[run_end][inv]
            if arg.ctype.kind != "float64":
                out = out.astype(arg.data.dtype)
            return DCol(out, got, arg.ctype, arg.dictionary)
        raise Unsupported(f"running window {w.func}", code="NDS209")

    # -- distinct ------------------------------------------------------------

    def _exec_distinct(self, p: lp.Distinct) -> DTable:
        return self._distinct_of(self.execute(p.child))

    def _distinct_of(self, dt: DTable) -> DTable:
        for c in dt.columns.values():
            if c.ctype.kind not in ("int32", "int64", "decimal", "date",
                                    "string", "bool", "float64"):
                raise Unsupported("distinct column type")
        cap = dt.capacity
        keys = [_key_col(c, dt.alive) for c in dt.columns.values()]
        gid, order, newgrp = _group_ids(keys)
        first_pos = jnp.full(cap, cap, jnp.int32).at[
            (jnp.cumsum(newgrp.astype(jnp.int32)) - 1)].min(
            jax.lax.iota(jnp.int32, cap))
        rep = order[jnp.clip(first_pos, 0, cap - 1)]
        slot_used = jnp.zeros(cap, bool).at[gid].set(True)
        galive = jax.ops.segment_sum(dt.alive.astype(jnp.int32), gid,
                                     num_segments=cap) > 0
        out_alive = slot_used & galive
        return DTable(_gather_cols(dt.columns, rep, out_alive), out_alive)

    # -- set ops -------------------------------------------------------------

    def _exec_setop(self, p: lp.SetOp) -> DTable:
        lt = self.execute(p.left)
        rt = self.execute(p.right)
        rt = DTable(dict(zip(lt.column_names, rt.columns.values())),
                    rt.alive)
        both = self._vconcat(lt, rt)
        if p.kind == "union":
            return both if p.all else self._distinct_of(both)
        # intersect / except, distinct semantics (Spark): keep the first
        # left occurrence of each qualifying row-value group
        self._op_kinds["setop"] += 1
        cap = both.capacity
        nl = lt.capacity
        keys = [_key_col(c, both.alive) for c in both.columns.values()]
        gid, order, newgrp = _group_ids(keys)
        pos = jax.lax.iota(jnp.int32, cap)
        is_left = pos < nl
        in_left = jax.ops.segment_sum(
            (both.alive & is_left).astype(jnp.int32), gid,
            num_segments=cap) > 0
        in_right = jax.ops.segment_sum(
            (both.alive & ~is_left).astype(jnp.int32), gid,
            num_segments=cap) > 0
        keepg = (in_left & in_right) if p.kind == "intersect" else \
            (in_left & ~in_right)
        lidx = jnp.where(both.alive & is_left, pos, cap)
        firstl = jnp.full(cap, cap, jnp.int32).at[gid].min(lidx)
        keep = (firstl[gid] == pos) & keepg[gid] & both.alive & is_left
        return DTable(both.columns, keep)

    def _vconcat(self, lt: DTable, rt: DTable) -> DTable:
        """Vertical concat with dictionary merge / numeric unification."""
        cols: Dict[str, DCol] = {}
        for n in lt.column_names:
            lc, rc = lt.column(n), rt.column(n)
            if lc.ctype.kind == "string":
                merged = _merged_dict([lc, rc])
                ld = _translate(lc, merged)
                rd = _translate(rc, merged)
                ld = jnp.where(ld == -2, -1, ld)
                rd = jnp.where(rd == -2, -1, rd)
                cols[n] = DCol(jnp.concatenate([ld, rd]),
                               jnp.concatenate([lc.valid, rc.valid]),
                               STRING, merged.astype(object))
            else:
                tgt = lc.ctype
                if rc.ctype.kind != tgt.kind or \
                        (tgt.kind == "decimal" and
                         rc.ctype.scale != tgt.scale):
                    tgt = ex.common_type(lc.ctype, rc.ctype)
                    lc = JEval(lt).cast(lc, tgt)
                    rc = JEval(rt).cast(rc, tgt)
                bounds = None
                if lc.bounds is not None and rc.bounds is not None:
                    bounds = (min(lc.bounds[0], rc.bounds[0]),
                              max(lc.bounds[1], rc.bounds[1]))
                cols[n] = DCol(
                    jnp.concatenate([lc.data, rc.data]),
                    jnp.concatenate([lc.valid, rc.valid]), tgt, None,
                    bounds)
        alive = jnp.concatenate([lt.alive, rt.alive])
        return DTable(cols, alive)

    # -- join ----------------------------------------------------------------

    @staticmethod
    def _direct_join_spec(lc: DCol, rc: DCol):
        """Static (lo, span, lmult, rmult) when this key pair can be
        encoded directly from values (no rank-pairing sort): int-like
        kinds on both sides with known bounds, scales aligned by exact
        host-side multipliers.  None -> rank-pair fallback."""
        int_kinds = ("int32", "int64", "date", "decimal")
        if lc.ctype.kind not in int_kinds or rc.ctype.kind not in int_kinds:
            return None
        if lc.bounds is None or rc.bounds is None:
            return None
        ls = lc.ctype.scale if lc.ctype.kind == "decimal" else 0
        rs = rc.ctype.scale if rc.ctype.kind == "decimal" else 0
        s = max(ls, rs)
        lmult, rmult = 10 ** (s - ls), 10 ** (s - rs)
        blo = min(lc.bounds[0] * lmult, rc.bounds[0] * rmult)
        bhi = max(lc.bounds[1] * lmult, rc.bounds[1] * rmult)
        span = bhi - blo + 1
        if span >= 2 ** 62:
            return None
        return (blo, span, lmult, rmult)

    @staticmethod
    def _string_join_spec(lc: DCol, rc: DCol):
        """Static (merged_dict_or_None, span) for a string key pair.
        merged is None when both sides share one dictionary (codes used
        as-is)."""
        if lc.ctype.kind != "string" or rc.ctype.kind != "string":
            return None
        if lc.dictionary is not None and rc.dictionary is not None and \
                len(lc.dictionary) == len(rc.dictionary) and \
                np.array_equal(lc.dictionary, rc.dictionary):
            return (None, max(len(lc.dictionary), 1))
        merged = _merged_dict([lc, rc])
        return (merged, max(len(merged), 1))

    def _join_key_cols(self, lt: DTable, rt: DTable,
                       keys: List[Tuple[ex.Expr, ex.Expr]]):
        """(left, right) key columns, pair by pair."""
        levl, revl = JEval(lt), JEval(rt)
        return ([levl.eval(self._resolve_subqueries(le)) for le, _ in keys],
                [revl.eval(self._resolve_subqueries(re_)) for _, re_ in keys])

    def _join_key_specs(self, lcols: List[DCol], rcols: List[DCol],
                        rank_radix: int):
        """Host side of _join_keys: each pair's direct encoding (None:
        the rank-pairing sort, over ``rank_radix`` ranks), whether the
        radixes pass int64 on the way and re-densify, and the composite
        key's exclusive bound.  With every pair direct and no
        re-densifying the three depend on neither side's capacity."""
        specs = []
        for lc, rc in zip(lcols, rcols):
            spec = self._direct_join_spec(lc, rc)
            if spec is None and lc.ctype.kind == "string":
                sspec = self._string_join_spec(lc, rc)
                if sspec is not None:
                    spec = ("str",) + sspec
            specs.append(spec)
        # simulate the radix accumulation to pick the key dtype
        bound = 1
        redensified = False
        for spec in specs:
            if spec is None:
                radix = rank_radix
            elif spec[0] == "str":
                radix = spec[2]
            else:
                radix = spec[1]
            if bound * radix >= 2 ** 62:
                redensified = True
                bound = rank_radix
            bound *= radix
        return specs, redensified, bound

    def _join_keys(self, lt: DTable, rt: DTable,
                   keys: List[Tuple[ex.Expr, ex.Expr]]):
        """Composite join keys on both sides (mixed-radix).

        Key pairs whose value domain is statically known (int-like with
        bounds, dictionary-coded strings) are encoded DIRECTLY from
        values — no joint dense-rank, which costs a full sort over the
        combined capacities per key.  Only unbounded pairs (raw float64,
        computed columns without bounds) pay the rank-pairing sort.
        When the final composite bound fits int32 the whole key build
        runs in int32 (native on v5e; int64 is emulated as s32 pairs)."""
        lcols, rcols = self._join_key_cols(lt, rt, keys)
        capl, capr = lt.capacity, rt.capacity
        rank_radix = capl + capr + 3
        specs, redensified, bound = self._join_key_specs(
            lcols, rcols, rank_radix)
        use32 = (not redensified) and bound < 2 ** 31
        kdt = jnp.int32 if use32 else jnp.int64
        lkey = jnp.zeros(capl, kdt)
        rkey = jnp.zeros(capr, kdt)
        lvalid = jnp.ones(capl, bool)
        rvalid = jnp.ones(capr, bool)
        bound = 1  # exclusive upper bound on current composite key values
        for (lc, rc), spec in zip(zip(lcols, rcols), specs):
            if spec is not None and spec[0] == "str":
                _, merged, span = spec
                la = _translate(lc, merged) if merged is not None \
                    else lc.data
                ra = _translate(rc, merged) if merged is not None \
                    else rc.data
                # invalid codes (<0) clip into range; those rows are
                # overridden by the validity sentinels downstream
                la = jnp.clip(la, 0, span - 1).astype(kdt)
                ra = jnp.clip(ra, 0, span - 1).astype(kdt)
                radix = span
            elif spec is not None:
                blo, span, lmult, rmult = spec
                radix = span
                # build in int32 only when the aligned value range fits;
                # garbage (dead/invalid) rows may wrap — they are
                # sentinel-overridden downstream
                if use32 and -(2 ** 31) < blo and \
                        blo + span - 1 < 2 ** 31:
                    la = jnp.clip(lc.data.astype(jnp.int32) * lmult - blo,
                                  0, span - 1)
                    ra = jnp.clip(rc.data.astype(jnp.int32) * rmult - blo,
                                  0, span - 1)
                else:
                    la = jnp.clip(lc.data.astype(jnp.int64) * lmult - blo,
                                  0, span - 1).astype(kdt)
                    ra = jnp.clip(rc.data.astype(jnp.int64) * rmult - blo,
                                  0, span - 1).astype(kdt)
            else:
                if capl * capr > 2 ** 48:
                    raise Unsupported("join too large for rank pairing")
                la64 = _key_i64(lc, lt.alive, peer=rc)
                ra64 = _key_i64(rc, rt.alive, peer=lc)
                # decimal/int alignment (rank path only; direct path
                # aligns via host multipliers)
                if lc.ctype.kind == "decimal" or rc.ctype.kind == "decimal":
                    ls = lc.ctype.scale if lc.ctype.kind == "decimal" else 0
                    rs = rc.ctype.scale if rc.ctype.kind == "decimal" else 0
                    s = max(ls, rs)
                    la64 = jnp.where(jnp.abs(la64) < _DEAD_KEY,
                                     la64 * (10 ** (s - ls)), la64)
                    ra64 = jnp.where(jnp.abs(ra64) < _DEAD_KEY,
                                     ra64 * (10 ** (s - rs)), ra64)
                lr, rr = _dense_rank_pair(la64, ra64)
                la, ra = lr.astype(kdt), rr.astype(kdt)
                radix = rank_radix
            if bound * radix >= 2 ** 62:
                # re-densify the accumulated composite so mixed-radix
                # never overflows int64, however many join keys there are
                lkey, rkey = _dense_rank_pair(lkey, rkey)
                lkey, rkey = lkey.astype(kdt), rkey.astype(kdt)
                bound = rank_radix
            lkey = lkey * radix + la
            rkey = rkey * radix + ra
            bound = bound * radix
            lvalid = lvalid & lc.valid
            rvalid = rvalid & rc.valid
        return lkey, rkey, lvalid, rvalid, bound

    def _lut_span(self, bound, m: int, n: int) -> Optional[int]:
        """Slots of the direct-addressed tables for a join of ``m`` build
        and ``n`` probe rows, or None for the sort path."""
        # LUT only when the domain is within both the absolute cap and a
        # small multiple of the table sizes: its cumsum/memset run over
        # `bound` slots, so a near-cap domain against tiny tables would
        # cost far more than the sort path over m+n rows
        if bound is not None and 0 < bound <= min(
                _JOIN_LUT_CAP, max(8 * (m + n), 1 << 20)):
            return int(bound)
        return None

    @staticmethod
    def _build_counts(bkey: jnp.ndarray, span: int):
        """(bidx, cnt_t): each build row's slot (dead and NULL-key rows
        go to the trash slot ``span``) and the rows per slot."""
        bidx = jnp.where(bkey >= 0, bkey, span).astype(jnp.int32)
        return bidx, jnp.zeros(span + 1, jnp.int32).at[bidx].add(1)

    def _probe_counts(self, pkey: jnp.ndarray, bkey: jnp.ndarray,
                      bound: int, need_order: bool = True,
                      cnt_t: Optional[jnp.ndarray] = None):
        """Per-probe-row (lo, counts) against the build side, plus the
        build-side stable key order: ``order[lo[i] .. lo[i]+counts[i]-1]``
        are the build rows matching probe row ``i``.

        NO ``searchsorted``: on TPU its binary-search lowering costs one
        full-capacity gather per iteration.  Instead:

        * ``bound <= _LUT_CAP``: direct-addressed lookup tables.  Build
          counts via one scatter-add over the key domain, starts via one
          cumsum, probe via two gathers.  (The composite join key bound
          is statically known — _join_keys tracks it — so this is the
          common case: surrogate-key joins are dense small domains.)
        * otherwise: ONE variadic sort of concat(build, probe) tagged by
          side; in sorted order, builds-before = prefix count, the run
          start carries lo, and unique-destination scatters route
          lo/counts back to probe positions and build ranks to `order`.

        Probe rows with key < 0 (sentinels) never match; build rows with
        key < 0 never enter the tables but DO occupy `order` slots (they
        sort first), matching the old sort+searchsorted layout.
        """
        m = int(bkey.shape[0])
        n = int(pkey.shape[0])
        iota_m = jax.lax.iota(jnp.int32, m)
        span = self._lut_span(bound, m, n)
        if span is not None:
            if cnt_t is None:   # else the caller's _build_counts table
                cnt_t = self._build_counts(bkey, span)[1]
            cnt = cnt_t[:span]
            ccnt = jnp.cumsum(cnt)
            # valid build keys sort AFTER the (<0) sentinel rows in the
            # stable key order, so starts are offset by the dead count
            n_dead = jnp.sum((bkey < 0).astype(jnp.int32))
            starts = ccnt - cnt + n_dead
            pk = jnp.clip(pkey, 0, span - 1).astype(jnp.int32)
            hit = pkey >= 0
            counts = jnp.where(hit, cnt[pk], 0)
            lo = starts[pk].astype(jnp.int32)
            order = None
            if need_order:
                # dead build rows (key < 0) sort FIRST, matching the
                # `starts` offset by n_dead above
                okey = jnp.where(bkey >= 0, bkey, -1).astype(jnp.int32)
                order = jax.lax.sort((okey, iota_m), num_keys=1,
                                     is_stable=True)[1]
            return lo, counts, order
        key = jnp.concatenate([bkey, pkey])
        tag = (jax.lax.iota(jnp.int32, m + n) >= m).astype(jnp.int32)
        idx = jax.lax.iota(jnp.int32, m + n)
        skey, stag, sidx = jax.lax.sort((key, tag, idx), num_keys=2,
                                        is_stable=True)
        isb = (stag == 0).astype(jnp.int32)
        builds_le = jnp.cumsum(isb)               # builds at pos <= s
        before = builds_le - isb                  # builds strictly before s
        newrun = jnp.ones(m + n, bool).at[1:].set(skey[1:] != skey[:-1])
        # `before` is non-decreasing, so cummax propagates each run
        # start's value (builds with key < run key) across the run
        lo_sorted = jax.lax.cummax(jnp.where(newrun, before, 0))
        cnt_sorted = builds_le - lo_sorted        # builds in run up to s
        dest = jnp.where(stag == 1, sidx - m, n)  # build rows -> trash slot
        lo = jnp.zeros(n + 1, jnp.int32).at[dest].set(lo_sorted)[:n]
        counts = jnp.zeros(n + 1, jnp.int32).at[dest].set(cnt_sorted)[:n]
        counts = jnp.where(pkey >= 0, counts, 0)
        order = None
        if need_order:
            bdest = jnp.where(isb == 1, builds_le - 1, m)
            order = jnp.zeros(m + 1, jnp.int32).at[bdest].set(sidx)[:m]
        return lo, counts, order

    @staticmethod
    def _expand_li(counts: jnp.ndarray, starts: jnp.ndarray,
                   out_cap: int) -> jnp.ndarray:
        """Left-row index feeding each expansion output position.

        Replaces ``searchsorted(cumsum(counts), pos)``: scatter each
        emitting row's id at its start position, cummax fills the run.
        Starts of emitting rows are strictly increasing, so destinations
        are unique."""
        cap = int(counts.shape[0])
        emit = counts > 0
        sdest = jnp.where(emit, starts, out_cap)
        rid = jnp.where(emit, jax.lax.iota(jnp.int32, cap) + 1, 0)
        tmp = jnp.zeros(out_cap + 1, jnp.int32).at[sdest].max(rid)
        li = jax.lax.cummax(tmp[:out_cap]) - 1
        return jnp.clip(li, 0, cap - 1)

    def _exec_join(self, p: lp.Join) -> DTable:
        kind = p.kind
        # the probe side of an inner / left equi-join on plain columns
        # may come with its survivors' compaction pending: _equi_join
        # settles it, or looks up over the rows where they lie and
        # leaves the compaction to this join's consumer (_probe_rows)
        takes_pending = kind in ("inner", "left") and p.extra is None \
            and bool(p.keys) and all(isinstance(e, ex.ColumnRef)
                                     for pair in p.keys for e in pair)
        lt = self.execute(p.left, settled=not takes_pending)
        rt = self.execute(p.right)
        extra = self._resolve_subqueries(p.extra) \
            if p.extra is not None else None
        if isinstance(p.left, lp.Aggregate) and any(
                n.startswith(optimizer.EXTREMES) for n, _ in p.left.aggs):
            self._op_kinds["exists_extremes"] += 1
        if kind == "cross" or not p.keys:
            if kind not in ("cross", "inner"):
                raise Unsupported(f"non-equi {kind} join", code="NDS210")
            return self._cross_join(lt, rt, extra)
        if kind == "right":
            out = self._equi_join(rt, lt,
                                  [(r, l) for l, r in p.keys], "left",
                                  extra)
            return out.select(list(lt.columns) + list(rt.columns))
        if kind == "full":
            return self._full_join(lt, rt, p.keys, extra)
        if kind == "mark":
            return self._equi_join(lt, rt, p.keys, kind, extra,
                                   mark=p.mark)
        return self._equi_join(lt, rt, p.keys, kind, extra)

    def _cross_join(self, lt: DTable, rt: DTable, extra) -> DTable:
        ltc = self.compact(lt)
        rtc = self.compact(rt)
        nl = jnp.sum(ltc.alive)
        nr = jnp.sum(rtc.alive)
        out_cap, total = self._capacity_for(nl * nr)
        pos = jax.lax.iota(jnp.int32, out_cap)
        nr_safe = jnp.maximum(nr, 1).astype(jnp.int32)
        li = jnp.minimum(pos // nr_safe, ltc.capacity - 1)
        ri = jnp.minimum(pos % nr_safe, rtc.capacity - 1)
        alive = pos < jnp.asarray(total).astype(jnp.int32)
        lcols = _gather_cols(ltc.columns, li, alive)
        rcols = _gather_cols(rtc.columns, ri, alive)
        out = DTable({**lcols, **rcols}, alive)
        if extra is not None:
            mask = JEval(out).predicate(extra)
            out = DTable(out.columns, out.alive & mask)
        return out

    def _full_join(self, lt: DTable, rt: DTable, keys, extra) -> DTable:
        self._op_kinds["join_full"] += 1
        left_part = self._equi_join(lt, rt, keys, "left", extra)
        # right rows with no key match (residual predicate excluded, as in
        # the reference interpreter's full-join path)
        lkey, rkey, lvalid, rvalid, bound = self._join_keys(lt, rt, keys)
        lkey = jnp.where(lvalid & lt.alive, lkey, -1)
        rkey = jnp.where(rvalid & rt.alive, rkey, -2)
        # the second probe, the other way round: counted by its path
        # like any other
        span = self._lut_span(bound, lt.capacity, rt.capacity)
        self._join_paths["sort" if span is None else "expand"] += 1
        _, rcounts, _ = self._probe_counts(rkey, lkey, bound,
                                           need_order=False)
        runmatched = rt.alive & ~(rcounts > 0)
        # bottom block: null left columns + unmatched right rows
        bottom_cols: Dict[str, DCol] = {}
        for n, c in lt.columns.items():
            # null left columns sized to the bottom block's (right)
            # capacity; bounds stay sound (filler rows are all invalid)
            bottom_cols[n] = DCol(jnp.zeros(rt.capacity, c.data.dtype),
                                  jnp.zeros(rt.capacity, bool), c.ctype,
                                  c.dictionary, c.bounds)
        for n, c in rt.columns.items():
            bottom_cols[n] = DCol(c.data, c.valid & runmatched, c.ctype,
                                  c.dictionary, c.bounds)
        bottom = DTable(bottom_cols, runmatched)
        return self._vconcat(left_part, bottom)

    def _residual_hits(self, lt: DTable, rt: DTable, order, lo, counts,
                       extra) -> jnp.ndarray:
        """Per-left-row mask: does any key match survive the residual
        predicate?  (shared by semi / anti / mark joins)"""
        self._op_kinds["join_residual"] += 1
        out_cap, total = self._capacity_for(
            jnp.sum(counts, dtype=jnp.int64))
        inner = self._expand(lt, rt, order, lo, counts, total, out_cap)
        keep = JEval(inner).predicate(extra)
        starts = (jnp.cumsum(counts) - counts).astype(jnp.int32)
        li_all = self._expand_li(counts, starts, out_cap)
        return jax.ops.segment_sum(keep.astype(jnp.int32), li_all,
                                   num_segments=lt.capacity) > 0

    def _equi_join(self, lt: DTable, rt: DTable, keys, kind,
                   extra, mark: Optional[str] = None) -> DTable:
        sized = None
        if lt.pending is not None:
            lt, sized = self._probe_rows(lt, rt, keys)
        lkey, rkey, lvalid, rvalid, bound = self._join_keys(lt, rt, keys)

        if kind == "nullaware_anti":
            rt_has_null = self._branch_bool(jnp.any(~rvalid & rt.alive))
            rt_nonempty = self._branch_bool(jnp.any(rt.alive))
            if rt_has_null:
                return DTable(lt.columns, jnp.zeros(lt.capacity, bool))
            kind = "anti"
            if rt_nonempty:
                lt = DTable(lt.columns, lt.alive & lvalid)

        # null keys never match; dead rows already sentineled apart
        lkey = jnp.where(lvalid & lt.alive, lkey, -1)
        rkey = jnp.where(rvalid & rt.alive, rkey, -2)

        # (the tables are sized for the probe's rows, not for the
        # capacity a pending compaction leaves them in)
        span = self._lut_span(bound, rt.capacity,
                              lt.pending or lt.capacity)
        cnt_t = None
        if span is not None and kind in ("inner", "left"):
            # a build side whose alive keys are unique (a dimension on
            # its surrogate key) gives each probe row 0 or 1 match: one
            # lookup, no expansion.  Observed, recorded and guarded like
            # every other data-dependent choice of the size plan.
            k_cap, n_b = sized or self._capacity_for(
                jnp.sum(rkey >= 0, dtype=jnp.int32))
            if self._compare_cheaper(lt.capacity, rt.capacity, k_cap):
                # few alive build keys (a filtered dimension): compare
                # each probe key with all of them -- no table over the
                # key domain, no probe-sized gather
                bkeys, brows = self._alive_build_keys(rkey, k_cap, n_b)
                skeys = jnp.sort(bkeys)
                unique = self._branch_bool(jnp.all(
                    (skeys[1:] != skeys[:-1]) | (skeys[1:] < 0)))
                if unique:
                    self._join_paths["compare"] += 1
                    ri = self._compare_rows(lkey, bkeys, brows, span)
            else:
                bidx, cnt_t = self._build_counts(rkey, span)
                unique = self._branch_bool(jnp.max(cnt_t[:span]) <= 1)
                if unique:
                    ri = self._table_rows(
                        lkey, bidx, jax.lax.iota(jnp.int32, rt.capacity),
                        span)
            if unique:
                self._join_paths["lookup"] += 1
                return self._lookup_join(lt, rt, lkey, ri, kind, extra)
            if lt.pending is not None:
                # the expansion wants dense rows after all
                lt = self._settle(lt)
                lkey, _, lvalid, _, _ = self._join_keys(lt, rt, keys)
                lkey = jnp.where(lvalid & lt.alive, lkey, -1)
        self._join_paths["sort" if span is None else "expand"] += 1
        if kind in ("semi", "anti", "mark"):
            self._op_kinds["join_mark" if kind == "mark"
                           else "join_semi"] += 1

        need_order = kind in ("inner", "left") or extra is not None
        lo, counts, order = self._probe_counts(lkey, rkey, bound,
                                               need_order=need_order,
                                               cnt_t=cnt_t)
        counts = jnp.where(lt.alive, counts, 0)
        matched = counts > 0

        if kind == "mark":
            # EXISTS under OR: left table + boolean mark column
            # (numpy analog: physical.py mark-join path)
            if extra is not None:
                matched = self._residual_hits(lt, rt, order, lo, counts,
                                              extra)
            cols = dict(lt.columns)
            cols[mark] = DCol(matched & lt.alive,
                              jnp.ones(lt.capacity, bool), BOOL)
            return DTable(cols, lt.alive)

        if kind in ("semi", "anti"):
            if extra is not None:
                hits = self._residual_hits(lt, rt, order, lo, counts,
                                           extra)
                mask = hits if kind == "semi" else ~hits
                return DTable(lt.columns, lt.alive & mask)
            mask = matched if kind == "semi" else \
                (~matched & lt.alive)
            return DTable(lt.columns, lt.alive & mask)

        # inner/left expansion: one sync point for output capacity
        if kind == "inner":
            out_cap, total = self._capacity_for(
                jnp.sum(counts, dtype=jnp.int64))
            out = self._expand(lt, rt, order, lo, counts, total, out_cap)
            if extra is not None:
                mask = JEval(out).predicate(extra)
                out = DTable(out.columns, out.alive & mask)
            return out
        if kind == "left":
            return self._left_join(lt, rt, order, lo, counts, extra)
        raise Unsupported(f"join kind {kind}", code="NDS210")

    def _lookup_join(self, lt: DTable, rt: DTable, lkey, ri, kind: str,
                     extra) -> DTable:
        """Inner / left join against a build side with unique alive keys,
        given each probe row's build row ``ri`` (-1: none): lazy build
        columns and (inner) the survivors' size class, recorded here and
        left PENDING on the result: the compaction to it is the
        consumer's (execute() settles it; a compare lookup above may
        pass it on, _probe_rows).  A left join hands on what its probe
        came with.  Output rows keep the probe's order."""
        matched = (ri >= 0) & (lkey >= 0) & lt.alive
        ri = jnp.maximum(ri, 0)
        rcols = _gather_cols(rt.columns, ri, matched)
        if lt.pending is not None:
            # (no ``extra`` here: _exec_join)
            self._join_paths["deferred"] += 1
        if kind == "left":
            if extra is not None:
                # at most one candidate a probe row: the residual
                # predicate is a mask over the joined row
                joined = DTable({**lt.columns, **rcols}, matched)
                matched = matched & JEval(joined).predicate(extra)
                rcols = _gather_cols(rt.columns, ri, matched)
            return DTable({**lt.columns, **rcols}, lt.alive, lt.pending)
        out = DTable({**lt.columns, **rcols}, matched)
        cap, n_out = self._capacity_for(jnp.sum(matched))
        # (survivors that fill the probe's size class: nothing moves)
        pending = cap if cap != lt.capacity else None
        if extra is None:
            return DTable(out.columns, matched, pending)
        if pending is not None:
            out = self._compact_to(out, cap, n_out)
        # on the compacted rows, as the expansion path does: the
        # predicate's right columns are gathered at ``cap``
        return DTable(out.columns, out.alive & JEval(out).predicate(extra))

    @staticmethod
    def _lookup_costs(n: int, m: int, k_cap: int) -> Tuple[float, float]:
        """(compare, gather): what finding the build row of ``n`` probe
        keys costs by comparing each with ``k_cap`` alive build keys
        (compacted out of ``m`` build rows first), and by the
        direct-addressed lookup (a gather of ``n`` elements behind two
        scatters of the ``m`` build rows).  In gathered elements;
        _COMPARE_PAIR_COST has the readings."""
        if k_cap > _COMPARE_MAX_KEYS:
            return math.inf, n + 2 * m
        steps = max(m - 1, 1).bit_length()
        return (n * k_cap * _COMPARE_PAIR_COST +
                min(k_cap * steps * _SEARCH_COMPACT_COST, m), n + 2 * m)

    @classmethod
    def _compare_cheaper(cls, n: int, m: int, k_cap: int) -> bool:
        """Is the compare the cheaper of _lookup_costs?"""
        compare, gather = cls._lookup_costs(n, m, k_cap)
        return compare < gather

    @classmethod
    def _defer_cheaper(cls, n: int, cap: int, m: int, k_cap: int,
                       lazy_now: int = 0,
                       lazy_settled: int = 1 + _PRED_GATHER_COST) -> bool:
        """A probe side of capacity ``n`` whose survivors' compaction to
        ``cap`` is pending: is the compare lookup over all ``n`` rows
        cheaper than compacting first?  In the unit of _lookup_costs.
        Unsettled, the compare and whatever the key columns gather a
        row as they are (``lazy_now``).  Settled, the compaction
        (_survivor_positions), the key columns behind it
        (``lazy_settled`` a row: every column is a lazy view there) and
        the cheaper lookup at ``cap``.  Only the compare path needs no
        dense rows, so a join it does not take at ``n`` settles."""
        compare_n, gather_n = cls._lookup_costs(n, m, k_cap)
        if compare_n >= gather_n:
            return False
        steps = max(n - 1, 1).bit_length()
        settled = min(cap * steps * _SEARCH_COMPACT_COST, n) + \
            cap * lazy_settled + min(cls._lookup_costs(cap, m, k_cap))
        return compare_n + n * lazy_now < settled

    @staticmethod
    def _key_gather_cost(cols: Sequence[DCol], settled: bool) -> int:
        """Elements gathered a row to read these key columns: nothing
        for a materialised column, its data and (_PRED_GATHER_COST) its
        validity for a lazy one, as every column is once ``settled``."""
        cost = 0
        for c in cols:
            if settled or c._data is None:
                cost += 1
            carries = c.src_valid if c.view is not None else c._valid
            if carries is not None and (settled or c._valid is None):
                cost += _PRED_GATHER_COST
        return cost

    def _probe_rows(self, lt: DTable, rt: DTable, keys):
        """``lt`` for a lookup join, given with its survivors'
        compaction pending (_exec_join: inner / left, plain key columns,
        no ``extra``): as it is where _defer_cheaper, else settled.
        Decided from the build side alone, before any probe key is
        read; with it ``(k_cap, n_b)`` if the alive build keys were
        sized here (the join's first record, as in _equi_join)."""
        lcols, rcols = self._join_key_cols(lt, rt, keys)
        specs, redensified, bound = self._join_key_specs(
            lcols, rcols, lt.pending + rt.capacity + 3)
        if redensified or any(spec is None for spec in specs) or \
                self._lut_span(bound, rt.capacity, lt.pending) is None:
            # rank pairing or the sort path: dense rows
            return self._settle(lt), None
        live = rt.alive
        for c in rcols:
            live = live & c.valid
        sized = self._capacity_for(jnp.sum(live, dtype=jnp.int32))
        if self._defer_cheaper(
                lt.capacity, lt.pending, rt.capacity, sized[0],
                self._key_gather_cost(lcols, False),
                self._key_gather_cost(lcols, True)):
            return lt, sized
        return self._settle(lt), sized

    def _alive_build_keys(self, rkey: jnp.ndarray, k_cap: int, n_b):
        """(keys, rows): the alive build keys (``rkey >= 0``) and their
        row ids in the first ``n_b`` of ``k_cap`` slots, ascending by
        row; the other slots keyed -2, which no probe key equals."""
        pos = self._survivor_positions(rkey >= 0, k_cap)
        used = jax.lax.iota(jnp.int32, k_cap) < \
            jnp.asarray(n_b).astype(jnp.int32)
        # keys on the LUT path lie under the span: int32 holds them
        return jnp.where(used, rkey[pos].astype(jnp.int32), -2), pos

    @staticmethod
    def _table_rows(lkey: jnp.ndarray, slots: jnp.ndarray,
                    rows: jnp.ndarray, span: int) -> jnp.ndarray:
        """Each probe row's build row (-1: none; anything where ``lkey``
        is negative) from a table of ``rows`` over the key domain (slot
        ``span`` is the trash): one probe-sized gather."""
        row_lut = jnp.full(span + 1, -1, jnp.int32).at[slots].set(rows)
        return row_lut[jnp.clip(lkey, 0, span - 1).astype(jnp.int32)]

    def _compare_rows(self, lkey: jnp.ndarray, bkeys: jnp.ndarray,
                      brows: jnp.ndarray, span: int) -> jnp.ndarray:
        """The same rows with no table and no gather: ``lkey`` against
        every one of the unique ``bkeys``, in the keycmp kernel.  Where
        no kernel is traced (_pallas_kernel: eager execution, where a
        [K, n] compare would be materialised) the K keys are scattered
        into the table instead."""
        kernel = self._pallas_kernel()
        if kernel is None:
            return self._table_rows(
                lkey, jnp.where(bkeys >= 0, bkeys, span), brows, span)
        from ndstpu.ops import keycmp
        return keycmp.match_rows(lkey, bkeys, brows, **kernel)

    @staticmethod
    def _survivor_positions(mask: jnp.ndarray, cap: int) -> jnp.ndarray:
        """Positions of the first ``cap`` set rows of ``mask``, ascending
        (slots past the last set row hold a valid, dead position): by
        binary search over the running count where few survive (cap x
        log2(n) element gathers), else by one scatter of n updates."""
        n = int(mask.shape[0])
        steps = max(n - 1, 1).bit_length()
        csum = jnp.cumsum(mask.astype(jnp.int32))
        if cap * steps * _SEARCH_COMPACT_COST > n:
            # each set row to its rank; the others to a trash slot
            dest = jnp.where(mask, csum - 1, cap)
            return jnp.zeros(cap + 1, jnp.int32).at[dest].set(
                jax.lax.iota(jnp.int32, n))[:cap]
        # src[j] = first i with csum[i] > j
        want = jax.lax.iota(jnp.int32, cap) + 1

        def halve(_, lo_hi):
            lo, hi = lo_hi
            mid = (lo + hi) // 2
            below = csum[mid] < want
            return jnp.where(below, mid + 1, lo), jnp.where(below, hi, mid)

        lo, _ = jax.lax.fori_loop(
            0, steps, halve,
            (jnp.zeros(cap, jnp.int32), jnp.full(cap, n - 1, jnp.int32)))
        return jnp.minimum(lo, n - 1)

    def _expand(self, lt: DTable, rt: DTable, order, lo, counts,
                total, out_cap: int) -> DTable:
        starts = (jnp.cumsum(counts) - counts).astype(jnp.int32)
        pos = jax.lax.iota(jnp.int32, out_cap)
        li = self._expand_li(counts, starts, out_cap)
        within = (pos - starts[li]).astype(lo.dtype)
        rpos = jnp.clip(lo[li] + within, 0, rt.capacity - 1)
        ri = order[rpos]
        alive = pos < jnp.asarray(total).astype(jnp.int32)
        lcols = _gather_cols(lt.columns, li, alive)
        rcols = _gather_cols(rt.columns, ri, alive)
        return DTable({**lcols, **rcols}, alive)

    def _left_join(self, lt: DTable, rt: DTable, order, lo, counts,
                   extra) -> DTable:
        matched_cap, total = self._capacity_for(
            jnp.sum(counts, dtype=jnp.int64))
        inner = self._expand(lt, rt, order, lo, counts, total, matched_cap)
        # left-row index feeding each inner output position
        starts = (jnp.cumsum(counts) - counts).astype(jnp.int32)
        li_all = self._expand_li(counts, starts, matched_cap)
        if extra is not None:
            keep = JEval(inner).predicate(extra)
            inner = DTable(inner.columns, keep)
        # left rows that kept >=1 match after the residual predicate
        hits = jax.ops.segment_sum(inner.alive.astype(jnp.int32), li_all,
                                   num_segments=lt.capacity)
        unmatched_mask = lt.alive & (hits == 0)
        inner_c = self.compact(inner)
        n_matched = jnp.sum(inner_c.alive, dtype=jnp.int32)
        n_unmatched = jnp.sum(unmatched_mask, dtype=jnp.int32)
        out_cap, _ = self._capacity_for(n_matched + n_unmatched)
        # out[pos] = matched[pos] for pos < n_matched,
        #            unmatched-left[pos - n_matched] after (null right side)
        pos = jax.lax.iota(jnp.int32, out_cap)
        is_m = pos < n_matched
        mi = jnp.clip(pos, 0, inner_c.capacity - 1)
        um_idx = self._survivor_positions(unmatched_mask, out_cap)
        um_rows = um_idx[jnp.clip(pos - n_matched, 0, out_cap - 1)]
        out_alive = pos < (n_matched + n_unmatched)
        cols = _select_cols(
            {n: inner_c.column(n) for n in lt.column_names},
            {n: lt.column(n) for n in lt.column_names},
            mi, um_rows, is_m, out_alive)
        cols.update(_gather_cols(
            {n: inner_c.column(n) for n in rt.column_names},
            mi, is_m & out_alive))
        return DTable(cols, out_alive)


@dataclasses.dataclass
class _CompiledPlan:
    plan: lp.Plan
    compilable: bool
    record: list
    versions: tuple
    # per-table column subset actually scanned (None = all columns)
    table_cols: Dict[str, Optional[List[str]]] = None
    fn: object = None                    # jitted replay function
    out_meta: List[tuple] = None         # (name, ctype, dictionary, bounds)
    # loaded from disk and not yet validated by a successful replay —
    # the first execution self-heals (rediscovers) on any failure
    preloaded: bool = False
    # fn has executed successfully at least once: later backend errors
    # are real device failures and propagate instead of falling back
    fn_validated: bool = False
    # segmented compilation (parent programs only): fingerprints of the
    # separately-compiled subtrees this plan consumes via DeviceResult
    seg_fps: Optional[List[str]] = None
    # output capacity after the final compact (segment replays feed the
    # parent at exactly this padded size)
    out_capacity: int = 0
    # "NDSxxx:NodeName" tags for every fallback hit during discovery
    # (empty when compilable) — the static analyzer's prediction target
    fallback_codes: tuple = ()
    # parameter materializations recorded during discovery (pdict hit
    # tables / pvec IN vectors, in traversal order) — drives the
    # "\x00params" replay-argument subtree for any later binding
    param_spec: list = None
    # representative SQL text for persisted records: canonical cache
    # keys are not re-plannable, so save/load round-trips through SQL
    source_sql: Optional[str] = None
    # equi-join operators of the traced program by path (_JOIN_PATHS
    # order), set when fn is traced; None before
    join_paths: Optional[Tuple[int, ...]] = None
    # ... and its operators by kind (_OP_KINDS order), set with it
    op_kinds: Optional[Tuple[int, ...]] = None


def _scan_columns(p: lp.Plan) -> Dict[str, Optional[List[str]]]:
    """Union of scanned columns per table (None = full table)."""
    out: Dict[str, Optional[List[str]]] = {}
    for node in p.walk():
        if isinstance(node, lp.Scan):
            if node.columns is None:
                out[node.table] = None
            elif node.table not in out:
                out[node.table] = list(node.columns)
            elif out[node.table] is not None:
                for c in node.columns:
                    if c not in out[node.table]:
                        out[node.table].append(c)
    return out


def _seg_argname(fp: str) -> str:
    """Replay-argument key for a segment result (cannot collide with a
    table name: NUL is not legal in identifiers)."""
    return "\x00seg:" + fp


# segmented compilation thresholds: one whole-query XLA program of
# ~10k HLO ops (q4) takes the TPU compiler far longer than the sum of
# its parts (q1/q3/q6 trace to 1-2k ops), so
# plans above _SEG_MIN_TOTAL nodes compile their big aggregate subtrees
# as separate programs whose results stay device-resident.
_SEG_CUT_TYPES = (lp.Aggregate, lp.Window, lp.Distinct)
_SEG_MIN_NODES = 5       # minimum subtree size worth its own program
_SEG_MIN_TOTAL = 14      # plans smaller than this stay single-program


def _cut_segments(p: lp.Plan):
    """Split a plan for segmented compilation.

    Returns ``(parent_plan, segments)`` where segments is an ordered
    {fingerprint: subplan} of maximal Aggregate/Window/Distinct subtrees
    and parent_plan has each occurrence replaced by lp.DeviceResult.
    Identical subtrees (multi-part CTE instantiation) share one segment.
    Deterministic for a given plan tree — discovery, replay, and record
    reload all cut identically."""
    segs: Dict[str, lp.Plan] = {}
    if sum(1 for _ in p.walk()) < _SEG_MIN_TOTAL:
        return p, segs

    import copy as _copy

    def rebuild(node: lp.Plan, is_root: bool) -> lp.Plan:
        if not is_root and isinstance(node, _SEG_CUT_TYPES) and \
                sum(1 for _ in node.walk()) >= _SEG_MIN_NODES:
            try:
                fp = _plan_fp(node)
            except TypeError:
                fp = None  # un-fingerprintable: keep the subtree inline
            if fp is not None:
                segs.setdefault(fp, node)
                return lp.DeviceResult(fp)
        kids = node.children()
        if not kids:
            return node
        new_kids = [rebuild(k, False) for k in kids]
        if all(nk is k for nk, k in zip(new_kids, kids)):
            return node
        q = _copy.copy(node)
        if hasattr(q, "child"):
            q.child = new_kids[0]
        elif hasattr(q, "left"):
            q.left, q.right = new_kids
        else:
            raise RuntimeError(
                f"unknown child layout on {type(node).__name__}")
        return q

    parent = rebuild(p, True)
    return parent, segs


# JAX's own compile-path events -> (counter, span attribute).  A
# backend_compile event wraps the persistent-cache lookup too: one that
# follows a cache retrieval on its thread was a load, not a compile.
_COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration":
        ("engine.compile.trace_s", "compile_trace_s"),
    "/jax/core/compile/jaxpr_to_mlir_module_duration":
        ("engine.compile.lower_s", "compile_lower_s"),
    "/jax/core/compile/backend_compile_duration":
        ("engine.compile.xla_s", "compile_xla_s"),
    "/jax/compilation_cache/cache_retrieval_time_sec":
        ("engine.compile.cache_load_s", "compile_cache_load_s"),
}
_compile_listener_state = threading.local()
_compile_listener_installed = False


def _on_compile_event(event: str, duration: float, **_kw) -> None:
    names = _COMPILE_EVENTS.get(event)
    if names is None or not obs.enabled():
        return
    st = _compile_listener_state
    if event.endswith("cache_retrieval_time_sec"):
        st.loaded = True
        obs.inc("engine.xla.cache_hits")
    elif event.endswith("backend_compile_duration"):
        if getattr(st, "loaded", False):
            st.loaded = False
            return      # the retrieval's seconds are already filed
        obs.inc("engine.xla.compiles")
    counter, attr = names
    obs.inc(counter, duration)
    obs.accumulate(**{attr: duration})


def _install_compile_listener() -> None:
    """Once per process: split ``compile_s`` into jit trace / lowering /
    XLA compile or cache load, as JAX itself times them, and count the
    real backend compiles (``engine.xla.compiles``: which step
    recompiled).  The seconds also land on the span they happened in
    (``discover_query``, or the warm-up ``replay``)."""
    global _compile_listener_installed
    if _compile_listener_installed:
        return
    _compile_listener_installed = True
    jax.monitoring.register_event_duration_secs_listener(_on_compile_event)


class CompilingExecutor(JaxExecutor):
    """JaxExecutor + whole-query compile cache keyed by SQL text.

    First execution of a query discovers its size plan eagerly; later
    executions run a FIXED set of jitted XLA programs per query — one
    parent program plus one per cut segment (_cut_segments); results of
    segments stay on the device and feed the parent as arguments.
    Segmentation bounds program size (the TPU compiler wedges on ~10k-op
    whole-query programs), shares identical CTE subtrees across query
    parts, and isolates numpy fallbacks to the segment that needs them.
    Guard failure (size-class overflow after data changes) or catalog
    version changes trigger rediscovery.
    """

    def __init__(self, catalog):
        super().__init__(catalog)
        # the eager bounds diagnostic syncs the device; pay it only
        # inside discovery (every query's first execution), not on
        # steady-state demoted eager aggregates
        self._in_discovery = False
        _install_compile_listener()

    def execute_cached(self, p: lp.Plan, key: str,
                       params: Optional[ex.ParamBinding] = None,
                       sql: Optional[str] = None) -> Table:
        # compile-once across concurrent streams: the key latch makes
        # the first arrival for a key pay discovery while later
        # arrivals block, then take the cache-hit replay path; the
        # exec lock serializes the actual device execution (see
        # JaxExecutor.__init__).  A failed discovery caches nothing
        # and releases the latch, so it cannot poison other streams.
        # Under canonical keying (analysis/canon.py) `key` is the plan's
        # structural fingerprint, `p` the parameterized exec plan, and
        # `params` the binding for THIS rendering — streams rendering
        # different literals for one template share the compiled entry.
        with self._key_latch.holding(key):
            with self._exec_lock:
                ctx = _ParamCtx(params.values, "concrete") \
                    if params is not None else None
                with _params_bound(ctx):
                    return self._execute_cached_locked(p, key, params,
                                                       sql)

    def _execute_cached_locked(self, p: lp.Plan, key: str,
                               params: Optional[ex.ParamBinding] = None,
                               sql: Optional[str] = None) -> Table:
        versions = tuple(sorted(
            getattr(self.catalog, "versions", {}).items()))
        cp = self._compiled.get(key)
        if cp is not None and cp.versions != versions:
            cp = None
        if cp is None:
            from ndstpu import faults
            faults.check("compile", key=key)
            obs.inc("engine.cache.compiled.miss")
            return self._discover_query(p, key, versions, params, sql)
        obs.inc("engine.cache.compiled.hit")
        if not cp.compilable:
            result = self._eager_with_segments(cp, params)
            if result is None:   # a shared segment was evicted: rebuild
                return self._forget_and_rediscover(p, key, versions,
                                                   params, sql)
            return result
        if cp.fn is None:
            # size-plan record preloaded from disk (see
            # save/load_compile_records): build the jitted replay now
            try:
                cp.fn = self._build_jit(cp)
            except Exception as e:  # noqa: BLE001
                self._compile_degraded(
                    "preloaded record did not build; rediscovering", e)
                return self._forget_and_rediscover(p, key, versions,
                                                   params, sql)
        if cp.preloaded:
            # first execution of a disk-loaded record: ANY failure —
            # arg build, compile, execution, or result assembly against
            # stale out_meta — means the record drifted; rediscover
            try:
                result = self._replay_query(cp, binding=params)
            except Exception:
                result = None
            if result is None:
                return self._forget_and_rediscover(p, key, versions,
                                                   params, sql)
            cp.preloaded = False
            cp.fn_validated = True
            return result
        try:
            result = self._replay_query(cp, binding=params)
        except jax.errors.JaxRuntimeError as first_err:
            if cp.fn_validated:
                raise  # a real device failure, not a compile rejection
            # could be a compile rejection OR a transient device fault
            # (preemption/OOM): retry once before permanently demoting
            # this query to the eager per-op path — slower, correct
            try:
                result = self._replay_query(cp, binding=params)
            except jax.errors.JaxRuntimeError:
                import warnings
                # warnings.warn (not print): the harness report layer
                # collects warnings into CompletedWithTaskFailures —
                # the reference's task-failure listener analog
                # (PysparkBenchReport.py:89-92); a run that silently
                # fell off the compiled path must say so
                obs.inc("engine.fallback.compile")
                warnings.warn(
                    f"whole-query compile failed twice, demoted to "
                    f"eager per-op execution: {first_err}",
                    stacklevel=2)
                cp.compilable = False
                cp.fn = None
                return self._eager_with_segments(cp, params)
        if result is None:  # size-class guard failed: data changed
            return self._forget_and_rediscover(p, key, versions,
                                               params, sql)
        cp.fn_validated = True
        return result

    @staticmethod
    def _compile_degraded(what: str, err: BaseException) -> None:
        """The engine survived a compile-path failure by leaving the
        compiled path.  Counted everywhere; on an accelerator it also
        warns with the compiler's message, so the report layer marks
        the query CompletedWithTaskFailures instead of filing an eager
        or numpy answer as a device result.  CPU platforms stay quiet:
        there the eager path IS the platform, and tests inject these
        failures on purpose."""
        obs.inc("engine.fallback.compile")
        if default_platform() != "cpu":
            import warnings
            warnings.warn(f"{what}: {type(err).__name__}: {err}",
                          stacklevel=3)

    def _forget_and_rediscover(self, p, key, versions,
                               params=None, sql=None) -> Table:
        import warnings
        warnings.warn(
            f"compiled plan invalidated (size-class guard failed or "
            f"preloaded record drifted); rediscovering "
            f"{key.split('|', 1)[-1][:80]!r}", stacklevel=2)
        cp = self._compiled.pop(key, None)
        if cp is not None:
            for fp in (cp.seg_fps or ()):
                self._seg_compiled.pop(fp, None)
        return self._discover_query(p, key, versions, params, sql)

    # -- replay ---------------------------------------------------------------

    def _replay_query(self, cp: _CompiledPlan, bucket: str = "execute_s",
                      binding: Optional[ex.ParamBinding] = None,
                      ) -> Optional[Table]:
        """Dispatch segment programs then the parent; ONE batched
        device->host fetch at the end.  None = some size guard failed
        (data changed).

        The whole replay runs under a tracer span attributed to
        ``bucket`` — ``execute_s`` normally, ``compile_s`` for the
        discovery-time warm-up call that pays the XLA compile — so the
        harness's per-query cost split is self-labeling.  The span
        carries where its host time went (``host_prep_s``,
        ``dispatch_s``, ``device_wait_s``, ``assemble_s``); a profiler
        trace has the same four as live ``ndstpu:replay.*`` marks.
        Where the GC hook runs (``obs.host``) it also carries the
        process's GC pause seconds in the replay (``gc_s``) and of them
        inside ``device_wait_s`` (``wait_gc_s``)."""
        with obs.span("replay", cat="plan-node", bucket=bucket,
                      n_programs=1 + len(cp.seg_fps or ())) as sp:
            result = self._replay_query_timed(cp, sp, binding)
        return result

    def _replay_query_timed(self, cp: _CompiledPlan, sp,
                            binding: Optional[ex.ParamBinding] = None,
                            ) -> Optional[Table]:
        t_start = time.perf_counter()
        gc_start = obs_host.gc_seconds()
        seg_args = {}
        seg_oks = []
        ran = [cp]      # the programs this replay runs on the device
        with obs.annotation("replay.prep"):
            for fp in (cp.seg_fps or ()):
                scp = self._seg_compiled.get(fp)
                if scp is None or scp.versions != cp.versions:
                    obs.inc("engine.cache.seg_compiled.miss")
                    return None
                obs.inc("engine.cache.seg_compiled.hit")
                if scp.compilable:
                    if scp.fn is None:
                        scp.fn = self._build_jit(scp)
                    args = {t: self._accel_args(t, c)
                            for t, c in scp.table_cols.items()}
                    args["\x00params"] = _param_args_np(scp.param_spec,
                                                        binding)
                    (out, alive), ok = scp.fn(args)
                    seg_args[_seg_argname(fp)] = (out, alive)
                    seg_oks.append(ok)
                    ran.append(scp)
                else:
                    # fallback-isolated segment: host numpy result,
                    # shipped to the device at the recorded output
                    # capacity (the ambient concrete _ParamCtx supplies
                    # bound values)
                    host = self.execute_to_host(scp.plan)
                    seg_args[_seg_argname(fp)] = self._seg_host_args(
                        scp, host)
            args = {t: self._accel_args(t, cols)
                    for t, cols in cp.table_cols.items()}
            args["\x00params"] = _param_args_np(cp.param_spec, binding)
            args.update(seg_args)
        t_dispatch = time.perf_counter()
        with obs.annotation("replay.dispatch"):
            (out, alive), ok = cp.fn(args)
        t_called = time.perf_counter()
        gc_called = obs_host.gc_seconds()
        # the device runs from here (one replay in flight: the host
        # waits in device_get for the programs and the D2H copy; a GC
        # pause on any thread in that time is inside the wait too)
        with obs.annotation("replay.device_wait"):
            (out, alive_np), okv, seg_okv = jax.device_get(
                ((out, alive), ok, seg_oks))
        t_fetched = time.perf_counter()
        gc_fetched = obs_host.gc_seconds()
        fetched = int(alive_np.nbytes) + sum(
            d.nbytes + v.nbytes for d, v in out.values())
        result = None
        if bool(okv) and all(bool(o) for o in seg_okv):
            for fp in (cp.seg_fps or ()):
                scp = self._seg_compiled.get(fp)
                if scp is not None:
                    scp.preloaded = False
                    scp.fn_validated = True
            with obs.annotation("replay.assemble"):
                result = self._assemble_host(cp, out, alive_np)
        t_end = time.perf_counter()
        gc_end = obs_host.gc_seconds()
        # join operators of the programs just run, by the path each took
        # when its program was traced
        joins = {"join_" + k: sum(p.join_paths[i] for p in ran)
                 for i, k in enumerate(_JOIN_PATHS)}
        # ... and their operators by kind
        joins.update({k: sum(p.op_kinds[i] for p in ran)
                      for i, k in enumerate(_OP_KINDS)})
        for k, v in joins.items():
            obs.inc("engine.replay." + k, v)
        if sp is not obs.NULL_SPAN:
            sp.set(host_prep_s=round(t_dispatch - t_start, 5),
                   dispatch_s=round(t_called - t_dispatch, 6),
                   device_wait_s=round(t_fetched - t_called, 6),
                   assemble_s=round(t_end - t_fetched, 6),
                   fetched_bytes=fetched, **joins)
            if obs_host.installed():
                sp.set(gc_s=round(gc_end - gc_start, 6),
                       wait_gc_s=round(gc_fetched - gc_called, 6))
        return result

    @staticmethod
    def _assemble_host(cp: _CompiledPlan, out, alive_np) -> Table:
        cols = {}
        for name, ctype, dictionary, _bounds in cp.out_meta:
            data, valid = out[name]
            data = data[alive_np]
            valid = valid[alive_np]
            cols[name] = Column(data, ctype,
                                None if valid.all() else valid, dictionary)
        return Table(cols)

    def _replay_one(self, scp: _CompiledPlan,
                    binding: Optional[ex.ParamBinding] = None,
                    ) -> Optional[Table]:
        """Replay a single segment program to a host Table (reuse path:
        a second query part sharing an already-compiled segment).  Under
        canonical keying the segment's parameter slots are bound from
        the CURRENT query's binding — fingerprint-identical subtrees
        share the compiled program even when their literals differ."""
        if not scp.compilable:
            return self.execute_to_host(scp.plan)
        if scp.fn is None:
            scp.fn = self._build_jit(scp)
        args = {t: self._accel_args(t, c)
                for t, c in scp.table_cols.items()}
        args["\x00params"] = _param_args_np(scp.param_spec, binding)
        (out, alive), ok = scp.fn(args)
        (out, alive_np), okv = jax.device_get(((out, alive), ok))
        if not bool(okv):
            return None
        return self._assemble_host(scp, out, alive_np)

    def _seg_host_args(self, scp: _CompiledPlan, host: Table):
        """(cols, alive) replay-argument structure for a host-computed
        segment result, padded to the segment's recorded capacity."""
        cap = max(scp.out_capacity, size_class(max(host.num_rows, 1)))
        n = host.num_rows
        alive = np.zeros(cap, bool)
        alive[:n] = True
        cols = {}
        for name, ctype, dictionary, _bounds in scp.out_meta:
            col = host.columns[name]
            data = _pad(np.asarray(col.data), cap)
            valid = _pad(col.validity(), cap, fill=False)
            cols[name] = (jnp.asarray(data), jnp.asarray(valid))
        return (cols, jnp.asarray(alive))

    def _dt_from_host(self, scp: _CompiledPlan, host: Table) -> DTable:
        """Eager DTable view of a segment's host result carrying EXACTLY
        the segment's out_meta (ctype/dictionary/bounds): parent
        discovery must see the same static metadata replay will, or the
        traced parent program diverges from the discovered record."""
        (cols, alive) = self._seg_host_args(scp, host)
        dcols = {}
        for name, ctype, dictionary, bounds in scp.out_meta:
            d, v = cols[name]
            dcols[name] = DCol(d, v, ctype, dictionary, bounds)
        return DTable(dcols, alive)

    # -- discovery ------------------------------------------------------------

    def _discover_query(self, p: lp.Plan, key: str, versions,
                        params: Optional[ex.ParamBinding] = None,
                        sql: Optional[str] = None) -> Table:
        # the whole first-ever pass — eager discovery, jit builds, and
        # the warm-up replay that pays the XLA compile — is cold-path
        # cost a steady-state run never pays: bucket it as compile_s so
        # headline numbers are self-labeling (round-5 verdict: a cold
        # run was committed as warm because nothing could tell)
        with obs.span("discover_query", cat="plan-node",
                      bucket="compile_s", n_segments=0) as sp:
            obs.inc("engine.discoveries")
            return self._discover_query_traced(p, key, versions, sp,
                                               params, sql)

    def _discover_query_traced(self, p: lp.Plan, key: str, versions, sp,
                               params: Optional[ex.ParamBinding] = None,
                               sql: Optional[str] = None) -> Table:
        parent, segs = _cut_segments(p)
        sp.set(n_segments=len(segs))
        self._seg_tables = {}
        for fp, sub in segs.items():
            dt = None
            scp = self._seg_compiled.get(fp)
            if scp is not None and scp.versions == versions:
                obs.inc("engine.cache.seg_compiled.hit")
            else:
                obs.inc("engine.cache.seg_compiled.miss")
            if scp is not None and scp.versions == versions:
                # already compiled for another query (part): replay it
                # for values instead of re-running eager discovery
                try:
                    host = self._replay_one(scp, params)
                except Exception:
                    host = None
                if host is not None:
                    with host_compute():
                        dt = self._dt_from_host(scp, host)
                    scp.preloaded = False
                    scp.fn_validated = True
            if dt is None:
                scp, dt = self._discover_plan(sub, versions,
                                              params=params)
                self._seg_compiled[fp] = scp
            self._seg_tables[fp] = dt
        # the parent's jit closure captures segment metas, so seg_fps
        # MUST be set before the fn is built (build_fn=False + build
        # here), or replay KeyErrors on the segment argument names
        cp, dtp = self._discover_plan(parent, versions, build_fn=False,
                                      params=params)
        cp.seg_fps = list(segs.keys())
        cp.source_sql = sql
        if cp.compilable:
            try:
                cp.fn = self._build_jit(cp)
            except Exception as e:  # noqa: BLE001
                self._compile_degraded(
                    "whole-query program did not build; answering on "
                    "the eager path", e)
                cp.compilable = False
        self._compiled[key] = cp
        if cp.compilable and self.warm_replay:
            # trace+compile+execute the replay NOW (jit is lazy: the
            # first fn call pays the whole compile).  Without this the
            # "steady-state" second run of every query paid its compile.
            # A warm failure is not fatal: the next execute_cached
            # replays (or demotes) through the normal path.
            try:
                # the warm-up call pays the XLA compile inside fn():
                # bucket it compile_s, not execute_s
                if self._replay_query(cp, bucket="compile_s",
                                      binding=params) is not None:
                    cp.fn_validated = True
            except Exception as e:  # noqa: BLE001
                import warnings
                obs.inc("engine.fallback.compile")
                warnings.warn(
                    f"replay warm-up failed ({type(e).__name__}: {e}); "
                    f"first replay will retry", stacklevel=2)
        try:
            with host_compute():
                return to_host(dtp)
        finally:
            # the eager segment DTables are device-resident padded
            # buffers; keeping them past the query holds HBM for nothing
            self._seg_tables = {}

    def _discover_plan(self, p: lp.Plan, versions, build_fn=True,
                       params: Optional[ex.ParamBinding] = None):
        """Discover ONE program (parent or segment): eager host
        execution recording every data-dependent decision; returns
        (cp, compacted eager DTable)."""
        self.n_discoveries += 1
        self._subq_cache = {}
        self._tree_cache = {}
        self.np_exec = physical.Executor(self.catalog)
        self.mode = "discover"
        self._in_discovery = True
        self._rec = []
        self._used_fallback = False
        self._fallback_codes = []
        # record parameter materializations (pdict/pvec) alongside the
        # size plan so replay can rebuild the argument subtree for any
        # later binding of the same canonical fingerprint
        pspec: list = []
        pctx = _ParamCtx(params.values, "concrete", spec=pspec,
                         record=True) if params is not None else None
        try:
            with _params_bound(pctx) if pctx is not None \
                    else contextlib.nullcontext():
                with host_compute():
                    dt = self.execute(p)
                    # compact to the result's own size class BEFORE
                    # output: replay fetches (or hands the parent) every
                    # output column at padded capacity, and results are
                    # usually far smaller than the fact capacity they
                    # ride in on.  The compaction capacity is one more
                    # recorded sync point, so replay stays static.
                    dt = self.compact(dt)
        finally:
            self.mode = "eager"
            self._in_discovery = False
        cp = _CompiledPlan(p, not self._used_fallback, self._rec, versions)
        cp.param_spec = pspec
        cp.fallback_codes = tuple(sorted(self._fallback_codes))
        cp.table_cols = _scan_columns(p)
        cp.out_capacity = dt.capacity
        cp.out_meta = [(name, c.ctype, c.dictionary, c.bounds)
                       for name, c in dt.columns.items()]
        if cp.compilable and build_fn:
            try:
                cp.fn = self._build_jit(cp)
            except Exception as e:  # noqa: BLE001
                self._compile_degraded(
                    "segment program did not build; answering on the "
                    "eager path", e)
                cp.compilable = False
        return cp, dt

    def _eager_with_segments(self, cp: _CompiledPlan,
                             params: Optional[ex.ParamBinding] = None):
        """Non-compilable parent: numpy-interpreter execution over
        segment results (still compiled where possible).  None when a
        shared segment is missing or its guard failed — the caller
        rediscovers.  The ambient concrete _ParamCtx (installed by
        execute_cached) binds any parameter slots the interpreter hits."""
        self._seg_tables = {}
        for fp in (cp.seg_fps or ()):
            scp = self._seg_compiled.get(fp)
            if scp is None:
                return None
            try:
                host = self._replay_one(scp, params)
            except Exception:
                host = None
            if host is None:
                return None
            with host_compute():
                self._seg_tables[fp] = self._dt_from_host(scp, host)
        try:
            return self.execute_to_host(cp.plan)
        finally:
            self._seg_tables = {}

    # -- persisted size-plan records ------------------------------------------

    def _table_fingerprint(self, name: str) -> tuple:
        """Cheap content identity for a catalog table: row count + a
        prefix checksum over integer-backed columns.  Guards persisted
        size-plan records against a *different dataset* at the same
        paths — per-process version counters cannot (they restart at 1)."""
        t = self.catalog.get(name)
        chk = 0
        for cname in t.column_names[:3]:
            col = t.column(cname)
            if col.data.dtype.kind in "iu":
                chk ^= int(np.asarray(col.data[:4096], dtype=np.int64)
                           .sum()) & (2 ** 61 - 1)
        return (name, t.num_rows, chk)

    _REC_FORMAT = 6   # bump when the pickle schema changes
                      # (4: + per-program param_spec; keys round-trip
                      # through representative SQL so canonical cache
                      # keys can be rebuilt by re-canonicalizing;
                      # 5: + one ("bool", unique build keys) entry per
                      # inner/left LUT join in the size plans;
                      # 6: + the ("cap", alive build keys) entry before
                      # it; where comparing with those keys is the
                      # cheaper lookup the bool is their uniqueness)

    def save_compile_records(self, path: str) -> int:
        """Persist discovery size-plan records (NOT compiled code — XLA
        has its own persistent cache) so a fresh process can skip the
        eager discovery pass per query.  Keys are stored as bare SQL
        text (the in-memory views-epoch prefix is process-local).
        Returns the record count."""
        import pickle
        with self._exec_lock:
            return self._save_compile_records_locked(path, pickle)

    def _save_compile_records_locked(self, path: str, pickle) -> int:
        data = {"\x00fmt": self._REC_FORMAT, "\x00segments": {}}
        segstore = data["\x00segments"]
        for key, cp in self._compiled.items():
            if not (cp.compilable and cp.record is not None):
                continue
            # canonical keys are not re-plannable text: prefer the
            # representative SQL captured at discovery
            sql = cp.source_sql or (
                key.split("|", 1)[1] if "|" in key else key)
            try:
                fps = tuple(self._table_fingerprint(t)
                            for t in sorted(cp.table_cols or ()))
            except KeyError:
                continue  # references a since-dropped table
            ok = True
            for fp in (cp.seg_fps or ()):
                scp = self._seg_compiled.get(fp)
                if scp is None or scp.record is None:
                    ok = False
                    break
                if fp not in segstore:
                    try:
                        sfps = tuple(self._table_fingerprint(t)
                                     for t in sorted(scp.table_cols or ()))
                    except KeyError:
                        ok = False
                        break
                    segstore[fp] = (scp.record, sfps, scp.table_cols,
                                    scp.out_meta, scp.out_capacity,
                                    scp.compilable, scp.param_spec)
            if ok:
                data[sql] = (cp.record, fps, cp.table_cols, cp.out_meta,
                             cp.seg_fps, cp.out_capacity, cp.param_spec)
        # MERGE with what's already on disk, then publish atomically:
        # a subset run (e.g. a 12-query validation pass) must never
        # truncate a full-corpus record file another process spent
        # hours warming, and concurrent throughput streams saving to
        # one path must never interleave writes into a corrupt pickle
        # (last atomic writer wins with a valid superset).
        try:
            with open(path, "rb") as f:
                prev = pickle.load(f)
            if isinstance(prev, dict) and \
                    prev.get("\x00fmt") == self._REC_FORMAT:
                for k, v in prev.items():
                    if k == "\x00segments":
                        for fp, sv in v.items():
                            segstore.setdefault(fp, sv)
                    else:
                        data.setdefault(k, v)
        except Exception:  # noqa: BLE001 — absent or corrupt prior file
            pass
        import os as _os
        import uuid as _uuid
        tmp = f"{path}.tmp.{_uuid.uuid4().hex}"
        with open(tmp, "wb") as f:
            pickle.dump(data, f)
        _os.replace(tmp, path)
        return len(data) - 2

    def load_compile_records(self, path: str, plan_for_key,
                             key_prefix: str = "0") -> int:
        """Preload size-plan records saved by save_compile_records.
        `plan_for_key(sql)` must return the optimized plan for the SQL
        text — or, under canonical keying, an ``(exec_plan, cache_key)``
        pair so the record registers under the same canonical key a
        fresh rendering will probe (or None to skip).  Records whose
        table fingerprints no longer match the catalog are dropped;
        drifted records self-heal at first execution (the replay guard
        rediscovers).  Returns the count loaded."""
        import pickle
        with open(path, "rb") as f:
            data = pickle.load(f)
        if not isinstance(data, dict) or \
                data.get("\x00fmt") != self._REC_FORMAT:
            return 0
        with self._exec_lock:
            return self._load_compile_records_locked(
                data, plan_for_key, key_prefix)

    def _load_compile_records_locked(self, data, plan_for_key,
                                     key_prefix: str) -> int:
        segstore = data.get("\x00segments", {})
        versions_now = tuple(sorted(
            getattr(self.catalog, "versions", {}).items()))

        def fingerprints_ok(fps):
            try:
                return all(self._table_fingerprint(fp[0]) == fp
                           for fp in fps)
            except KeyError:
                return False

        from ndstpu.engine.sql import normalize_sql_key
        n = 0
        for sql, ent in data.items():
            if sql.startswith("\x00"):
                continue
            (record, fps, table_cols, out_meta, seg_fps, out_cap,
             pspec) = ent
            if not fingerprints_ok(fps):
                continue
            res = plan_for_key(sql)
            if res is None:
                continue
            if isinstance(res, tuple):
                plan, ckey = res   # canonical keying
            else:
                plan, ckey = res, normalize_sql_key(sql)
            parent, segs = _cut_segments(plan)
            if sorted(segs.keys()) != sorted(seg_fps or ()):
                continue  # cut heuristic or plan changed: rediscover
            seg_ok = True
            for fp in (seg_fps or ()):
                if fp in self._seg_compiled and \
                        self._seg_compiled[fp].versions == versions_now:
                    continue
                sent = segstore.get(fp)
                if sent is None or not fingerprints_ok(sent[1]):
                    seg_ok = False
                    break
                (srec, _sfps, stc, som, socap, scomp, spspec) = sent
                scp = _CompiledPlan(segs[fp], scomp, srec, versions_now,
                                    stc, None, som, preloaded=True)
                scp.out_capacity = socap
                scp.param_spec = spspec
                self._seg_compiled[fp] = scp
            if not seg_ok:
                continue
            cp = _CompiledPlan(parent, True, record, versions_now,
                               table_cols, None, out_meta, preloaded=True)
            cp.seg_fps = list(seg_fps or ())
            cp.out_capacity = out_cap
            cp.param_spec = pspec
            cp.source_sql = sql
            self._compiled[f"{key_prefix}|{ckey}"] = cp
            n += 1
        if n:
            self._make_resident()
        return n

    def _make_resident(self) -> None:
        """Fetch the replay inputs of every loaded record now, table by
        table in one fixed order: the largest table first (bytes of the
        columns the records scan), ties by name (_accel_args: resident
        on a chip, plain host arguments where the platform is the CPU).
        Uploaded at first use they lie where the programs and
        temporaries of the statements before them left room, and a
        program's gathers then run 2-8 % faster or slower by the
        statement a process happened to start with (query95 617-675 ms
        a replay over seven starting texts: builder's chip runs, PR 32).
        Sorted by name alone the power cell's pass read 1.3 % over the
        parent's median in six same-seed pairs, largest first 0.4 %
        (my chip runs, PR 33: PERF.md section 6); one order for every
        configuration.  A discovery cannot do the same: the columns a
        statement scans are known once it has run, so a process that
        discovers still uploads at first use."""
        need: Dict[str, set] = {}
        for cp in (*self._compiled.values(), *self._seg_compiled.values()):
            for table, cols in (cp.table_cols or {}).items():
                need.setdefault(table, set()).update(
                    cols if cols is not None
                    else self._table_device(table).column_names)
        def nbytes(table: str) -> int:
            cols = self._table_device(table).columns
            return sum(cols[c].data.nbytes + cols[c].valid.nbytes
                       for c in need[table])

        # the largest table first, ties by name (a table's columns go
        # in one transfer, which JAX lays out by name)
        for table in sorted(need, key=lambda t: (-nbytes(t), t)):
            self._accel_args(table, sorted(need[table]))

    # -- replay argument assembly --------------------------------------------

    def _table_args(self, name: str, cols: Optional[List[str]] = None):
        dt = self._table_device(name)
        names = dt.column_names if cols is None else cols
        return ({n: (dt.columns[n].data, dt.columns[n].valid)
                 for n in names}, dt.alive)

    def _accel_args(self, name: str, cols: Optional[List[str]] = None):
        """Replay inputs, resident on the accelerator.  Cached per
        (table version, COLUMN) — different queries scan overlapping
        column subsets, and caching whole subsets pinned duplicate
        copies of every shared column in HBM (at SF1 the accumulation
        crashed the TPU worker under the big rollup programs).  Args
        are assembled from the shared per-column buffers; the structure
        the jitted replay sees is unchanged."""
        version = getattr(self.catalog, "versions", {}).get(name)
        dev = jax.devices()[0]
        if dev.platform == "cpu":
            return self._table_args(name, cols)
        dt = self._table_device(name)
        names = dt.column_names if cols is None else cols
        akey = (name, None)     # None can never be a column name
        ent = self._accel_cache.get(akey)
        if ent is None or ent[0] != version or version is None:
            # version changed: drop every stale buffer of this table
            for k in [k for k in self._accel_cache if k[0] == name]:
                del self._accel_cache[k]
            self._accel_cache[akey] = (
                version, jax.device_put(dt.alive, dev))
        alive = self._accel_cache[akey][1]
        missing = [n for n in names
                   if self._accel_cache.get((name, n)) is None or
                   self._accel_cache[(name, n)][0] != version or
                   version is None]
        if missing:
            # one batched transfer for every missing column
            up = jax.device_put(
                {n: (dt.columns[n].data, dt.columns[n].valid)
                 for n in missing}, dev)
            for n in missing:
                self._accel_cache[(name, n)] = (version, up[n])
        return ({n: self._accel_cache[(name, n)][1] for n in names},
                alive)

    def _build_jit(self, cp: _CompiledPlan):
        self.n_jit_builds += 1
        obs.inc("engine.jit_builds")
        with obs.span("build_jit", cat="plan-node", bucket="compile_s"):
            return self._build_jit_traced(cp)

    def _build_jit_traced(self, cp: _CompiledPlan):
        metas = {}
        for name in cp.table_cols:
            dt = self._table_device(name)
            metas[name] = {n: (c.ctype, c.dictionary, c.bounds)
                           for n, c in dt.columns.items()}
        for fp in (cp.seg_fps or ()):
            scp = self._seg_compiled[fp]
            metas[_seg_argname(fp)] = {
                n: (ct, d, b) for n, ct, d, b in scp.out_meta}

        def replay(tables):
            self._subq_cache = {}
            self._tree_cache = {}
            self.mode = "replay"
            self._pos = 0
            self._oks = []
            self._rec = cp.record
            self._scope_ids = {id(n): i
                               for i, n in enumerate(cp.plan.walk())}
            self._trace_tables = {}
            self._join_paths = dict.fromkeys(_JOIN_PATHS, 0)
            self._op_kinds = dict.fromkeys(_OP_KINDS, 0)
            for name, entry in tables.items():
                if name == "\x00params":
                    continue   # parameter subtree, not a table
                cols, alive = entry
                # iterate in META order, not arg order: jax pytrees sort
                # dict keys, and column ORDER must match what discovery
                # saw (SubqueryAlias zips aliases positionally)
                dcols = {n: DCol(*cols[n], *metas[name][n])
                         for n in metas[name] if n in cols}
                self._trace_tables[name] = DTable(dcols, alive)
            pctx = _ParamCtx(None, "trace", spec=cp.param_spec or [],
                             traced=tables.get("\x00params") or {})
            try:
                with _params_bound(pctx):
                    dt = self.execute(cp.plan)
                    dt = self.compact(dt)   # mirror of _discover_plan
                if pctx.pos != len(pctx.spec):
                    raise RuntimeError(
                        "param-spec drift (unconsumed entries)")
                # output-type guard: engine typing changes (e.g. the
                # r04 coalesce decimal-literal fix) can retype a
                # column without changing the PLAN tree, so a
                # preloaded record's out_meta goes stale while its
                # size plan still matches.  Assembling scaled-decimal
                # data under a recorded float64 meta silently wrote
                # x100 values — raise at trace time instead (ctypes
                # are static here); callers rediscover.
                rec_meta = {name: ct for name, ct, _d, _b in cp.out_meta}
                for name, c in dt.columns.items():
                    if rec_meta.get(name) != c.ctype:
                        raise RuntimeError(
                            f"size-plan drift: output column {name} "
                            f"traced as {c.ctype}, recorded "
                            f"{rec_meta.get(name)}")
                ok = jnp.asarray(True)
                for o in self._oks:
                    ok = ok & o
                cp.join_paths = tuple(self._join_paths[k]
                                      for k in _JOIN_PATHS)
                cp.op_kinds = tuple(self._op_kinds[k] for k in _OP_KINDS)
            finally:
                self.mode = "eager"
                self._trace_tables = None
                self._scope_ids = {}
            out = {name: (c.data, c.valid) for name, c in dt.columns.items()}
            return (out, dt.alive), ok

        return jax.jit(replay)


def execute(plan: lp.Plan, catalog) -> Table:
    """Execute a plan on the JAX backend, returning a host Table."""
    return JaxExecutor(catalog).execute_to_host(plan)
