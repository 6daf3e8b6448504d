"""Engine session: the `SparkSession.sql()` analog.

Holds the table catalog + temp views, parses/plans/executes SQL, and
dispatches DM statements (CREATE TEMP VIEW / CTAS / INSERT / DELETE / DROP)
— the surface the harness layers (power run, maintenance, validation) drive,
replacing the reference's SparkSession usage (nds_power.py:221-245,
nds_maintenance.py:107-116).
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

from ndstpu.engine import columnar, physical, planner as pl, plan as lp
from ndstpu.engine.sql import ast, parse_statement, parse_statements


class _NullCM:
    """No-op lock stand-in for Session-like objects that predate the
    __post_init__ lock set (e.g. unpickled from an old snapshot)."""

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NULL_CM = _NullCM()


class ChunkFallthroughError(RuntimeError):
    """NDS311 under NDSTPU_SPMD_STRICT: configured chunked streaming
    silently degraded to the single-chip whole-fact path."""


@dataclass
class SnapshotPin:
    """An immutable catalog epoch captured at query admission
    (docs/ARCHITECTURE.md "Snapshot-pinned reads").

    Catalog entries are REPLACED, never mutated, by DML and register
    (io/loader.Catalog), so shallow-copied dicts are a frozen,
    consistent view: a query planned and executed against this pin
    sees exactly the snapshot that existed when the pin was taken,
    however many refresh functions commit meanwhile.  ``epoch`` is the
    durable data-version identity (io/lake.warehouse_epoch when a
    warehouse is attached) the ingest differential keys results on."""

    catalog: object
    views: Dict[str, lp.Plan]
    views_epoch: int
    versions: tuple  # sorted (table, version) catalog-version vector
    epoch: Optional[str] = None

    @property
    def state(self):
        """Cache-state tuple, same shape the live caches key on."""
        return (self.views_epoch, self.versions)


@dataclass
class Session:
    catalog: object  # ndstpu.io.loader.Catalog
    views: Dict[str, lp.Plan] = field(default_factory=dict)
    # ndslake warehouse root for ACID INSERT/DELETE passthrough (maintenance)
    warehouse: Optional[str] = None
    # cpu | tpu | tpu-spmd (tpu falls back per-plan when needed; tpu-spmd
    # runs the distributed SPMD executor over the device mesh and falls
    # back to the single-chip tpu path on unsupported plan shapes)
    backend: str = "cpu"
    # tpu-spmd: minimum table rows to shard (None = dplan default)
    spmd_threshold: Optional[int] = None
    # out-of-core streaming (tpu AND tpu-spmd): stream facts larger
    # than this through the mesh shard-major in chunks of this many
    # rows — each device scans only its own shard's chunks.  "auto"
    # lets the spill-aware planner (engine/memplan.py) size chunks and
    # prefetch depth from device memory stats; None = whole-fact
    # HBM-resident.  On a multi-device mesh a plan shape the chunked
    # executor cannot run falls back to the whole-fact single-chip
    # path, defeating out-of-core — that fall-through is surfaced as
    # diagnostic NDS311 (warning + counter; NDSTPU_SPMD_STRICT raises)
    spmd_chunk_rows: Optional[object] = None
    # chunks staged ahead of compute by the H2D prefetch ring
    # (0 = synchronous streaming; None = planner/executor default)
    spmd_prefetch_depth: Optional[int] = None
    # cross-query spine-materialization cache (engine/spine.SpineCache);
    # None = no sharing.  Installed by the inproc scheduler when its
    # streams share flagged spines; NDSTPU_SPINES=0 kills splicing even
    # when installed
    spine_cache: Optional[object] = None
    # bumped on view create/drop — part of the compiled-query cache key
    # (same SQL text over a redefined view must not reuse a stale plan)
    _views_epoch: int = 0

    def __post_init__(self):
        # Thread-safety contract (inproc throughput scheduler,
        # ndstpu/harness/scheduler.py): N stream threads share one
        # Session.  Three pieces make that sound:
        #   _cache_lock — guards _plan_cache get/put and lazy
        #       sub-object init (executor, spmd caches);
        #   _plan_latch — per-query-text "plan once, others wait"
        #       (ndstpu.engine.latch.KeyedLatch), so concurrent streams
        #       never duplicate planning work and cache-hit counters
        #       stay an honest compile-once proof;
        #   _exec_lock  — serializes statement EXECUTION (and all
        #       DDL/DML).  The executor keeps per-query mutable state
        #       (discovery recorder, subquery memos) and the physical
        #       device runs programs serially anyway, so statement-
        #       granularity serialization loses no real parallelism;
        #       cross-statement overlap happens at the admission gate.
        # RLocks: CTAS/INSERT recurse into _run on the same thread.
        import threading

        from ndstpu.engine import device
        from ndstpu.engine.latch import KeyedLatch
        # the accelerator engines run on whatever jax.devices()[0] is:
        # refuse a quiet CPU fallback here, before any query, and keep
        # the chip's compiled programs in the one resolved cache dir
        # (a cpu-pinned rehearsal leaves the cache to JAX's own env var)
        device.require_accelerator(self.backend)
        if device.wants_chip(self.backend):
            device.configure_compile_cache()
        if self.spmd_chunk_rows is not None and not (
                self.spmd_chunk_rows == "auto"
                or (isinstance(self.spmd_chunk_rows, int)
                    and not isinstance(self.spmd_chunk_rows, bool)
                    and self.spmd_chunk_rows > 0)):
            raise ValueError(
                f"spmd_chunk_rows must be a positive int, 'auto', or "
                f"None, got {self.spmd_chunk_rows!r}")
        if self.spmd_prefetch_depth is not None and (
                not isinstance(self.spmd_prefetch_depth, int)
                or self.spmd_prefetch_depth < 0):
            raise ValueError(
                f"spmd_prefetch_depth must be a non-negative int or "
                f"None, got {self.spmd_prefetch_depth!r}")
        self._cache_lock = threading.RLock()
        self._exec_lock = threading.RLock()
        self._plan_latch = KeyedLatch()
        self._plan_cache: Dict[str, tuple] = {}

    def sql(self, text: str,
            pin: Optional[SnapshotPin] = None) -> Optional[columnar.Table]:
        """Execute one statement; returns a Table for queries, None for
        DDL.  With ``pin`` (from :meth:`pin_snapshot`), a query runs
        against that frozen catalog epoch regardless of concurrent
        ingest commits — DML/DDL under a pin is an error."""
        from ndstpu import obs
        from ndstpu.engine.sql import normalize_sql_key
        # before the statement span: a short query's parse is a tenth
        # of its wall, and unbucketed it went unattributed
        with obs.span("parse", cat="plan-node", bucket="execute_s"):
            stmt = parse_statement(text)
            key = normalize_sql_key(text)
        return self._run(stmt, key=key, pin=pin)

    def sql_script(self, text: str) -> List[Optional[columnar.Table]]:
        return [self._run(s) for s in parse_statements(text)]

    def pin_snapshot(self) -> SnapshotPin:
        """Resolve and freeze the current catalog epoch for a query's
        lifetime.  Taken under the execution lock — the micro-batch
        ingestor (harness/ingest.py) holds the same lock across each
        whole refresh function, so a pin can only ever observe batch
        boundaries, never half a refresh function."""
        from ndstpu import obs
        with self._exec_lock:
            from ndstpu.io.loader import Catalog
            cat = Catalog(tables=dict(self.catalog.tables),
                          meta=dict(getattr(self.catalog, "meta", {})),
                          versions=dict(
                              getattr(self.catalog, "versions", {})))
            pin = SnapshotPin(
                catalog=cat, views=dict(self.views),
                views_epoch=self._views_epoch,
                versions=tuple(sorted(cat.versions.items())),
                epoch=self.snapshot_epoch())
        obs.inc("engine.snapshot.pinned")
        return pin

    def snapshot_epoch(self) -> Optional[str]:
        """Durable data-version identity of this session's data: the
        lake warehouse epoch (io/lake.py) when a warehouse is attached,
        else a local tag over the in-memory catalog-version vector."""
        if self.warehouse is not None:
            from ndstpu.io import lake
            ep = lake.warehouse_epoch(self.warehouse)
            if ep is not None:
                return ep
        import hashlib
        versions = tuple(sorted(
            getattr(self.catalog, "versions", {}).items()))
        blob = repr((self._views_epoch, versions)).encode()
        return "mem" + hashlib.sha256(blob).hexdigest()[:12]

    def plan(self, text: str):
        stmt = parse_statement(text)
        if not isinstance(stmt, ast.Query):
            raise ValueError("plan() expects a query")
        planner = pl.Planner(self.catalog, dict(self.views))
        plan, cols = planner.plan_query(stmt)
        from ndstpu.engine.optimizer import optimize
        return optimize(plan, self.catalog), cols

    def _run(self, stmt: ast.Node, key: Optional[str] = None,
             pin: Optional[SnapshotPin] = None
             ) -> Optional[columnar.Table]:
        # the whole statement is execute_s; cold-path work nested inside
        # (discovery, jit builds) carries its own compile_s bucket and
        # is subtracted by the tracer's self-time accounting, so the
        # per-query compile/execute split needs no bookkeeping here
        from ndstpu import obs
        with obs.span("statement", cat="plan-node", bucket="execute_s",
                      kind=type(stmt).__name__,
                      backend=self.backend) as sp:
            return self._run_traced(stmt, key, pin, sp)

    def _run_traced(self, stmt: ast.Node,
                    key: Optional[str], pin: Optional[SnapshotPin],
                    sp) -> Optional[columnar.Table]:
        if isinstance(stmt, ast.Query):
            plan, disp, canon = self._plan_cached(stmt, key, pin)
            if canon is not None:
                # canonical identity on the query span: sidecars and the
                # run ledger can group renderings by structure
                from ndstpu import obs
                obs.annotate(canon_fp=canon.fingerprint,
                             canon_key=canon.cache_key)
                codes = sorted({d.code for d in canon.diagnostics})
                if codes:
                    obs.annotate(canon_codes=",".join(codes))
            # execution serialized (see __post_init__): the executor's
            # per-query mutable state is not safe under concurrent
            # statements, and one device runs programs serially anyway
            t_lock = time.perf_counter()
            with self._exec_lock:
                # 0 behind a one-slot gate; streams over one Session
                # (inproc throughput) wait here for one another
                sp.set(exec_lock_wait_s=round(
                    time.perf_counter() - t_lock, 6))
                if getattr(self, "spine_cache", None) is not None:
                    plan, canon = self._splice_spines(plan, canon, key,
                                                      pin)
                out = self._execute(plan, key=key, canon=canon, pin=pin)
            return columnar.Table(dict(zip(disp, out.columns.values())))
        if pin is not None:
            raise ValueError(
                "DDL/DML cannot run against a snapshot pin — pins are "
                "read-only views of a committed epoch")
        with self._exec_lock:
            return self._run_ddl(stmt)

    def _plan_cached(self, stmt: "ast.Query", key: Optional[str],
                     pin: Optional[SnapshotPin] = None):
        """Plan + optimize + canonicalize with the text-keyed plan
        cache; returns ``(plan, display_names, CanonResult-or-None)``.

        A steady-state replay of a compiled query must not re-plan +
        re-optimize the SQL every call (50-150 ms of pure host overhead
        per execution on complex plans).  The key is the TEXT alone —
        one slot per query, with views epoch + catalog versions stored
        in the value and replace-on-mismatch (like _spmd_cache): DML or
        view churn must invalidate without stranding old-epoch entries
        forever.  Under the per-key latch, concurrent streams plan each
        distinct text exactly once: later arrivals block, then hit.
        Planning itself is host-pure (reads catalog/views), so distinct
        texts plan concurrently while the device executes.

        A pinned query plans against the pin's frozen catalog/views and
        keys the cache on the pin's state — a pin that fell behind the
        live epoch replaces the entry and vice versa (thrash, never a
        wrong plan), while a pin still AT the live epoch (the common
        case between refresh batches) shares the live entry.
        """
        from ndstpu import faults, obs
        faults.check("plan", key=key)
        pc = getattr(self, "_plan_cache", None)
        if pc is None:
            with getattr(self, "_cache_lock", _NULL_CM):
                pc = getattr(self, "_plan_cache", None)
                if pc is None:
                    pc = self._plan_cache = {}
        if key is None:
            with obs.span("plan", cat="plan-node"):
                plan, disp = self._plan_fresh(stmt, pin)
            return plan, disp, None
        latch = getattr(self, "_plan_latch", None)
        with (latch.holding(key) if latch is not None else _NULL_CM):
            if pin is not None:
                state = pin.state
            else:
                versions = tuple(sorted(
                    getattr(self.catalog, "versions", {}).items()))
                state = (self._views_epoch, versions)
            with getattr(self, "_cache_lock", _NULL_CM):
                ent = pc.get(key)
            if ent is not None and ent[0] != state:
                ent = None
            obs.inc("engine.cache.plan.hit" if ent is not None
                    else "engine.cache.plan.miss")
            if ent is not None:
                _s, plan, disp, canon = ent
                return plan, disp, canon
            with obs.span("plan", cat="plan-node"):
                plan, disp = self._plan_fresh(stmt, pin)
            canon = self._canonicalize(plan, key)
            # store only on success: a planner exception propagates
            # with nothing cached (no poisoning), the latch releases
            # in its finally, and the next arrival retries
            with getattr(self, "_cache_lock", _NULL_CM):
                pc[key] = (state, plan, disp, canon)
            return plan, disp, canon

    def _canonicalize(self, plan: lp.Plan, key: str):
        """Parameter-lift an optimized plan (analysis/canon.py) for
        shape-keyed compile caching.  None (→ text keying) on any
        canonicalization failure, counted in ``engine.canon.errors`` —
        the safety valve keeps queries running when the analyzer is
        wrong."""
        from ndstpu import obs
        try:
            from ndstpu.analysis import canon as _canon
            with obs.span("canonicalize", cat="plan-node"):
                return _canon.canonicalize(plan, query=key)
        except Exception as e:  # noqa: BLE001
            obs.inc("engine.canon.errors")
            obs.annotate(canon_error=f"{type(e).__name__}: {e}")
            return None

    # -- cross-query spine sharing (engine/spine.py + analysis/spines.py) ----

    def _spine_sites_for(self, plan: lp.Plan, key: str):
        """Eligible spine sites for one cached plan: the outermost
        non-overlapping shareable subtrees the analyzer flags
        (analysis/spines.py — shared rule set with the MQO audit).
        Memoized per query text; invalidated with the plan cache's
        state so site node references always point into the plan
        object `_plan_cached` currently serves."""
        from ndstpu.analysis import spines as sp
        memo = getattr(self, "_spine_sites_cache", None)
        if memo is None:
            with getattr(self, "_cache_lock", _NULL_CM):
                memo = getattr(self, "_spine_sites_cache", None)
                if memo is None:
                    memo = self._spine_sites_cache = {}
        ent = memo.get(key)
        if ent is not None and ent[0] == id(plan):
            return ent[1]
        sites = sp.eligible_sites(sp.subtree_sites(plan, query=key))
        with getattr(self, "_cache_lock", _NULL_CM):
            memo[key] = (id(plan), sites)
        return sites

    def spine_candidate_keys(self, text: str) -> set:
        """Value keys of the eligible spine sites in one query text —
        what the scheduler counts across streams to decide which spines
        are worth publishing (>= 2 occurrences)."""
        from ndstpu.engine.sql import normalize_sql_key
        try:
            stmt = parse_statement(text)
            if not isinstance(stmt, ast.Query):
                return set()
            key = normalize_sql_key(text)
            plan, _disp, canon = self._plan_cached(stmt, key)
            if canon is None:
                return set()   # canonicalization off/failed: no splicing
            return {s.value_key for s in self._spine_sites_for(plan, key)}
        except Exception:  # noqa: BLE001 — unplannable text
            return set()

    def _splice_spines(self, plan: lp.Plan, canon, key: Optional[str],
                       pin: Optional[SnapshotPin] = None):
        """Replace this plan's flagged spine subtrees with their
        materialized tables (InlineTable), publishing on first use.

        Requires a successful canonicalization and a text key: the
        spliced plan re-canonicalizes before execution, and the
        InlineTable content hash folds into that fingerprint, so the
        spliced and unspliced programs get distinct compile-cache
        entries by construction.  Runs under `_exec_lock` — the per-key
        latch in the cache only adds materialize-once semantics for
        callers outside it.  A materialization failure propagates like
        any query failure (the harness retry/fault taxonomy owns it);
        analysis failures just skip splicing."""
        import os
        if os.environ.get("NDSTPU_SPINES", "1") in ("", "0"):
            return plan, canon
        cache = self.spine_cache
        if cache is None or canon is None or key is None:
            return plan, canon
        from ndstpu import obs
        try:
            sites = [s for s in self._spine_sites_for(plan, key)
                     if cache.eligible(s.value_key)]
        except Exception:  # noqa: BLE001 — analyzer defect: run unspliced
            obs.inc("engine.spine.errors")
            return plan, canon
        if not sites:
            return plan, canon
        if pin is not None:
            # spine entries are keyed to the PIN's epoch: a query
            # pinned before an ingest commit neither serves nor is
            # served a post-commit spine (the cache's state check
            # drops the mismatch and ticks engine.snapshot.stale_drops)
            state = pin.state
        else:
            versions = tuple(sorted(
                getattr(self.catalog, "versions", {}).items()))
            state = (self._views_epoch, versions)
        memo = getattr(self, "_spine_splice_memo", None)
        if memo is None:
            memo = self._spine_splice_memo = {}
        from ndstpu.engine import spine as spine_mod
        hits = 0
        saved = 0
        replacements = {}
        spliced_keys = []
        for site in sites:
            vk = site.value_key
            with cache.holding(vk):
                t = cache.get(vk, state)
                if t is None:
                    obs.inc("engine.spine.miss")
                    cache.misses += 1
                    # materialize the subtree standalone; exceptions
                    # propagate as this query's failure
                    t = self._execute(site.node, pin=pin)
                    cache.put(vk, state, t)
                else:
                    hits += 1
                    cache.hits += 1
                    nbytes = spine_mod.table_bytes(t)
                    saved += nbytes
                    obs.inc("engine.spine.hit")
                    obs.inc("engine.spine.bytes", nbytes)
            replacements[id(site.node)] = lp.InlineTable(
                t, name=f"spine:{vk[:16]}")
            spliced_keys.append(vk)
        if hits:
            obs.annotate(spine_hits=hits, spine_bytes_saved=saved)
        # memo the spliced plan + its canon: same text + same spine
        # tables + same state = same splice (tables are replaced, not
        # mutated, so identity-keying on them is sound).  Host-memory
        # pin until the memo entry rotates out (capped) — accepted.
        mk = (key, tuple(spliced_keys), state,
              tuple(id(r.table) for r in replacements.values()))
        ent = memo.get(mk)
        if ent is not None:
            return ent
        new_plan = spine_mod.replace_nodes(plan, replacements)
        canon2 = self._canonicalize(new_plan, key)
        if canon2 is None:
            # without a canonical key the spliced plan would collide
            # with the unspliced program under the text key — run
            # unspliced instead (correct, just unshared)
            return plan, canon
        if len(memo) >= 256:
            memo.pop(next(iter(memo)))
        memo[mk] = (new_plan, canon2)
        return new_plan, canon2

    def _plan_fresh(self, stmt: "ast.Query",
                    pin: Optional[SnapshotPin] = None):
        cat = self.catalog if pin is None else pin.catalog
        views = self.views if pin is None else pin.views
        planner = pl.Planner(cat, dict(views))
        plan, cols = planner.plan_query(stmt)
        from ndstpu.engine.optimizer import optimize
        plan = optimize(plan, cat)
        # display names: strip alias qualifiers
        disp = self._dedupe(planner._display_names(cols))
        return plan, disp

    def _run_ddl(self, stmt: ast.Node) -> Optional[columnar.Table]:
        if isinstance(stmt, ast.CreateView):
            planner = pl.Planner(self.catalog, dict(self.views))
            plan, cols = planner.plan_query(stmt.query)
            disp = planner._display_names(cols)
            from ndstpu.engine import expr as ex
            self.views[stmt.name] = lp.Project(
                plan, [(d, ex.ColumnRef(c)) for d, c in zip(
                    self._dedupe(disp), cols)])
            self._views_epoch += 1
            return None
        if isinstance(stmt, ast.CreateTableAs):
            t = self._run(stmt.query)
            self.catalog.register(stmt.name, t)
            return None
        if isinstance(stmt, ast.InsertInto):
            return self._insert(stmt)
        if isinstance(stmt, ast.DeleteFrom):
            return self._delete(stmt)
        if isinstance(stmt, ast.DropRel):
            self.views.pop(stmt.name, None)
            self._views_epoch += 1
            if stmt.kind == "table":
                self.catalog.unregister(stmt.name)
            return None
        raise NotImplementedError(f"statement {type(stmt).__name__}")

    @staticmethod
    def _dedupe(names: List[str]) -> List[str]:
        seen: Dict[str, int] = {}
        out = []
        for n in names:
            if n in seen:
                seen[n] += 1
                out.append(f"{n}_{seen[n]}")
            else:
                seen[n] = 0
                out.append(n)
        return out

    def _execute(self, plan: lp.Plan, key: Optional[str] = None,
                 canon=None,
                 pin: Optional[SnapshotPin] = None) -> columnar.Table:
        from ndstpu import faults
        faults.check("execute", key=key)
        if pin is not None and not self._pin_matches_live(pin):
            # the catalog advanced past this pin (ingest committed
            # between admission and execution): run against the pinned
            # snapshot directly on the host engine.  Device-side caches
            # are keyed to live state, so a stale pin trades device
            # speed for snapshot isolation — the robustness-over-perf
            # choice; the common case (pin == live epoch) stays on the
            # normal backend path below.
            return physical.execute(plan, pin.catalog)
        # single-chip out-of-core: when chunk_rows is set, the `tpu`
        # backend streams facts through the SAME chunked executor as
        # tpu-spmd, just over a 1-device mesh (SF >> HBM on one chip;
        # host partial combine).  Unsupported shapes fall through to
        # the whole-fact-resident jaxexec path below.
        if self.backend == "tpu-spmd" or (
                self.backend == "tpu" and self.spmd_chunk_rows is not None):
            from ndstpu.engine import jaxexec
            from ndstpu.parallel import dplan
            versions = tuple(sorted(
                getattr(self.catalog, "versions", {}).items()))
            cache = getattr(self, "_spmd_cache", None)
            if cache is None:
                cache = self._spmd_cache = {}
                self._spmd_dev_cache = {}
            # shape-keyed SPMD cache: a canonical plan with an empty
            # shape residual is keyed on fingerprint + bound-value hash
            # (the values substitute back into literals before tracing,
            # so distinct bindings are distinct compiled programs) and
            # the parameterized exec plan rides with its binding;
            # renderings differing only in text share one entry
            spmd_plan, spmd_params = plan, None
            if canon is not None and not canon.residual:
                import hashlib
                vh = hashlib.sha256(
                    repr(canon.binding.values).encode()).hexdigest()[:16]
                ck = f"{self._views_epoch}|{canon.cache_key}|v{vh}"
                spmd_plan, spmd_params = canon.exec_plan, canon.binding
            else:
                ck = f"{self._views_epoch}|{key}" if key is not None \
                    else None
            ent = cache.get(ck) if ck else None
            if ent is not None and ent[0] != versions:
                # data changed: drop the stale executor (its pinned
                # device args go with it) and rebuild below
                del cache[ck]
                ent = None
            from ndstpu import obs
            obs.inc("engine.cache.spmd.hit" if ent is not None
                    else "engine.cache.spmd.miss")
            if ent is not None:
                try:
                    out = ent[1].execute_again()
                    self._spmd_used = True
                    return out
                except Exception as e:  # noqa: BLE001
                    # degrade a cached re-execution defect the same way
                    # as a first-run one: drop the executor, fall back
                    del cache[ck]
                    self._record_spmd_error(e)
                    ent = None
            try:
                kw = {"dev_cache": self._spmd_dev_cache}
                if self.spmd_threshold is not None:
                    kw["shard_threshold_rows"] = self.spmd_threshold
                if self.spmd_chunk_rows is not None:
                    kw["chunk_rows"] = self.spmd_chunk_rows
                if self.spmd_prefetch_depth is not None:
                    kw["prefetch_depth"] = self.spmd_prefetch_depth
                kw["cost_advisor"] = self._cost_advisor()
                exe = dplan.DistributedPlanExecutor(
                    self.catalog, self._mesh(), **kw)
                out = exe.execute_plan(spmd_plan, params=spmd_params)
                if ck:
                    cache[ck] = (versions, exe)
                self._spmd_used = True
                return out
            except (dplan.DistUnsupported, jaxexec.Unsupported) as u:
                # plan shape or an expression outside the distributed
                # subset: the single-chip path below has per-plan fallback
                obs.inc("engine.spmd.unsupported_fallbacks")
                code = getattr(u, "code", None)
                obs.annotate(spmd_fallback=f"{code or 'uncoded'}: {u}")
                if code:
                    obs.inc(f"engine.spmd.fallback.{code}")
                self._note_chunk_fallthrough(u)
            except Exception as e:  # noqa: BLE001
                # a distributed-executor defect must degrade to the
                # single-chip path, not fail the query; strict mode
                # (tests/CI) re-raises instead, and the first defect
                # warns — see _record_spmd_error
                self._record_spmd_error(e)
        if self.backend in ("tpu", "tpu-spmd"):
            exe = self._jax_executor()
            if key is not None:
                if canon is not None:
                    # shape-keyed compile cache: the key is the plan's
                    # canonical fingerprint (+ shape residual), the plan
                    # is the parameterized exec plan, and this
                    # rendering's literals travel as the binding —
                    # every rendering of a template shares one compile
                    return exe.execute_cached(
                        canon.exec_plan,
                        f"{self._views_epoch}|{canon.cache_key}",
                        params=canon.binding, sql=key)
                return exe.execute_cached(
                    plan, f"{self._views_epoch}|{key}")
            return exe.execute_to_host(plan)
        return physical.execute(plan, self.catalog)

    def _pin_matches_live(self, pin: SnapshotPin) -> bool:
        versions = tuple(sorted(
            getattr(self.catalog, "versions", {}).items()))
        return pin.views_epoch == self._views_epoch \
            and pin.versions == versions

    def _note_chunk_fallthrough(self, u: Exception) -> None:
        """NDS311: out-of-core streaming was configured on a multi-device
        mesh but this plan fell back to the single-chip whole-fact path,
        where `spmd_chunk_rows` is ignored and the fact must fit HBM
        resident.  Silent before this diagnostic — a run configured for
        SF100 streaming could quietly become a whole-fact load.  Warns
        + counts (`engine.spmd.fallback.NDS311`); NDSTPU_SPMD_STRICT
        turns it into an error."""
        import os
        import warnings

        from ndstpu import obs
        if self.spmd_chunk_rows is None or self.backend != "tpu-spmd" \
                or self._mesh().devices.size <= 1:
            return
        code = getattr(u, "code", None)
        msg = (f"NDS311: chunked streaming configured "
               f"(spmd_chunk_rows={self.spmd_chunk_rows!r}) but this "
               f"plan fell back to the single-chip whole-fact path "
               f"({code or 'uncoded'}: {u}); the fact must fit HBM "
               f"resident there")
        obs.inc("engine.spmd.fallback.NDS311")
        obs.annotate(chunk_fallthrough=f"{code or 'uncoded'}")
        if os.environ.get("NDSTPU_SPMD_STRICT"):
            raise ChunkFallthroughError(msg) from u
        warnings.warn(msg, stacklevel=3)

    def _record_spmd_error(self, e: Exception) -> None:
        """A non-DistUnsupported distributed failure is a defect, not a
        capability gap: NDSTPU_SPMD_STRICT re-raises it (tests/CI), and
        the first one warns on stderr so a distributed-correctness
        regression cannot hide as an invisible perf cliff."""
        import os
        import sys
        import warnings

        from ndstpu import obs
        obs.inc("engine.spmd.error_fallbacks")
        if os.environ.get("NDSTPU_SPMD_STRICT"):
            raise e
        errs = getattr(self, "_spmd_errors", None)
        if errs is None:
            errs = self._spmd_errors = []
        if not errs:
            print(f"WARNING: distributed executor failed "
                  f"({type(e).__name__}: {e}); falling back to the "
                  f"single-chip path (further fallbacks collected in "
                  f"Session._spmd_errors)", file=sys.stderr)
        # surfaces in the BenchReport as CompletedWithTaskFailures —
        # the reference's task-failure listener analog (report.py)
        warnings.warn(f"distributed executor fell back to single-chip: "
                      f"{type(e).__name__}: {e}", stacklevel=2)
        errs.append(repr(e))

    def _mesh(self):
        m = getattr(self, "_mesh_cache", None)
        if m is None:
            from ndstpu.parallel import mesh as pmesh
            # tpu = single-chip out-of-core (1-device mesh); tpu-spmd =
            # every visible device
            m = pmesh.make_mesh(1) if self.backend == "tpu" \
                else pmesh.default_mesh()
            self._mesh_cache = m
        return m

    def _cost_advisor(self):
        """Session-cached exchange-placement advisor (analysis/cost.py)
        for the distributed executors; re-checks the NDSTPU_COST kill
        switch per query so tests may flip it around one session, but
        probes the device budget only once."""
        from ndstpu.analysis import cost
        if not cost.enabled():
            return None
        adv = getattr(self, "_cost_advisor_cache", None)
        if adv is None:
            from ndstpu.analysis import lowering as lowreg
            adv = cost.default_advisor(lowreg.SPMD_BROADCAST_LIMIT_ROWS)
            self._cost_advisor_cache = adv
        return adv

    def canonical_key(self, text: str) -> str:
        """Structure-first dedup key for a query text: the canonical
        plan fingerprint + shape residual (analysis/canon.py) when
        canonicalization succeeds, the normalized text otherwise.  Two
        renderings of a template that differ only in runtime-bindable
        literals map to the SAME key — in-flight dedup and compile
        caches keyed on this collapse per-stream permutations."""
        from ndstpu.engine.sql import normalize_sql_key
        norm = normalize_sql_key(text)
        try:
            stmt = parse_statement(text)
            if not isinstance(stmt, ast.Query):
                return norm
            _plan, _disp, canon = self._plan_cached(stmt, norm)
        except Exception:  # noqa: BLE001 — unparseable/unplannable text
            return norm
        return canon.cache_key if canon is not None else norm

    def compiled_plan(self, text: str):
        """The cached whole-query compile record for a SQL text (or None).
        Test/introspection hook — mirrors the key used by `_execute`:
        canonical fingerprint first, normalized text as fallback."""
        from ndstpu.engine.sql import normalize_sql_key
        exe = getattr(self, "_jax_exec_cache", None)
        if exe is None:
            return None
        cp = exe._compiled.get(
            f"{self._views_epoch}|{self.canonical_key(text)}")
        if cp is None:
            cp = exe._compiled.get(
                f"{self._views_epoch}|{normalize_sql_key(text)}")
        return cp

    def compiled_count(self) -> int:
        """Number of whole-query compile records this session holds.
        The serve layer polls this after each request to persist compile
        records incrementally — a SIGKILL'd server must still warm-start
        from everything compiled before the kill, so it cannot wait for
        a clean drain to call :meth:`save_compiled`."""
        exe = getattr(self, "_jax_exec_cache", None)
        return len(exe._compiled) if exe is not None else 0

    def save_compiled(self, path: str) -> int:
        """Persist whole-query size-plan records for the jax backend."""
        return self._jax_executor().save_compile_records(path)

    def preload_compiled(self, path: str) -> int:
        """Preload size-plan records: later sql() calls skip discovery
        and go straight to the jitted replay (warm XLA cache makes the
        first execution ~compile-free too).  Records re-canonicalize on
        load so they register under the same canonical key a fresh
        rendering will probe — a discover-process and a preload-process
        agree on cache identity by construction."""
        def plan_for_sql(sql):
            from ndstpu.engine.sql import normalize_sql_key
            try:
                stmt = parse_statement(sql)
                if not isinstance(stmt, ast.Query):
                    return None
                plan, _disp, canon = self._plan_cached(
                    stmt, normalize_sql_key(sql))
            except Exception:  # noqa: BLE001
                return None
            if canon is not None:
                return canon.exec_plan, canon.cache_key
            return plan

        import os
        if not os.path.exists(path):
            return 0
        return self._jax_executor().load_compile_records(
            path, plan_for_sql, key_prefix=str(self._views_epoch))

    def _jax_executor(self):
        """One executor per session: keeps uploaded tables cached in HBM
        and whole-query compiled programs cached by SQL text (analog of
        Spark's cached TempViews + codegen cache).  Per-table invalidation
        happens inside the executor via catalog versions."""
        from ndstpu.engine import jaxexec
        with getattr(self, "_cache_lock", _NULL_CM):
            exe = getattr(self, "_jax_exec_cache", None)
            if exe is None or exe.catalog is not self.catalog:
                exe = jaxexec.CompilingExecutor(self.catalog)
                self._jax_exec_cache = exe
            return exe

    # -- DML against the warehouse (ACID ndslake tables) ---------------------

    def _insert(self, stmt: ast.InsertInto):
        from ndstpu.engine import expr as ex
        rows = self._run(stmt.query)
        target = self.catalog.get(stmt.table)
        if len(rows.column_names) != len(target.column_names):
            raise ValueError(
                f"INSERT INTO {stmt.table}: {len(rows.column_names)} values "
                f"for {len(target.column_names)} columns")
        # positional mapping + cast to the target's exact column types
        rows = columnar.Table({
            name: ex.cast_column(col, target.column(name).ctype)
            for name, col in zip(target.column_names,
                                 rows.columns.values())})
        if self.warehouse is not None:
            import os

            from ndstpu.io import lake
            root = os.path.join(self.warehouse, stmt.table)
            if lake.is_lake(root):
                lake.append(root, columnar.to_arrow(rows))
        merged = columnar.Table.concat([target, rows])
        self.catalog.register(stmt.table, merged)
        return None

    def _delete(self, stmt: ast.DeleteFrom):
        import numpy as np

        from ndstpu.engine import expr as ex
        target = self.catalog.get(stmt.table)
        if stmt.where is None:
            mask = np.ones(target.num_rows, dtype=bool)
        else:
            planner = pl.Planner(self.catalog, dict(self.views))
            scope = pl.Scope()
            scope.add(pl.Source(stmt.table, target.column_names))
            bound = planner._bind(stmt.where, scope)
            bound = physical.Executor(self.catalog)._resolve_subqueries(bound)
            # bound refs are internal "table.col" names; rename view
            renamed = columnar.Table({f"{stmt.table}.{n}": c
                                      for n, c in target.columns.items()})
            mask = ex.eval_predicate(renamed, bound)
        if self.warehouse is not None:
            import os

            from ndstpu.io import lake
            root = os.path.join(self.warehouse, stmt.table)
            if lake.is_lake(root):
                # re-evaluate the WHERE per data file — never assume the
                # in-memory row order matches file iteration order
                if stmt.where is None:
                    lake.delete_rows(
                        root, lambda at: np.ones(at.num_rows, dtype=bool))
                else:
                    from ndstpu import schema as nds_schema
                    try:
                        sch = nds_schema.get_schema(stmt.table)
                    except KeyError:
                        sch = None

                    def pred(at):
                        t = columnar.from_arrow(at, sch)
                        rn = columnar.Table(
                            {f"{stmt.table}.{n}": c
                             for n, c in t.columns.items()})
                        return ex.eval_predicate(rn, bound)
                    lake.delete_rows(root, pred)
        self.catalog.register(stmt.table, target.filter(~mask))
        return None
