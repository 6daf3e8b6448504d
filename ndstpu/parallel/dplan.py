"""Distributed plan executor: SQL plans as single SPMD XLA programs.

Executes the planner/optimizer's logical plans over a ``jax.sharding.Mesh``
— the multi-chip analog of Spark's distributed SQL execution (reference:
executors + shuffle exchange, power_run_cpu.template:23-33) designed
TPU-first rather than translated:

* The **spine** — the operator chain over the single largest table — runs
  row-sharded over the mesh's data axis inside ONE ``jit(shard_map)``
  program: filters/projects are local, dimension joins are broadcast
  joins (host-resolved build side, searchsorted probe — surrogate keys
  are ints), aggregation is local sort-grouped partials combined via
  ``lax.all_gather`` over ICI and re-grouped replicated (exact, no hash
  collisions; the psum combine for dense keys lives in
  ndstpu.parallel.dquery, the all_to_all repartition in
  ndstpu.parallel.exchange).
* **Existence-join build sides containing a fact** (q10/q35/q69
  EXISTS-over-store_sales shape) are not host-executed wholesale: a
  child executor reduces the build subtree to its distinct
  (key, residual column) tuples distributed, and only that small
  reduction broadcasts (:meth:`_reduce_build`).
* **Window functions** whose exprs are ranking or whole-partition
  aggregates run sharded: rows are colocated by a partition-key hash
  exchange (all_to_all) and the window is computed per device with the
  original row id as the deterministic tiebreak.
* **Plan tails finalize on-device** where the shape allows: aggregate
  combines are already an all_gather of partials, and a final
  Sort+Limit (or bare Limit) above a row spine becomes a per-device
  top-k plus a k-row all_gather — only the (small) result is fetched,
  tracked by the ``engine.spmd.host_gather_bytes`` counter.
* **Build sides and the remaining plan tail** (dimension subtrees,
  final Project over a handful of groups) execute on the host numpy
  interpreter — the driver side of a broadcast join.
* Plans without a sharded-size table, or using operators outside the
  distributed subset, raise :class:`DistUnsupported`; callers fall back
  to the single-chip engine (ndstpu.engine.jaxexec).

Differentially tested against the numpy interpreter on a virtual
8-device CPU mesh (tests/test_parallel.py) and compile-checked by the
driver via __graft_entry__.dryrun_multichip.
"""

from __future__ import annotations

import copy
import dataclasses
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp  # noqa: E402
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ndstpu import obs
from ndstpu.analysis import lowering as lowreg
from ndstpu.engine import columnar, expr as ex, physical, plan as lp
from ndstpu.engine.columnar import BOOL, FLOAT64, INT64, Column, Table
from ndstpu.engine.jaxexec import (
    DCol,
    DTable,
    JEval,
    Unsupported,
    _DEAD_KEY,
    _NULL32,
    _NULL_KEY,
    _ORD_DEAD32,
    _group_ids,
    _key_col,
    _key_i64,
    _lexsort_order,
    _minmax_vals,
    _narrow_span,
    _sum_input,
    jnp_dtype,
)
from ndstpu.parallel.mesh import SHARD_AXIS, shard_map


class DistUnsupported(Exception):
    """Plan shape outside the distributed subset — fall back single-chip.

    ``code`` is the static analyzer's NDS3xx diagnostic for raise sites
    it models (ndstpu/analysis/diagnostics.py); data-dependent guards
    (dup runs, key-domain overflow, shuffle drops) stay uncoded."""

    def __init__(self, msg: str, code=None):
        super().__init__(msg)
        self.code = code


def _has_params(plan: lp.Plan) -> bool:
    """True when any expression in the plan carries a parameter slot.
    Parameterized (canonical) plans can still take the SPMD path when
    the caller supplies the binding — execute_plan substitutes the bound
    values back into literals (:func:`bind_plan_params`) and compiles
    the concrete plan, keyed upstream on fingerprint + value hash."""
    for node in plan.walk():
        for f in dataclasses.fields(node):
            v = getattr(node, f.name)
            items = v if isinstance(v, (list, tuple)) else (v,)
            for it in items:
                if isinstance(it, tuple):  # sort keys: (expr, asc[, nf])
                    it = it[0] if it else None
                if isinstance(it, ex.Expr) and any(
                        isinstance(x, (ex.Param, ex.InParam))
                        for x in it.walk()):
                    return True
    return False


def _subst_params(e: ex.Expr, values) -> ex.Expr:
    """Rebuild `e` with every Param/InParam replaced by the bound
    literal / IN-list (slot-indexed into the canonicalizer's values)."""
    if isinstance(e, ex.Param):
        return ex.Literal(values[e.slot])
    if isinstance(e, ex.InParam):
        return ex.InList(_subst_params(e.operand, values),
                         list(values[e.slot]), e.negated)
    changed = False
    kw = {}
    for f in dataclasses.fields(e):
        v = getattr(e, f.name)
        if isinstance(v, ex.Expr):
            nv = _subst_params(v, values)
        elif isinstance(v, (list, tuple)):
            nv = type(v)(
                _subst_params(it, values) if isinstance(it, ex.Expr)
                else (tuple(_subst_params(x, values)
                            if isinstance(x, ex.Expr) else x for x in it)
                      if isinstance(it, tuple) else it)
                for it in v)
            if nv == v:
                nv = v
        else:
            nv = v
        kw[f.name] = nv
        changed = changed or nv is not v
    return dataclasses.replace(e, **kw) if changed else e


def bind_plan_params(plan: lp.Plan, binding) -> lp.Plan:
    """Concrete copy of a canonical exec_plan: every Param/InParam slot
    replaced by its bound value from ``binding`` (an
    :class:`~ndstpu.engine.expr.ParamBinding`).  The SPMD compiler then
    traces plain literals — shape slots were already substituted by the
    canonicalizer, so the result is exactly the original plan's shape."""
    values = binding.values if hasattr(binding, "values") else binding
    plan = copy.deepcopy(plan)
    for node in plan.walk():
        for f in dataclasses.fields(node):
            v = getattr(node, f.name)
            if isinstance(v, ex.Expr):
                setattr(node, f.name, _subst_params(v, values))
            elif isinstance(v, (list, tuple)):
                out = []
                for it in v:
                    if isinstance(it, ex.Expr):
                        out.append(_subst_params(it, values))
                    elif isinstance(it, tuple):
                        out.append(tuple(
                            _subst_params(x, values)
                            if isinstance(x, ex.Expr) else x for x in it))
                    else:
                        out.append(it)
                setattr(node, f.name, type(v)(out))
    return plan


def _table_bytes(t: Table) -> int:
    """Replicated footprint of a host build table through memplan's
    row-width model (the same width the static analyzer estimates)."""
    from ndstpu.engine import memplan
    return memplan.row_bytes(
        [t.column(nm).data.dtype.itemsize
         for nm in t.column_names]) * int(t.num_rows)


_SPINE_NODES = (lp.Scan, lp.Filter, lp.Project, lp.Join, lp.SubqueryAlias)
# shardable key kinds and decomposable aggregates come from the shared
# supported-op registry so the static analyzer (NDS3xx) cannot drift
_KEY_KINDS = tuple(sorted(lowreg.SPMD_KEY_KINDS))
_AGG_FUNCS = tuple(sorted(lowreg.SPMD_AGG_FUNCS))


@dataclasses.dataclass
class _BroadcastJoin:
    """Host-resolved build side of a spine join (driver-side broadcast)."""
    kind: str
    mark: Optional[str]
    extra: Optional[ex.Expr]
    probe_key_exprs: List[ex.Expr]
    radices: List[Tuple[int, int]]   # (lo, span) per key part
    sorted_keys: np.ndarray          # valid build keys, sorted
    row_of: np.ndarray               # sorted position -> build row index
    build: Table                     # host build table (post plan)
    spine_left: bool                 # spine side is the join's left child
    build_has_null: bool = False     # any build row with a NULL key part
    build_empty: bool = False
    # per key part: the build dictionary for string keys (None = numeric)
    key_dicts: Optional[List[Optional[np.ndarray]]] = None
    # >0: duplicate build key runs — inner joins EXPAND the probe side
    # by this factor; semi/anti/mark residuals probe every duplicate
    dup_max: int = 0


@dataclasses.dataclass
class _ShuffleJoin:
    """Partitioned equi-join for build sides too large to broadcast —
    the fact-fact join path (e.g. store_sales ⋈ store_returns on
    item_sk+ticket_number).  The build side is hash-partitioned by key
    across devices on the host (each device holds its partition, sorted
    by key); the traced probe side repartitions the live spine rows with
    ``all_to_all`` using the same splitmix64 bucket hash, then joins
    locally with a searchsorted probe.  This is the Spark shuffle-
    exchange analog (power_run_cpu.template:30-32) as an ICI collective.
    """
    kind: str
    mark: Optional[str]
    extra: Optional[ex.Expr]
    probe_key_exprs: List[ex.Expr]
    radices: List[Tuple[int, int]]
    spine_left: bool
    build_has_null: bool
    build_empty: bool
    part_cap: int                    # rows per device partition (padded)
    # host-staged [n_dev * part_cap] arrays (device_put at spine launch):
    # partition-local keys sorted ascending, _DEAD_KEY padding
    keys_flat: np.ndarray
    # build columns gathered into partition order: name -> (data, valid,
    # ctype, dictionary)
    cols_flat: Dict[str, tuple]
    # filled per trace: index of this join's first arg in the flat
    # shard_map argument list
    arg_start: int = -1
    n_args: int = 0
    # per key part: the build dictionary for string keys (None = numeric)
    key_dicts: Optional[List[Optional[np.ndarray]]] = None
    # >0: semi/anti/mark residual probes every duplicate in a key run
    dup_max: int = 0


class DistributedPlanExecutor:
    """Compiles + runs one logical plan over the mesh (one-shot object)."""

    def __init__(self, catalog, mesh, shard_threshold_rows: int = 65536,
                 broadcast_limit_rows: int = lowreg.SPMD_BROADCAST_LIMIT_ROWS,
                 dev_cache: Optional[dict] = None,
                 chunk_rows=None,
                 prefetch_depth: Optional[int] = None,
                 cost_advisor="auto"):
        self.catalog = catalog
        self.mesh = mesh
        self.n_dev = int(mesh.devices.size)
        self.threshold = shard_threshold_rows
        self.broadcast_limit = broadcast_limit_rows
        # exchange-placement advisor (analysis/cost.py): "auto" resolves
        # to the cost model over the runtime device budget when
        # NDSTPU_COST is on; None restores the fixed rows-only rule
        if cost_advisor == "auto":
            from ndstpu.analysis import cost as _cost
            cost_advisor = _cost.default_advisor(broadcast_limit_rows) \
                if _cost.enabled() else None
        self.cost_advisor = cost_advisor
        # per-join advisor decisions for this plan (query-span attr ->
        # ledger extra); _order_safe: an aggregate spine is insensitive
        # to row placement, a row spine's output order is not
        self.cost_decisions: List[dict] = []
        self._order_safe = False
        # out-of-core: facts above this row count stream through the
        # device shard-major — device d owns fact rows
        # [d*shard_rows, (d+1)*shard_rows) and streams only its shard's
        # chunks (one compiled program, partials combined across chunks
        # on the host).  None = whole-fact resident; "auto" = the
        # spill-aware planner (engine/memplan.py) sizes chunk_rows and
        # the prefetch depth per fact from device memory stats
        self.chunk_rows = chunk_rows
        # H2D staging ring depth (chunks staged ahead of compute);
        # None = planner default, 0 = synchronous
        self.prefetch_depth = prefetch_depth
        self.np_exec = physical.Executor(catalog)
        # shared (table, column, version) -> device arrays cache so many
        # cached query executors don't pin duplicate fact copies in HBM
        self.dev_cache = dev_cache if dev_cache is not None else {}
        self.joins: Dict[int, object] = {}   # _BroadcastJoin | _ShuffleJoin
        self.fact: Optional[lp.Scan] = None
        # probe-shuffle receive bucket = slack * capacity / n_dev; doubled
        # on overflow up to n_dev (lossless) by _run_spine_retrying
        self.shuffle_slack = 2
        self._last_dropped = 0
        self._prepared = False
        # collect_partials mode: _post_spine returns raw finest-group
        # partials instead of a finalized Table (union-agg branches)
        self._emit_partials = False
        self._union_ctx = None
        # trace-time metadata side channels (static python values)
        self._row_meta: Optional[List[tuple]] = None
        self._key_meta: Optional[List[tuple]] = None
        self._leaf_meta: Optional[List[tuple]] = None
        # NDS3xx codes hit while probing candidates / child executors —
        # kept even on success so spmd_coverage can report which raise
        # sites the plan brushed against on its way to a working spine
        self.attempt_codes: List[str] = []
        # (join kind, reduced build rows) per _reduce_build success
        self.build_reduced: List[tuple] = []
        # on-device row-spine tail: (sort keys or None, LIMIT n)
        self._tail: Optional[tuple] = None
        # the spine absorbs Window nodes (rowid threading needed)
        self._has_win = False

    # -- public --------------------------------------------------------------

    def execute_plan(self, plan: lp.Plan, params=None) -> Table:
        """Try candidate fact tables largest-first (at tiny scale factors
        a fixed-size dimension like date_dim can out-size the fact, and
        some spines fail preparation, e.g. non-unique build keys)."""
        if _has_params(plan):
            if params is None:
                raise DistUnsupported(
                    "parameterized (canonical) plan on spmd path without "
                    "a binding", code="NDS301")
            plan = bind_plan_params(plan, params)
        union = self._try_union_agg(plan)
        if union is not None:
            self._annotate_decisions()
            return union
        offload = self._try_subquery_offload(plan)
        if offload is not None:
            self._annotate_decisions()
            return offload
        scans = [n for n in plan.walk() if isinstance(n, lp.Scan)]
        if not scans:
            raise DistUnsupported("no base-table scan in plan",
                                  code="NDS301")
        sized = sorted(((self.catalog.get(n.table).num_rows, i, n)
                        for i, n in enumerate(scans)),
                       key=lambda t: (-t[0], t[1]))
        last: Optional[DistUnsupported] = None
        for rows, _, target in sized:
            if rows < self.threshold:
                break
            self.joins = {}
            self.fact = None
            self.fact_target = target
            self._prepared = False
            self._tail = None
            self._has_win = False
            self.cost_decisions = []
            try:
                spine, top = self._split(plan)
                result = self._run_spine_retrying(spine)
            except DistUnsupported as e:
                if e.code:
                    self.attempt_codes.append(e.code)
                last = e
                continue
            self._spine, self._top = spine, top
            self._annotate_decisions()
            return self._finish(result)
        raise last or DistUnsupported("no sharded-size table in plan",
                                      code="NDS301")

    def _annotate_decisions(self) -> None:
        """Compact advisor trail on the query span (-> ledger extra
        ``cost_decisions``): one ``kind:strategy`` token per spine
        join, ``*`` marking a cost override of the structural rule."""
        if not self.cost_decisions:
            return
        obs.annotate(cost_decisions=" ".join(
            f"{d['kind']}:{d['strategy']}"
            + ("*" if d["overrode"] else "")
            for d in self.cost_decisions))

    def _try_subquery_offload(self, plan: lp.Plan) -> Optional[Table]:
        """q9 shape: the outer plan scans only sub-threshold tables (its
        FROM is the tiny `reason` dim) while uncorrelated SCALAR
        subqueries embedded in its expressions aggregate a sharded-size
        fact.  Execute each such subquery body distributed (one child
        executor per body), inline the scalars, and run the tiny outer
        plan on host — the reference distributes these trivially through
        Spark (query9.tpl's 15 store_sales aggregates)."""
        for n in plan.walk():
            if isinstance(n, lp.Scan) and n.table in self.catalog and \
                    self.catalog.get(n.table).num_rows >= self.threshold:
                return None     # normal spine path handles it
        from ndstpu.engine.optimizer import _plan_exprs

        subs: List[ex.SubqueryExpr] = []

        def collect(p: lp.Plan) -> None:
            for e in _plan_exprs(p):
                for x in e.walk():
                    if isinstance(x, ex.SubqueryExpr) and \
                            x.plan is not None and x.kind == "scalar" and \
                            not x.correlated_predicates:
                        subs.append(x)
            for c in p.children():
                collect(c)

        collect(plan)
        targets = [
            s for s in subs
            if any(isinstance(n, lp.Scan) and n.table in self.catalog and
                   self.catalog.get(n.table).num_rows >= self.threshold
                   for n in s.plan.walk())]
        if not targets:
            return None
        children: List[Tuple[ex.SubqueryExpr,
                             "DistributedPlanExecutor"]] = []
        firsts: List[Table] = []
        for s in targets:
            child = DistributedPlanExecutor(
                self.catalog, self.mesh,
                shard_threshold_rows=self.threshold,
                broadcast_limit_rows=self.broadcast_limit,
                dev_cache=self.dev_cache, chunk_rows=self.chunk_rows,
                prefetch_depth=self.prefetch_depth,
                cost_advisor=self.cost_advisor)
            firsts.append(child.execute_plan(s.plan))  # DistUnsupported
            self.attempt_codes += child.attempt_codes  # propagates
            self.cost_decisions += child.cost_decisions
            children.append((s, child))
        self._scalar_ctx = (plan, children)
        return self._scalar_finish(firsts)

    @staticmethod
    def _scalar_literal(t: Table) -> ex.Expr:
        return physical.scalar_subquery_literal(t, too_many=DistUnsupported)

    def _scalar_finish(self, results: Optional[List[Table]]) -> Table:
        """Inline distributed subquery results as literals (pre-seeding
        the host interpreter's subquery cache) and run the tiny outer
        plan; `results=None` re-runs the children's compiled spines."""
        plan, children = self._scalar_ctx
        self.np_exec = physical.Executor(self.catalog)
        for i, (s, child) in enumerate(children):
            out = results[i] if results is not None else \
                child.execute_again()
            self.np_exec._subq_cache[id(s)] = self._scalar_literal(out)
        return self.np_exec.execute(plan)

    def collect_partials(self, plan: lp.Aggregate):
        """Run an Aggregate-rooted plan over the mesh and return the raw
        finest-group (key_cols, leaf_parts) instead of finalizing — one
        branch of a union-all aggregate."""
        self._emit_partials = True
        scans = [n for n in plan.walk() if isinstance(n, lp.Scan)]
        if not scans:
            raise DistUnsupported("no base-table scan in branch",
                                  code="NDS301")
        sized = sorted(((self.catalog.get(n.table).num_rows, i, n)
                        for i, n in enumerate(scans)),
                       key=lambda t: (-t[0], t[1]))
        last: Optional[DistUnsupported] = None
        for rows, _, target in sized:
            if rows < self.threshold:
                break
            self.joins = {}
            self.fact = None
            self.fact_target = target
            self._prepared = False
            self._tail = None
            self._has_win = False
            self.cost_decisions = []
            try:
                spine, top = self._split(plan)
                if spine is not plan:
                    raise DistUnsupported(
                        "branch spine is not the union aggregate")
                out = self._run_spine_retrying(spine)
            except DistUnsupported as e:
                if e.code:
                    self.attempt_codes.append(e.code)
                last = e
                continue
            self._spine, self._top = spine, top
            return out
        raise last or DistUnsupported("no sharded-size table in branch",
                                      code="NDS301")

    def _run_spine_retrying(self, spine: lp.Plan) -> Table:
        """Run the spine; if a shuffle-join receive bucket overflowed
        (key skew), double the slack and re-trace.  slack >= n_dev makes
        every bucket as large as a whole shard, which cannot drop."""
        while True:
            result = self._run_spine(spine)
            if not self._last_dropped:
                return result
            if self.shuffle_slack >= self.n_dev:
                raise DistUnsupported(
                    "shuffle join dropped rows at lossless bucket size")
            self.shuffle_slack = min(self.shuffle_slack * 2, self.n_dev)

    def _finish(self, result: Table) -> Table:
        if self._top is None:
            return result
        grafted = _graft(self._top, self._spine,
                         lp.InlineTable(result, "__dist__"))
        return self.np_exec.execute(grafted)

    def execute_again(self) -> Table:
        """Re-run the already-compiled spine program (caller must have
        checked catalog versions are unchanged) and redo the host
        finalize + plan tail — the repeat-execution path for cached
        tpu-spmd queries (no re-trace, no re-compile, no host build)."""
        obs.inc("engine.spmd.reexecs")
        if self._union_ctx is not None:
            return self._union_again()
        if getattr(self, "_scalar_ctx", None) is not None:
            return self._scalar_finish(None)
        if getattr(self, "_chunk_info", (False,))[0]:
            return self._finish(self._run_chunks())
        out = jax.device_get(self._compiled_fn(*self._dev_args))
        return self._finish(self._post_spine(out))

    # -- union-all aggregates ------------------------------------------------

    def _try_union_agg(self, plan: lp.Plan) -> Optional[Table]:
        """Distribute an Aggregate over a UNION ALL of channel subplans
        (q2/q5/q33/q56/q60/q66/q71/q76... shape): run each branch as its
        own sharded spine (the union may sit under joins/projects inside
        the aggregate), collect finest-group partials, and combine the
        decomposable partials across branches on the host.  The plan
        remainder (outer rollups, second union sites from reused CTEs)
        recurses into a fresh executor so EVERY union site distributes.
        Returns None when no site matches or no branch distributes."""
        found = self._find_union_site(plan)
        if found is None:
            return None
        agg, setop = found
        try:
            self._check_agg(agg)
        except DistUnsupported:
            return None
        return self._run_union_site(plan, agg, setop)

    def _find_union_site(self, plan: lp.Plan):
        """Deepest Aggregate that directly dominates (no intervening
        aggregate) a union-all SetOp; among its unions, the one holding
        the largest base table."""

        def walk_depth(p, d=0):
            yield p, d
            for c in p.children():
                yield from walk_depth(c, d + 1)

        def union_size(s: lp.SetOp) -> int:
            rows = [self.catalog.get(n.table).num_rows
                    for n in s.walk() if isinstance(n, lp.Scan)]
            return max(rows, default=0)

        best = None
        for node, depth in walk_depth(plan):
            if not isinstance(node, lp.Aggregate):
                continue
            direct = [s for s in node.child.walk()
                      if isinstance(s, lp.SetOp) and s.kind == "union"
                      and s.all and _distributive_path(node.child, s)
                      and union_size(s) >= self.threshold]
            if not direct:
                continue
            # outermost first among sharded-size sites: nested unions
            # inside a branch are flattened by _expand_branches
            s = min(direct,
                    key=lambda s: (len(_path_to(node.child, s) or ()),
                                   -union_size(s)))
            if best is None or depth > best[0]:
                best = (depth, node, s)
        return (best[1], best[2]) if best is not None else None

    def _run_union_site(self, plan: lp.Plan, agg: lp.Aggregate,
                        setop: lp.SetOp) -> Optional[Table]:
        leaves = self._agg_leaves(agg)
        if any(a.distinct for a in leaves):
            return None    # cross-branch dedup not supported
        branches: List[lp.Plan] = []

        def flat(s: lp.SetOp) -> None:
            for side in (s.left, s.right):
                if isinstance(side, lp.SetOp) and side.kind == "union" \
                        and side.all:
                    flat(side)
                else:
                    branches.append(side)

        flat(setop)
        branches = self._expand_branches(branches)
        left_names = _output_names(branches[0], self.catalog)
        if left_names is None:
            return None
        sub_execs: List[Optional[DistributedPlanExecutor]] = []
        parts: List[tuple] = []   # (key_cols, leaf_parts, leaf_meta)
        any_dist = False
        for i, b in enumerate(branches):
            nb = b
            if i > 0:
                bn = _output_names(b, self.catalog)
                if bn is None or len(bn) != len(left_names):
                    return None
                # SetOp semantics are positional: align this branch's
                # output names with the left branch's
                nb = lp.Project(b, [(ln, ex.ColumnRef(n))
                                    for ln, n in zip(left_names, bn)])
            child = _graft(agg.child, setop, nb)
            bplan = lp.Aggregate(child, list(agg.group_by),
                                 list(agg.aggs), None)
            exe = DistributedPlanExecutor(
                self.catalog, self.mesh, self.threshold,
                self.broadcast_limit, self.dev_cache,
                chunk_rows=self.chunk_rows,
                prefetch_depth=self.prefetch_depth,
                cost_advisor=self.cost_advisor)
            try:
                kc, lps = exe.collect_partials(bplan)
                self.attempt_codes += exe.attempt_codes
                self.cost_decisions += exe.cost_decisions
                parts.append((kc, lps, list(exe._leaf_meta)))
                sub_execs.append(exe)
                any_dist = True
            except DistUnsupported as du:
                if du.code:
                    self.attempt_codes.append(du.code)
                self.attempt_codes += exe.attempt_codes
                try:
                    kc, lps, meta = self._host_partials(bplan)
                except Exception:  # noqa: BLE001 — any planner/eval gap
                    return None    # falls back to the non-union paths
                parts.append((kc, lps, meta))
                sub_execs.append(None)
        if not any_dist:
            return None
        result = self._finalize_union(agg, leaves, parts)
        self._union_ctx = (plan, agg, sub_execs, parts, leaves)
        if agg is plan:
            self._union_rest = None
            self._union_next = None
            return result
        # recurse on the remainder so further union sites (other
        # channels, a CTE's second instantiation) distribute too; the
        # recursion bottoms out in the single-spine path or numpy
        rest = _graft(plan, agg, lp.InlineTable(result, "__dist_union__"))
        self._union_rest = rest
        nxt = DistributedPlanExecutor(
            self.catalog, self.mesh, self.threshold,
            self.broadcast_limit, self.dev_cache,
            chunk_rows=self.chunk_rows,
            prefetch_depth=self.prefetch_depth,
            cost_advisor=self.cost_advisor)
        try:
            out = nxt.execute_plan(rest)
            self.attempt_codes += nxt.attempt_codes
            self.cost_decisions += nxt.cost_decisions
            self._union_next = nxt
            return out
        except DistUnsupported:
            self.attempt_codes += nxt.attempt_codes
            self._union_next = None
            return self.np_exec.execute(rest)

    def _expand_branches(self, branches: List[lp.Plan],
                         cap: int = 16) -> List[lp.Plan]:
        """Flatten unions NESTED inside branches into extra top-level
        branches while the path to them distributes over UNION ALL
        (q5 shape: each channel joins dims onto an inner sales∪returns
        union).  Branches beyond `cap` stay unexpanded (host fallback).
        Union semantics are positional, so every nested side is aligned
        to its union's left-side names before grafting."""
        work = list(branches)
        out: List[lp.Plan] = []
        while work:
            b = work.pop(0)
            inner = next(
                (s for s in b.walk()
                 if isinstance(s, lp.SetOp) and s.kind == "union"
                 and s.all and _distributive_path(b, s)), None)
            if inner is None:
                out.append(b)
                continue
            sides: List[lp.Plan] = []

            def flat(s: lp.SetOp) -> None:
                for side in (s.left, s.right):
                    if isinstance(side, lp.SetOp) and \
                            side.kind == "union" and side.all:
                        flat(side)
                    else:
                        sides.append(side)

            flat(inner)
            left_names = _output_names(sides[0], self.catalog)
            aligned: Optional[List[lp.Plan]] = []
            for i, s in enumerate(sides):
                if i == 0:
                    aligned.append(s)
                    continue
                sn = _output_names(s, self.catalog)
                if left_names is None or sn is None or \
                        len(sn) != len(left_names):
                    aligned = None
                    break
                aligned.append(lp.Project(
                    s, [(ln, ex.ColumnRef(n))
                        for ln, n in zip(left_names, sn)]))
            if aligned is None or \
                    len(out) + len(work) + len(aligned) > cap:
                out.append(b)   # unexpandable: keep whole (host path)
                continue
            work = [_graft(b, inner, s) for s in aligned] + work
        return out

    def _union_again(self) -> Table:
        plan, agg, sub_execs, first_parts, leaves = self._union_ctx
        parts = []
        for exe, cached in zip(sub_execs, first_parts):
            if exe is not None:
                kc, lps = exe.execute_again()
                parts.append((kc, lps, list(exe._leaf_meta)))
            else:
                # host-fallback branch: the caller only reuses this
                # executor when catalog versions are unchanged, so the
                # first run's numpy partials are still valid — no
                # re-execution of the branch subplan
                parts.append(cached)
        result = self._finalize_union(agg, leaves, parts)
        if agg is plan:
            return result
        # versions unchanged => identical union result; the remainder
        # plan staged at first execution (with that result inlined) is
        # still valid, so replay it
        if self._union_next is not None:
            return self._union_next.execute_again()
        return self.np_exec.execute(self._union_rest)

    def _host_partials(self, bplan: lp.Aggregate):
        """Numpy finest-group partials for one union branch that can't
        be distributed (sub-threshold fact or unsupported shape)."""
        rows = self.np_exec.execute(bplan.child)
        ev = ex.Evaluator(rows)
        key_cols: Dict[str, Column] = {}
        for name, e in bplan.group_by:
            key_cols[name] = ev.eval(
                self.np_exec._resolve_subqueries(e))
        n = rows.num_rows
        if bplan.group_by:
            gids, first = self.np_exec._factorize(
                list(key_cols.values()))
            ng = len(first)
            key_cols = {name: c.gather(first)
                        for name, c in key_cols.items()}
        else:
            gids = np.zeros(n, np.int64)
            ng = 1 if n else 0
        leaves = self._agg_leaves(bplan)
        leaf_parts, metas = [], []
        for a in leaves:
            p, meta = self._host_leaf_partial(rows, ev, a, gids, ng)
            leaf_parts.append(p)
            metas.append(meta)
        return key_cols, leaf_parts, metas

    def _host_leaf_partial(self, rows: Table, ev: ex.Evaluator,
                           a: ex.AggExpr, gids, ng):
        """Numpy mirror of the traced _leaf_partial."""
        if isinstance(a.arg, ex.Star) or a.arg is None:
            cnt = np.bincount(gids, minlength=ng).astype(np.int64) \
                if len(gids) else np.zeros(ng, np.int64)
            return [cnt], (a.func, None, None)
        c = ev.eval(self.np_exec._resolve_subqueries(a.arg))
        meta = (a.func, c.ctype, c.dictionary)
        valid = c.validity()
        cnt = np.zeros(ng, np.int64)
        np.add.at(cnt, gids[valid], 1)
        if a.func == "count":
            return [cnt], meta
        if a.func in ("sum", "avg"):
            if c.ctype.kind in ("decimal", "int32", "int64"):
                s = np.zeros(ng, np.int64)
                np.add.at(s, gids[valid], c.data[valid].astype(np.int64))
            else:
                s = np.zeros(ng, np.float64)
                np.add.at(s, gids[valid],
                          c.data[valid].astype(np.float64))
            return [s, cnt], meta
        if a.func in ("min", "max"):
            if c.ctype.kind == "float64":
                init = np.inf if a.func == "min" else -np.inf
                acc = np.full(ng, init, np.float64)
                vals = c.data[valid].astype(np.float64)
            else:
                init = np.int64(_DEAD_KEY if a.func == "min"
                                else -_DEAD_KEY)
                acc = np.full(ng, init, np.int64)
                vals = c.data[valid].astype(np.int64)
            fold = np.minimum if a.func == "min" else np.maximum
            fold.at(acc, gids[valid], vals)
            return [acc, cnt], meta
        # stddev family: partials are [s1, m2, cnt] with m2 the CENTERED
        # second moment (shifted two-pass); combines use Chan's formula —
        # raw sum-of-squares cancels catastrophically when mean >> stddev
        x = c.data[valid].astype(np.float64)
        if c.ctype.kind == "decimal":
            x = x / (10 ** c.ctype.scale)
        s1 = np.zeros(ng, np.float64)
        np.add.at(s1, gids[valid], x)
        mean = s1 / np.maximum(cnt, 1)
        d = x - mean[gids[valid]]
        d1 = np.zeros(ng, np.float64)
        m2 = np.zeros(ng, np.float64)
        np.add.at(d1, gids[valid], d)
        np.add.at(m2, gids[valid], d * d)
        m2 -= np.where(cnt > 0, d1 * d1 / np.maximum(cnt, 1), 0.0)
        return [s1, m2, cnt], meta

    def _finalize_union(self, agg: lp.Aggregate, leaves,
                        parts: List[tuple]) -> Table:
        """Concatenate per-branch finest groups and re-combine through
        the grouping-sets machinery (a plain GROUP BY is the single
        all-keys grouping set)."""
        names = [n for n, _ in agg.group_by]
        # merge group-key columns (Table.concat merges dictionaries)
        if names:
            merged = Table.concat([Table(kc) for kc, _, _ in parts])
            key_cols = dict(merged.columns)
        else:
            key_cols = {}
        leaf_parts: List[List[np.ndarray]] = []
        metas: List[tuple] = []
        for li, a in enumerate(leaves):
            bmetas = [m[li] for _, _, m in parts]
            func, ct0, _ = bmetas[0]

            def compatible(ct2) -> bool:
                # partials combine on kind + decimal scale; precision
                # widening (e.g. `0 - x`) doesn't change the encoding
                if ct0 is None or ct2 is None:
                    return ct0 is ct2
                ints = ("int32", "int64")
                if ct2.kind != ct0.kind and not (
                        ct2.kind in ints and ct0.kind in ints):
                    return False
                return ct0.kind != "decimal" or ct2.scale == ct0.scale

            for f2, ct2, _ in bmetas[1:]:
                if f2 != func or not compatible(ct2):
                    raise DistUnsupported(
                        "union branches disagree on aggregate type",
                        code="NDS302")
            dicts = [m[li][2] for _, _, m in parts]
            has_dict = any(d is not None for d in dicts)
            merged_dict = None
            branch_parts = [lp_[li] for _, lp_, _ in parts]
            if has_dict and func in ("min", "max"):
                # per-branch dictionary codes are not comparable across
                # branches: translate into the union dictionary
                arrs = [d for d in dicts if d is not None]
                merged_dict = arrs[0]
                for d in arrs[1:]:
                    merged_dict = np.union1d(merged_dict, d)
                init = np.int64(_DEAD_KEY if func == "min"
                                else -_DEAD_KEY)
                for bi, (bp, d) in enumerate(zip(branch_parts, dicts)):
                    if d is None:
                        continue
                    codes = bp[0]
                    cnt = bp[1]
                    safe = np.clip(codes, 0, len(d) - 1).astype(np.int64)
                    remap = np.searchsorted(
                        merged_dict, d[safe]).astype(np.int64)
                    branch_parts[bi] = [np.where(cnt > 0, remap, init)] \
                        + list(bp[1:])
            cat = [np.concatenate([bp[pi] for bp in branch_parts])
                   for pi in range(len(branch_parts[0]))]
            leaf_parts.append(cat)
            metas.append((func, ct0, merged_dict if merged_dict
                          is not None else dicts[0]))
        self._leaf_meta = metas
        sets = agg.grouping_sets if agg.grouping_sets is not None \
            else [list(range(len(names)))]
        shim = lp.Aggregate(agg.child, list(agg.group_by),
                            list(agg.aggs), sets)
        return self._grouping_sets_result(shim, leaves, key_cols,
                                          leaf_parts)

    # -- plan analysis -------------------------------------------------------

    def _split(self, plan: lp.Plan) -> Tuple[lp.Plan, Optional[lp.Plan]]:
        """Find the distributed spine: the chain from the single big Scan
        up to the first Aggregate above it (or the highest supported node).
        Returns (spine_head, top_plan); top_plan executes on host over the
        spine's result (None = the spine is the whole plan)."""
        target = self.fact_target

        chain: List[lp.Plan] = []

        def descend(node) -> bool:
            chain.append(node)
            if node is target:
                return True
            for c in node.children():
                if descend(c):
                    return True
            chain.pop()
            return False

        descend(plan)

        def spine_ok(node) -> bool:
            if isinstance(node, lp.Join):
                return node.kind in ("inner", "left", "semi", "anti",
                                    "nullaware_anti", "mark")
            if isinstance(node, lp.Window):
                # ranking / whole-partition aggregate windows run
                # sharded after a partition-colocating exchange
                # (shared legality check with the NDS310 audit)
                return lowreg.spmd_window_ok(node)
            return isinstance(node, _SPINE_NODES)

        # longest spine-ok suffix of the chain ending at the fact scan;
        # if the node directly above it is a supported Aggregate, take it
        # as the spine top (the DEEPEST aggregate — everything above,
        # including outer aggregates/windows over the now-small result,
        # runs on the host tail)
        ok_from = len(chain) - 1
        for i in range(len(chain) - 1, -1, -1):
            if spine_ok(chain[i]):
                ok_from = i
            else:
                break
        self._has_win = any(isinstance(nd, lp.Window)
                            for nd in chain[ok_from:])
        if ok_from > 0 and isinstance(chain[ok_from - 1], lp.Aggregate):
            self._check_agg(chain[ok_from - 1])
            spine = chain[ok_from - 1]
        else:
            spine = chain[ok_from]
        self._tail = None
        if not isinstance(spine, lp.Aggregate):
            # on-device row-spine tail: a Sort+Limit (or bare Limit)
            # directly above the spine becomes a per-device top-k by
            # (order keys, original row id) — the host then re-applies
            # the tiny Sort/Limit over exactly those k rows, so the
            # result is bit-identical to the single-chip path while
            # only k*n_dev rows ever leave the device
            i = ok_from - 1
            sort_keys = None
            if i >= 0 and isinstance(chain[i], lp.Sort):
                sort_keys = list(chain[i].keys)
                i -= 1
            if i >= 0 and isinstance(chain[i], lp.Limit) and \
                    chain[i].n and int(chain[i].n) > 0:
                self._tail = (sort_keys, int(chain[i].n))
        if not isinstance(spine, lp.Aggregate) and \
                self._tail is None and not self._has_win and not any(
                isinstance(nd, (lp.Join, lp.Filter)) or
                (isinstance(nd, lp.Scan) and nd.predicate is not None)
                for nd in spine.walk()):
            # a pass-through row spine (bare scan/project) would shard
            # the fact only to ship every row straight back to the host
            raise DistUnsupported("row spine does no distributed work",
                                  code="NDS306")
        top = plan if spine is not plan else None
        return spine, top

    def _check_agg(self, node: lp.Aggregate) -> None:
        for _, e in node.aggs:
            for sub in e.walk():
                if isinstance(sub, ex.AggExpr):
                    if sub.func not in _AGG_FUNCS:
                        raise DistUnsupported(f"agg {sub.func} on spine",
                                              code="NDS302")
                    if sub.distinct and (isinstance(sub.arg, ex.Star)
                                         or sub.arg is None):
                        raise DistUnsupported("distinct star agg",
                                              code="NDS302")
                    if sub.distinct and node.grouping_sets is not None:
                        # a distinct count at the finest grouping cannot
                        # be re-combined into coarser rollup groups (the
                        # same value can occur under many fine groups)
                        raise DistUnsupported(
                            "distinct agg under grouping sets",
                            code="NDS302")
                if isinstance(sub, ex.WindowExpr):
                    raise DistUnsupported("window inside aggregate",
                                          code="NDS302")

    # -- spine preparation ---------------------------------------------------

    def _evict_stale(self, table: str, col: str) -> None:
        """Drop superseded-version device copies of (table, col) so
        maintenance rounds don't accumulate dead fact copies in HBM."""
        for k in [k for k in self.dev_cache
                  if k[0] == table and k[1] == col]:
            del self.dev_cache[k]

    def _resolve_all(self, p: lp.Plan) -> None:
        for node in p.walk():
            if isinstance(node, lp.Scan) and node.predicate is not None:
                node.predicate = self.np_exec._resolve_subqueries(
                    node.predicate)
            elif isinstance(node, lp.Filter):
                node.condition = self.np_exec._resolve_subqueries(
                    node.condition)
            elif isinstance(node, lp.Project):
                node.exprs = [(n, self.np_exec._resolve_subqueries(e))
                              for n, e in node.exprs]

    def _prepare(self, p: lp.Plan) -> bool:
        """True when `p` contains the sharded scan; resolves broadcast-join
        build sides on the host as it walks."""
        if isinstance(p, lp.Scan):
            if p is self.fact_target:
                self.fact = p
                return True
            return False
        if isinstance(p, lp.Join):
            on_left = self._prepare(p.left)
            on_right = False if on_left else self._prepare(p.right)
            if not (on_left or on_right):
                return False
            kind = p.kind
            if kind not in lowreg.SPMD_SPINE_JOIN_KINDS:
                raise DistUnsupported(f"{kind} join on spine", code="NDS303")
            keys = list(p.keys)
            if not keys:
                raise DistUnsupported("non-equi join on spine", code="NDS304")
            if not on_left:
                if kind in lowreg.SPMD_REDUCIBLE_BUILD_JOIN_KINDS:
                    # this candidate can't continue (the join's output
                    # is the build side), but the probe-side anchor will
                    # take the join with a distributed reduced build —
                    # info, not a warning (see _reduce_build)
                    raise DistUnsupported(
                        f"sharded table on the build side of {kind} join",
                        code="NDS308")
                if kind != "inner":
                    raise DistUnsupported(
                        f"sharded table on the build side of {kind} join",
                        code="NDS303")
                keys = [(r, l) for l, r in keys]
            build_plan = p.right if on_left else p.left
            build = None
            if kind in lowreg.SPMD_REDUCIBLE_BUILD_JOIN_KINDS and not (
                    kind == "nullaware_anti" and p.extra is not None):
                reduced = self._reduce_build(p, keys, build_plan)
                if reduced is not None:
                    build, keys = reduced
            if build is None:
                build = self.np_exec.execute(build_plan)
            probe_exprs = [l for l, _ in keys]
            bvalid = np.ones(build.num_rows, dtype=bool)
            key_parts = []
            key_dicts: List[Optional[np.ndarray]] = []
            fixed_spans: List[Optional[Tuple[int, int]]] = []
            for _, be in keys:
                c = ex.Evaluator(build).eval(be)
                if c.ctype.kind == "string":
                    # string keys join in the BUILD dictionary's code
                    # space; the traced probe translates its own codes
                    # through a static mapping (both dictionaries are
                    # host metadata at trace time) — or uses them
                    # directly when both sides carry the same frozen
                    # global dictionary (_probe_keys identity path)
                    if c.dictionary is None:
                        raise DistUnsupported(
                            "string join key without dictionary (no "
                            "frozen global dict either — see "
                            "DICT_AUDIT.md coverage; transcode the "
                            "warehouse to build the sidecar)",
                            code="NDS307")
                    key_parts.append(c.data.astype(np.int64))
                    key_dicts.append(c.dictionary)
                    fixed_spans.append((0, len(c.dictionary) + 1))
                elif c.ctype.kind in _KEY_KINDS:
                    key_parts.append(c.data.astype(np.int64))
                    key_dicts.append(None)
                    fixed_spans.append(None)
                else:
                    raise DistUnsupported(
                        f"{c.ctype.kind} join key on spine",
                        code="NDS307")
                bvalid &= c.validity()
            bkeys = np.zeros(build.num_rows, dtype=np.int64)
            radices: List[Tuple[int, int]] = []
            bound = 1
            for part, fixed in zip(key_parts, fixed_spans):
                if fixed is not None:
                    lo, span = fixed
                else:
                    lo = int(part.min()) if len(part) else 0
                    hi = int(part.max()) if len(part) else 0
                    span = hi - lo + 2
                bound *= span
                if bound >= 2 ** 62:
                    raise DistUnsupported("composite key domain overflow")
                radices.append((lo, span))
                bkeys = bkeys * span + np.clip(part - lo, 0, span - 1) + 1
            bkeys = np.where(bvalid, bkeys, np.int64(-1))
            order = np.argsort(bkeys, kind="stable")
            skeys = bkeys[order]
            first_valid = int(np.searchsorted(skeys, 0))
            skeys = skeys[first_valid:]
            row_of = order[first_valid:]
            unique = len(np.unique(skeys)) == len(skeys)
            if not unique and kind == "inner" and self._dup_insensitive \
                    and not (set(build.column_names)
                             & self._refs_above_join(p, build_plan)):
                # an expanding inner join none of whose build columns
                # survive past the join itself, feeding a
                # duplicate-insensitive aggregate (pure GROUP BY dedup or
                # min/max/distinct leaves): row multiplicity is
                # irrelevant, so probe existence suffices — run it as a
                # semi join (q37/q82 inventory-expansion shape)
                kind = "semi"
            dup_max = 0
            if not unique:
                if kind == "left":
                    # unmatched-row bookkeeping under expansion not built
                    raise DistUnsupported(
                        "non-unique build keys for left join")
                if kind == "inner":
                    # bounded duplicate EXPANSION: the probe side tiles
                    # d copies per row, copy k matching the k-th
                    # duplicate in the build key run (q72's d1-d2
                    # week_seq join: 7 days per week)
                    _, counts = np.unique(skeys, return_counts=True)
                    dup_max = int(counts.max()) if len(counts) else 0
                    if dup_max > 8:
                        raise DistUnsupported(
                            f"expanding inner join: build key runs too "
                            f"long ({dup_max})")
                elif p.extra is not None:
                    # semi/anti/mark with a residual: probe every
                    # duplicate in the key run (bounded unrolled loop,
                    # q16/q94 self-join EXISTS shape)
                    if kind == "nullaware_anti":
                        raise DistUnsupported(
                            "residual on nullaware anti join")
                    _, counts = np.unique(skeys, return_counts=True)
                    dup_max = int(counts.max()) if len(counts) else 0
                    if dup_max > 32:
                        raise DistUnsupported(
                            f"build key runs too long ({dup_max})")
            # exchange placement: the structural rule is rows-only; the
            # cost advisor (analysis/cost.py, same choose_strategy the
            # static NDS305/NDS601 analysis uses) may demote a
            # byte-heavy under-row-limit build to the shuffle path —
            # demote-only, and only on placement-order-insensitive
            # (aggregate) spines, so results stay bit-identical to
            # NDSTPU_COST=0
            strategy = "shuffle" if build.num_rows > self.broadcast_limit \
                else "broadcast"
            if self.cost_advisor is not None:
                d = self.cost_advisor.decide_join(
                    build_rows=build.num_rows,
                    build_bytes=_table_bytes(build), kind=kind,
                    dup_max=dup_max, order_safe=self._order_safe)
                obs.inc("engine.cost.decisions")
                if d.overrode:
                    obs.inc("engine.cost.overrides")
                self.cost_decisions.append({
                    "kind": kind, "strategy": d.strategy,
                    "structural": d.structural,
                    "build_rows": int(build.num_rows),
                    "build_bytes": _table_bytes(build),
                    "overrode": d.overrode, "reason": d.reason})
                strategy = d.strategy
            if strategy == "shuffle":
                if dup_max and kind == "inner":
                    raise DistUnsupported(
                        "expanding inner join on a shuffle build side")
                sj = self._stage_shuffle_join(
                    p, kind, probe_exprs, radices, skeys, row_of, build,
                    on_left, bool((~bvalid).any()))
                sj.key_dicts = key_dicts
                sj.dup_max = dup_max
                self.joins[id(p)] = sj
            else:
                self.joins[id(p)] = _BroadcastJoin(
                    kind, p.mark, p.extra, probe_exprs, radices, skeys,
                    row_of, build, on_left,
                    build_has_null=bool((~bvalid).any()),
                    build_empty=build.num_rows == 0,
                    key_dicts=key_dicts, dup_max=dup_max)
            return True
        spine = False
        for c in p.children():
            spine = self._prepare(c) or spine
        return spine

    def _refs_above_join(self, p: lp.Join, build_plan: lp.Plan) -> set:
        """Column names referenced anywhere on the spine OUTSIDE the
        given join's build subtree — i.e. the columns that must survive
        past the join.  The join's own build-side keys and residual are
        consumed by the join and excluded."""
        skip = {id(n) for n in build_plan.walk()}
        refs = set(self._agg_refs)

        def collect(e: ex.Expr) -> None:
            refs.update(nd.name for nd in e.walk()
                        if isinstance(nd, ex.ColumnRef))

        for nd in self._row_head.walk():
            if id(nd) in skip:
                continue
            if isinstance(nd, lp.Scan) and nd.predicate is not None:
                collect(nd.predicate)
            elif isinstance(nd, lp.Filter):
                collect(nd.condition)
            elif isinstance(nd, lp.Project):
                for _, e in nd.exprs:
                    collect(e)
            elif isinstance(nd, lp.Join):
                if nd is p:
                    continue   # own keys/extra are consumed here
                for le, re in nd.keys:
                    collect(le)
                    collect(re)
                if nd.extra is not None:
                    collect(nd.extra)
        return refs

    def _reduce_build(self, p: lp.Join, keys, build_plan: lp.Plan):
        """Distributed reduction of an existence-join build side that
        contains a sharded-size fact (q10/q35/q69 EXISTS-over-store_sales
        shape): semi/anti/nullaware_anti/mark joins are insensitive to
        build-side row multiplicity, so instead of executing the whole
        build subtree on host numpy, a CHILD spine groups it by the join
        keys (plus any residual-referenced build columns) over the mesh
        and only the distinct tuples come back to broadcast.  Returns
        (reduced_build_table, rewritten_keys) or None to keep the host
        path (status quo) — any child failure degrades, never errors."""
        if not any(isinstance(n, lp.Scan) and n.table in self.catalog and
                   self.catalog.get(n.table).num_rows >= self.threshold
                   for n in build_plan.walk()):
            return None
        group = [(f"__bk{i}", be) for i, (_pe, be) in enumerate(keys)]
        if p.extra is not None:
            names = _output_names(build_plan, self.catalog)
            if names is None:
                return None
            used = {nd.name for nd in p.extra.walk()
                    if isinstance(nd, ex.ColumnRef)}
            group += [(c, ex.ColumnRef(c)) for c in sorted(used
                                                           & set(names))]
        bplan = lp.Aggregate(build_plan, group, [], None)
        child = DistributedPlanExecutor(
            self.catalog, self.mesh, self.threshold,
            self.broadcast_limit, self.dev_cache,
            chunk_rows=self.chunk_rows,
            prefetch_depth=self.prefetch_depth,
            cost_advisor=self.cost_advisor)
        try:
            reduced = child.execute_plan(bplan)
        except (DistUnsupported, Unsupported) as e:
            code = getattr(e, "code", None)
            if code:
                self.attempt_codes.append(code)
            self.attempt_codes += child.attempt_codes
            return None
        self.attempt_codes += child.attempt_codes
        self.cost_decisions += child.cost_decisions
        self.build_reduced.append((p.kind, reduced.num_rows))
        obs.inc("engine.spmd.build_reduce")
        if self.cost_advisor is not None:
            obs.inc("engine.cost.decisions")
            self.cost_decisions.append({
                "kind": p.kind, "strategy": "build-reduce",
                "structural": "build-reduce",
                "build_rows": int(reduced.num_rows),
                "build_bytes": _table_bytes(reduced),
                "overrode": False,
                "reason": "existence build reduced to distinct key "
                          "tuples distributed"})
        new_keys = [(pe, ex.ColumnRef(f"__bk{i}"))
                    for i, (pe, _be) in enumerate(keys)]
        return reduced, new_keys

    def _stage_shuffle_join(self, p: lp.Join, kind: str, probe_exprs,
                            radices, skeys: np.ndarray, row_of: np.ndarray,
                            build: Table, on_left: bool,
                            build_has_null: bool) -> _ShuffleJoin:
        """Hash-partition the (too-large-to-broadcast) build side across
        devices by the same splitmix64 bucket hash the traced probe
        shuffle uses; each partition is sorted by key for a local
        searchsorted probe, and build columns are gathered into
        partition order so the probe position indexes them directly."""
        from ndstpu.parallel import exchange
        nd = self.n_dev
        dest = (exchange.mix64_np(skeys.astype(np.uint64))
                % np.uint64(nd)).astype(np.int64)
        order = np.lexsort((skeys, dest))
        counts = np.bincount(dest, minlength=nd)
        part_cap = max(int(counts.max()) if len(skeys) else 0, 1)
        offs = np.concatenate([[0], np.cumsum(counts)])
        within = np.arange(len(skeys)) - offs[dest[order]]
        slot = dest[order] * part_cap + within
        keys_flat = np.full(nd * part_cap, _DEAD_KEY, np.int64)
        keys_flat[slot] = skeys[order]
        rowsel = row_of[order]
        cols_flat: Dict[str, tuple] = {}
        for name in build.column_names:
            c = build.column(name)
            data = np.zeros(nd * part_cap, c.data.dtype)
            valid = np.zeros(nd * part_cap, bool)
            data[slot] = c.data[rowsel]
            valid[slot] = c.validity()[rowsel]
            cols_flat[name] = (data, valid, c.ctype, c.dictionary)
        return _ShuffleJoin(
            kind, p.mark, p.extra, probe_exprs, radices, on_left,
            build_has_null, build.num_rows == 0, part_cap, keys_flat,
            cols_flat)

    # -- spine execution -----------------------------------------------------

    def _run_spine(self, spine: lp.Plan) -> Table:
        agg = spine if isinstance(spine, lp.Aggregate) else None
        row_head = agg.child if agg is not None else spine
        if not self._prepared:
            # host-side join staging runs ONCE per plan: skew retries
            # re-enter only to re-trace with a larger bucket slack
            with obs.span("spine_stage", cat="plan-node"):
                self._run_spine_stage(row_head, agg)
        return self._run_spine_traced(spine, agg, row_head)

    def _run_spine_stage(self, row_head, agg) -> None:
        if True:
            self._resolve_all(row_head)
            if agg is not None:
                for _, e in agg.aggs + agg.group_by:
                    for sub in e.walk():
                        if isinstance(sub, ex.SubqueryExpr):
                            raise DistUnsupported(
                                "subquery above row spine")
            # duplicate row multiplicity is invisible to the spine's
            # aggregate when every leaf is min/max or DISTINCT (or the
            # aggregate is a pure GROUP BY dedup) — _prepare may then
            # demote expanding inner joins to semi joins
            self._dup_insensitive = agg is not None and all(
                a.func in ("min", "max") or a.distinct
                for a in self._agg_leaves(agg))
            # an aggregate spine combines partials key-wise, so exchange
            # placement cannot change the observable result; a row spine
            # emits rows in placement order, so the cost advisor must
            # not re-place its joins (bit-identical vs NDSTPU_COST=0)
            self._order_safe = agg is not None
            self._row_head = row_head
            self._agg_refs = set()
            if agg is not None:
                for _, e in agg.aggs + agg.group_by:
                    self._agg_refs |= {
                        nd.name for nd in e.walk()
                        if isinstance(nd, ex.ColumnRef)}
            self._prepare(row_head)
            if (self._tail is not None or self._has_win) and any(
                    getattr(j, "dup_max", 0) and j.kind == "inner"
                    for j in self.joins.values()):
                # row ids number the pre-expansion fact rows; an
                # expanding inner join duplicates them, breaking the
                # deterministic tail/window tiebreak
                raise DistUnsupported(
                    "expanding inner join under a row-id tail/window")
            self._prepared = True

    def _run_spine_traced(self, spine: lp.Plan, agg, row_head) -> Table:
        if self.fact is None:
            raise DistUnsupported("no sharded scan on spine")
        fact_table = self.catalog.get(self.fact.table)

        cols = self.fact.columns
        names = list(cols) if cols is not None else \
            list(fact_table.column_names)
        if not names:
            names = fact_table.column_names[:1]
        n = fact_table.num_rows
        agg_leaves = self._agg_leaves(agg) if agg is not None else []
        has_distinct = any(a.distinct for a in agg_leaves)
        # out-of-core: stream the fact through the device chunk by chunk
        # (one compiled program, per-chunk partials combined on the host
        # exactly like union branches).  DISTINCT needs all rows of a
        # group in one program, so it keeps the resident path.
        # windows need every row of a partition resident in one program
        # (the colocating exchange is per-launch), so they disable
        # chunking; device tails chunk fine (per-chunk top-k supersets)
        chunk_rows, depth = self._resolve_stream(fact_table, names, n)
        chunked = (chunk_rows is not None and n > chunk_rows
                   and not has_distinct and not self._has_win)
        # shard-major streaming geometry: device d owns the contiguous
        # fact rows [d*shard_rows, (d+1)*shard_rows) and launch c
        # streams the shard-local window [c*m, c*m+m) from every shard
        # at once — each device only ever sees its own shard's chunks,
        # and its scan stays a sequential read over its shard.
        # Unchunked degenerates to m == shard_rows, one launch.
        shard_rows = -(-max(n, 1) // self.n_dev)
        m = min(max(-(-chunk_rows // self.n_dev), 1), shard_rows) \
            if chunked else shard_rows
        padded = m * self.n_dev
        n_launches = -(-shard_rows // m) if chunked else 1
        version = getattr(self.catalog, "versions", {}).get(
            self.fact.table)
        row_sh = NamedSharding(self.mesh, P(SHARD_AXIS))

        metas = [(name, fact_table.column(name).ctype,
                  fact_table.column(name).dictionary) for name in names]
        self._fact_metas = metas

        if chunked:
            fact_args = self._build_stream(fact_table, names, n,
                                           shard_rows, m, padded,
                                           n_launches, depth, row_sh)
        else:
            def fact_args(ci: int) -> list:
                args = []
                for name in names:
                    c = fact_table.column(name)
                    ckey = (self.fact.table, name, version, padded)
                    ent = self.dev_cache.get(ckey)
                    if ent is None:
                        self._evict_stale(self.fact.table, name)
                        data = np.zeros(padded, dtype=c.data.dtype)
                        data[:n] = c.data
                        valid = np.zeros(padded, dtype=bool)
                        valid[:n] = c.validity()
                        ent = (jax.device_put(data, row_sh),
                               jax.device_put(valid, row_sh))
                        self.dev_cache[ckey] = ent
                    args += [ent[0], ent[1]]
                akey = (self.fact.table, "__alive__", version, padded)
                al = self.dev_cache.get(akey)
                if al is None:
                    self._evict_stale(self.fact.table, "__alive__")
                    alive = np.zeros(padded, dtype=bool)
                    alive[:n] = True
                    al = jax.device_put(alive, row_sh)
                    self.dev_cache[akey] = al
                args.append(al)
                return args

        self._fact_args_fn = fact_args
        dev_args = fact_args(0)
        # where the fact really lives: devices holding a shard of its
        # first column, by platform (a 4-chip run must read 4 / tpu)
        placed = dev_args[0].sharding.device_set
        obs.annotate(spmd_fact_placement=f"{len(placed)}x"
                     f"{next(iter(placed)).platform}")

        # shuffle-join build partitions ride in as extra sharded args
        # (closure constants would be replicated on every device)
        for sj in self.joins.values():
            if not isinstance(sj, _ShuffleJoin):
                continue
            sj.arg_start = len(dev_args)
            sj.n_args = 1 + 2 * len(sj.cols_flat)
            # cached on the join object (skew retries re-enter here) —
            # NOT in the shared dev_cache, whose id()-keyed entries could
            # alias a recycled object id from a dead executor
            dev = getattr(sj, "_dev", None)
            if dev is None:
                staged = [sj.keys_flat] + [
                    a for (d, v, _, _) in sj.cols_flat.values()
                    for a in (d, v)]
                dev = sj._dev = [jax.device_put(a, row_sh)
                                 for a in staged]
                # the device copies are the only ones read from here on;
                # drop the host staging arrays (a whole padded build side)
                # but keep the per-column (ctype, dictionary) metadata
                sj.keys_flat = None
                sj.cols_flat = {nm: (None, None, ct, dic)
                                for nm, (_d, _v, ct, dic)
                                in sj.cols_flat.items()}
            dev_args += dev
        # shard-local launch offset: a tiny replicated scalar traced
        # LAST (so the sharded fact/shuffle arg indices stay stable)
        # that gives every launch its true global row ids
        dev_args.append(np.int64(0))
        n_args = len(dev_args)
        n_fact_args = 2 * len(names) + 1

        # chunked row-mode launches interleave shards, so they also
        # need the global id to restore single-chip row order host-side
        need_rowid = self._tail is not None or self._has_win \
            or (chunked and agg is None)
        self._emit_rowid = chunked

        def body(*args):
            self._cur_args = args
            self._drop_terms = []
            nf = len(metas)
            col_args, alive_arg = args[:2 * nf], args[2 * nf]
            chunk_off = args[-1]
            dcols = {}
            for i, (name, ctype, dictionary) in enumerate(metas):
                dcols[name] = DCol(col_args[2 * i], col_args[2 * i + 1],
                                   ctype, dictionary)
            if need_rowid:
                # global pre-join row position: the deterministic
                # tiebreak that makes the device tail / sharded window
                # bit-identical to the single-chip stable sort.  Device
                # d's launch c covers global rows d*shard_rows +
                # chunk_off + [0, m); unchunked, chunk_off == 0 and
                # shard_rows == m
                base = (lax.axis_index(SHARD_AXIS).astype(jnp.int64)
                        * shard_rows + chunk_off
                        + lax.iota(jnp.int64, m))
                dcols["__rowid__"] = DCol(base, jnp.ones(m, bool), INT64)
            dt = self._exec(row_head, DTable(dcols, alive_arg))
            if has_distinct:
                # DISTINCT needs every row of a group on one device:
                # exchange rows by group-key hash so the local sort-dedup
                # in _leaf_partial is globally exact (the Spark distinct
                # exchange as an ICI all_to_all)
                dt = self._colocate_by_group(agg, dt)
            dropped = sum(self._drop_terms) if self._drop_terms \
                else jnp.int64(0)
            if agg is None:
                if self._tail is not None:
                    return self._device_tail(dt), dropped
                out_names = [nm for nm in dt.column_names
                             if nm != "__rowid__"]
                if chunked:
                    # carried through so _run_chunks can restore the
                    # global row order after the shard-interleaved
                    # launch concat (then dropped host-side)
                    out_names.append("__rowid__")
                self._row_meta = [(nm, dt.columns[nm].ctype,
                                   dt.columns[nm].dictionary)
                                  for nm in out_names]
                flat = []
                for nm in out_names:
                    flat += [dt.columns[nm].data, dt.columns[nm].valid]
                return tuple(flat) + (dt.alive,), dropped
            return self._agg_partials(agg, agg_leaves, dt), dropped

        row_spec = P(SHARD_AXIS) if (agg is None and self._tail is None) \
            else P()
        sharded = shard_map(
            body, mesh=self.mesh,
            in_specs=tuple(P(SHARD_AXIS) for _ in range(n_args - 1))
            + (P(),),
            out_specs=(row_spec, P()),
            check_vma=False)
        self._agg_ctx = (agg, agg_leaves)
        self._compiled_fn = jax.jit(sharded)
        self._dev_args = dev_args
        self._chunk_info = (chunked, n_launches, m, n_fact_args)
        obs.inc("engine.spmd.traces")
        if not chunked:
            # jit is lazy: this first call pays shard_map trace + XLA
            # compile, then runs — a mixed region, so it is left in the
            # statement's execute self-time rather than a cost bucket
            with obs.span("spine_trace_exec", cat="plan-node",
                          n_args=n_args):
                out = jax.device_get(self._compiled_fn(*dev_args))
            return self._post_spine(out)
        with obs.span("spine_trace_exec", cat="plan-node", chunked=True):
            return self._run_chunks()

    def _resolve_stream(self, fact_table, names, n):
        """Resolve the session's chunk_rows / prefetch_depth setting to
        concrete values for this fact.  ``"auto"`` defers to the
        spill-aware planner (engine/memplan.py): chunk size and staging
        depth come from the device memory budget and this fact's
        scanned row width, not a hand-tuned constant."""
        if self.chunk_rows == "auto":
            from ndstpu.engine import memplan
            from ndstpu.io import gdict
            bpr = memplan.row_bytes(
                [fact_table.column(nm).data.dtype.itemsize
                 for nm in names])
            # string codes stream per chunk, but their frozen
            # dictionaries ride every device whole-query — carve their
            # bytes out of the budget before sizing chunks
            dict_bytes = sum(
                gdict.dictionary_nbytes(fact_table.column(nm).dictionary)
                for nm in names
                if fact_table.column(nm).ctype.kind == "string")
            max_depth = self.prefetch_depth \
                if self.prefetch_depth is not None \
                else memplan.DEFAULT_MAX_DEPTH
            # cost-model working set: broadcast builds ride every device
            # whole-query (shuffle builds are partitioned 1/n_dev and
            # already inside COMPUTE_MULT slack) — carve their bytes out
            # so fat replicated builds buy smaller chunks, not spills
            resident = 0
            if self.cost_advisor is not None:
                resident = sum(
                    _table_bytes(j.build) for j in self.joins.values()
                    if isinstance(j, _BroadcastJoin))
            plan = memplan.plan_stream(n, bpr, self.n_dev,
                                       max_depth=max_depth,
                                       dict_bytes=dict_bytes,
                                       resident_bytes=resident)
            obs.annotate(stream_plan=plan.describe())
            obs.set_gauge("engine.stream.chunk_rows",
                          plan.chunk_rows or 0)
            obs.set_gauge("engine.stream.prefetch_depth",
                          plan.prefetch_depth)
            return plan.chunk_rows, plan.prefetch_depth
        depth = self.prefetch_depth if self.prefetch_depth is not None \
            else 2
        return self.chunk_rows, max(int(depth), 0)

    def _build_stream(self, fact_table, names, n, shard_rows, m,
                      padded, n_launches, depth, row_sh):
        """Wire the streaming pipeline for a chunked fact and return
        the per-launch device-arg function.

        Three overlapped stages (docs/ARCHITECTURE.md "Streaming
        out-of-core pipeline"): a :class:`~ndstpu.io.loader.ChunkScanPool`
        reads + decodes shard segments ahead on worker threads (from
        the catalog's registered :class:`~ndstpu.io.loader.ChunkSource`
        when one exists, else a ``TableChunkSource`` view of the
        resident copy, so both paths exercise the same machinery); a
        :class:`~ndstpu.engine.jaxexec.ChunkPrefetcher` stages the
        decoded chunks into HBM with ``jax.device_put`` on a background
        thread while the current launch computes.  ``depth == 0``
        collapses both to synchronous streaming."""
        from ndstpu.engine.jaxexec import ChunkPrefetcher
        from ndstpu.io import loader as io_loader
        source = getattr(self.catalog, "streams", {}).get(
            self.fact.table)
        if source is not None and (
                source.num_rows != n
                or not set(names) <= set(getattr(source, "columns", []))):
            source = None   # stale or partial source: resident scan
        if source is None:
            source = io_loader.TableChunkSource(
                fact_table, self.fact.table, names)

        def host_chunk(ci: int) -> list:
            """Scan/decode launch ci into padded shard-major host
            arrays: [data, valid] per column + the alive mask."""
            bufs = [(np.zeros(padded,
                              dtype=fact_table.column(nm).data.dtype),
                     np.zeros(padded, dtype=bool)) for nm in names]
            alive = np.zeros(padded, dtype=bool)
            off = ci * m
            for d in range(self.n_dev):
                g0 = d * shard_rows + off
                cnt = max(min(m, shard_rows - off, n - g0), 0)
                if cnt <= 0:
                    continue
                lo = d * m
                payload = source.read(g0, cnt)
                for (data, valid), nm in zip(bufs, names):
                    data[lo:lo + cnt] = payload[nm][0]
                    valid[lo:lo + cnt] = payload[nm][1]
                alive[lo:lo + cnt] = True
            flat = [a for pair in bufs for a in pair]
            flat.append(alive)
            return flat

        old_pool = getattr(self, "_stream_pool", None)
        if old_pool is not None:   # superseded by a slack retry retrace
            old_pool.close()
        old_pf = getattr(self, "_prefetch", None)
        if old_pf is not None:
            old_pf.close()
        # scan runs one chunk further ahead than staging so the
        # prefetcher's device_put never waits on a cold read
        pool = io_loader.ChunkScanPool(
            host_chunk, list(range(n_launches)),
            workers=min(max(depth + 1, 1), 4),
            depth=depth + 1 if depth else 0)
        pool.start_ahead()   # cold reads overlap whole-query compile
        self._stream_pool = pool
        self._stream_fresh = True

        def stage(ci: int) -> list:
            host = pool.get(ci)
            nbytes = sum(a.nbytes for a in host)
            devs = [jax.device_put(a, row_sh) for a in host]
            obs.inc("engine.h2d.bytes", nbytes)
            return devs

        self._prefetch = ChunkPrefetcher(stage, n_launches, depth=depth)
        return self._prefetch.get

    def _run_chunks(self):
        """Out-of-core execution: stream fact chunks through the one
        compiled spine program; combine per-chunk outputs on the host
        (aggregate partials re-group like union branches, row-mode
        chunks concatenate)."""
        _chunked, n_launches, m, n_fact_args = self._chunk_info
        shuffle_args = self._dev_args[n_fact_args:-1]
        agg, leaves = self._agg_ctx
        if getattr(self, "_stream_fresh", False):
            self._stream_fresh = False
        else:
            # repeat pass over a cached chunked query: rewind the scan
            # window and staging ring (chunk 0's device args persist
            # from the first pass, so pre-stage from chunk 1)
            pool = getattr(self, "_stream_pool", None)
            if pool is not None:
                pool.reset(next_idx=1)
            pf = getattr(self, "_prefetch", None)
            if pf is not None:
                pf.reset(next_i=1)
        outs = []
        dropped_total = 0
        t_wall = time.monotonic()
        for ci in range(n_launches):
            args = (self._dev_args[:n_fact_args] if ci == 0
                    else self._fact_args_fn(ci))
            off = np.int64(ci * m)
            out, dropped = jax.device_get(
                self._compiled_fn(*(list(args) + shuffle_args + [off])))
            dropped_total += int(np.asarray(dropped))
            outs.append(out)
            if dropped_total:
                break   # the whole pass is discarded and retried
        obs.inc("engine.stream.execute_s", time.monotonic() - t_wall)
        self._last_dropped = dropped_total
        if dropped_total:
            return None   # _run_spine_retrying re-traces with more slack
        for out in outs:
            self._note_host_gather(out)
        if agg is None:
            tables = []
            for out in outs:
                flat, alive_out = out[:-1], np.asarray(out[-1])
                sel = np.nonzero(alive_out)[0]
                cols = {}
                for i, (name, ctype, dictionary) in enumerate(
                        self._row_meta):
                    data = np.asarray(flat[2 * i])[sel]
                    valid = np.asarray(flat[2 * i + 1])[sel]
                    cols[name] = Column(
                        data, ctype, None if valid.all() else valid,
                        dictionary)
                tables.append(Table(cols))
            result = Table.concat(tables)
            rid = result.columns.get("__rowid__")
            if rid is not None:
                # shard-major launches interleave the shards' windows;
                # the threaded global row id restores the single-chip
                # row order exactly (stable: duplicates from expanding
                # joins keep their in-device expansion order)
                result = result.gather(
                    np.argsort(rid.data, kind="stable"))
                result.columns.pop("__rowid__", None)
            return result
        parts = [(*self._unpack_agg(out), list(self._leaf_meta))
                 for out in outs]
        if self._emit_partials:
            # one "branch" worth of partials: chunks simply concatenate
            # (the union combiner re-groups duplicate keys anyway)
            kcs = [p[0] for p in parts]
            merged = Table.concat([Table(kc) for kc in kcs]) \
                if agg.group_by else Table({})
            key_cols = dict(merged.columns)
            leaf_parts = [
                [np.concatenate([p[1][li][pi] for p in parts])
                 for pi in range(len(parts[0][1][li]))]
                for li in range(len(leaves))]
            return key_cols, leaf_parts
        return self._finalize_union(agg, leaves, parts)

    def _post_spine(self, out):
        out, dropped = out
        self._last_dropped = int(np.asarray(dropped))
        if self._last_dropped:
            # truncated by a shuffle bucket overflow: the retry loop
            # discards this result, skip the host finalize
            return None
        self._note_host_gather(out)
        agg, agg_leaves = self._agg_ctx
        if agg is not None:
            key_cols, leaf_parts = self._unpack_agg(out)
            if self._emit_partials:
                return key_cols, leaf_parts
            return self._finalize_from(agg, agg_leaves, key_cols,
                                       leaf_parts)
        flat, alive_out = out[:-1], np.asarray(out[-1])
        sel = np.nonzero(alive_out)[0]
        res = {}
        for i, (name, ctype, dictionary) in enumerate(self._row_meta):
            data = np.asarray(flat[2 * i])[sel]
            valid = np.asarray(flat[2 * i + 1])[sel]
            res[name] = Column(data, ctype,
                               None if valid.all() else valid, dictionary)
        return Table(res)

    # -- traced operators ----------------------------------------------------

    def _exec(self, p: lp.Plan, dt: DTable) -> DTable:
        if isinstance(p, lp.Scan):
            if p.predicate is not None:
                mask = JEval(dt).predicate(p.predicate)
                dt = DTable(dt.columns, dt.alive & mask)
            return dt
        if isinstance(p, lp.SubqueryAlias):
            dt = self._exec(p.child, dt)
            if p.column_aliases:
                cols = dict(dt.columns)
                rid = cols.pop("__rowid__", None)
                cols = dict(zip(p.column_aliases, cols.values()))
                if rid is not None:
                    cols["__rowid__"] = rid
                dt = DTable(cols, dt.alive)
            return dt
        if isinstance(p, lp.Filter):
            dt = self._exec(p.child, dt)
            mask = JEval(dt).predicate(p.condition)
            return DTable(dt.columns, dt.alive & mask)
        if isinstance(p, lp.Project):
            dt = self._exec(p.child, dt)
            evl = JEval(dt)
            out = {n: evl.eval(e) for n, e in p.exprs}
            rid = dt.columns.get("__rowid__")
            if rid is not None and "__rowid__" not in out:
                out["__rowid__"] = rid
            return DTable(out, dt.alive)
        if isinstance(p, lp.Window):
            dt = self._exec(p.child, dt)
            return self._exec_window_dist(p, dt)
        if isinstance(p, lp.Join):
            bj = self.joins.get(id(p))
            if bj is None:
                raise DistUnsupported("unprepared join on spine")
            dt = self._exec(p.left if bj.spine_left else p.right, dt)
            if isinstance(bj, _ShuffleJoin):
                return self._shuffle_join(bj, dt)
            return self._broadcast_join(bj, dt)
        raise DistUnsupported(f"{type(p).__name__} in traced spine")

    def _probe_keys(self, evl: JEval, key_exprs, radices, cap,
                    key_dicts=None):
        """Radix-encode the probe-side key parts into one int64 plus
        NULL/out-of-domain masks (shared by broadcast + shuffle joins).
        String parts translate probe dictionary codes into the build
        dictionary's code space via a static (trace-time) mapping."""
        pkey = jnp.zeros(cap, jnp.int64)
        pnull = jnp.zeros(cap, bool)
        in_dom = jnp.ones(cap, bool)
        dicts = key_dicts or [None] * len(radices)
        for e, (lo, span), kd in zip(key_exprs, radices, dicts):
            c = evl.eval(e)
            if kd is not None:
                if c.ctype.kind != "string" or c.dictionary is None:
                    raise DistUnsupported(
                        f"string key against {c.ctype.kind} probe "
                        f"(no shared global dictionary — see "
                        f"DICT_AUDIT.md; transcode the warehouse to "
                        f"build the sidecar)",
                        code="NDS307")
                np_dict = c.dictionary
                if len(kd) == len(np_dict) and \
                        np.array_equal(kd, np_dict):
                    # both sides carry the same frozen code space
                    # (warehouse-wide global dictionary): codes ARE the
                    # key parts, no translation table.  Negative codes
                    # (NULL -1 / translate-miss -2) map out-of-domain.
                    obs.inc("engine.dict.identity_joins")
                    part = jnp.where(
                        c.data >= 0, c.data.astype(jnp.int64),
                        jnp.int64(len(kd)))
                elif len(np_dict) and len(kd):
                    pos = np.searchsorted(kd, np_dict)
                    posc = np.clip(pos, 0, len(kd) - 1)
                    ok = kd[posc] == np_dict
                    mapping = np.where(ok, posc,
                                       np.int64(len(kd))).astype(np.int64)
                    codes = jnp.clip(c.data.astype(jnp.int64), 0,
                                     max(len(np_dict) - 1, 0))
                    part = jnp.asarray(mapping)[codes]
                else:
                    mapping = np.full(max(len(np_dict), 1), len(kd),
                                      np.int64)
                    codes = jnp.clip(c.data.astype(jnp.int64), 0,
                                     max(len(np_dict) - 1, 0))
                    part = jnp.asarray(mapping)[codes]
            elif c.ctype.kind not in _KEY_KINDS:
                raise DistUnsupported(f"{c.ctype.kind} probe key",
                                      code="NDS307")
            else:
                part = c.data.astype(jnp.int64)
            pnull |= ~c.valid
            in_dom &= (part >= lo) & (part < lo + span - 1)
            pkey = pkey * span + jnp.clip(part - lo, 0, span - 1) + 1
        return pkey, pnull, in_dom

    def _shuffle_join(self, sj: _ShuffleJoin, dt: DTable) -> DTable:
        """all_to_all the live spine rows to the device owning their key
        bucket, then probe this device's sorted build partition."""
        from ndstpu.parallel import exchange
        cap = dt.capacity
        pkey, pnull, in_dom = self._probe_keys(
            JEval(dt), sj.probe_key_exprs, sj.radices, cap,
            sj.key_dicts)
        pok = ~pnull & in_dom
        # keyless-but-alive rows (NULL / out-of-domain) stay local: they
        # can't match anywhere but must survive left/anti/mark joins
        my = lax.axis_index(SHARD_AXIS).astype(jnp.int32)
        dest = jnp.where(
            pok,
            (exchange._mix64(pkey) % jnp.uint64(self.n_dev))
            .astype(jnp.int32),
            my)
        bucket_cap = max(16, -(-(cap * self.shuffle_slack) // self.n_dev))
        metas = [(n, c.ctype, c.dictionary) for n, c in dt.columns.items()]
        cols = {}
        for name, c in dt.columns.items():
            cols["d" + name] = c.data
            cols["v" + name] = c.valid
        cols["__pkey"] = pkey
        cols["__pok"] = pok
        cols["__pnull"] = pnull
        shuf, alive, n_dropped = exchange.repartition_by_dest(
            cols, dest, dt.alive, self.n_dev, bucket_cap)
        self._drop_terms.append(n_dropped)
        ncap = self.n_dev * bucket_cap
        dcols = {n: DCol(shuf["d" + n], shuf["v" + n], ct, dic)
                 for n, ct, dic in metas}
        pkey = shuf["__pkey"]
        pnull = shuf["__pnull"]
        pok = shuf["__pok"] & alive
        # local probe: this device's partition slice of the staged args
        sl = self._cur_args[sj.arg_start: sj.arg_start + sj.n_args]
        lkeys = sl[0]
        npart = lkeys.shape[0]
        if sj.dup_max and sj.extra is not None:
            # duplicate keys + residual (semi/anti/mark): probe the
            # whole key run; runs are contiguous inside a partition
            # because staging sorts each partition by key
            start = jnp.searchsorted(lkeys, pkey)
            found = jnp.zeros(ncap, bool)
            for k in range(sj.dup_max):
                posk = jnp.clip(start + k, 0, npart - 1)
                cand = (start + k < npart) & (lkeys[posk] == pkey) & pok
                bc = {}
                for i, (name, (_d, _v, ct, dic)) in enumerate(
                        sj.cols_flat.items()):
                    bc[name] = DCol(sl[1 + 2 * i][posk],
                                    sl[2 + 2 * i][posk] & cand, ct, dic)
                res = JEval(DTable({**dcols, **bc},
                                   alive)).predicate(sj.extra)
                found = found | (cand & res)
            combined = DTable(dcols, alive)
        else:
            pos = jnp.searchsorted(lkeys, pkey)
            posc = jnp.clip(pos, 0, npart - 1)
            found = (lkeys[posc] == pkey) & pok
            bcols: Dict[str, DCol] = {}
            for i, (name, (_d, _v, ct, dic)) in enumerate(
                    sj.cols_flat.items()):
                bcols[name] = DCol(sl[1 + 2 * i][posc],
                                   sl[2 + 2 * i][posc] & found, ct, dic)
            combined = DTable({**dcols, **bcols}, alive)
            if sj.extra is not None:
                found = found & JEval(combined).predicate(sj.extra)
                bcols = {n: DCol(c.data, c.valid & found, c.ctype,
                                 c.dictionary) for n, c in bcols.items()}
                combined = DTable({**dcols, **bcols}, alive)
        if sj.kind == "inner":
            return DTable(combined.columns, alive & found)
        if sj.kind == "left":
            return combined
        if sj.kind == "semi":
            return DTable(dcols, alive & found)
        if sj.kind == "anti":
            return DTable(dcols, alive & ~found)
        if sj.kind == "nullaware_anti":
            if sj.extra is not None:
                raise DistUnsupported("residual on nullaware anti join")
            if sj.build_has_null:   # NOT IN (... NULL ...): never TRUE
                return DTable(dcols, jnp.zeros(ncap, bool))
            if sj.build_empty:      # NOT IN (empty): keep everything
                return DTable(dcols, alive)
            return DTable(dcols, alive & ~found & ~pnull)
        # mark
        out = dict(dcols)
        out[sj.mark] = DCol(found, jnp.ones(ncap, bool), BOOL)
        return DTable(out, alive)

    def _broadcast_join(self, bj: _BroadcastJoin, dt: DTable) -> DTable:
        cap = dt.capacity
        pkey, pnull, in_dom = self._probe_keys(
            JEval(dt), bj.probe_key_exprs, bj.radices, cap,
            bj.key_dicts)
        pvalid = ~pnull & in_dom & dt.alive
        bcols: Dict[str, DCol] = {}
        if len(bj.sorted_keys) == 0:
            # empty build side (a filter left no rows): no matches, and
            # there is nothing to gather from — emit typed NULL columns
            found = jnp.zeros(cap, bool)
            for name in bj.build.column_names:
                c = bj.build.column(name)
                data = jnp.zeros(cap, jnp_dtype(c.ctype))
                bcols[name] = DCol(data, jnp.zeros(cap, bool), c.ctype,
                                   c.dictionary)
            combined = DTable({**dt.columns, **bcols}, dt.alive)
        elif bj.dup_max and bj.kind == "inner":
            # EXPANDING inner join: tile the probe side d times
            # (copy-major: expanded row k*cap+i is probe row i matched
            # against the k-th duplicate in its build key run); dead
            # copies are masked, downstream ops just see a d-times
            # capacity (q72's week_seq join, 7 days per week)
            d = bj.dup_max
            skeys = jnp.asarray(bj.sorted_keys)
            rowof = jnp.asarray(bj.row_of)
            nb = len(bj.sorted_keys)
            start = jnp.searchsorted(skeys, pkey)

            def tile(a):
                return jnp.concatenate([a] * d)

            pos = tile(start) + jnp.repeat(jnp.arange(d), cap)
            posc = jnp.clip(pos, 0, nb - 1)
            cand = (pos < nb) & (skeys[posc] == tile(pkey)) & tile(pvalid)
            bidx = rowof[posc]
            pcols = {n: DCol(tile(c.data), tile(c.valid), c.ctype,
                             c.dictionary)
                     for n, c in dt.columns.items()}
            for name in bj.build.column_names:
                c = bj.build.column(name)
                bcols[name] = DCol(
                    jnp.asarray(c.data)[bidx],
                    jnp.asarray(c.validity())[bidx] & cand,
                    c.ctype, c.dictionary)
            combined = DTable({**pcols, **bcols}, cand)
            if bj.extra is not None:
                cand = cand & JEval(combined).predicate(bj.extra)
                combined = DTable(combined.columns, cand)
            return combined
        elif bj.dup_max and bj.extra is not None:
            # duplicate build keys + residual (semi/anti/mark): probe
            # every candidate in the key run with an unrolled bounded
            # loop (q16/q94 correlated-EXISTS self-join shape)
            skeys = jnp.asarray(bj.sorted_keys)
            rowof = jnp.asarray(bj.row_of)
            nb = len(bj.sorted_keys)
            start = jnp.searchsorted(skeys, pkey)
            found = jnp.zeros(cap, bool)
            for k in range(bj.dup_max):
                posk = jnp.clip(start + k, 0, nb - 1)
                cand = (start + k < nb) & (skeys[posk] == pkey) & pvalid
                bidx_k = rowof[posk]
                bc = {}
                for name in bj.build.column_names:
                    c = bj.build.column(name)
                    bc[name] = DCol(
                        jnp.asarray(c.data)[bidx_k],
                        jnp.asarray(c.validity())[bidx_k] & cand,
                        c.ctype, c.dictionary)
                res = JEval(DTable({**dt.columns, **bc},
                                   dt.alive)).predicate(bj.extra)
                found = found | (cand & res)
            combined = DTable(dt.columns, dt.alive)
        else:
            skeys = jnp.asarray(bj.sorted_keys)
            pos = jnp.searchsorted(skeys, pkey)
            posc = jnp.clip(pos, 0, len(bj.sorted_keys) - 1)
            found = (skeys[posc] == pkey) & pvalid
            bidx = jnp.asarray(bj.row_of)[posc]
            for name in bj.build.column_names:
                c = bj.build.column(name)
                data = jnp.asarray(c.data)[bidx]
                valid = jnp.asarray(c.validity())[bidx] & found
                bcols[name] = DCol(data, valid, c.ctype, c.dictionary)
            combined = DTable({**dt.columns, **bcols}, dt.alive)
            if bj.extra is not None:
                found = found & JEval(combined).predicate(bj.extra)
                bcols = {n: DCol(c.data, c.valid & found, c.ctype,
                                 c.dictionary) for n, c in bcols.items()}
                combined = DTable({**dt.columns, **bcols}, dt.alive)
        if bj.kind == "inner":
            return DTable(combined.columns, dt.alive & found)
        if bj.kind == "left":
            return combined
        if bj.kind == "semi":
            return DTable(dt.columns, dt.alive & found)
        if bj.kind == "anti":
            return DTable(dt.columns, dt.alive & ~found)
        if bj.kind == "nullaware_anti":
            if bj.extra is not None:
                raise DistUnsupported("residual on nullaware anti join")
            if bj.build_has_null:   # NOT IN (... NULL ...): never TRUE
                return DTable(dt.columns, jnp.zeros(cap, bool))
            if bj.build_empty:      # NOT IN (empty): keep everything
                return DTable(dt.columns, dt.alive)
            return DTable(dt.columns, dt.alive & ~found & ~pnull)
        # mark
        cols = dict(dt.columns)
        cols[bj.mark] = DCol(found, jnp.ones(cap, bool), BOOL)
        return DTable(cols, dt.alive)

    # -- distributed aggregation ---------------------------------------------

    def _colocate_by_group(self, agg: lp.Aggregate, dt: DTable) -> DTable:
        """Repartition live rows so every row of one group lands on the
        device owning hash(group keys)."""
        return self._colocate_by_keys([e for _, e in agg.group_by], dt)

    def _colocate_by_keys(self, key_exprs, dt: DTable) -> DTable:
        """Repartition live rows so every row sharing the key tuple lands
        on the device owning hash(keys) — the group/partition-colocating
        all_to_all exchange (DISTINCT aggregation and sharded windows).
        Empty keys collapse everything onto device 0 (a global window
        partition); overflowed receive buckets report via _drop_terms and
        the slack-doubling retry makes the exchange lossless."""
        from ndstpu.parallel import exchange
        evl = JEval(dt)
        cap = dt.capacity
        keys = [_key_i64(evl.eval(e), dt.alive) for e in key_exprs]
        h = jnp.zeros(cap, jnp.uint64)
        for k in keys:
            # float64 group keys keep their float encoding in _key_i64;
            # hash their bits via int64 round-trip is unavailable on TPU,
            # so quantize through int64 cast (collisions only merge
            # devices, never corrupt results — grouping re-checks keys)
            ki = k.astype(jnp.int64) if k.dtype != jnp.int64 else k
            h = exchange._mix64(h ^ exchange._mix64(ki.astype(jnp.uint64)))
        dest = (h % jnp.uint64(self.n_dev)).astype(jnp.int32) \
            if keys else jnp.zeros(cap, jnp.int32)
        bucket_cap = max(16, -(-(cap * self.shuffle_slack) // self.n_dev))
        metas = [(n, c.ctype, c.dictionary)
                 for n, c in dt.columns.items()]
        cols = {}
        for name, c in dt.columns.items():
            cols["d" + name] = c.data
            cols["v" + name] = c.valid
        shuf, alive, n_dropped = exchange.repartition_by_dest(
            cols, dest, dt.alive, self.n_dev, bucket_cap)
        self._drop_terms.append(n_dropped)
        return DTable({n: DCol(shuf["d" + n], shuf["v" + n], ct, dic)
                       for n, ct, dic in metas}, alive)

    # -- sharded windows + device tail ---------------------------------------

    def _exec_window_dist(self, p: lp.Window, dt: DTable) -> DTable:
        """Sharded window functions: colocate rows by partition-key hash
        (one all_to_all per distinct PARTITION BY list), then mirror the
        single-chip _window_column per device with the original row id
        as the deterministic ranking tiebreak (the exchange scrambles
        local row order)."""
        groups: Dict[str, list] = {}
        gorder: List[str] = []
        for name, e in p.exprs:
            if not isinstance(e, ex.WindowExpr):
                raise DistUnsupported("non-window expr in Window node")
            gk = repr(tuple(e.partition_by))
            if gk not in groups:
                groups[gk] = []
                gorder.append(gk)
            groups[gk].append((name, e))
        for gk in gorder:
            exprs = groups[gk]
            dt = self._colocate_by_keys(list(exprs[0][1].partition_by), dt)
            cols = dict(dt.columns)
            for name, w in exprs:
                cols[name] = self._window_column_dist(dt, w)
            dt = DTable(cols, dt.alive)
        return dt

    def _window_column_dist(self, dt: DTable, w: ex.WindowExpr) -> DCol:
        """jaxexec._window_column mirror after the partition-colocating
        exchange: every row of a partition is resident on this device, so
        the local segment ops are globally exact.  Ranking sorts append
        __rowid__ as the last sort key (replays the original row order
        for ties); rank/dense_rank tie detection still looks at the
        ORDER BY keys only.  Running frames and subquery-bearing exprs
        never reach here (lowering.spmd_window_ok)."""
        cap = dt.capacity
        evl = JEval(dt)
        if w.partition_by:
            pcols = [evl.eval(e) for e in w.partition_by]
            pkeys = [_key_col(c, dt.alive) for c in pcols]
        else:
            pkeys = [jnp.where(dt.alive, 0, 1).astype(jnp.int32)]
        pid, _, _ = _group_ids(pkeys)
        okeys = []
        for e, asc in w.order_by:
            c = evl.eval(e)
            okeys.append(self._dev_order_key(evl, c, asc, None))
        if w.func in ("row_number", "rank", "dense_rank"):
            ridk = jnp.where(dt.alive, dt.columns["__rowid__"].data,
                             _DEAD_KEY)
            order = _lexsort_order([pid] + okeys + [ridk])
            idx = lax.iota(jnp.int32, cap)
            pid_s = pid[order]
            newpart = jnp.ones(cap, bool)
            if cap > 1:
                newpart = newpart.at[1:].set(pid_s[1:] != pid_s[:-1])
            part_start = lax.cummax(jnp.where(newpart, idx, 0))
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
                last_nontie = lax.cummax(jnp.where(~tie, idx, 0))
                ranks = pos_in_part[last_nontie] + 1
            else:
                incr = jnp.where(newpart, 0, (~tie).astype(jnp.int32))
                csum = jnp.cumsum(incr)
                base = lax.cummax(jnp.where(newpart, csum, 0))
                ranks = csum - base + 1
            return DCol(ranks[inv].astype(jnp.int64),
                        jnp.ones(cap, bool), INT64)
        if w.order_by:
            raise DistUnsupported("running window frame on spine")
        gid = pid
        if w.func == "count" and (w.arg is None or
                                  isinstance(w.arg, ex.Star)):
            cnt = jax.ops.segment_sum(dt.alive.astype(jnp.int32), gid,
                                      num_segments=cap)
            return DCol(cnt[gid].astype(jnp.int64), jnp.ones(cap, bool),
                        INT64)
        arg = evl.eval(w.arg)
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
                return DCol(tot[gid], got,
                            columnar.decimal(38, arg.ctype.scale))
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
        raise DistUnsupported(f"window {w.func} on spine")

    def _dev_order_key(self, evl: JEval, c: DCol, asc: bool,
                       nulls_first) -> jnp.ndarray:
        """jaxexec._order_key mirror for traced spine sort keys (floats
        order via +/-inf, narrow ints in int32, else int64; NULLs follow
        nulls_first defaulting to the ascending side; dead rows strictly
        last)."""
        if nulls_first is None:
            nulls_first = asc
        alive = evl.t.alive
        if c.ctype.kind == "float64":
            data = c.data.astype(jnp.float64)
            key = data if asc else -data
            key = jnp.where(c.valid, key,
                            -jnp.inf if nulls_first else jnp.inf)
            return jnp.where(alive, key, jnp.inf)
        if _narrow_span(c) is not None:
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

    def _device_tail(self, dt: DTable):
        """On-device top-k tail: per-device top `limit` rows by
        (ORDER BY keys, original row id), then a k-row all_gather — the
        host fetches n_dev*k rows instead of the whole sharded relation
        and replays the suffix Sort/Limit over them.  The host's stable
        sort keeps exactly the (okeys, rowid)-least rows, which is the
        set selected here, so the differential stays bit-identical; a
        bare LIMIT degenerates to rowid order = original row order."""
        sort_keys, limit = self._tail
        cap = dt.capacity
        evl = JEval(dt)
        okeys = []
        for entry in (sort_keys or []):
            e, asc = entry[0], entry[1]
            nf = entry[2] if len(entry) > 2 else None
            try:
                c = evl.eval(e)
            except Unsupported as u:
                raise DistUnsupported(f"tail sort key: {u}", code=u.code)
            okeys.append(self._dev_order_key(evl, c, asc, nf))
        rid = dt.columns["__rowid__"].data
        ridk = jnp.where(dt.alive, rid, _DEAD_KEY)
        k = min(limit, cap)
        order = _lexsort_order(okeys + [ridk])[:k]

        def gather(x):
            obs.inc("exchange.collective.calls")
            obs.inc("exchange.all_gather.calls")
            obs.inc("exchange.shuffle_bytes",
                    int(x.size * x.dtype.itemsize
                        * self.n_dev * (self.n_dev - 1)))
            return lax.all_gather(x, SHARD_AXIS).reshape(
                (self.n_dev * k,) + x.shape[1:])

        # dead rows carry the dead-last order keys, so a device with
        # fewer than k live rows pads the gather with rows that sort
        # after every live one and are masked out host-side
        g_alive = gather(dt.alive[order])
        g_okeys = [gather(kk[order]) for kk in okeys]
        g_rid = gather(ridk[order])
        forder = _lexsort_order(g_okeys + [g_rid])[
            :min(limit, self.n_dev * k)]
        names = [nm for nm in dt.column_names if nm != "__rowid__"]
        if getattr(self, "_emit_rowid", False):
            # chunked tails: per-launch top-k supersets interleave the
            # shards, so the host combine needs the global row id to
            # restore original order before _finish replays Sort/Limit
            names.append("__rowid__")
        self._row_meta = [(nm, dt.columns[nm].ctype,
                           dt.columns[nm].dictionary) for nm in names]
        flat = []
        for nm in names:
            c = dt.columns[nm]
            flat += [gather(c.data[order])[forder],
                     gather(c.valid[order])[forder]]
        return tuple(flat) + (g_alive[forder],)

    @staticmethod
    def _note_host_gather(out) -> None:
        """Ledger evidence for the tail work: bytes actually fetched
        device->host per spine launch (whole row relations before this
        PR; agg partial tuples or a device tail's k-row result now)."""
        total = 0
        for a in out:
            total += int(np.asarray(a).nbytes)
        obs.inc("engine.spmd.host_gather_bytes", total)

    @staticmethod
    def _agg_leaves(agg: lp.Aggregate) -> List[ex.AggExpr]:
        leaves, seen = [], set()
        for _, e in agg.aggs:
            for sub in e.walk():
                if isinstance(sub, ex.AggExpr) and id(sub) not in seen:
                    seen.add(id(sub))
                    leaves.append(sub)
        return leaves

    def _agg_partials(self, agg: lp.Aggregate, leaves, dt: DTable):
        """Local sort-grouped partials -> all_gather over the mesh ->
        replicated exact final re-group.  Returns a flat tuple of
        replicated arrays; names/ctypes captured via side channels."""
        evl = JEval(dt)
        cap = dt.capacity
        key_cols = [(n, evl.eval(e)) for n, e in agg.group_by]
        self._key_meta = [(n, c.ctype, c.dictionary) for n, c in key_cols]
        if key_cols:
            keys = [_key_i64(c, dt.alive) for _, c in key_cols]
        else:
            keys = [jnp.where(dt.alive, jnp.int64(0), _DEAD_KEY)]
        gid, order, newgrp = _group_ids(keys)
        idx = jnp.arange(cap)
        first_pos = jnp.full(cap, cap, jnp.int64).at[
            (jnp.cumsum(newgrp) - 1)].min(idx)
        rep = order[jnp.clip(first_pos, 0, cap - 1)]
        slot_used = jnp.zeros(cap, bool).at[gid].set(True)
        galive = jax.ops.segment_sum(dt.alive.astype(jnp.int32), gid,
                                     num_segments=cap) > 0
        out_alive = slot_used & galive

        def gather(x):
            # traced-collective instrument: counted once per compiled
            # program (see exchange._note_collective)
            obs.inc("exchange.collective.calls")
            obs.inc("exchange.all_gather.calls")
            obs.inc("exchange.shuffle_bytes",
                    int(x.size * x.dtype.itemsize
                        * self.n_dev * (self.n_dev - 1)))
            return lax.all_gather(x, SHARD_AXIS).reshape(
                (self.n_dev * cap,) + x.shape[1:])

        g_alive = gather(out_alive)
        g_keys = [gather(jnp.where(out_alive, k[rep], _DEAD_KEY))
                  for k in keys]
        g_key_cols = [(gather(c.data[rep]),
                       gather(c.valid[rep] & out_alive))
                      for _, c in key_cols]

        self._leaf_meta = []
        g_leaves = []
        for a in leaves:
            parts, meta = self._leaf_partial(dt, evl, a, gid, cap, order)
            self._leaf_meta.append(meta)
            g_leaves.append([gather(p) for p in parts])

        # replicated exact final re-group over n_dev * cap slots
        total = self.n_dev * cap
        fgid, forder, fnew = _group_ids(g_keys)
        fidx = jnp.arange(total)
        ffirst = jnp.full(total, total, jnp.int64).at[
            (jnp.cumsum(fnew) - 1)].min(fidx)
        frep = forder[jnp.clip(ffirst, 0, total - 1)]
        fused = jnp.zeros(total, bool).at[fgid].set(True)
        fal = jax.ops.segment_sum(g_alive.astype(jnp.int32), fgid,
                                  num_segments=total) > 0
        final_alive = fused & fal

        flat = [final_alive]
        for gdata, gvalid in g_key_cols:
            flat += [gdata[frep], gvalid[frep] & final_alive]
        for a, parts in zip(leaves, g_leaves):
            flat += self._combine_partials(a, parts, fgid, total, g_alive)
        return tuple(flat)

    def _leaf_partial(self, dt: DTable, evl: JEval, a: ex.AggExpr, gid,
                      cap, order):
        """Per-slot partial arrays + static meta for one leaf aggregate.
        ``order`` sorts rows by gid — float sums use the compensated
        segmented scan (TPU f64 runs at f32 precision; df64 module)."""

        def fsum(vals):
            from ndstpu.engine import df64
            return df64.segment_sum_compensated(vals, gid, cap, order)

        alive = dt.alive
        if isinstance(a.arg, ex.Star) or a.arg is None:
            cnt = jax.ops.segment_sum(alive.astype(jnp.int64), gid,
                                      num_segments=cap)
            return [cnt], (a.func, None, None)
        c = evl.eval(a.arg)
        meta = (a.func, c.ctype, c.dictionary)
        valid = c.valid & alive
        if a.distinct:
            # rows were colocated by group key: keep only the first
            # (gid, value) occurrence on this device — globally unique.
            # dorder must NOT shadow `order` — fsum's compensated scan
            # requires the gid-sorted order, not this dedup order
            g2 = jnp.where(valid, gid, jnp.int64(cap))
            xkey = _key_i64(c, valid)
            dorder = _lexsort_order([g2, xkey])
            gs, xs = g2[dorder], xkey[dorder]
            first = jnp.ones(cap, bool).at[1:].set(
                (gs[1:] != gs[:-1]) | (xs[1:] != xs[:-1]))
            valid = valid & jnp.zeros(cap, bool).at[dorder].set(
                first & (gs < cap))
        cnt = jax.ops.segment_sum(valid.astype(jnp.int64), gid,
                                  num_segments=cap)
        if a.func == "count":
            return [cnt], meta
        if a.func in ("sum", "avg"):
            si = _sum_input(c.data, valid, c.ctype.kind)
            if c.ctype.kind in ("decimal", "int32", "int64"):
                s = jax.ops.segment_sum(si, gid, num_segments=cap)
            else:
                s = fsum(si)
            return [s, cnt], meta
        if a.func in ("min", "max"):
            if c.ctype.kind == "float64":
                init = jnp.inf if a.func == "min" else -jnp.inf
                vals = jnp.where(valid, c.data, init)
            else:
                init = _DEAD_KEY if a.func == "min" else -_DEAD_KEY
                vals = jnp.where(valid, c.data.astype(jnp.int64),
                                 jnp.int64(init))
            seg = jax.ops.segment_min if a.func == "min" \
                else jax.ops.segment_max
            return [seg(vals, gid, num_segments=cap), cnt], meta
        # stddev family: [s1, m2(centered), cnt] — see _host_leaf_partial;
        # Chan combine downstream keeps mean >> stddev cases exact
        x = jnp.where(valid, c.data.astype(jnp.float64), 0.0)
        if c.ctype.kind == "decimal":
            x = x / (10 ** c.ctype.scale)
        s1 = fsum(x)
        mean = s1 / jnp.maximum(cnt, 1)
        d = jnp.where(valid, x - mean[gid], 0.0)
        d1 = fsum(d)
        m2 = fsum(d * d) - jnp.where(
            cnt > 0, d1 * d1 / jnp.maximum(cnt, 1), 0.0)
        return [s1, m2, cnt], meta

    def _combine_partials(self, a: ex.AggExpr, parts, fgid, total,
                          g_alive):
        if a.func in ("stddev_samp", "var_samp", "stddev", "variance") \
                and len(parts) == 3:
            # Chan combine: M2 = sum m2_i + sum n_i (mean_i - mean)^2.
            # The correction MUST subtract the means before squaring —
            # expanding it reintroduces the raw-moment cancellation.
            s1, m2, cnt = [jnp.where(g_alive, p, jnp.zeros((), p.dtype))
                           for p in parts]
            S1 = jax.ops.segment_sum(s1, fgid, num_segments=total)
            CNT = jax.ops.segment_sum(cnt, fgid, num_segments=total)
            mean_tot = S1 / jnp.maximum(CNT, 1)
            mean_i = s1 / jnp.maximum(cnt, 1)
            dm = mean_i - mean_tot[fgid]
            corr = jax.ops.segment_sum(
                jnp.where(cnt > 0, cnt * dm * dm, 0.0), fgid,
                num_segments=total)
            M2 = jax.ops.segment_sum(m2, fgid, num_segments=total) + corr
            return [S1, M2, CNT]
        out = []
        minmax = a.func in ("min", "max")
        for pi, part in enumerate(parts):
            if minmax and pi == 0:
                seg = jax.ops.segment_min if a.func == "min" \
                    else jax.ops.segment_max
                if part.dtype == jnp.float64:
                    init = jnp.inf if a.func == "min" else -jnp.inf
                else:
                    init = jnp.int64(
                        _DEAD_KEY if a.func == "min" else -_DEAD_KEY)
                vals = jnp.where(g_alive, part, init)
                out.append(seg(vals, fgid, num_segments=total))
            else:
                vals = jnp.where(g_alive, part,
                                 jnp.zeros((), part.dtype))
                out.append(jax.ops.segment_sum(vals, fgid,
                                               num_segments=total))
        return out

    # -- host finalize -------------------------------------------------------

    _PARTS_PER_FUNC = {"count": 1, "sum": 2, "avg": 2, "min": 2, "max": 2,
                       "stddev_samp": 3, "var_samp": 3, "stddev": 3,
                       "variance": 3}

    def _unpack_agg(self, out):
        """Flat replicated spine output -> per-finest-group key Columns
        and raw leaf partial arrays."""
        flat = [np.asarray(a) for a in out]
        final_alive = flat[0]
        sel = np.nonzero(final_alive)[0]
        pos = 1
        key_cols: Dict[str, Column] = {}
        for name, ctype, dictionary in self._key_meta:
            data, valid = flat[pos][sel], flat[pos + 1][sel]
            pos += 2
            key_cols[name] = Column(
                data, ctype, None if valid.all() else valid, dictionary)
        leaf_parts: List[List[np.ndarray]] = []
        for a, meta in zip(self._agg_ctx[1], self._leaf_meta):
            func, _ctype, _dictionary = meta
            nparts = self._PARTS_PER_FUNC[func] if not (
                isinstance(a.arg, ex.Star) or a.arg is None) else 1
            leaf_parts.append([flat[pos + k][sel] for k in range(nparts)])
            pos += nparts
        return key_cols, leaf_parts

    def _finalize_from(self, agg: lp.Aggregate, leaves, key_cols,
                       leaf_parts) -> Table:
        if agg.grouping_sets is not None:
            return self._grouping_sets_result(agg, leaves, key_cols,
                                              leaf_parts)
        leaf_final = {li: self._finalize_leaf(a, meta, parts)
                      for li, (a, meta, parts) in enumerate(
                          zip(leaves, self._leaf_meta, leaf_parts))}
        n_fine = len(next(iter(key_cols.values())).data) if key_cols \
            else (len(leaf_parts[0][0]) if leaf_parts else 0)

        if not agg.group_by and n_fine == 0:
            # SQL global aggregate over zero rows: one row, count 0 / NULL
            for li, (a, meta) in enumerate(zip(leaves, self._leaf_meta)):
                c = leaf_final[li]
                if a.func == "count":
                    leaf_final[li] = Column(
                        np.zeros(1, np.int64), INT64)
                else:
                    leaf_final[li] = Column(
                        np.zeros(1, c.data.dtype), c.ctype,
                        np.zeros(1, bool), c.dictionary)

        sub_cols = {f"__agg{li}": c for li, c in leaf_final.items()}
        gtable = Table({**key_cols, **sub_cols})
        out_cols: Dict[str, Column] = {}
        for name, _ in agg.group_by:
            out_cols[name] = key_cols[name]
        for name, e in agg.aggs:
            out_cols[name] = ex.Evaluator(gtable).eval(
                self._lower_expr(e, leaves))
        return Table(out_cols)

    def _grouping_sets_result(self, agg: lp.Aggregate, leaves,
                              key_cols: Dict[str, Column],
                              leaf_parts) -> Table:
        """ROLLUP/grouping sets: the spine aggregated at the FINEST
        grouping (all keys); each set re-combines those decomposable
        partials on the host (sums add, counts add, min/max fold,
        moments add) — never re-touching the fact rows — then finalizes
        and evaluates the output expressions with ``grouping()``
        resolved per set (Spark semantics, reference rollup queries
        e.g. q18/q22/q27/q36/q67/q70/q86)."""
        names = [n for n, _ in agg.group_by]
        n_fine = len(key_cols[names[0]].data) if names else (
            len(leaf_parts[0][0]) if leaf_parts else 0)
        outs: List[Table] = []
        for subset in agg.grouping_sets:
            sub_keys: List[Tuple[str, Column]] = []
            for i, name in enumerate(names):
                c = key_cols[name]
                if i in subset:
                    sub_keys.append((name, c))
                else:
                    sub_keys.append((name, Column(
                        np.zeros_like(c.data), c.ctype,
                        np.zeros(n_fine, bool), c.dictionary)))
            if names:
                gids, first = self.np_exec._factorize(
                    [c for _, c in sub_keys])
                ng = len(first)
            else:
                # global aggregate: one output row even over no groups
                gids = np.zeros(n_fine, np.int64)
                first = np.zeros(1, np.int64)
                ng = 1
            out_cols: Dict[str, Column] = {}
            for name, c in sub_keys:
                out_cols[name] = c.gather(first) if n_fine else Column(
                    np.zeros(0, c.data.dtype), c.ctype,
                    np.zeros(0, bool), c.dictionary)
            leaf_final: Dict[int, Column] = {}
            for li, (a, meta, parts) in enumerate(
                    zip(leaves, self._leaf_meta, leaf_parts)):
                combined = self._combine_host(a, meta, parts, gids, ng)
                leaf_final[li] = self._finalize_leaf(a, meta, combined)
            # leaf columns are per-group (ng); key cols were gathered to
            # group granularity above — evaluate outputs at that grain
            gtable = Table({**out_cols,
                            **{f"__agg{li}": c
                               for li, c in leaf_final.items()}})
            for name, e in agg.aggs:
                out_cols[name] = ex.Evaluator(gtable).eval(
                    self._lower_expr(e, leaves, gctx=(names, subset)))
            outs.append(Table(out_cols))
        return Table.concat(outs)

    def _combine_host(self, a: ex.AggExpr, meta, parts, gids, ng):
        """Numpy re-combine of finest-group partials into one grouping
        set's groups (mirror of the traced _combine_partials)."""
        func = meta[0]
        has_arg = not (isinstance(a.arg, ex.Star) or a.arg is None)
        cnt = parts[-1] if has_arg and func != "count" else parts[0]
        if func in ("stddev_samp", "var_samp", "stddev", "variance") \
                and has_arg and len(parts) == 3:
            # numpy mirror of the traced Chan combine
            s1, m2, n_i = parts
            S1 = np.zeros(ng, np.float64)
            CNT = np.zeros(ng, np.int64)
            np.add.at(S1, gids, s1)
            np.add.at(CNT, gids, n_i)
            mean_tot = S1 / np.maximum(CNT, 1)
            mean_i = s1 / np.maximum(n_i, 1)
            dm = mean_i - mean_tot[gids]
            corr = np.zeros(ng, np.float64)
            np.add.at(corr, gids, np.where(n_i > 0, n_i * dm * dm, 0.0))
            M2 = np.zeros(ng, np.float64)
            np.add.at(M2, gids, m2)
            return [S1, M2 + corr, CNT]
        out = []
        for pi, part in enumerate(parts):
            if func in ("min", "max") and pi == 0 and has_arg:
                if part.dtype == np.float64:
                    init = np.inf if func == "min" else -np.inf
                else:
                    init = np.int64(_DEAD_KEY if func == "min"
                                    else -_DEAD_KEY)
                acc = np.full(ng, init, part.dtype)
                fold = np.minimum if func == "min" else np.maximum
                vals = np.where(cnt > 0, part, init)
                fold.at(acc, gids, vals)
                out.append(acc)
            else:
                acc = np.zeros(ng, part.dtype)
                np.add.at(acc, gids, part)
                out.append(acc)
        return out

    def _lower_expr(self, e: ex.Expr, leaves,
                    gctx: Optional[tuple] = None) -> ex.Expr:
        for li, a in enumerate(leaves):
            if a is e:
                return ex.ColumnRef(f"__agg{li}")
        if isinstance(e, ex.BinOp):
            return ex.BinOp(e.op, self._lower_expr(e.left, leaves, gctx),
                            self._lower_expr(e.right, leaves, gctx))
        if isinstance(e, ex.UnaryOp):
            return ex.UnaryOp(e.op,
                              self._lower_expr(e.operand, leaves, gctx))
        if isinstance(e, ex.Cast):
            return ex.Cast(self._lower_expr(e.operand, leaves, gctx),
                           e.target)
        if isinstance(e, ex.Func):
            if e.name == "grouping":
                # grouping(key) = 0 when the key participates in this
                # grouping set, 1 when rolled up (Spark semantics,
                # mirror of physical._eval_agg)
                if gctx is None:
                    return ex.Literal(0)
                names, subset = gctx
                arg = e.args[0]
                idx = names.index(arg.name) if isinstance(
                    arg, ex.ColumnRef) and arg.name in names else -1
                active = subset is None or idx in subset
                return ex.Literal(0 if active else 1)
            return ex.Func(e.name, tuple(self._lower_expr(a, leaves, gctx)
                                         for a in e.args))
        if isinstance(e, ex.Case):
            return ex.Case(
                tuple((self._lower_expr(c, leaves, gctx),
                       self._lower_expr(v, leaves, gctx))
                      for c, v in e.whens),
                self._lower_expr(e.default, leaves, gctx)
                if e.default is not None else None)
        if isinstance(e, ex.InList):
            return ex.InList(self._lower_expr(e.operand, leaves, gctx),
                             e.values, e.negated)
        if isinstance(e, ex.AggExpr):
            # an aggregate leaf the collection pass missed — bail to the
            # single-chip path rather than crash at finalize
            raise DistUnsupported("unlowered aggregate in output expr",
                                  code="NDS302")
        return e

    def _finalize_leaf(self, a: ex.AggExpr, meta, parts) -> Column:
        func, ctype, dictionary = meta
        if isinstance(a.arg, ex.Star) or a.arg is None or func == "count":
            return Column(parts[0].astype(np.int64), INT64)
        if func == "sum":
            s, cnt = parts
            got = cnt > 0
            vopt = None if got.all() else got
            if ctype.kind == "decimal":
                return Column(s.astype(np.int64),
                              columnar.decimal(38, ctype.scale), vopt)
            if ctype.kind in ("int32", "int64"):
                return Column(s.astype(np.int64), INT64, vopt)
            return Column(s.astype(np.float64), FLOAT64, vopt)
        if func == "avg":
            s, cnt = parts
            got = cnt > 0
            mean = s.astype(np.float64) / np.maximum(cnt, 1)
            if ctype.kind == "decimal":
                mean = mean / (10 ** ctype.scale)
            return Column(mean, FLOAT64, None if got.all() else got)
        if func in ("min", "max"):
            v, cnt = parts
            got = cnt > 0
            vopt = None if got.all() else got
            if ctype.kind == "float64":
                return Column(v.astype(np.float64), ctype, vopt)
            dtype = columnar.numpy_dtype(ctype)
            return Column(v.astype(dtype), ctype, vopt, dictionary)
        # stddev family: parts[1] is already the centered M2 (Chan
        # combine upstream) — no raw-moment subtraction left to cancel
        _s1, m2, cnt = parts
        ok = cnt > 1
        denom = np.where(ok, cnt - 1, 1)
        var = np.maximum(m2, 0.0) / denom
        data = var if func in ("var_samp", "variance") else np.sqrt(var)
        return Column(data, FLOAT64, None if ok.all() else ok)


# the union-distribution walk is shared with the static analyzer
# (lowering._audit_spine models the same split the executor performs)
_path_to = lowreg.plan_path_to
_distributive_path = lowreg.union_distributive_path


def _output_names(p: lp.Plan, catalog) -> Optional[List[str]]:
    """Static output column names of a plan (mirror of how the numpy
    executor names each node's output), or None when unknown."""
    if isinstance(p, lp.Scan):
        if p.columns is not None:
            return list(p.columns) or \
                [catalog.get(p.table).column_names[0]]
        return list(catalog.get(p.table).column_names)
    if isinstance(p, lp.InlineTable):
        return list(p.table.column_names)
    if isinstance(p, lp.Project):
        return [n for n, _ in p.exprs]
    if isinstance(p, lp.Aggregate):
        return [n for n, _ in p.group_by] + [n for n, _ in p.aggs]
    if isinstance(p, lp.Window):
        base = _output_names(p.child, catalog)
        if base is None:
            return None
        return base + [n for n, _ in p.exprs if n not in base]
    if isinstance(p, (lp.Filter, lp.Sort, lp.Limit, lp.Distinct)):
        return _output_names(p.child, catalog)
    if isinstance(p, lp.SubqueryAlias):
        if p.column_aliases:
            return list(p.column_aliases)
        return _output_names(p.child, catalog)
    if isinstance(p, lp.SetOp):
        return _output_names(p.left, catalog)
    if isinstance(p, lp.Join):
        left = _output_names(p.left, catalog)
        if p.kind in ("semi", "anti", "nullaware_anti"):
            return left
        if p.mark is not None:
            return None if left is None else left + [p.mark]
        right = _output_names(p.right, catalog)
        if left is None or right is None:
            return None
        return left + right
    return None


def _graft(top: lp.Plan, old: lp.Plan, new: lp.Plan) -> lp.Plan:
    """Copy of `top` with the subtree `old` replaced by `new`."""
    if top is old:
        return new
    n = copy.copy(top)
    for attr in ("child", "left", "right"):
        c = getattr(n, attr, None)
        if c is not None:
            setattr(n, attr, _graft(c, old, new))
    return n


def execute_distributed(catalog, mesh, plan: lp.Plan,
                        shard_threshold_rows: int = 65536,
                        broadcast_limit_rows: int = 8_000_000) -> Table:
    """One-shot helper: run `plan` over `mesh`, DistUnsupported on plans
    outside the distributed subset."""
    return DistributedPlanExecutor(
        catalog, mesh, shard_threshold_rows,
        broadcast_limit_rows).execute_plan(plan)
