"""Where a chain of lookup joins compacts its survivors.

An inner lookup join sizes its survivors and leaves their compaction
PENDING on its result (``DTable.pending``); ``execute()`` settles it for
every consumer but one: a lookup join above, on the compare path, may
probe the rows where they lie and hand the compaction on
(``JaxExecutor._probe_rows`` / ``_defer_cheaper``).  Differential
against the numpy engine on small traced tables, plus the rule, the
traced program's shape, the size plan's guards and the memo.
"""

import warnings

import numpy as np
import pytest

from ndstpu.engine import jaxexec
from ndstpu.engine.columnar import Table
from ndstpu.engine.session import Session
from ndstpu.io.loader import Catalog
from test_jaxexec import _i32, assert_tables_match

_MI = 1 << 20
_KI = 1 << 10


def _star_catalog(n_rows=4096, n_a=40, n_b=250, nulls=False):
    """fact (4 Ki rows) with three dimensions: dima (40 keys; a_g splits
    them in halves), dimb (250 keys: one alive-key class whatever the
    filter keeps; b_g splits them in tenths), dimc (16 keys)."""
    rng = np.random.default_rng(31)
    f_b = [int(v) for v in rng.integers(0, n_b, n_rows)]
    b_k = list(range(n_b))
    if nulls:
        for i in range(0, n_rows, 7):
            f_b[i] = None
        b_k[1] = b_k[11] = None
    cat = Catalog()
    cat.register("fact", Table({
        "f_id": _i32(list(range(n_rows))),
        "f_a": _i32([int(v) for v in rng.integers(0, n_a, n_rows)]),
        "f_b": _i32(f_b),
        "f_c": _i32([int(v) for v in rng.integers(0, 20, n_rows)]),
        "f_v": _i32([int(v) for v in rng.integers(0, 50, n_rows)]),
    }))
    cat.register("dima", Table({
        "a_k": _i32(list(range(n_a))),
        "a_g": _i32([i % 2 for i in range(n_a)]),
        "a_one": _i32([1] * n_a)}))
    cat.register("dimb", Table({
        "b_k": _i32(b_k),
        "b_g": _i32([i % 10 for i in range(n_b)]),
        "b_v": _i32([(3 * i) % 50 for i in range(n_b)]),
        "b_one": _i32([1] * n_b)}))
    cat.register("dimc", Table({
        "c_k": _i32(list(range(16))),
        "c_v": _i32([i * i for i in range(16)])}))
    return cat


_CATALOGS = {}


def _catalog(name):
    if name not in _CATALOGS:
        _CATALOGS[name] = {
            "star": lambda: _star_catalog(),
            "nulls": lambda: _star_catalog(nulls=True),
            # many build keys against few survivors of many probe rows
            "wide": lambda: _star_catalog(n_rows=32768, n_a=200, n_b=8192),
        }[name]()
    return _CATALOGS[name]


_ROWS = "select f_id, f_v, b_v from "
_DIMA = "(select * from dima where {a}) a on f_a = a_k "
_DIMB = "(select * from dimb where {b}) b on f_b = b_k"
# name: (catalog, sql, join_paths as (lookup, expand, sort, compare,
# deferred), _COMPARE_PAIR_COST or None for the module's)
_CHAINS = {
    # the second lookup compares with a few alive keys over the first's
    # uncompacted survivors; the Project above it settles 4 Ki -> 256
    "deferred": ("star", _ROWS + "fact join " + _DIMA.format(a="a_g = 0") +
                 "join " + _DIMB.format(b="b_g = 1"), (2, 0, 0, 2, 1), None),
    # 8 Ki alive keys against the 256-row class of the first join's
    # survivors of 32 Ki probe rows: compacting first is cheaper
    "rule_settles": ("wide", _ROWS + "fact join " +
                     _DIMA.format(a="a_k = 7") + "join " +
                     _DIMB.format(b="b_k >= 0"), (2, 0, 0, 2, 0), None),
    # no compare path, no deferral: the gather wants dense rows
    "gather": ("star", _ROWS + "fact join " + _DIMA.format(a="a_g = 0") +
               "join " + _DIMB.format(b="b_g = 1"), (2, 0, 0, 0, 0), 1e9),
    # a residual predicate is evaluated over dense rows (an inner
    # join's becomes a Filter above it, which settles like any consumer)
    "extra": ("star", _ROWS + "fact join " + _DIMA.format(a="a_g = 0") +
              "left join " + _DIMB.format(b="b_g = 1") + " and f_v < b_v",
              (2, 0, 0, 2, 0), None),
    "filter_above": ("star", _ROWS + "fact join " +
                     _DIMA.format(a="a_g = 0") + "join " +
                     _DIMB.format(b="b_g = 1") + " and f_v < b_v",
                     (2, 0, 0, 2, 1), None),
    # a left lookup passes its probe's pending class through
    "left": ("star", _ROWS + "fact join " + _DIMA.format(a="a_g = 0") +
             "left join " + _DIMB.format(b="b_g = 1"), (2, 0, 0, 2, 1), None),
    "nulls": ("nulls", _ROWS + "fact join " + _DIMA.format(a="a_g = 0") +
              "join " + _DIMB.format(b="b_g < 5"), (2, 0, 0, 2, 1), None),
    # the first join keeps every row: its survivors fill the probe's
    # class, nothing is pending
    "keeps_all": ("star", _ROWS + "fact join " + _DIMA.format(a="a_g < 2") +
                  "join " + _DIMB.format(b="b_g = 1"), (2, 0, 0, 2, 0), None),
    "keeps_none": ("star", _ROWS + "fact join " +
                   _DIMA.format(a="a_g = 9") + "join " +
                   _DIMB.format(b="b_g = 1"), (2, 0, 0, 2, 1), None),
    # three lookups, one compaction: the second and the third defer
    "chain3": ("star", "select f_id, f_v, b_v, c_v from fact join " +
               _DIMA.format(a="a_g = 0") + "join " +
               _DIMB.format(b="b_g < 5") + " join dimc on f_c = c_k",
               (3, 0, 0, 3, 2), None),
    # an Aggregate directly above an unsettled join settles it
    "aggregate": ("star", "select b_v, sum(f_v) s, count(*) c from fact "
                  "join " + _DIMA.format(a="a_g = 0") + "join " +
                  _DIMB.format(b="b_g = 1") + " group by b_v",
                  (2, 0, 0, 2, 1), None),
}


@pytest.mark.parametrize("case", list(_CHAINS))
def test_chain_matches_numpy_engine(monkeypatch, case):
    which, sql, paths, pair_cost = _CHAINS[case]
    if pair_cost is not None:
        monkeypatch.setattr(jaxexec, "_COMPARE_PAIR_COST", pair_cost)
    catalog = _catalog(which)
    want = Session(catalog, backend="cpu").sql(sql)
    assert (want.num_rows == 0) == (case == "keeps_none")
    sess = Session(catalog, backend="tpu")
    for _run in ("discovery", "replay"):
        got = sess.sql(sql)
        assert got.column_names == want.column_names
        assert_tables_match(want, got)
        if case != "aggregate":
            # output rows keep the probe's order
            ids = [r[0] for r in got.to_rows()]
            assert ids == sorted(ids)
    cp = sess.compiled_plan(sql)
    assert cp is not None and cp.compilable
    programs = [cp] + [sess._jax_executor()._seg_compiled[fp]
                       for fp in (cp.seg_fps or ())]
    assert tuple(sum(p.join_paths[i] for p in programs)
                 for i in range(5)) == paths


# (n, cap, m, K): the probe's capacity, its survivors' pending class,
# the build side's capacity, the alive build keys' class -- ISSUE 31's
# four readings of the SF1 cells
@pytest.mark.parametrize("n,cap,m,k_cap,defer", [
    (4 * _MI, 2 * _MI, 128 * _KI, 512, True),      # query7 x date_dim
    (4 * _MI, 4 * _KI, 32 * _KI, 8192, False),     # query3's second join
    (4 * _MI, 2 * _MI, 2 * _MI, 32768, False),     # no compare at 4 Mi
    (_MI, 256 * _KI, 128 * _KI, 64, True),         # query12 x date_dim
], ids=["q7-date_dim", "q3-few-survivors", "32Ki-keys", "q12-date_dim"])
def test_defer_rule_readings(n, cap, m, k_cap, defer):
    rule = jaxexec.JaxExecutor._defer_cheaper
    assert rule(n, cap, m, k_cap) is defer
    # a key column that is lazy at the probe's capacity is gathered
    # there: nothing is saved by probing the uncompacted rows
    assert rule(n, cap, m, k_cap, lazy_now=3) is False
    # over the kernel's key limit there is no compare path to defer on
    assert rule(n, cap, m, jaxexec._COMPARE_MAX_KEYS * 2) is False


def _eqns(jaxpr):
    for e in jaxpr.eqns:
        yield e
        for v in e.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


def test_traced_chain_compacts_once(monkeypatch):
    """A query7-shaped plan (fact x filtered dimension x filtered
    dimension under an Aggregate): the replay program scatters the
    fact's rows ONCE, to the last join's class, and no array of it has
    the first join's class: no halving compaction, no fact column
    gathered behind it.  The replay counts one deferred join."""
    import jax
    from ndstpu import obs
    _which, sql, _paths, _ = _CHAINS["aggregate"]
    catalog = _catalog("star")
    # one program: the Aggregate is no segment of its own
    monkeypatch.setattr(jaxexec, "_SEG_MIN_TOTAL", 10 ** 6)
    obs.reset(enabled=True)
    try:
        sess = Session(catalog, backend="tpu")
        want = sess.sql(sql)                        # discovery
        cp = sess.compiled_plan(sql)
        # the first join keeps about half of 4 Ki rows, the second a
        # tenth of those: the classes the size plan recorded
        assert [c for tag, c in cp.record if tag == "cap"][:4] == \
            [256, 2048, 256, 256]
        fn, seen = cp.fn, []
        cp.fn = lambda args: seen.append(args) or fn(args)
        assert_tables_match(want, sess.sql(sql))                   # replay
        snap = obs.counters_snapshot()
        assert snap["engine.replay.join_deferred"] == 1
        assert snap["engine.replay.join_lookup"] == 2
        span = [e for e in obs.tracer().events if e["name"] == "replay"][-1]
        assert span["args"]["join_deferred"] == 1
    finally:
        obs.reset()
    eqns = list(_eqns(jax.make_jaxpr(fn)(seen[0]).jaxpr))
    shapes = {tuple(v.aval.shape) for e in eqns
              for v in list(e.invars) + list(e.outvars)
              if hasattr(v.aval, "shape")}
    assert (4096,) in shapes and (256,) in shapes
    assert not {(2048,), (2049,)} & shapes
    fact_scatters = [e for e in eqns if e.primitive.name == "scatter"
                     and tuple(e.invars[2].aval.shape) == (4096,)]
    assert [tuple(e.outvars[0].aval.shape) for e in fact_scatters] == \
        [(257,)]
    # nothing is gathered at the fact's capacity either: both lookups
    # compare (the kernel, interpreted here)
    assert not [e for e in eqns if e.primitive.name == "gather"
                and tuple(e.outvars[0].aval.shape) == (4096,)]


_GUARD_SQL = (_ROWS + "fact join " + _DIMA.format(a="a_g = 0") + "join " +
              _DIMB.format(b="b_k < {hi}"))


def test_saved_records_replay_and_pending_class_guard(tmp_path):
    """The size plan of a deferred chain holds what it held before (the
    same ``cap`` / ``bool`` entries in the same order: _REC_FORMAT
    stays): saved, loaded by a new session and replayed, it answers as
    discovery did.  A later binding whose survivors outgrow the pending
    class trips the join's guard and rediscovers."""
    from ndstpu import obs
    catalog = _catalog("star")
    cpu = Session(catalog, backend="cpu")
    fits, outgrows = _GUARD_SQL.format(hi=25), _GUARD_SQL.format(hi=200)
    s1 = Session(catalog, backend="tpu")
    assert s1.canonical_key(fits) == s1.canonical_key(outgrows)
    want = cpu.sql(fits)
    assert_tables_match(want, s1.sql(fits))
    rec = s1.compiled_plan(fits).record
    # K, unique, survivors of each join, then the result's compaction
    assert [tag for tag, _ in rec] == \
        ["cap", "bool", "cap", "cap", "bool", "cap", "cap"]
    path = str(tmp_path / "plans.pkl")
    assert s1.save_compiled(path) == 1
    s2 = Session(catalog, backend="tpu")
    assert s2.preload_compiled(path) == 1
    assert_tables_match(want, s2.sql(fits))
    assert s2._jax_executor().n_discoveries == 0
    cp = s2.compiled_plan(fits)
    assert cp.record == rec and cp.join_paths == (2, 0, 0, 2, 1)
    # 200 of dimb's 250 keys stay in the alive keys' class (256), but
    # four fifths of the first join's 2 Ki survivors pass the 256 rows
    # the second join's class holds
    before = obs.counters_snapshot().get("engine.discoveries", 0)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        assert_tables_match(cpu.sql(outgrows), s2.sql(outgrows))
    assert any("rediscover" in str(w.message) for w in caught)
    assert obs.counters_snapshot().get("engine.discoveries", 0) == before + 1
    cp = s2.compiled_plan(outgrows)
    assert [c for tag, c in cp.record if tag == "cap"][3] == 2048
    assert_tables_match(cpu.sql(outgrows), s2.sql(outgrows))
    # the smaller draw fits the larger class: no rediscovery back
    assert_tables_match(want, s2.sql(fits))
    assert obs.counters_snapshot().get("engine.discoveries", 0) == before + 1


def test_join_used_twice_settles_once(monkeypatch):
    """A Join node two consumers share (_tree_cache) is compacted once:
    the memo keeps the settled table."""
    from ndstpu.engine import expr as ex, plan as lp
    catalog = _catalog("star")
    settles = []
    settle = jaxexec.JaxExecutor._settle
    monkeypatch.setattr(
        jaxexec.JaxExecutor, "_settle",
        lambda self, dt: settles.append(dt.pending) or settle(self, dt))
    col = ex.ColumnRef
    join = lp.Join(
        lp.Scan("fact", "fact"),
        lp.Scan("dima", "dima", predicate=ex.BinOp(
            "=", col("a_g"), ex.Literal(0))),
        "inner", [(col("f_a"), col("a_k"))])
    exe = jaxexec.JaxExecutor(catalog)
    with jaxexec.host_compute():
        raw = exe.execute(join, settled=False)
        assert raw.pending == 2048 and raw.capacity == 4096 and not settles
        dense = exe.execute(join)
        assert dense.pending is None and dense.capacity == 2048
        assert settles == [2048]
        # both kinds of consumer get the settled table from here on
        assert exe.execute(join) is dense
        assert exe.execute(join, settled=False) is dense
        assert settles == [2048]
    assert int(np.asarray(dense.alive).sum()) == \
        int(np.asarray(raw.alive).sum())
    # and through SQL: a CTE instantiated twice
    # (no literal in it: the canonical plan gives each instance's
    # literals parameter slots of their own)
    sql = ("with j as (" + _ROWS + "fact join " +
           _DIMA.format(a="a_g < a_one") + "join " +
           _DIMB.format(b="b_g = b_one") + ") "
           "select * from j union all select * from j")
    del settles[:]
    want = Session(catalog, backend="cpu").sql(sql)
    sess = Session(catalog, backend="tpu")
    assert_tables_match(want, sess.sql(sql))                   # discovery
    assert settles == [256]
    assert_tables_match(want, sess.sql(sql))                   # replay
    assert settles == [256, 256]


@pytest.mark.parametrize("cell,metric,moves", [
    ("power-sf1.opclass7", "join_deferred_per_op.power", "power_pass_s"),
    ("serve-sf1.short4-r80", "join_deferred_per_op.serve", "serve_p95_ms"),
])
def test_deferred_metric_files_load_and_read(cell, metric, moves):
    """The benchmark finds the counter's two per-layer metrics by name;
    a record without the counter (the parent's program) gives no value
    and no failure -- never 0 -- beside metrics that do read."""
    from benchmark.harness import readers, spec
    by_name = {m["name"]: m for m in spec.load_cell(cell).per_layer}
    m = by_name[metric]
    assert m["moves"] == moves and m["unit"] == "joins/op"
    assert m["file"]["arguments"] == {
        "counter": "engine.replay.join_deferred"}
    suffix = metric.rsplit(".", 1)[1]
    lookup = by_name["join_lookup_per_op." + suffix]["file"]
    change = readers.RunRecord(spans=[], ops=7, counters={
        "engine.replay.join_lookup": 18, "engine.replay.join_deferred": 2})
    parent = readers.RunRecord(spans=[], ops=7, counters={
        "engine.replay.join_lookup": 18})
    assert readers.read_metric(m["file"], change) == pytest.approx(2 / 7)
    assert readers.read_metric(m["file"], parent) is None
    assert readers.read_metric(lookup, parent) == pytest.approx(18 / 7)
