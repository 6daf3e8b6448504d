"""Canonical plan-shape analyzer: fingerprint stability, slot lifting,
and the shape-keyed compile cache it feeds.

Static half (zero-row schema catalog, no warehouse, no jax): the
canonicalizer's fingerprint must be a pure function of plan STRUCTURE —
renderings of one template that differ only in literals share it, and
the value-dependent artifacts the optimizer leaves behind (generated
``__ssa`` column names, ``UnaryOp('neg')`` wrappers) must not leak in.

Runtime half (tiny generated warehouse): canonical keying must be
invisible to results (differential vs the numpy engine; a raising
canonicalizer falls back to text keying), must make re-renderings
compile ZERO new programs, and must give a discover-process and a
preload-process identical compile cache keys.
"""

import math

import pytest

from ndstpu import analysis, obs
from ndstpu.engine.session import Session
from ndstpu.io import loader
from ndstpu.queries import streamgen

SEED_A = "07291122510"   # pinned bench seed
SEED_B = "19980713042"

# corpus sample for the runtime property tests: star joins + grouped
# aggregates, all verified to collapse to ONE cache key across seeds
# (scripts/canon_audit.py) — re-renderings must be compile-free
SAMPLE = ["query3", "query42", "query52", "query55", "query96"]


def render(name, seed, stream=0):
    parts = streamgen.render_template_parts(
        str(streamgen.TEMPLATE_DIR / f"{name}.tpl"), seed, stream)
    return [(p, sql) for p, sql in parts]


# -- static: fingerprint + slot semantics ------------------------------------


@pytest.fixture(scope="module")
def ssess():
    return Session(analysis.schema_catalog())


@pytest.fixture(scope="module")
def tables():
    return analysis.schema_tables()


def canon_of(ssess, tables, sql, query="q"):
    plan, _cols = ssess.plan(sql)
    return analysis.canonicalize(plan, tables=tables, query=query)


def test_fingerprint_stable_across_renderings(ssess, tables):
    """Different literal draws of one template -> one fingerprint;
    the drawn values travel in the binding, not the structure."""
    for name in ("query7", "query52"):
        fps, bindings = set(), []
        for seed in (SEED_A, SEED_B):
            for pname, sql in render(name, seed):
                res = canon_of(ssess, tables, sql, pname)
                fps.add(res.fingerprint)
                bindings.append(tuple(res.binding.values))
        assert len(fps) == 1, f"{name}: structure varied with literals"
        assert len(set(bindings)) > 1, \
            f"{name}: seeds drew identical literals (bad sample)"


def test_slots_are_per_occurrence_not_value_deduped(ssess, tables):
    """Two predicates that coincidentally render the SAME literal ('M'
    is a gender AND a marital status) must lift into two slots —
    value-based dedup would make structure depend on the draw."""
    res = canon_of(ssess, tables,
                   "select count(*) as n from customer_demographics "
                   "where cd_gender = 'M' and cd_marital_status = 'M'")
    cols = sorted(s.column for s in res.slots if s.column)
    assert cols == [("customer_demographics", "cd_gender"),
                    ("customer_demographics", "cd_marital_status")]
    # and the collision rendering shares its fingerprint with a
    # collision-free one
    res2 = canon_of(ssess, tables,
                    "select count(*) as n from customer_demographics "
                    "where cd_gender = 'F' and cd_marital_status = 'S'")
    assert res.fingerprint == res2.fingerprint


def test_negated_literal_folds_into_binding(ssess, tables):
    """`= -6` parses as UnaryOp('neg', 6); the sign must fold into the
    bound value so negative and positive draws share one structure."""
    neg = canon_of(ssess, tables,
                   "select count(*) as n from customer_address "
                   "where ca_gmt_offset = -6")
    pos = canon_of(ssess, tables,
                   "select count(*) as n from customer_address "
                   "where ca_gmt_offset = 7")
    assert neg.fingerprint == pos.fingerprint
    assert -6 in [s.value for s in neg.slots]
    assert ("customer_address", "ca_gmt_offset") in \
        [s.column for s in neg.slots]


def test_generated_ssa_names_normalized(ssess, tables):
    """The sibling-aggregate fusion names internal columns with an md5
    of the conjuncts — literal-dependent.  Canonicalization renumbers
    generated names so the q28 idiom collapses across draws."""
    def q28ish(b):
        return ("select * from "
                f"(select avg(ss_list_price) a1 from store_sales "
                f" where ss_quantity between {b[0]} and {b[1]}) x1, "
                f"(select avg(ss_list_price) a2 from store_sales "
                f" where ss_quantity between {b[2]} and {b[3]}) x2")
    r1 = canon_of(ssess, tables, q28ish((0, 5, 6, 10)))
    r2 = canon_of(ssess, tables, q28ish((11, 15, 16, 20)))
    assert r1.fingerprint == r2.fingerprint


def _plan_exprs(plan):
    import dataclasses

    from ndstpu.engine import expr as ex
    for node in plan.walk():
        for f in dataclasses.fields(node):
            v = getattr(node, f.name)
            for it in (v if isinstance(v, (list, tuple)) else (v,)):
                if isinstance(it, tuple) and it and \
                        isinstance(it[0], ex.Expr):
                    it = it[0]
                if isinstance(it, ex.Expr):
                    yield from it.walk()


def test_exec_plan_param_sites_match_slot_classes(ssess, tables):
    """exec_plan (what the runtime compiles) keeps a Param at every
    BINDABLE site — that is the whole point of the shape key — while
    every shape-affecting value is substituted back as a literal so
    array extents stay concrete at trace time."""
    from ndstpu.engine import expr as ex
    for _p, sql in render("query7", SEED_A):
        res = canon_of(ssess, tables, sql)
        slots_seen = sorted(
            e.slot for e in _plan_exprs(res.exec_plan)
            if isinstance(e, (ex.Param, ex.InParam)))
        assert slots_seen == sorted(s.slot for s in res.bindable)
        from ndstpu.engine import plan as lp
        lits = [e.value for e in _plan_exprs(res.exec_plan)
                if isinstance(e, ex.Literal)]
        lits += [n.n for n in res.exec_plan.walk()
                 if isinstance(n, lp.Limit)]   # LIMIT count is shape
        for s in res.shape_affecting:
            vals = s.value if isinstance(s.value, tuple) else (s.value,)
            for v in vals:
                assert any(v == x or (isinstance(x, float) and
                           isinstance(v, (int, float)) and
                           math.isclose(float(v), x)) for x in lits), \
                    f"shape slot value {v!r} missing from exec_plan"
        # the bound values line up slot-for-slot with the lift
        assert res.binding.values == res.values
        # string binds never appear in the scalar spec (they reach the
        # device as dictionary hit tables, not broadcast scalars)
        assert all(ct.kind != "string" for _s, ct in res.binding.scalars)


def test_canonical_key_session_helper(ssess):
    """Session.canonical_key: two renderings -> same key; unparseable
    text degrades to the normalized-text key instead of raising."""
    from ndstpu.engine.sql import normalize_sql_key
    (_, sql_a), = render("query52", SEED_A)
    (_, sql_b), = render("query52", SEED_B)
    assert sql_a != sql_b
    key = ssess.canonical_key(sql_a)
    assert key.startswith("c:")
    assert key == ssess.canonical_key(sql_b)
    junk = "not sql at all"
    assert ssess.canonical_key(junk) == normalize_sql_key(junk)


# -- one slot per source literal ----------------------------------------------

# the power cells' fixed seeds (benchmark/workloads/*.json)
CELL_SEED = {"opclass7": "3000000041", "joinclass6": "3000000043",
             "aggwin3": "3000000047"}
# part: (uses of its CTE, slots) at the aggwin3 cell's seed; per
# occurrence the parts had 25 and 28 slots, and 2 and 3 segments
CTE_USES = {"query2": (2, 18), "query47": (3, 14)}


@pytest.mark.parametrize("name", list(CTE_USES))
def test_cte_uses_share_slots_and_one_segment(ssess, tables, name):
    """A CTE used more than once is planned once and copied per use, the
    copies sharing its expression objects: each literal of the body is
    one slot, the uses' aggregates and windows fingerprint equal, and
    the segment cut folds them into one program the uses read."""
    from ndstpu.engine import plan as lp
    from ndstpu.engine.jaxexec import _cut_segments, _plan_fp
    uses, n_slots = CTE_USES[name]
    (pname, sql), = render(name, CELL_SEED["aggwin3"])
    res = canon_of(ssess, tables, sql, pname)
    assert len(res.slots) == n_slots
    assert any(len(s.paths) == uses for s in res.slots)
    for kind in (lp.Aggregate, lp.Window):
        fps = [_plan_fp(n) for n in res.exec_plan.walk()
               if isinstance(n, kind)]
        assert len(fps) == uses * len(set(fps)), kind
    parent, segs = _cut_segments(res.exec_plan)
    reads = [n.key for n in parent.walk() if isinstance(n, lp.DeviceResult)]
    assert len(segs) == 1 and reads == list(segs) * uses


def test_equal_literals_of_two_sources_keep_two_slots(ssess, tables):
    """Identity, never value: two literals written apart keep two slots
    where they render one value, and share the structure of a rendering
    whose values differ; one literal of a CTE used twice is one slot."""
    def apart(a, b):
        return ("select count(*) as n from store_sales, date_dim, item "
                "where ss_sold_date_sk = d_date_sk and ss_item_sk = "
                f"i_item_sk and d_moy = {a} and i_manufact_id = {b}")

    def twice(a, b):
        return ("select count(*) as n from "
                f"(select d_date_sk from date_dim where d_moy = {a}) x, "
                f"(select d_date_sk from date_dim where d_moy = {b}) y "
                "where x.d_date_sk = y.d_date_sk")
    for sql in (apart, twice):
        same = canon_of(ssess, tables, sql(11, 11))
        differ = canon_of(ssess, tables, sql(11, 12))
        assert sorted(s.value for s in same.slots) == [11, 11]
        assert all(len(s.paths) == 1 for s in same.slots)
        assert same.fingerprint == differ.fingerprint
        assert same.cache_key == differ.cache_key
    cte = canon_of(ssess, tables,
                   "with v as (select d_date_sk from date_dim "
                   "where d_moy = 11) select count(*) as n from v x, v y "
                   "where x.d_date_sk = y.d_date_sk")
    assert [(s.value, len(s.paths)) for s in cte.slots] == [(11, 2)]


# parts whose renderings at the two seeds below give two cache keys (a
# shape-affecting slot draws apart); every other part gives one.  Keying
# slots by source literal split none of them further.
TWO_KEYS = {"query14_part2", "query24_part1", "query24_part2", "query35",
            "query4", "query44", "query54", "query58", "query6",
            "query66", "query91"}


def test_corpus_keys_split_no_further_across_seeds(ssess, tables):
    keys = {}
    for seed in (CELL_SEED["aggwin3"], SEED_A):
        for name, sql in streamgen.render_power_corpus(rngseed=seed,
                                                       stream=0):
            keys.setdefault(name, set()).add(
                canon_of(ssess, tables, sql, name).cache_key)
    assert len(keys) == 103
    wider = {n: len(k) for n, k in keys.items()
             if len(k) > (2 if n in TWO_KEYS else 1)}
    assert not wider, wider


# (cache key, slots) of each part the other cells run, at the cell's
# seed: no CTE of theirs is used twice, so their programs are the ones
# compiled before slots were keyed by source literal.  The served cell
# draws its texts from the run's seed; its parts are opclass7's.
OTHER_CELLS = {
    ("opclass7", "query3"): ("c:76794393083688b2:6eefa5acd663", 3),
    ("opclass7", "query7"): ("c:41570cd560308da0:d23da4e9d8b5", 7),
    ("opclass7", "query96"): ("c:eb3e23189de543b7:29783a1b0aac", 5),
    ("opclass7", "query12"): ("c:fbec7d464233b5a9:8d7aec4045aa", 6),
    ("opclass7", "query86"): ("c:e81e819c7fe0ec8f:6cb770322914", 6),
    ("opclass7", "query25"): ("c:82f9985545ce0488:50461cbdeb3e", 9),
    ("opclass7", "query9"): ("c:7193e17a8dff2cae:3362743655b5", 36),
    ("joinclass6", "query69"): ("c:a546ee12dbb5bd53:af85c6dd3485", 14),
    ("joinclass6", "query10"): ("c:20bff49d080a53c9:af85c6dd3485", 14),
    ("joinclass6", "query94"): ("c:233cc05b41bb32b8:fb238a8e9f80", 6),
    ("joinclass6", "query97"): ("c:871dade758feb703:42524ae942dd", 13),
    ("joinclass6", "query38"): ("c:eedeb84ddee6a61f:fbfc74ee649f", 10),
    ("joinclass6", "query95"): ("c:a1203195d9568428:fb238a8e9f80", 6),
    ("aggwin3", "query51"): ("c:837ce022ed38711d:d23da4e9d8b5", 7),
}
SERVED = ["query96", "query3", "query86", "query9"]


@pytest.mark.parametrize("cell,name", list(OTHER_CELLS))
def test_other_cells_keep_their_cache_keys(ssess, tables, cell, name):
    (pname, sql), = render(name, CELL_SEED[cell])
    res = canon_of(ssess, tables, sql, pname)
    assert (res.cache_key, len(res.slots)) == OTHER_CELLS[cell, name]


@pytest.mark.parametrize("name", SERVED)
def test_served_parts_keep_their_cache_keys(ssess, tables, name):
    for seed in (SEED_A, SEED_B):
        (pname, sql), = render(name, seed)
        res = canon_of(ssess, tables, sql, pname)
        assert (res.cache_key, len(res.slots)) == \
            OTHER_CELLS["opclass7", name]


# -- runtime: differential + cache-counter properties -------------------------


@pytest.fixture(scope="module")
def warehouse(sf002_warehouse):
    return sf002_warehouse


@pytest.fixture(scope="module")
def catalog(warehouse):
    return loader.load_catalog(str(warehouse))


def _rows(t):
    out = []
    for r in t.to_rows():
        row = []
        for v in r:
            if isinstance(v, float):
                row.append(round(v, 4))
            else:
                row.append(v)
        out.append(tuple(row))
    return sorted(out, key=repr)


def test_canonical_results_match_text_keyed(catalog):
    """Property: for every sample rendering, the canonical (param-bound)
    execution equals the numpy engine's answer to the SAME sql."""
    canon_sess = Session(catalog, backend="tpu")
    cpu_sess = Session(catalog, backend="cpu")
    for name in SAMPLE:
        for seed in (SEED_A, SEED_B):
            for pname, sql in render(name, seed):
                got = _rows(canon_sess.sql(sql))
                want = _rows(cpu_sess.sql(sql))
                assert got == want, f"{pname} seed={seed}"


def test_raising_canonicalizer_falls_back_to_text_keying(catalog,
                                                         monkeypatch):
    """The safety valve: where canonicalization raises, the statement is
    keyed by its normalized text (two renderings: two programs), counted
    in ``engine.canon.errors``, and still answered correctly."""
    from ndstpu.analysis import canon
    from ndstpu.engine.sql import normalize_sql_key

    def broken(plan, query=""):
        raise RuntimeError("analyzer is wrong")

    (_p, sql_a), = render("query96", SEED_A)
    (_p, sql_b), = render("query96", SEED_B)
    cpu_sess = Session(catalog, backend="cpu")
    want = {sql: _rows(cpu_sess.sql(sql)) for sql in (sql_a, sql_b)}
    monkeypatch.setattr(canon, "canonicalize", broken)
    sess = Session(catalog, backend="tpu")
    before = obs.counters_snapshot()
    for sql in (sql_a, sql_b):
        assert sess.canonical_key(sql) == normalize_sql_key(sql)
        assert _rows(sess.sql(sql)) == want[sql]
    moved = obs.counter_delta(before)
    assert moved.get("engine.canon.errors", 0) == 2
    assert moved.get("engine.cache.compiled.miss", 0) == 2


def test_second_seed_compiles_zero_new_programs(catalog):
    """The acceptance property: seed A's sweep misses the compile cache
    exactly once per distinct fingerprint; seed B's re-rendered sweep
    compiles NOTHING new — every part replays seed A's programs."""
    sess = Session(catalog, backend="tpu")
    fps = set()
    for name in SAMPLE:
        for _p, sql in render(name, SEED_A):
            fps.add(sess.canonical_key(sql))
    before = obs.counters_snapshot()
    for name in SAMPLE:
        for _p, sql in render(name, SEED_A):
            sess.sql(sql).to_rows()
    cold = obs.counter_delta(before)
    assert cold.get("engine.cache.compiled.miss", 0) == len(fps)

    before = obs.counters_snapshot()
    for name in SAMPLE:
        for _p, sql in render(name, SEED_B):
            sess.sql(sql).to_rows()
    warm = obs.counter_delta(before)
    assert warm.get("engine.cache.compiled.miss", 0) == 0, \
        "re-rendered corpus sample recompiled under canonical keying"
    assert warm.get("engine.cache.compiled.hit", 0) >= len(SAMPLE)


def test_discover_and_preload_agree_on_cache_keys(catalog, tmp_path):
    """A records-preloaded process must register every record under the
    SAME canonical key a fresh discover-process computes — otherwise the
    preload is dead weight and the first power query re-discovers."""
    sql = ("select i_category, count(*) as n, sum(ss_net_paid) as s "
           "from store_sales join item on ss_item_sk = i_item_sk "
           "group by i_category order by i_category")
    s1 = Session(catalog, backend="tpu")
    want = _rows(s1.sql(sql))
    path = str(tmp_path / "plans.pkl")
    assert s1.save_compiled(path) >= 1
    keys1 = set(s1._jax_executor()._compiled)

    s2 = Session(catalog, backend="tpu")
    assert s2.preload_compiled(path) >= 1
    keys2 = set(s2._jax_executor()._compiled)
    assert keys1 == keys2, \
        f"discover/preload key mismatch: {keys1 ^ keys2}"
    # the canonical key is what execution probes — and it is a
    # fingerprint key, not a text key
    ck = f"{s2._views_epoch}|{s2.canonical_key(sql)}"
    assert ck in keys2
    assert s2.canonical_key(sql).startswith("c:")
    # execution replays the preloaded record: no new cache entries,
    # identical rows
    before = obs.counters_snapshot()
    got = _rows(s2.sql(sql))
    assert got == want
    assert set(s2._jax_executor()._compiled) == keys2
    delta = obs.counter_delta(before)
    assert delta.get("engine.cache.compiled.miss", 0) == 0
