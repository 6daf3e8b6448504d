"""Differential tests: JAX backend vs numpy reference interpreter.

Mirrors the reference's differential-validation strategy (CPU Spark vs GPU
rapids, nds/nds_validate.py) inside the test suite: every query template in
the corpus runs on both backends and must agree row-by-row under the
validator's epsilon/NULL/Decimal semantics, ignoring row order.
"""

import math

import numpy as np
import pytest

from ndstpu.engine.session import Session
from ndstpu.io import loader
from ndstpu.queries import streamgen


@pytest.fixture(scope="module")
def warehouse(sf002_warehouse):
    return sf002_warehouse


@pytest.fixture(scope="module")
def catalog(warehouse):
    return loader.load_catalog(str(warehouse))


@pytest.fixture(scope="module")
def cpu_sess(catalog):
    return Session(catalog, backend="cpu")


@pytest.fixture(scope="module")
def tpu_sess(catalog):
    return Session(catalog, backend="tpu")


def _canon(v):
    if v is None:
        return None
    if isinstance(v, float):
        return v
    return v


def _rows_equal(a, b, eps=1e-5):
    if len(a) != len(b):
        return False
    for x, y in zip(a, b):
        if x is None or y is None:
            if not (x is None and y is None):
                return False
            continue
        if isinstance(x, float) or isinstance(y, float):
            fx, fy = float(x), float(y)
            if math.isnan(fx) or math.isnan(fy):
                if not (math.isnan(fx) and math.isnan(fy)):
                    return False
                continue
            tol = max(abs(fx), abs(fy)) * eps + 1e-9
            if abs(fx - fy) > tol:
                return False
        elif x != y:
            return False
    return True


def _sort_key(row):
    out = []
    for v in row:
        if v is None:
            out.append((0, ""))
        elif isinstance(v, float):
            out.append((1, f"{v:.4f}"))
        else:
            out.append((1, str(v)))
    return out


def assert_tables_match(t_cpu, t_tpu, ordered=False):
    rows_a = t_cpu.to_rows()
    rows_b = t_tpu.to_rows()
    assert len(rows_a) == len(rows_b), \
        f"row count {len(rows_a)} vs {len(rows_b)}"
    if not ordered:
        rows_a = sorted(rows_a, key=_sort_key)
        rows_b = sorted(rows_b, key=_sort_key)
    for i, (ra, rb) in enumerate(zip(rows_a, rows_b)):
        assert _rows_equal(ra, rb), f"row {i}: {ra} != {rb}"


@pytest.mark.parametrize("tpl", streamgen.list_templates())
def test_template_differential(cpu_sess, tpu_sess, tpl):
    for _name, sql in streamgen.render_template_parts(
            str(streamgen.TEMPLATE_DIR / tpl), "07291122510", 0):
        out_cpu = cpu_sess.sql(sql)
        out_tpu = tpu_sess.sql(sql)
        assert out_cpu.column_names == out_tpu.column_names
        assert_tables_match(out_cpu, out_tpu)


def _both(cpu_sess, tpu_sess, sql, ordered=False):
    a = cpu_sess.sql(sql)
    b = tpu_sess.sql(sql)
    assert a.column_names == b.column_names
    assert_tables_match(a, b, ordered=ordered)
    return b


def test_filter_project(cpu_sess, tpu_sess):
    _both(cpu_sess, tpu_sess,
          "select ss_item_sk, ss_quantity * 2 as q2, ss_sales_price "
          "from store_sales where ss_quantity > 10 and ss_sales_price > 50")


def test_join_groupby_sort(cpu_sess, tpu_sess):
    _both(cpu_sess, tpu_sess,
          "select i_category, count(*) as cnt, sum(ss_ext_sales_price) as s "
          "from store_sales, item where ss_item_sk = i_item_sk "
          "group by i_category order by i_category", ordered=True)


def test_left_join_nulls(cpu_sess, tpu_sess):
    _both(cpu_sess, tpu_sess,
          "select sr_item_sk, sr_ticket_number, ss_ticket_number "
          "from store_returns left join store_sales on "
          "sr_item_sk = ss_item_sk and sr_ticket_number = ss_ticket_number")


def test_decimal_agg_exact(cpu_sess, tpu_sess):
    out = _both(cpu_sess, tpu_sess,
                "select sum(ss_net_paid) as total, avg(ss_net_paid) as a, "
                "min(ss_net_paid) as lo, max(ss_net_paid) as hi "
                "from store_sales")
    assert out.num_rows == 1


def test_case_and_strings(cpu_sess, tpu_sess):
    _both(cpu_sess, tpu_sess,
          "select i_item_id, case when i_category = 'Music' then 'M' "
          "else 'other' end as tag, upper(i_brand) as ub "
          "from item where i_brand like '%max%' or i_category in "
          "('Music', 'Books')")


def test_distinct_and_dates(cpu_sess, tpu_sess):
    _both(cpu_sess, tpu_sess,
          "select distinct d_year, d_moy from date_dim "
          "where d_year between 1999 and 2001")


def test_scalar_subquery(cpu_sess, tpu_sess):
    _both(cpu_sess, tpu_sess,
          "select ss_item_sk, ss_sales_price from store_sales "
          "where ss_sales_price > (select avg(ss_sales_price) "
          "from store_sales)")


def test_limit_after_sort(cpu_sess, tpu_sess):
    _both(cpu_sess, tpu_sess,
          "select ss_item_sk, ss_net_paid from store_sales "
          "order by ss_net_paid desc, ss_item_sk limit 10", ordered=True)


def test_semi_anti_via_in(cpu_sess, tpu_sess):
    _both(cpu_sess, tpu_sess,
          "select count(*) as n from store_sales where ss_item_sk in "
          "(select i_item_sk from item where i_category = 'Music')")
    _both(cpu_sess, tpu_sess,
          "select count(*) as n from store_sales where ss_item_sk not in "
          "(select i_item_sk from item where i_category = 'Music')")


def test_in_list_untyped_date_literals(cpu_sess, tpu_sess):
    # plain string literals against a DATE column must coerce on BOTH
    # backends (query83 shape); result is non-empty so a silent
    # no-match bug can't hide
    out = _both(cpu_sess, tpu_sess,
                "select d_date, d_year from date_dim where d_date in "
                "('2000-06-30', '2000-09-27', '2000-11-17')")
    assert len(out.to_rows()) == 3
    # an uncoercible literal casts to NULL and never matches
    _both(cpu_sess, tpu_sess,
          "select count(*) as n from date_dim where d_date in "
          "('2000-06-30', 'not-a-date')")
    # NOT IN with a NULL-casting literal is never TRUE (NULL semantics)
    out = _both(cpu_sess, tpu_sess,
                "select count(*) as n from date_dim where d_date not in "
                "('2000-06-30', 'not-a-date')")
    assert out.to_rows()[0][0] == 0


def test_empty_result(cpu_sess, tpu_sess):
    _both(cpu_sess, tpu_sess,
          "select ss_item_sk, ss_quantity from store_sales "
          "where ss_quantity > 1000000")


def test_window_functions(cpu_sess, tpu_sess):
    _both(cpu_sess, tpu_sess,
          "select * from (select i_category, i_item_id, "
          "rank() over (partition by i_category "
          "order by i_current_price desc) as r from item) t where r <= 3")
    _both(cpu_sess, tpu_sess,
          "select ss_store_sk, ss_item_sk, "
          "sum(ss_net_paid) over (partition by ss_store_sk) as tot, "
          "row_number() over (partition by ss_store_sk "
          "order by ss_item_sk, ss_ticket_number) as rn "
          "from store_sales where ss_quantity > 40")


def test_rollup_on_device(cpu_sess, tpu_sess):
    _both(cpu_sess, tpu_sess,
          "select i_category, i_class, sum(ss_ext_sales_price) as s "
          "from store_sales, item where ss_item_sk = i_item_sk "
          "group by rollup(i_category, i_class) "
          "order by i_category, i_class", ordered=False)


def test_setops_on_device(cpu_sess, tpu_sess):
    _both(cpu_sess, tpu_sess,
          "select d_year from date_dim where d_moy = 11 intersect "
          "select d_year from date_dim where d_moy = 12")
    _both(cpu_sess, tpu_sess,
          "select i_category from item except "
          "select i_category from item where i_current_price > 50")
    _both(cpu_sess, tpu_sess,
          "select d_year from date_dim where d_year > 2000 union "
          "select d_year from date_dim where d_year < 1995")


def test_full_and_right_joins(cpu_sess, tpu_sess):
    _both(cpu_sess, tpu_sess,
          "select sr_item_sk, sr_ticket_number, ss_quantity from "
          "store_returns right join store_sales on "
          "sr_item_sk = ss_item_sk and sr_ticket_number = "
          "ss_ticket_number where ss_quantity > 45")


def test_distinct_aggregates_on_device(cpu_sess, tpu_sess):
    _both(cpu_sess, tpu_sess,
          "select ss_store_sk, count(distinct ss_item_sk) as di, "
          "sum(distinct ss_quantity) as sq, "
          "avg(distinct ss_wholesale_cost) as aw, "
          "count(ss_item_sk) as ci "
          "from store_sales group by ss_store_sk")


def test_distinct_aggregate_float_no_truncation(cpu_sess, tpu_sess):
    # distinct dedup must key on exact float values (bit pattern), not an
    # int cast; 1.5-scaling makes truncation merge distinct values
    out = _both(cpu_sess, tpu_sess,
                "select ss_store_sk, "
                "sum(distinct ss_wholesale_cost * 1.5) as s, "
                "count(distinct ss_wholesale_cost * 1.5) as c "
                "from store_sales group by ss_store_sk")
    rows = out.to_rows()
    assert any(r[1] is not None and r[1] != int(r[1]) for r in rows
               if r[1] is not None), "expected non-integer distinct sums"


def test_string_concat_on_device(cpu_sess, tpu_sess):
    # literal || column (q5/q80 shape)
    _both(cpu_sess, tpu_sess,
          "select 'store' || s_store_id as id from store")
    # column || literal || column (q84 shape) + concat() function
    _both(cpu_sess, tpu_sess,
          "select coalesce(c_last_name, '') || ', ' || "
          "coalesce(c_first_name, '') as customername, "
          "concat('id:', c_customer_id) as cid from customer")


def test_running_window_range_vs_rows(cpu_sess, tpu_sess):
    # RANGE (default): peer rows share the run value; ROWS: per-row
    _both(cpu_sess, tpu_sess,
          "select ss_store_sk, ss_sold_date_sk, "
          "sum(ss_quantity) over (partition by ss_store_sk "
          "order by ss_sold_date_sk) as run_range, "
          "sum(ss_quantity) over (partition by ss_store_sk "
          "order by ss_sold_date_sk rows between unbounded preceding "
          "and current row) as run_rows, "
          "max(ss_quantity) over (partition by ss_store_sk "
          "order by ss_sold_date_sk) as run_max "
          "from store_sales where ss_store_sk is not null "
          "and ss_sold_date_sk is not null")


def test_multi_key_join_no_radix_overflow(cpu_sess, tpu_sess):
    # 4-key equi-join exercises the composite-key re-densify path
    _both(cpu_sess, tpu_sess,
          "select count(*) as n from store_sales ss join store_returns sr "
          "on ss.ss_item_sk = sr.sr_item_sk "
          "and ss.ss_ticket_number = sr.sr_ticket_number "
          "and ss.ss_customer_sk = sr.sr_customer_sk "
          "and ss.ss_store_sk = sr.sr_store_sk")


def test_exists_under_or_mark_join(cpu_sess, tpu_sess):
    # q10/q35 shape: EXISTS subqueries under OR -> mark join on device
    _both(cpu_sess, tpu_sess,
          "select c_customer_sk from customer c where "
          "exists (select * from store_sales where ss_customer_sk = "
          "c.c_customer_sk and ss_quantity > 10) or "
          "exists (select * from web_sales where ws_bill_customer_sk = "
          "c.c_customer_sk)")


def test_compile_record_persistence(catalog, cpu_sess, tmp_path):
    """Saved size-plan records let a fresh session skip discovery and go
    straight to jitted replay, with identical results."""
    from ndstpu.engine.session import Session
    sql = ("select i_category, count(*) as n, sum(ss_net_paid) as s "
           "from store_sales join item on ss_item_sk = i_item_sk "
           "group by i_category order by i_category")
    s1 = Session(catalog, backend="tpu")
    want = s1.sql(sql).to_rows()
    path = str(tmp_path / "plans.pkl")
    assert s1.save_compiled(path) >= 1
    s2 = Session(catalog, backend="tpu")
    assert s2.preload_compiled(path) >= 1
    got = s2.sql(sql).to_rows()
    assert sorted(map(str, got)) == sorted(map(str, want))
    # the preloaded entry went straight to replay: the executor never ran
    # discovery for this SQL (its compiled record has a jitted fn now)
    cp = s2.compiled_plan(sql)
    assert cp is not None and cp.compilable and cp.fn is not None
    assert sorted(map(str, cpu_sess.sql(sql).to_rows())) == \
        sorted(map(str, got))


def test_corpus_compile_coverage(catalog):
    """Most corpus templates must compile to single XLA programs (no
    numpy fallback) — fallbacks are allowed but should be the minority.
    The static analyzer's per-part verdict must agree with the runtime
    outcome: its entire value is predicting device-vs-fallback without
    executing anything."""
    from ndstpu import analysis
    from ndstpu.engine.session import Session
    sess = Session(catalog, backend="tpu")
    tables = analysis.schema_tables()
    compiled, fallback, disagree = [], [], []
    for tpl in streamgen.list_templates():
        for name, sql in streamgen.render_template_parts(
                str(streamgen.TEMPLATE_DIR / tpl), "07291122510", 0):
            sess.sql(sql)
            cp = sess.compiled_plan(sql)
            ran_on_device = cp is not None and cp.compilable
            (compiled if ran_on_device else fallback).append(name)
            res = analysis.analyze_sql(sess, name, sql, tables=tables)
            predicted_device = res.verdict == "device"
            if predicted_device != ran_on_device:
                disagree.append(
                    (name, res.verdict,
                     "device" if ran_on_device else "fallback",
                     res.fallback_codes,
                     getattr(cp, "fallback_codes", ())))
    assert not fallback, \
        f"corpus queries falling back to numpy: {fallback}"
    assert not disagree, \
        f"static verdict vs runtime (query, static, runtime, " \
        f"static codes, runtime codes): {disagree}"


def test_compiled_replay_path(catalog, cpu_sess):
    """Second execution of a query must run the jitted whole-query
    program (replay) and agree with both the first run and the CPU
    interpreter."""
    from ndstpu.engine.session import Session
    sess = Session(catalog, backend="tpu")
    sql = ("select i_category, count(*) as cnt, "
           "sum(ss_ext_sales_price) as s "
           "from store_sales join item on ss_item_sk = i_item_sk "
           "where ss_quantity > 5 "
           "group by i_category order by i_category")
    first = sess.sql(sql)
    cp = sess.compiled_plan(sql)
    assert cp is not None
    assert cp.compilable and cp.fn is not None
    second = sess.sql(sql)   # replay path
    assert_tables_match(first, second, ordered=True)
    assert_tables_match(cpu_sess.sql(sql), second, ordered=True)


def test_steady_state_no_retrace(catalog, cpu_sess, monkeypatch):
    """With replay warm-up on (the bench configuration), the FIRST
    execute_cached pays discovery + jit compile; every later execution
    must dispatch only already-compiled programs — no discovery, no new
    jit builds, no retrace.  Guards the r03 regression where query1's
    'steady-state' second run took 59.4 s recompiling its replay."""
    monkeypatch.setenv("NDSTPU_WARM_REPLAY", "1")
    from ndstpu.engine.session import Session
    sess = Session(catalog, backend="tpu")
    sql = ("select i_category, count(*) as n, sum(ss_net_paid) as s "
           "from store_sales join item on ss_item_sk = i_item_sk "
           "where ss_quantity > 2 group by i_category "
           "order by i_category")
    first = sess.sql(sql)
    exe = sess._jax_executor()
    assert exe.warm_replay
    cp = sess.compiled_plan(sql)
    assert cp is not None and cp.compilable and cp.fn is not None
    # warm-up already validated the jitted program during discovery
    assert cp.fn_validated
    disc, builds = exe.n_discoveries, exe.n_jit_builds
    caches = [cp.fn] + [exe._seg_compiled[fp].fn
                        for fp in (cp.seg_fps or ())]
    sizes = [f._cache_size() for f in caches if f is not None]
    for _ in range(2):
        got = sess.sql(sql)
        assert_tables_match(first, got, ordered=True)
    assert exe.n_discoveries == disc, "steady-state run re-discovered"
    assert exe.n_jit_builds == builds, "steady-state run re-built a jit"
    assert [f._cache_size() for f in caches
            if f is not None] == sizes, "steady-state run re-traced"
    assert_tables_match(cpu_sess.sql(sql), got, ordered=True)


def test_compiled_invalidation_on_dml(catalog):
    """Catalog version changes must invalidate compiled plans (stale
    baked subquery literals / table uploads)."""
    from ndstpu.engine.session import Session
    sess = Session(catalog, backend="tpu")
    sql = "select count(*) as n from item"
    before = sess.sql(sql).to_rows()[0][0]
    item = catalog.get("item")
    import numpy as np
    keep = np.ones(item.num_rows, dtype=bool)
    if item.num_rows:
        keep[0] = False
    catalog.register("item", item.filter(keep))
    after = sess.sql(sql).to_rows()[0][0]
    assert after == before - (1 if before else 0)
    # restore for other tests
    catalog.register("item", item)


# -- group-by paths (sort / direct small-domain with the segsum kernel) ------

_GB_QUERIES = {
    # int key with static bounds + decimal sum (kernel-eligible)
    "bounded_int_decimal_sum":
    "select ss_store_sk, sum(ss_ext_sales_price) as s, count(*) as n "
    "from store_sales group by ss_store_sk",
    # dictionary-coded string key + avg + min/max
    "string_key":
    "select i_category, avg(i_current_price) as p, min(i_brand_id) as lo, "
    "max(i_brand_id) as hi from item group by i_category",
    # composite string x int domain; NULL keys from outer join misses
    "composite_null_keys":
    "select i_category, ss_store_sk, sum(ss_quantity) as q, "
    "count(ss_item_sk) as n from store_sales "
    "left join item on ss_item_sk = i_item_sk "
    "group by i_category, ss_store_sk",
    # float aggregate: exercises the lazy-order compensated path
    "float_aggregates":
    "select d_year, stddev_samp(ss_sales_price) as sd, "
    "avg(ss_net_profit) as m from store_sales "
    "join date_dim on ss_sold_date_sk = d_date_sk group by d_year",
    # composite int domain over the cap (1922 tickets x 1833 items at
    # this scale, 3.5 M slots): must fall back to the sort path
    "ticket_item_sort":
    "select ss_ticket_number, ss_item_sk, count(*) as n from store_sales "
    "group by ss_ticket_number, ss_item_sk",
    "rollup":
    "select i_category, i_class, count(*) as n from item "
    "group by rollup(i_category, i_class)",
}


@pytest.mark.parametrize("name", list(_GB_QUERIES))
def test_groupby_paths_differential(catalog, cpu_sess, monkeypatch, name):
    """Each group-by shape against the numpy engine, in discovery and
    in the compiled replay, on the path its key domain selects: the
    linearized group ids for bounded keys, the sort where the domain is
    over the cap."""
    from ndstpu.engine import jaxexec
    answered = []
    direct = jaxexec.JaxExecutor._direct_group_ids

    def spy(self, key_cols, alive):
        out = direct(self, key_cols, alive)
        answered.append(out is not None)
        return out

    monkeypatch.setattr(jaxexec.JaxExecutor, "_direct_group_ids", spy)
    sql = _GB_QUERIES[name]
    sess = Session(catalog, backend="tpu")
    want = cpu_sess.sql(sql)
    for _run in ("discovery", "replay"):
        assert_tables_match(want, sess.sql(sql))
    assert answered
    if name == "ticket_item_sort":
        assert not any(answered)
    else:
        assert all(answered)


def test_replay_contains_segsum_kernel(catalog, cpu_sess):
    """A Pallas kernel is part of a traced replay program and of nothing
    else (`_pallas_kernel`): discovery sums a kernel-eligible decimal
    by scatter, the replay's program contains the interpreted kernel
    (the counter ticks when a program is traced), and both equal the
    numpy engine."""
    from ndstpu import obs
    sql = _GB_QUERIES["bounded_int_decimal_sum"]
    want = cpu_sess.sql(sql)
    obs.reset(enabled=True)
    try:
        sess = Session(catalog, backend="tpu")
        assert_tables_match(want, sess.sql(sql))            # discovery
        assert "engine.pallas.segsum_calls" not in obs.counters_snapshot()
        assert_tables_match(want, sess.sql(sql))            # traced
        assert obs.counters_snapshot()["engine.pallas.segsum_calls"] == 1
        assert_tables_match(want, sess.sql(sql))            # not again
        assert obs.counters_snapshot()["engine.pallas.segsum_calls"] == 1
    finally:
        obs.reset()


def test_groupby_direct_path_engages(catalog):
    """The small-domain linearized-gid path must actually be taken for a
    bounded int key (not silently fall back to the sort path)."""
    sess = Session(catalog, backend="tpu")
    exe = sess._jax_executor()
    from ndstpu.engine import jaxexec
    dt = jaxexec.to_device(catalog.get("store_sales"))
    key = dt.columns["ss_store_sk"]
    assert key.bounds is not None
    direct = exe._direct_group_ids([("k", key)], dt.alive)
    assert direct is not None
    gid, ngseg, out_alive, out_cols, order = direct
    lo, hi = key.bounds
    assert ngseg == (hi - lo + 1 + 1) + 1  # +NULL slot, +trash slot
    # pallas eligibility for the decimal measure column
    assert exe._pallas_sum_ok(dt.columns["ss_ext_sales_price"], ngseg)


def test_cast_preserves_bounds(catalog):
    """Value-preserving casts must carry column bounds through, so a
    CASE whose common type is decimal (or with one int64 branch) stays
    on the dense/bitmap group-by paths instead of falling to the sort
    path (r5 roadmap: bounds-through-cast)."""
    from ndstpu.engine import jaxexec
    from ndstpu.schema import DType

    dt = jaxexec.to_device(catalog.get("store_sales"))
    ev = jaxexec.JEval(dt)
    key = dt.columns["ss_store_sk"]
    assert key.bounds is not None
    lo, hi = key.bounds

    # int32 -> int64 widening preserves bounds exactly
    wide = ev.cast(key, DType("int64"))
    assert wide.bounds == (lo, hi)
    # int -> decimal scales bounds by 10^scale
    dec = ev.cast(key, DType("decimal", precision=12, scale=2))
    assert dec.bounds == (lo * 100, hi * 100)
    # decimal identity (same scale, wider precision) keeps bounds
    dec2 = ev.cast(dec, DType("decimal", precision=18, scale=2))
    assert dec2.bounds == (lo * 100, hi * 100)
    # decimal scale-up multiplies; scale-down divides monotonically
    up = ev.cast(dec, DType("decimal", precision=18, scale=4))
    assert up.bounds == (lo * 10000, hi * 10000)
    down = ev.cast(up, DType("decimal", precision=18, scale=2))
    assert down.bounds == (lo * 100, hi * 100)
    # decimal -> int truncates toward zero
    back = ev.cast(dec, DType("int32"))
    assert back.bounds == (lo, hi)


def test_case_of_decimal_literals_keeps_dense_groupby(catalog, cpu_sess):
    """A CASE key whose common type is decimal must still reach the
    small-domain direct group-by path (pre-fix: cast() dropped the
    branch bounds and the plan fell to the full sort path)."""
    from ndstpu.engine import jaxexec

    sql = ("select case when ss_quantity < 10 then 0.5 "
           "when ss_quantity < 50 then 1.5 else 2.5 end as bucket, "
           "count(*) as n, sum(ss_ext_sales_price) as s "
           "from store_sales group by bucket")
    sess = Session(catalog, backend="tpu")
    assert_tables_match(cpu_sess.sql(sql), sess.sql(sql))
    # the key expression itself must carry bounds through the decimal
    # casts the CASE inserts
    dt = jaxexec.to_device(catalog.get("store_sales"))
    ev = jaxexec.JEval(dt)
    from ndstpu.engine import expr as ex
    from ndstpu.schema import DType
    dt10 = DType("decimal", precision=3, scale=1)
    case = ex.Case(
        ((ex.BinOp("<", ex.ColumnRef("ss_quantity"), ex.Literal(10)),
          ex.Literal(0.5, dt10)),
         (ex.BinOp("<", ex.ColumnRef("ss_quantity"), ex.Literal(50)),
          ex.Literal(1.5, dt10))),
        ex.Literal(2.5, dt10))
    out = ev.eval(case)
    assert out.ctype.kind == "decimal"
    assert out.bounds == (5, 25)


def test_coalesce_decimal_literal_stays_decimal(cpu_sess, tpu_sess):
    """Spark types `0.0` as DECIMAL(1,1), so coalesce(decimal, 0.0)
    must stay DECIMAL (exact scaled-int math on TPU) instead of
    promoting to emulated f64 — q75's UNION-distinct drifted on real
    hardware when the money column went through float."""
    sql = ("select ss_item_sk, "
           "ss_ext_sales_price - coalesce(ss_ext_discount_amt, 0.0) as x "
           "from store_sales order by ss_item_sk, x limit 50")
    a = cpu_sess.sql(sql)
    b = tpu_sess.sql(sql)
    from ndstpu.schema import DType  # noqa: F401
    assert a.columns["x"].ctype.kind == "decimal"
    assert b.columns["x"].ctype.kind == "decimal"
    assert a.to_rows() == b.to_rows()


def test_distinct_bitmap_path_matches_sort_path(catalog, cpu_sess, tpu_sess):
    """Small-domain int/decimal distinct aggregates take the presence-
    bitmap path (no sort); results must equal the CPU interpreter and
    the sort path (forced by shrinking the slot budget)."""
    sql = ("select ss_store_sk, count(distinct ss_quantity) cd, "
           "sum(distinct ss_quantity) sd, avg(distinct ss_quantity) ad, "
           "count(distinct ss_list_price) cdp "
           "from store_sales group by ss_store_sk order by ss_store_sk")
    want = cpu_sess.sql(sql).to_rows()
    got = tpu_sess.sql(sql).to_rows()
    assert _rows_equal(got, want)
    # force the sort path and compare (same session would reuse the
    # compiled plan, so use a fresh one with a tiny slot budget)
    from ndstpu.engine import jaxexec
    sort_sess = Session(catalog, backend="tpu")
    exe = sort_sess._jax_executor()
    exe._DISTINCT_BITMAP_SLOTS = 0
    got_sort = sort_sess.sql(sql).to_rows()
    assert _rows_equal(got_sort, want)


def test_pivot_rewrite_fires_and_matches(catalog, cpu_sess, tpu_sess):
    """The masked-sum pivot rewrite (optimizer.pivot_case_aggregates)
    must fire on a q2-style aggregate and produce identical results."""
    sql = ("select d_week_seq, "
           "sum(case when d_day_name='Sunday' then ss_net_paid else null end) s1, "
           "sum(case when d_day_name='Monday' then ss_net_paid else null end) s2, "
           "sum(case when d_day_name='Tuesday' then ss_net_paid else null end) s3, "
           "count(*) n "
           "from store_sales join date_dim on ss_sold_date_sk = d_date_sk "
           "group by d_week_seq order by d_week_seq limit 50")
    p, _cols = cpu_sess.plan(sql)
    from ndstpu.engine import plan as lp

    def has_pivot(node):
        if isinstance(node, lp.Aggregate) and \
                any(n == "__pv_s" for n, _ in node.group_by):
            return True
        return any(has_pivot(c) for c in node.children())

    assert has_pivot(p), "pivot rewrite did not fire"
    want = cpu_sess.sql(sql).to_rows()
    got = tpu_sess.sql(sql).to_rows()
    assert _rows_equal(got, want)


def test_null_filter_left_join_becomes_anti(catalog, cpu_sess, tpu_sess):
    """q78's refresh-exclusion idiom must plan as an ANTI join, and the
    right key must still resolve (as NULL) when selected."""
    sql = ("select ss_ticket_number, sr_ticket_number "
           "from store_sales left join store_returns "
           "on sr_ticket_number = ss_ticket_number "
           "and ss_item_sk = sr_item_sk "
           "where sr_ticket_number is null "
           "order by ss_ticket_number limit 20")
    from ndstpu.engine import plan as lp
    p, _cols = cpu_sess.plan(sql)
    kinds = []

    def walk(n):
        if isinstance(n, lp.Join):
            kinds.append(n.kind)
        for c in n.children():
            walk(c)

    walk(p)
    assert "anti" in kinds, kinds
    want = cpu_sess.sql(sql).to_rows()
    got = tpu_sess.sql(sql).to_rows()
    assert len(want) == 20 and all(r[1] is None for r in want)
    assert _rows_equal(got, want)


def test_anti_rewrite_blocked_when_parent_selects_right_column(
        catalog, cpu_sess, tpu_sess):
    """Selecting a NON-key right column (legal, all-NULL) must not be
    broken by the anti-join conversion."""
    sql = ("select ss_ticket_number, sr_returned_date_sk "
           "from store_sales left join store_returns "
           "on sr_ticket_number = ss_ticket_number "
           "and ss_item_sk = sr_item_sk "
           "where sr_ticket_number is null "
           "order by ss_ticket_number limit 10")
    want = cpu_sess.sql(sql).to_rows()
    assert len(want) == 10 and all(r[1] is None for r in want)
    got = tpu_sess.sql(sql).to_rows()
    assert _rows_equal(got, want)


def test_pivot_keyless_count_on_empty_input(cpu_sess, tpu_sess):
    """A keyless pivoted aggregate over zero rows must keep count()=0
    (sum-of-partials over no rows is NULL; the rewrite coalesces)."""
    sql = ("select sum(case when d_day_name='Sunday' then d_year end) a, "
           "sum(case when d_day_name='Monday' then d_year end) b, "
           "sum(case when d_day_name='Tuesday' then d_year end) c, "
           "count(*) n "
           "from date_dim where d_year = -5")
    want = cpu_sess.sql(sql).to_rows()
    got = tpu_sess.sql(sql).to_rows()
    assert want == [(None, None, None, 0)]
    assert _rows_equal(got, want)


def test_compile_records_merge_not_truncate(catalog, tmp_path):
    """A subset session saving records must MERGE with the on-disk file
    (a 12-query validation run must never truncate a full-corpus warm),
    and the write must be atomic."""
    rec = str(tmp_path / "plans.pkl")
    s1 = Session(catalog, backend="tpu")
    s1.sql("select ss_store_sk, sum(ss_quantity) q from store_sales "
           "group by ss_store_sk").to_rows()
    s1.sql("select i_category, count(*) n from item "
           "group by i_category").to_rows()
    n1 = s1.save_compiled(rec)
    assert n1 >= 2
    s2 = Session(catalog, backend="tpu")
    s2.sql("select d_year, count(*) n from date_dim "
           "group by d_year").to_rows()
    n2 = s2.save_compiled(rec)
    assert n2 >= n1 + 1, "merge lost prior records"
    s3 = Session(catalog, backend="tpu")
    assert s3.preload_compiled(rec) >= n1 + 1


def test_sibling_scalar_agg_fusion_fires_and_matches(catalog, cpu_sess,
                                                     tpu_sess):
    """The q28 idiom (cross-joined keyless aggregates over the same
    table with disjoint-interval filters) must fuse into ONE scan +
    one grouped aggregate, and produce identical results on both
    backends — including the count(distinct) columns."""
    sql = ("select * from "
           "(select avg(ss_list_price) a1, count(ss_list_price) c1, "
           " count(distinct ss_list_price) d1 from store_sales "
           " where ss_quantity between 0 and 5) b1, "
           "(select avg(ss_list_price) a2, count(ss_list_price) c2, "
           " count(distinct ss_list_price) d2 from store_sales "
           " where ss_quantity between 6 and 10) b2, "
           "(select avg(ss_list_price) a3, count(ss_list_price) c3, "
           " count(distinct ss_list_price) d3 from store_sales "
           " where ss_quantity between 11 and 15) b3")
    from ndstpu.engine import plan as lp
    p, _cols = cpu_sess.plan(sql)
    scans = [n for n in p.walk() if isinstance(n, lp.Scan)]
    assert len(scans) == 1, "fusion did not collapse the sibling scans"
    grouped = [n for n in p.walk() if isinstance(n, lp.Aggregate)
               and any(name.endswith("_b") for name, _ in n.group_by)]
    assert grouped, "no bucket-grouped aggregate in the fused plan"
    want = cpu_sess.sql(sql).to_rows()
    got = tpu_sess.sql(sql).to_rows()
    assert len(want) == 1
    assert _rows_equal(got, want)
    # ground truth from a session with the pass disabled — both
    # backends above share the optimizer, so a systematic soundness
    # bug (e.g. buckets swapped between branches) would match itself
    from ndstpu.engine import optimizer as opt
    orig = opt.fuse_sibling_scalar_aggregates
    opt.fuse_sibling_scalar_aggregates = lambda p, _used=None: p
    try:
        unfused_sess = Session(cpu_sess.catalog, backend="cpu")
        unfused = unfused_sess.sql(sql).to_rows()
    finally:
        opt.fuse_sibling_scalar_aggregates = orig
    assert _rows_equal(want, unfused)


def test_sibling_scalar_agg_fusion_empty_bucket(catalog, cpu_sess,
                                                tpu_sess):
    """A branch whose interval matches no rows must keep scalar-
    aggregate semantics through the fusion: avg NULL, counts 0."""
    sql = ("select * from "
           "(select avg(ss_list_price) a1, count(ss_list_price) c1, "
           " count(distinct ss_list_price) d1 from store_sales "
           " where ss_quantity between 0 and 5) b1, "
           "(select avg(ss_list_price) a2, count(ss_list_price) c2, "
           " count(distinct ss_list_price) d2 from store_sales "
           " where ss_quantity between 1000000 and 1000005) b2")
    want = cpu_sess.sql(sql).to_rows()
    got = tpu_sess.sql(sql).to_rows()
    assert len(want) == 1
    assert want[0][3] is None and want[0][4] == 0 and want[0][5] == 0
    assert _rows_equal(got, want)


def test_sibling_scalar_agg_fusion_rejects_overlap(catalog, cpu_sess):
    """Overlapping intervals must NOT fuse (a row could belong to two
    branches) — and the un-fused plan must still answer correctly."""
    sql = ("select * from "
           "(select count(ss_list_price) c1 from store_sales "
           " where ss_quantity between 0 and 10) b1, "
           "(select count(ss_list_price) c2 from store_sales "
           " where ss_quantity between 5 and 15) b2")
    from ndstpu.engine import plan as lp
    p, _cols = cpu_sess.plan(sql)
    scans = [n for n in p.walk() if isinstance(n, lp.Scan)]
    assert len(scans) == 2, "overlapping intervals must not fuse"


# -- lookup join: a build side with unique alive keys -----------------------
#
# fact probes dim on its key.  Every case runs on Session(backend="cpu")
# and twice on a fresh Session(backend="tpu") (discovery, then the jitted
# replay with the recorded branch and its guard).

_LJ_ROWS = 1000     # probe rows (capacity 1024: survivors of a filtered
#                     dimension fit a smaller size class and compact)


def _i32(vals):
    from ndstpu.engine.columnar import INT32, Column
    valid = np.array([v is not None for v in vals])
    data = np.array([0 if v is None else v for v in vals], dtype=np.int32)
    return Column(data, INT32, None if valid.all() else valid)


def _lookup_catalog(nulls: str):
    """fact(f_id, f_k, f_v, f_hit) and dim(d_k, d_grp, d_val, d_name).
    ``nulls``: which side has NULL join keys (probe / build / both),
    ``dup`` (no NULLs, every third build key twice), or ``edge`` (no
    NULLs, 300 build keys: filters keep 256 or 257 of them, the last of
    one capacity class and the first of the next), or ``wide`` (NULLs on
    both sides, 8000 probe rows: capacity 8192)."""
    from ndstpu.engine.columnar import Column, Table
    from ndstpu.io.loader import Catalog
    rng = np.random.default_rng(26)
    n_rows = 8000 if nulls == "wide" else _LJ_ROWS
    n_dim = 300 if nulls == "edge" else 60
    # every fifth value of the key domain: wider than any capacity of
    # alive keys here, as a dimension's domain is
    d_k = list(range(100, 100 + 5 * n_dim, 5))
    if nulls in ("build", "both", "wide"):
        d_k[5] = d_k[17] = None
    if nulls == "dup":
        d_k = d_k + d_k[::3]
    # four probe keys in five are on the build side's grid (those of
    # its NULLed and filtered rows too)
    f_k = [100 + 5 * int(i) + int(off) for i, off in zip(
        rng.integers(-2, n_dim + 2, n_rows),
        rng.random(n_rows) >= 0.8)]
    if nulls in ("probe", "both", "wide"):
        for i in range(0, n_rows, 7):
            f_k[i] = None
    alive_dim = {k for k in d_k if k is not None}
    cat = Catalog()
    cat.register("fact", Table({
        "f_id": _i32(list(range(n_rows))),
        "f_k": _i32(f_k),
        "f_v": _i32([int(v) for v in rng.integers(0, 50, n_rows)]),
        "f_hit": _i32([int(k in alive_dim) for k in f_k]),
    }))
    cat.register("dim", Table({
        "d_k": _i32(d_k),
        "d_grp": _i32([i % 4 for i in range(len(d_k))]),
        "d_val": _i32([(7 * i) % 50 for i in range(len(d_k))]),
        "d_name": Column.from_strings([f"name{i % 9}"
                                       for i in range(len(d_k))]),
    }))
    return cat


_LJ_CATALOGS = {}


def _lj_catalog(nulls):
    if nulls not in _LJ_CATALOGS:
        _LJ_CATALOGS[nulls] = _lookup_catalog(nulls)
    return _LJ_CATALOGS[nulls]


_LJ_SHAPES = {
    # (probe side, build side)
    "whole": ("fact", "dim"),
    "filtered": ("fact", "(select * from dim where d_grp = 1) d"),
    "none": ("fact", "(select * from dim where d_grp = 99) d"),
    "all": ("(select * from fact where f_hit = 1) f", "dim"),
    # alive build keys at a capacity class's edge (catalog "edge")
    "k256": ("fact", "(select * from dim where d_k < 1380) d"),
    "k257": ("fact", "(select * from dim where d_k < 1385) d"),
    # a probe capacity that is no power of two (catalog "wide"): UNION
    # ALL keeps both branches' capacities, 256 survivors of a join (ids
    # moved under the fact's: the probe's order stays ascending) + 8192
    "union": ("(select f_id - 10000 f_id, f_k, f_v from "
              "(select * from fact where f_v < 5) f1 join "
              "(select * from dim where d_grp = 1) d1 on f_k = d_k "
              "union all select f_id, f_k, f_v from fact) f", "dim"),
}


def _lj_sql(kind, shape, extra):
    probe, build = _LJ_SHAPES[shape]
    on = "f_k = d_k" + (" and f_v < d_val" if extra else "")
    return (f"select f_id, f_k, f_v, d_k, d_val, d_name from {probe} "
            f"{kind} join {build} on {on}")


_LJ_CASES = [(k, s, n, e) for k in ("inner", "left")
             for s in ("whole", "filtered", "none", "all")
             for n in ("probe", "build", "both")
             for e in (False, True)] + \
            [(k, "whole", "dup", e) for k in ("inner", "left")
             for e in (False, True)] + \
            [(k, s, "edge", False) for k in ("inner", "left")
             for s in ("k256", "k257")] + \
            [(k, "union", "wide", False) for k in ("inner", "left")]
# how a unique-key lookup finds each probe row's build row: by comparing
# with the alive build keys, or by a gather from a table over the key
# domain; forced through the constant the choice rests on
_LJ_METHODS = {"compare": 0.0, "gather": 1e9}


@pytest.mark.parametrize("method", list(_LJ_METHODS))
@pytest.mark.parametrize("kind,shape,nulls,extra", _LJ_CASES)
def test_lookup_join(monkeypatch, kind, shape, nulls, extra, method):
    from ndstpu.engine import jaxexec
    monkeypatch.setattr(jaxexec, "_COMPARE_PAIR_COST", _LJ_METHODS[method])
    catalog = _lj_catalog(nulls)
    sql = _lj_sql(kind, shape, extra)
    want = Session(catalog, backend="cpu").sql(sql)
    sess = Session(catalog, backend="tpu")
    for _run in ("discovery", "replay"):
        got = sess.sql(sql)
        assert got.column_names == want.column_names
        assert_tables_match(want, got)
        if kind == "inner":
            # output rows keep the probe's order
            ids = [r[0] for r in got.to_rows()]
            assert ids == sorted(ids)
    cp = sess.compiled_plan(sql)
    assert cp is not None and cp.compilable
    # a duplicated build key expands; unique alive keys look up
    # (lookup, expand, sort, compare: a compare is a lookup too; and
    # deferred: none here, no lookup probes another's survivors)
    n_joins = 2 if shape == "union" else 1
    assert cp.join_paths == (
        (0, 1, 0, 0, 0) if nulls == "dup" else
        (n_joins, 0, 0, n_joins if method == "compare" else 0, 0))
    if shape == "union":
        assert ("cap", 256) in cp.record and want.num_rows > 5000
    if nulls == "edge" and method == "compare":
        # the alive build keys' capacity: the size plan's first entry
        # of the join
        assert ("cap", 256 if shape == "k256" else 512) in cp.record
    # bounds and dictionaries of both sides' columns survive the join
    meta = {name: (d, b) for name, _ct, d, b in cp.out_meta}
    base = sess._jax_executor()._table_device
    for name, table in (("f_k", "fact"), ("f_v", "fact"), ("d_val", "dim")):
        bounds = base(table).column(name).bounds
        assert bounds is not None and meta[name][1] == bounds
    assert list(meta["d_name"][0]) == \
        list(base("dim").column("d_name").dictionary)


@pytest.mark.parametrize("method", list(_LJ_METHODS))
def test_lookup_join_int64_composite_key(monkeypatch, method):
    """Three key pairs whose radixes pass 2^62 re-densify: the composite
    key is int64 though its domain fits the tables, and both lookup
    methods take it (the compare narrows it: the domain fits int32)."""
    import jax.numpy as jnp
    from ndstpu.engine import jaxexec
    from ndstpu.engine.columnar import Table
    from ndstpu.io.loader import Catalog
    monkeypatch.setattr(jaxexec, "_COMPARE_PAIR_COST", _LJ_METHODS[method])
    seen = []
    join_keys = jaxexec.JaxExecutor._join_keys
    monkeypatch.setattr(
        jaxexec.JaxExecutor, "_join_keys",
        lambda self, *a: seen.append(join_keys(self, *a)) or seen[-1])
    rng = np.random.default_rng(29)
    n_dim = 40
    d_a = [0, 2 ** 31 - 1] + [int(v) for v in
                              rng.integers(1, 2 ** 31 - 1, n_dim - 2)]
    d_b = [0, 2 ** 30 - 1] + [int(v) for v in
                              rng.integers(1, 2 ** 30 - 1, n_dim - 2)]
    d_g = [i % 4 for i in range(n_dim)]
    pick = rng.integers(0, n_dim, _LJ_ROWS)
    miss = rng.random(_LJ_ROWS) < 0.3
    cat = Catalog()
    cat.register("dim", Table({
        "d_a": _i32(d_a), "d_b": _i32(d_b), "d_g": _i32(d_g),
        "d_val": _i32(list(range(n_dim)))}))
    cat.register("fact", Table({
        "f_id": _i32(list(range(_LJ_ROWS))),
        "f_a": _i32([d_a[i] for i in pick]),
        "f_b": _i32([d_b[i] for i in pick]),
        "f_g": _i32([(d_g[i] + int(x)) % 4 for i, x in zip(pick, miss)])}))
    sql = ("select f_id, d_val from fact join dim "
           "on f_a = d_a and f_b = d_b and f_g = d_g")
    want = Session(cat, backend="cpu").sql(sql)
    assert 0 < want.num_rows < _LJ_ROWS
    sess = Session(cat, backend="tpu")
    for _run in ("discovery", "replay"):
        assert_tables_match(want, sess.sql(sql), ordered=True)
    assert all(k[0].dtype == jnp.int64 and k[4] < 2 ** 20 for k in seen)
    assert sess.compiled_plan(sql).join_paths == \
        ((1, 0, 0, 1, 0) if method == "compare" else (1, 0, 0, 0, 0))


@pytest.mark.parametrize("method", ["search", "scatter"])
@pytest.mark.parametrize("n,frac", [(256, 0.0), (1024, 0.01), (1024, 0.3),
                                    (4096, 0.2), (4096, 1.0)])
def test_survivor_positions(monkeypatch, method, n, frac):
    """Both compaction methods give the set rows' positions, ascending,
    and valid positions past them."""
    import jax.numpy as jnp
    from ndstpu.engine import jaxexec
    monkeypatch.setattr(jaxexec, "_SEARCH_COMPACT_COST",
                        0 if method == "search" else 10 ** 9)
    mask = np.random.default_rng(n).random(n) < frac
    k = int(mask.sum())
    cap = jaxexec.size_class(max(k, 1))
    src = np.asarray(jaxexec.JaxExecutor._survivor_positions(
        jnp.asarray(mask), cap))
    assert src.shape == (cap,)
    assert (src[:k] == np.nonzero(mask)[0]).all()
    assert ((src >= 0) & (src < n)).all()


@pytest.mark.parametrize("method", ["search", "scatter"])
def test_lookup_join_compaction_methods(monkeypatch, method):
    from ndstpu.engine import jaxexec
    monkeypatch.setattr(jaxexec, "_SEARCH_COMPACT_COST",
                        0 if method == "search" else 10 ** 9)
    catalog = _lj_catalog("both")
    sql = _lj_sql("inner", "filtered", True)
    want = Session(catalog, backend="cpu").sql(sql)
    sess = Session(catalog, backend="tpu")
    for _run in ("discovery", "replay"):
        assert_tables_match(want, sess.sql(sql), ordered=True)


_GUARD_SQL = ("select f_id, d_k, d_val from fact join "
              "(select * from dim where d_grp = {grp}) d on f_v = d_val")


def _guard_catalog():
    """dim.d_val is no key: unique among the rows of d_grp 1, duplicated
    among those of d_grp 2."""
    from ndstpu.engine.columnar import Table
    from ndstpu.io.loader import Catalog
    src = _lookup_catalog("probe")
    dim = src.get("dim")
    d_val = [(i if g == 1 else i % 5) for i, g in
             enumerate(np.asarray(dim.column("d_grp").data))]
    d_val[3] = 1000   # (d_grp 3) a key domain wider than the alive keys
    cat = Catalog()
    cat.register("fact", src.get("fact"))
    cat.register("dim", Table({**dim.columns, "d_val": _i32(d_val)}))
    return cat


@pytest.mark.parametrize("method", list(_LJ_METHODS))
def test_lookup_join_guard_rediscovers(monkeypatch, method):
    """The uniqueness the lookup rests on is a replay guard, under either
    lookup method (compare: the sorted alive keys differ; gather: no
    count over the key domain passes 1): a parameter draw (same compiled
    key, same catalog version) or a swapped-in table whose alive build
    keys repeat rediscovers onto the expand path and answers as the
    reference does."""
    import warnings
    from ndstpu import obs
    from ndstpu.engine import jaxexec
    monkeypatch.setattr(jaxexec, "_COMPARE_PAIR_COST", _LJ_METHODS[method])
    looked_up = (1, 0, 0, 1 if method == "compare" else 0, 0)
    catalog = _guard_catalog()
    cpu = Session(catalog, backend="cpu")
    sess = Session(catalog, backend="tpu")
    uniq, dup = _GUARD_SQL.format(grp=1), _GUARD_SQL.format(grp=2)
    assert sess.canonical_key(uniq) == sess.canonical_key(dup)
    for _run in ("discovery", "replay"):
        assert_tables_match(cpu.sql(uniq), sess.sql(uniq))
    assert sess.compiled_plan(uniq).join_paths == looked_up
    before = obs.counters_snapshot().get("engine.discoveries", 0)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        assert_tables_match(cpu.sql(dup), sess.sql(dup))
    assert any("rediscover" in str(w.message) for w in caught)
    assert obs.counters_snapshot()["engine.discoveries"] == before + 1
    assert_tables_match(cpu.sql(dup), sess.sql(dup))     # replay
    assert sess.compiled_plan(dup).join_paths == (0, 1, 0, 0, 0)
    # and back: unique again under the first draw
    assert_tables_match(cpu.sql(uniq), sess.sql(uniq))
    assert_tables_match(cpu.sql(uniq), sess.sql(uniq))
    assert sess.compiled_plan(uniq).join_paths == looked_up
    # a table swapped in under a new catalog version
    from ndstpu.engine.columnar import Table
    dim = catalog.get("dim")
    catalog.register("dim", Table({
        **dim.columns, "d_val": _i32([i % 3 for i in range(dim.num_rows)])}))
    for _run in ("discovery", "replay"):
        assert_tables_match(cpu.sql(uniq), sess.sql(uniq))
    assert sess.compiled_plan(uniq).join_paths == (0, 1, 0, 0, 0)


_EDGE_SQL = ("select f_id, d_k, d_val from fact join "
             "(select * from dim where d_k < {hi}) d on f_k = d_k")


def test_compare_join_capacity_guard_rediscovers():
    """The compare path is traced for a capacity of alive build keys,
    a replay guard like its uniqueness branch (exercised above): a
    later binding of the same compiled key that keeps
    one build key more than the class holds rediscovers once, answers
    as the reference does, and compares over the next class."""
    import warnings
    from ndstpu import obs
    catalog = _lj_catalog("edge")
    cpu = Session(catalog, backend="cpu")
    sess = Session(catalog, backend="tpu")
    fits, outgrows = _EDGE_SQL.format(hi=1380), _EDGE_SQL.format(hi=1385)
    assert sess.canonical_key(fits) == sess.canonical_key(outgrows)
    for _run in ("discovery", "replay"):
        assert_tables_match(cpu.sql(fits), sess.sql(fits))
    cp = sess.compiled_plan(fits)
    assert cp.join_paths == (1, 0, 0, 1, 0) and ("cap", 256) in cp.record
    before = obs.counters_snapshot().get("engine.discoveries", 0)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        assert_tables_match(cpu.sql(outgrows), sess.sql(outgrows))
    assert any("rediscover" in str(w.message) for w in caught)
    assert_tables_match(cpu.sql(outgrows), sess.sql(outgrows))   # replay
    assert obs.counters_snapshot()["engine.discoveries"] == before + 1
    cp = sess.compiled_plan(outgrows)
    assert cp.join_paths == (1, 0, 0, 1, 0) and ("cap", 512) in cp.record
    # the smaller draw fits the larger class: no rediscovery back
    assert_tables_match(cpu.sql(fits), sess.sql(fits))
    assert obs.counters_snapshot()["engine.discoveries"] == before + 1


def test_compile_records_older_format_ignored(tmp_path):
    """A record file written before the size plans gained the alive
    build keys' capacity (format 5) loads nothing and is rewritten, not
    merged."""
    import pickle
    from ndstpu.engine.jaxexec import CompilingExecutor
    assert CompilingExecutor._REC_FORMAT == 6
    catalog = _lj_catalog("probe")
    sql = _lj_sql("inner", "filtered", False)
    s1 = Session(catalog, backend="tpu")
    want = s1.sql(sql).to_rows()
    path = str(tmp_path / "plans.pkl")
    assert s1.save_compiled(path) == 1
    with open(path, "rb") as f:
        data = pickle.load(f)
    data["\x00fmt"] = 5
    data["select 'written by format 5'"] = data[sql]
    with open(path, "wb") as f:
        pickle.dump(data, f)
    s2 = Session(catalog, backend="tpu")
    assert s2.preload_compiled(path) == 0
    assert s2.sql(sql).to_rows() == want      # discovery runs
    assert s2._jax_executor().n_discoveries == 1
    assert s2.save_compiled(path) == 1
    with open(path, "rb") as f:
        data = pickle.load(f)
    assert data["\x00fmt"] == 6 and sql in data
    assert "select 'written by format 5'" not in data
    assert Session(catalog, backend="tpu").preload_compiled(path) == 1


@pytest.mark.parametrize("method", list(_LJ_METHODS))
def test_join_path_counters_and_span(monkeypatch, catalog, method):
    """Each replay adds its programs' join operators, by the path each
    took at trace time, to four counters and to the replay span."""
    from ndstpu import obs
    from ndstpu.engine import jaxexec
    monkeypatch.setattr(jaxexec, "_COMPARE_PAIR_COST", _LJ_METHODS[method])
    n_compare = 2 if method == "compare" else 0
    sql = next(iter(streamgen.render_template_parts(
        str(streamgen.TEMPLATE_DIR / "query3.tpl"), "07291122510", 0)))[1]
    obs.reset(enabled=True)
    try:
        sess = Session(catalog, backend="tpu")
        sess.sql(sql)                               # discovery: no replay
        paths = ("lookup", "expand", "sort", "compare")
        names = ["engine.replay.join_" + k for k in paths]
        assert not any(k in obs.counters_snapshot() for k in names)
        # both of query3's joins are lookups; those that compare are
        # counted under lookup too
        for n_replays in (1, 2):
            sess.sql(sql)
            snap = obs.counters_snapshot()
            assert [snap[k] for k in names] == \
                [2 * n_replays, 0, 0, n_compare * n_replays]
        cp = sess.compiled_plan(sql)
        programs = [cp] + [sess._jax_executor()._seg_compiled[fp]
                           for fp in (cp.seg_fps or ())]
        assert [sum(p.join_paths[i] for p in programs)
                for i in range(4)] == [2, 0, 0, n_compare]
        span = [e for e in obs.tracer().events if e["name"] == "replay"][-1]
        assert [span["args"]["join_" + k] for k in paths] == [2, 0, 0, n_compare]
    finally:
        obs.reset()
