"""Multi-chip sharding tests on the virtual 8-device CPU mesh.

Validates that the distributed query step (shard_map + collectives)
compiles and produces results identical to a numpy oracle, and that the
exchange primitives preserve rows."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ndstpu.parallel import dquery, exchange, mesh as pmesh
from ndstpu.parallel.mesh import shard_map


@pytest.fixture(scope="module")
def mesh8():
    assert len(jax.devices()) >= 8, "conftest must force 8 CPU devices"
    return pmesh.make_mesh(8)


def test_q3_step_matches_oracle(mesh8):
    n_items, n_dates, d_base = 64, 64, 2450815
    args = dquery.example_inputs(n_rows=4096, n_items=n_items,
                                 n_dates=n_dates, d_base=d_base,
                                 n_dev=8)
    step = dquery.build_q3_step(mesh8, n_items, n_dates, d_base)
    sharding = pmesh.row_sharding(mesh8)
    sharded_args = [jax.device_put(a, sharding) for a in args[:3]] + \
        [jax.device_put(a, pmesh.replicated(mesh8)) for a in args[3:]]
    per_brand, n_rows, shuffled, dropped = step(*sharded_args)
    ref_brand, ref_n, ref_item = dquery.reference_result(
        *args, n_items=n_items, n_dates=n_dates, d_base=d_base)
    assert int(dropped) == 0
    np.testing.assert_array_equal(np.asarray(per_brand), ref_brand)
    assert int(n_rows) == ref_n
    np.testing.assert_array_equal(np.asarray(shuffled), ref_item)


def test_hash_repartition_preserves_rows(mesh8):
    """Every alive row lands on exactly one device, keyed consistently."""
    n_dev = 8
    n_local = 128
    bucket_cap = 64
    rng = np.random.RandomState(7)
    keys = rng.randint(0, 50, n_dev * n_local).astype(np.int64)
    vals = rng.randint(0, 1000, n_dev * n_local).astype(np.int64)
    alive = rng.rand(n_dev * n_local) < 0.9

    def body(k, v, a):
        cols, alive_out, dropped = exchange.hash_repartition(
            {"v": v, "k": k}, k, a, n_dev, bucket_cap)
        # per-key sums of received rows
        local = jax.ops.segment_sum(
            jnp.where(alive_out, cols["v"], 0),
            jnp.clip(cols["k"], 0, 49).astype(jnp.int32), num_segments=50)
        return jax.lax.psum(local, pmesh.SHARD_AXIS), dropped

    fn = jax.jit(shard_map(
        body, mesh=mesh8,
        in_specs=(P(pmesh.SHARD_AXIS),) * 3, out_specs=(P(), P()),
        check_vma=False))
    got, dropped = fn(
        jax.device_put(jnp.asarray(keys), pmesh.row_sharding(mesh8)),
        jax.device_put(jnp.asarray(vals), pmesh.row_sharding(mesh8)),
        jax.device_put(jnp.asarray(alive), pmesh.row_sharding(mesh8)))
    assert int(dropped) == 0
    ref = np.zeros(50, np.int64)
    np.add.at(ref, keys[alive], vals[alive])
    np.testing.assert_array_equal(np.asarray(got), ref)


def test_broadcast_gather(mesh8):
    n_dev, n_local = 8, 16
    x = np.arange(n_dev * n_local, dtype=np.int32)

    def body(v):
        return exchange.broadcast_gather(v)

    fn = jax.jit(shard_map(body, mesh=mesh8,
                           in_specs=P(pmesh.SHARD_AXIS),
                           out_specs=P(pmesh.SHARD_AXIS)))
    out = fn(jax.device_put(jnp.asarray(x), pmesh.row_sharding(mesh8)))
    # each shard gathered the full array; sharded output stacks them
    assert out.shape == (n_dev * n_dev * n_local,)
    np.testing.assert_array_equal(np.asarray(out)[:n_dev * n_local], x)


# -- distributed plan executor (SQL -> SPMD program) ------------------------


@pytest.fixture(scope="module")
def dist_catalog(sf002_warehouse):
    from ndstpu.io import loader
    return loader.load_catalog(str(sf002_warehouse))


def _dist_vs_cpu(catalog, mesh, sql, threshold=1000, broadcast_limit=None,
                 expect_shuffle=0):
    """Plan once; run distributed and on the numpy interpreter; compare."""
    from ndstpu.engine import physical
    from ndstpu.engine.session import Session
    from ndstpu.parallel import dplan

    sess = Session(catalog, backend="cpu")
    plan, _cols = sess.plan(sql)
    want = physical.execute(plan, catalog)
    kw = {}
    if broadcast_limit is not None:
        kw["broadcast_limit_rows"] = broadcast_limit
    exe = dplan.DistributedPlanExecutor(catalog, mesh,
                                        shard_threshold_rows=threshold, **kw)
    got = exe.execute_plan(plan)
    n_shuffle = sum(1 for j in exe.joins.values()
                    if isinstance(j, dplan._ShuffleJoin))
    assert n_shuffle >= expect_shuffle, \
        f"expected >= {expect_shuffle} shuffle joins, traced {n_shuffle}"
    assert want.column_names == got.column_names
    rows_w = sorted(want.to_rows(), key=lambda r: tuple(
        (v is None, str(v)) for v in r))
    rows_g = sorted(got.to_rows(), key=lambda r: tuple(
        (v is None, str(v)) for v in r))
    assert len(rows_w) == len(rows_g), \
        f"{len(rows_w)} vs {len(rows_g)} rows"
    for rw, rg in zip(rows_w, rows_g):
        for vw, vg in zip(rw, rg):
            if isinstance(vw, float) and isinstance(vg, float):
                assert vw == pytest.approx(vg, rel=1e-9, abs=1e-9)
            else:
                assert vw == vg, f"{rw} != {rg}"
    return got


def test_dist_filter_project(dist_catalog, mesh8):
    _dist_vs_cpu(dist_catalog, mesh8,
                 "select ss_item_sk, ss_quantity, ss_sales_price "
                 "from store_sales where ss_quantity > 40")


def test_dist_star_join_groupby(dist_catalog, mesh8):
    # the q3 shape: fact scan -> dim joins -> group-by -> (host) sort/limit
    _dist_vs_cpu(dist_catalog, mesh8,
                 "select d_year, i_brand_id, sum(ss_ext_sales_price) as s, "
                 "count(*) as n "
                 "from store_sales, date_dim, item "
                 "where ss_sold_date_sk = d_date_sk "
                 "and ss_item_sk = i_item_sk and i_manufact_id > 500 "
                 "group by d_year, i_brand_id "
                 "order by d_year, s desc limit 10")


def test_dist_global_aggregate(dist_catalog, mesh8):
    _dist_vs_cpu(dist_catalog, mesh8,
                 "select count(*) as n, sum(ss_net_paid) as s, "
                 "avg(ss_quantity) as a, min(ss_sales_price) as lo, "
                 "max(ss_sales_price) as hi from store_sales "
                 "where ss_store_sk is not null")


def test_dist_global_aggregate_empty(dist_catalog, mesh8):
    # SQL: a global aggregate over zero rows still returns one row
    # (count 0, NULL sums)
    _dist_vs_cpu(dist_catalog, mesh8,
                 "select count(*) as n, sum(ss_net_paid) as s "
                 "from store_sales where ss_quantity > 1000000")


def test_dist_semi_anti_join(dist_catalog, mesh8):
    _dist_vs_cpu(dist_catalog, mesh8,
                 "select count(*) as n from store_sales where ss_item_sk "
                 "in (select i_item_sk from item "
                 "where i_category = 'Music')")
    _dist_vs_cpu(dist_catalog, mesh8,
                 "select count(*) as n from store_sales where ss_item_sk "
                 "not in (select i_item_sk from item "
                 "where i_category = 'Music')")


def test_dist_agg_expression_outputs(dist_catalog, mesh8):
    _dist_vs_cpu(dist_catalog, mesh8,
                 "select ss_store_sk, "
                 "sum(ss_net_paid) / count(ss_net_paid) as ratio "
                 "from store_sales group by ss_store_sk")


def test_session_spmd_backend(dist_catalog):
    """backend='tpu-spmd' distributes supported queries and silently
    falls back on the rest; results must match the cpu interpreter."""
    from ndstpu.engine.session import Session

    cpu = Session(dist_catalog, backend="cpu")
    spmd = Session(dist_catalog, backend="tpu-spmd", spmd_threshold=1000)
    # distributable star aggregate — must take the distributed branch
    sql = ("select d_year, sum(ss_ext_sales_price) as s from store_sales, "
           "date_dim where ss_sold_date_sk = d_date_sk group by d_year "
           "order by d_year")
    a = cpu.sql(sql).to_rows()
    b = spmd.sql(sql).to_rows()
    assert sorted(map(str, a)) == sorted(map(str, b))
    assert getattr(spmd, "_spmd_used", False), \
        "distributed executor was never used"
    # a window over the sharded scan runs sharded too: rows colocate by
    # partition key (here: none -> one device) and rank on-device
    sql = ("select * from (select ss_item_sk, row_number() over "
           "(order by ss_net_paid desc, ss_item_sk) as rn from "
           "store_sales) t where rn <= 5")
    a = cpu.sql(sql).to_rows()
    b = spmd.sql(sql).to_rows()
    assert sorted(map(str, a)) == sorted(map(str, b))
    # repeat execution takes the cached-executor path (no re-trace) and
    # stays correct; the cache is keyed on the canonical plan
    # fingerprint (parameterized plans share one compiled program)
    from ndstpu import obs
    sql = ("select d_year, sum(ss_ext_sales_price) as s from store_sales, "
           "date_dim where ss_sold_date_sk = d_date_sk group by d_year "
           "order by d_year")
    first = spmd.sql(sql).to_rows()
    assert spmd._spmd_cache, "executor cache never populated"
    before = obs.counters_snapshot()
    again = spmd.sql(sql).to_rows()
    assert obs.counter_delta(before).get("engine.cache.spmd.hit", 0) >= 1
    assert first == again == cpu.sql(sql).to_rows()
    # not distributable (no sharded-size table) -> single-chip fallback
    spmd._spmd_used = False
    sql = "select s_store_sk, s_store_id from store order by s_store_sk"
    a = cpu.sql(sql).to_rows()
    b = spmd.sql(sql).to_rows()
    assert sorted(map(str, a)) == sorted(map(str, b))
    assert not spmd._spmd_used


def test_dist_shuffle_join_inner(dist_catalog, mesh8):
    # fact-fact join over the broadcast limit: all_to_all hash exchange
    _dist_vs_cpu(dist_catalog, mesh8,
                 "select count(*) as c, sum(ss_quantity) as q "
                 "from store_sales, store_returns "
                 "where ss_item_sk = sr_item_sk "
                 "and ss_ticket_number = sr_ticket_number",
                 broadcast_limit=50, expect_shuffle=1)


def test_dist_shuffle_join_left_groupby(dist_catalog, mesh8):
    _dist_vs_cpu(dist_catalog, mesh8,
                 "select i_item_id, count(sr_ticket_number) as r, "
                 "sum(ss_ext_sales_price) as s "
                 "from store_sales left join store_returns "
                 "on ss_item_sk = sr_item_sk "
                 "and ss_ticket_number = sr_ticket_number "
                 "join item on ss_item_sk = i_item_sk "
                 "group by i_item_id",
                 broadcast_limit=50, expect_shuffle=2)


def test_dist_shuffle_join_semi_rowmode(dist_catalog, mesh8):
    _dist_vs_cpu(dist_catalog, mesh8,
                 "select count(*) as c from store_sales where exists "
                 "(select 1 from store_returns where sr_item_sk = ss_item_sk "
                 "and sr_ticket_number = ss_ticket_number)",
                 broadcast_limit=50, expect_shuffle=1)
    # row-mode spine: joined rows come back sharded, no aggregate
    _dist_vs_cpu(dist_catalog, mesh8,
                 "select ss_item_sk, ss_ticket_number, sr_return_quantity "
                 "from store_sales, store_returns "
                 "where ss_item_sk = sr_item_sk "
                 "and ss_ticket_number = sr_ticket_number",
                 broadcast_limit=50, expect_shuffle=1)


def test_dist_shuffle_skew_retry(dist_catalog, mesh8):
    """A low-cardinality shuffle key (every probe row hashes to a handful
    of buckets) overflows the first receive-bucket size; the executor
    must retry with doubled slack up to the lossless bound, never drop."""
    from ndstpu.engine import physical
    from ndstpu.engine.session import Session
    from ndstpu.parallel import dplan

    sess = Session(dist_catalog, backend="cpu")
    sql = ("select s_store_id, count(*) as n from store_sales, store "
           "where ss_store_sk = s_store_sk group by s_store_id")
    plan, _ = sess.plan(sql)
    want = physical.execute(plan, dist_catalog)
    exe = dplan.DistributedPlanExecutor(dist_catalog, mesh8,
                                        shard_threshold_rows=1000,
                                        broadcast_limit_rows=0)
    got = exe.execute_plan(plan)
    assert exe.shuffle_slack > 2, "skew did not trigger a slack retry"
    assert exe._last_dropped == 0
    assert sorted(map(str, want.to_rows())) == sorted(map(str, got.to_rows()))


def test_dist_empty_build_side(dist_catalog, mesh8):
    # a dimension filter that matches nothing: the broadcast build side
    # is empty — joins must produce typed NULLs / empty results, not
    # crash in a zero-row gather
    _dist_vs_cpu(dist_catalog, mesh8,
                 "select count(*) as n, sum(ss_net_paid) as s "
                 "from store_sales, date_dim where ss_sold_date_sk = "
                 "d_date_sk and d_year = 1800")
    _dist_vs_cpu(dist_catalog, mesh8,
                 "select ss_item_sk, d_year from store_sales left join "
                 "date_dim on ss_sold_date_sk = d_date_sk and d_year = 1800")


def test_dist_deep_aggregate_split(dist_catalog, mesh8):
    # stacked aggregates: the DEEPEST one is the spine top; the outer
    # aggregate and sort run in the host tail over the small result
    _dist_vs_cpu(dist_catalog, mesh8,
                 "select avg(s) as a from (select ss_store_sk, "
                 "sum(ss_net_paid) as s from store_sales "
                 "group by ss_store_sk) t")


def test_dist_rollup_grouping_sets(dist_catalog, mesh8):
    # ROLLUP runs the spine at the finest grouping; each set re-combines
    # the decomposable partials on the host (q18/q22/q27/q36/q70 shape)
    _dist_vs_cpu(dist_catalog, mesh8,
                 "select i_category, i_class, "
                 "grouping(i_category) + grouping(i_class) as lochierarchy, "
                 "sum(ss_net_profit) as p, avg(ss_quantity) as aq, "
                 "count(*) as n, min(ss_sales_price) as lo, "
                 "max(ss_sales_price) as hi "
                 "from store_sales, item where ss_item_sk = i_item_sk "
                 "group by rollup(i_category, i_class)")
    _dist_vs_cpu(dist_catalog, mesh8,
                 "select d_year, stddev_samp(ss_quantity) as sd "
                 "from store_sales, date_dim "
                 "where ss_sold_date_sk = d_date_sk "
                 "group by rollup(d_year)")


def test_dist_distinct_aggregates(dist_catalog, mesh8):
    # DISTINCT colocates each group's rows on one device (all_to_all by
    # group-key hash), then dedups locally — globally exact
    _dist_vs_cpu(dist_catalog, mesh8,
                 "select ss_store_sk, count(distinct ss_ticket_number) "
                 "as t, count(*) as n, sum(ss_quantity) as q "
                 "from store_sales group by ss_store_sk")
    _dist_vs_cpu(dist_catalog, mesh8,
                 "select d_year, count(distinct ss_customer_sk) as c, "
                 "sum(distinct ss_sales_price) as sd "
                 "from store_sales, date_dim "
                 "where ss_sold_date_sk = d_date_sk group by d_year")
    _dist_vs_cpu(dist_catalog, mesh8,
                 "select count(distinct ss_item_sk) as u from store_sales")


def test_dist_union_all_aggregate(dist_catalog, mesh8):
    """Channel-union aggregates (q5/q33/q56/q60/q66/q71/q76 shape): each
    branch runs as its own sharded spine; the host combines decomposable
    partials across branches."""
    from ndstpu.engine import physical
    from ndstpu.engine.session import Session
    from ndstpu.parallel import dplan

    sess = Session(dist_catalog, backend="cpu")
    queries = [
        # union -> group by
        "select item_sk, sum(amt) as total, count(*) as n from ("
        "select ss_item_sk as item_sk, ss_ext_sales_price as amt "
        "from store_sales union all "
        "select cs_item_sk as item_sk, cs_ext_sales_price as amt "
        "from catalog_sales union all "
        "select ws_item_sk as item_sk, ws_ext_sales_price as amt "
        "from web_sales) t group by item_sk",
        # union -> rollup (q5 shape)
        "select chan, sk, sum(amt) as total from ("
        "select 'store' as chan, ss_store_sk as sk, ss_net_profit as amt "
        "from store_sales union all "
        "select 'web' as chan, ws_web_site_sk as sk, ws_net_profit as amt "
        "from web_sales) t group by rollup(chan, sk)",
        # union -> global aggregate; min/max fold across branches
        "select sum(amt) as total, min(amt) as lo, max(amt) as hi from ("
        "select ss_ext_sales_price as amt from store_sales union all "
        "select ws_ext_sales_price as amt from web_sales) t",
        # min/max over per-branch dictionary-encoded strings must
        # translate into the union dictionary before folding
        "select k, min(id) as lo, max(id) as hi from ("
        "select ss_store_sk as k, i_item_id as id from store_sales, item "
        "where ss_item_sk = i_item_sk union all "
        "select cs_call_center_sk as k, i_item_id as id from "
        "catalog_sales, item where cs_item_sk = i_item_sk) t group by k",
    ]
    for sql in queries:
        plan, _ = sess.plan(sql)
        want = physical.execute(plan, dist_catalog)
        exe = dplan.DistributedPlanExecutor(dist_catalog, mesh8,
                                            shard_threshold_rows=500)
        got = exe.execute_plan(plan)
        assert exe._union_ctx is not None, f"union path not taken: {sql}"
        assert any(e is not None for e in exe._union_ctx[2])
        rw = sorted(map(str, want.to_rows()))
        rg = sorted(map(str, got.to_rows()))
        assert want.column_names == got.column_names
        assert rw == rg
        # cached repeat execution
        assert sorted(map(str, exe.execute_again().to_rows())) == rg


def test_dist_string_join_keys(dist_catalog, mesh8):
    # string keys join in the build dictionary's code space; the traced
    # probe translates its codes through a static mapping (q56/q60 shape)
    _dist_vs_cpu(dist_catalog, mesh8,
                 "select count(*) as n, sum(ss_ext_sales_price) as s "
                 "from store_sales, item where ss_item_sk = i_item_sk "
                 "and i_item_id in (select i_item_id from item "
                 "where i_color in ('red', 'blue'))")


def test_dist_semi_anti_residual_runs(dist_catalog, mesh8):
    # duplicate build keys + correlated residual: the probe walks the
    # whole key run (q16/q94 EXISTS self-join shape), on both the
    # broadcast and the all_to_all shuffle paths
    sql_exists = (
        "select count(*) as c from web_sales ws1 where exists "
        "(select 1 from web_sales ws2 where ws1.ws_order_number = "
        "ws2.ws_order_number and ws1.ws_warehouse_sk <> "
        "ws2.ws_warehouse_sk)")
    sql_not = sql_exists.replace("where exists", "where not exists")
    for sql in (sql_exists, sql_not):
        _dist_vs_cpu(dist_catalog, mesh8, sql, threshold=500)
        _dist_vs_cpu(dist_catalog, mesh8, sql, threshold=500,
                     broadcast_limit=50, expect_shuffle=1)


def test_dist_multi_union_sites(dist_catalog, mesh8):
    # a q5-shaped plan: rollup over channels whose unions sit UNDER the
    # per-channel aggregates; every union site must distribute (the
    # executor recurses on the plan remainder)
    from ndstpu.engine import physical
    from ndstpu.engine.session import Session
    from ndstpu.parallel import dplan

    sess = Session(dist_catalog, backend="cpu")
    sql = (
        "select chan, sum(amt) as total from ("
        " select 'c1' as chan, sk, amt from ("
        "  select ss_store_sk as sk, ss_net_profit as amt from store_sales"
        "  union all select sr_store_sk as sk, (0 - sr_return_amt) as amt "
        "  from store_returns) a, store where sk = s_store_sk"
        " union all"
        " select 'c2' as chan, sk2, amt2 from ("
        "  select ws_web_site_sk as sk2, ws_net_profit as amt2 "
        "  from web_sales"
        "  union all select wr_web_page_sk as sk2, (0 - wr_return_amt) "
        "  as amt2 from web_returns) b"
        ") t group by rollup(chan)")
    plan, _ = sess.plan(sql)
    want = physical.execute(plan, dist_catalog)
    exe = dplan.DistributedPlanExecutor(dist_catalog, mesh8,
                                        shard_threshold_rows=500)
    got = exe.execute_plan(plan)
    assert exe._union_ctx is not None
    rw = sorted(map(str, want.to_rows()))
    assert sorted(map(str, got.to_rows())) == rw
    assert sorted(map(str, exe.execute_again().to_rows())) == rw


def test_dist_out_of_core_chunks(dist_catalog, mesh8):
    """chunk_rows streams the fact through the device chunk by chunk
    (one compiled program); per-chunk partials combine on the host like
    union branches, row-mode chunks concatenate."""
    from ndstpu.engine import physical
    from ndstpu.engine.session import Session
    from ndstpu.parallel import dplan

    sess = Session(dist_catalog, backend="cpu")
    queries = [
        "select d_year, i_brand_id, sum(ss_ext_sales_price) as s, "
        "count(*) as n from store_sales, date_dim, item "
        "where ss_sold_date_sk = d_date_sk and ss_item_sk = i_item_sk "
        "group by d_year, i_brand_id",
        "select i_category, sum(ss_net_profit) as p, "
        "min(ss_sales_price) as lo from store_sales, item "
        "where ss_item_sk = i_item_sk group by rollup(i_category)",
        "select ss_item_sk, ss_quantity from store_sales "
        "where ss_quantity > 90",
        "select count(*) as c, sum(ss_quantity) as q from store_sales, "
        "store_returns where ss_item_sk = sr_item_sk "
        "and ss_ticket_number = sr_ticket_number",
    ]
    for sql in queries:
        plan, _ = sess.plan(sql)
        want = physical.execute(plan, dist_catalog)
        exe = dplan.DistributedPlanExecutor(
            dist_catalog, mesh8, shard_threshold_rows=500,
            broadcast_limit_rows=50, chunk_rows=1000)
        got = exe.execute_plan(plan)
        assert exe._chunk_info[0], f"not chunked: {sql[:50]}"
        rw = sorted(map(str, want.to_rows()))
        assert sorted(map(str, got.to_rows())) == rw, sql[:60]
        assert sorted(map(str, exe.execute_again().to_rows())) == rw


def test_dist_dup_insensitive_semi_conversion(dist_catalog, mesh8):
    # q37/q82 shape: an expanding inner join (inventory's non-unique
    # item keys) feeding a pure GROUP BY dedup — demoted to a semi join
    _dist_vs_cpu(dist_catalog, mesh8,
                 "select i_item_id, i_current_price from item, inventory, "
                 "store_sales where i_item_sk = inv_item_sk "
                 "and i_item_sk = ss_item_sk "
                 "and inv_quantity_on_hand between 100 and 500 "
                 "group by i_item_id, i_current_price")


SPMD_CORPUS_TPLS = [
    "query2.tpl",    # CTE union reused twice (multi union sites)
    "query5.tpl",    # rollup over channels with nested unions
    "query10.tpl",   # EXISTS build sides that contain sharded facts
    "query16.tpl",   # semi/anti self-join with residual runs
    "query35.tpl",   # EXISTS-over-three-channels build reduction
    "query37.tpl",   # expanding inventory join -> semi conversion
    "query56.tpl",   # string join keys in union channels
    "query69.tpl",   # EXISTS + NOT EXISTS mixed build reduction
    "query75.tpl",   # multi-channel union with fact-fact joins
    "query82.tpl",   # expanding inventory join -> semi conversion
    "query94.tpl",   # EXISTS/NOT EXISTS self-join residual runs
]


@pytest.mark.slow
@pytest.mark.parametrize("tpl", SPMD_CORPUS_TPLS)
def test_spmd_corpus_differential(dist_catalog, mesh8, tpl):
    """The corpus queries that exercise the newest distributed paths
    must DISTRIBUTE (no fallback) and match the numpy oracle."""
    from ndstpu.engine import physical
    from ndstpu.engine.session import Session
    from ndstpu.parallel import dplan
    from ndstpu.queries import streamgen

    sess = Session(dist_catalog, backend="cpu")
    for _name, sql in streamgen.render_template_parts(
            str(streamgen.TEMPLATE_DIR / tpl), "07291122510", 0):
        plan, _ = sess.plan(sql)
        want = physical.execute(plan, dist_catalog)
        exe = dplan.DistributedPlanExecutor(dist_catalog, mesh8,
                                            shard_threshold_rows=500)
        got = exe.execute_plan(plan)   # DistUnsupported = regression
        rows_w = sorted(want.to_rows(), key=lambda r: tuple(
            (v is None, str(v)) for v in r))
        rows_g = sorted(got.to_rows(), key=lambda r: tuple(
            (v is None, str(v)) for v in r))
        assert want.column_names == got.column_names
        assert len(rows_w) == len(rows_g)
        for rw, rg in zip(rows_w, rows_g):
            for vw, vg in zip(rw, rg):
                if isinstance(vw, float) and isinstance(vg, float):
                    assert vw == pytest.approx(vg, rel=1e-7, abs=1e-7)
                else:
                    assert vw == vg, f"{rw} != {rg}"


def test_dist_unsupported_falls_out(dist_catalog, mesh8):
    from ndstpu.engine.session import Session
    from ndstpu.parallel import dplan

    sess = Session(dist_catalog, backend="cpu")
    # full outer join is outside the spine subset
    plan, _ = sess.plan(
        "select count(*) as n from store_sales full join store_returns "
        "on ss_ticket_number = sr_ticket_number "
        "and ss_item_sk = sr_item_sk")
    with pytest.raises(dplan.DistUnsupported):
        dplan.execute_distributed(dist_catalog, mesh8, plan,
                                  shard_threshold_rows=1000,
                                  broadcast_limit_rows=100)
    # no sharded-size table at all
    plan2, _ = sess.plan("select count(*) as n from item")
    with pytest.raises(dplan.DistUnsupported):
        dplan.execute_distributed(dist_catalog, mesh8, plan2,
                                  shard_threshold_rows=10**9)


def test_mesh_construction():
    m = pmesh.make_mesh(8)
    assert m.devices.size == 8
    assert m.axis_names == (pmesh.SHARD_AXIS,)
    with pytest.raises(ValueError):
        pmesh.make_mesh(10**6)


def test_single_chip_out_of_core(dist_catalog):
    """Session backend='tpu' + spmd_chunk_rows routes aggregates through
    the chunked executor over a 1-DEVICE mesh (SF >> HBM on one chip,
    VERDICT weak #7): differential vs the numpy interpreter at an
    artificially small chunk size, with chunking actually engaged."""
    from ndstpu.engine.session import Session

    cpu = Session(dist_catalog, backend="cpu")
    tpu = Session(dist_catalog, backend="tpu",
                  spmd_threshold=500, spmd_chunk_rows=1000)
    queries = [
        "select d_year, i_brand_id, sum(ss_ext_sales_price) as s, "
        "count(*) as n from store_sales, date_dim, item "
        "where ss_sold_date_sk = d_date_sk and ss_item_sk = i_item_sk "
        "group by d_year, i_brand_id",
        # row-mode spine (no aggregate): chunks concatenate
        "select ss_item_sk, ss_quantity from store_sales "
        "where ss_quantity > 90",
    ]
    for sql in queries:
        want = sorted(map(str, cpu.sql(sql).to_rows()))
        got = sorted(map(str, tpu.sql(sql).to_rows()))
        assert got == want, sql[:60]
    assert getattr(tpu, "_spmd_used", False)
    assert not getattr(tpu, "_spmd_errors", None)
    # the mesh really is single-device
    assert len(tpu._mesh().devices.ravel()) == 1
    # chunking engaged on the cached executors
    chunked = [ent[1]._chunk_info[0]
               for ent in tpu._spmd_cache.values()]
    assert any(chunked)
    # a shape the chunked executor can't take still answers (fallback)
    out = tpu.sql("select count(*) as n from item")
    assert out.to_rows()[0][0] == dist_catalog.get("item").num_rows


def test_dist_scalar_subquery_offload(dist_catalog, mesh8):
    """q9 shape: outer FROM is a tiny dim; the work lives in uncorrelated
    scalar subqueries over the fact. Each body runs distributed (child
    executors) and the scalars are inlined into the host outer plan."""
    from ndstpu.engine import physical
    from ndstpu.engine.session import Session
    from ndstpu.parallel import dplan
    from ndstpu.queries import streamgen

    sess = Session(dist_catalog, backend="cpu")
    _name, sql = streamgen.render_template_parts(
        str(streamgen.TEMPLATE_DIR / "query9.tpl"), "07291122510", 0)[0]
    plan, _ = sess.plan(sql)
    want = physical.execute(plan, dist_catalog)
    exe = dplan.DistributedPlanExecutor(dist_catalog, mesh8,
                                        shard_threshold_rows=500)
    got = exe.execute_plan(plan)
    assert getattr(exe, "_scalar_ctx", None) is not None
    assert len(exe._scalar_ctx[1]) == 15      # 5 buckets x (count,avg,avg)
    assert sorted(map(str, got.to_rows())) == \
        sorted(map(str, want.to_rows()))
    assert sorted(map(str, exe.execute_again().to_rows())) == \
        sorted(map(str, want.to_rows()))


def test_dist_expanding_inner_broadcast_join(dist_catalog, mesh8):
    """Non-unique build keys on an inner broadcast join expand the probe
    side by bounded duplication (q72's d1-d2 week_seq join: <=7 days per
    week), instead of falling back to the single-chip path."""
    from ndstpu.engine import physical
    from ndstpu.engine.session import Session
    from ndstpu.parallel import dplan

    sess = Session(dist_catalog, backend="cpu")
    # d2 joins the spine on inv_date_sk (unique), then d1 arrives via
    # the NON-unique d_week_seq edge and must expand (7 days/week), with
    # the quantity filter as a lifted residual
    sql = ("select d1.d_day_name, count(*) as n, "
           "sum(inv_quantity_on_hand) as q "
           "from inventory "
           "join date_dim d2 on inv_date_sk = d2.d_date_sk "
           "join date_dim d1 on d1.d_week_seq = d2.d_week_seq "
           "where inv_quantity_on_hand < 500 "
           "group by d1.d_day_name")
    plan, _ = sess.plan(sql)
    want = physical.execute(plan, dist_catalog)
    exe = dplan.DistributedPlanExecutor(dist_catalog, mesh8,
                                        shard_threshold_rows=500)
    got = exe.execute_plan(plan)
    assert any(isinstance(j, dplan._BroadcastJoin) and j.dup_max > 1
               and j.kind == "inner" for j in exe.joins.values()), \
        "expansion not engaged"
    assert sorted(map(str, got.to_rows())) == \
        sorted(map(str, want.to_rows()))
    assert sorted(map(str, exe.execute_again().to_rows())) == \
        sorted(map(str, want.to_rows()))


def test_dist_build_reduce_existence_join(dist_catalog, mesh8):
    """q10/q35/q69 shape: an EXISTS / NOT EXISTS build side contains a
    sharded-size fact.  Instead of executing the whole subtree on host
    numpy, a child spine reduces it to its distinct join-key tuples over
    the mesh (existence joins are insensitive to build multiplicity) and
    only those broadcast."""
    from ndstpu.engine import physical
    from ndstpu.engine.session import Session
    from ndstpu.parallel import dplan

    sess = Session(dist_catalog, backend="cpu")
    sql_exists = (
        "select count(*) as c from store_sales where exists "
        "(select 1 from web_sales where ws_item_sk = ss_item_sk)")
    for sql in (sql_exists,
                sql_exists.replace("where exists", "where not exists")):
        plan, _ = sess.plan(sql)
        want = physical.execute(plan, dist_catalog)
        exe = dplan.DistributedPlanExecutor(dist_catalog, mesh8,
                                            shard_threshold_rows=500)
        got = exe.execute_plan(plan)
        assert exe.build_reduced, f"build not reduced distributed: {sql}"
        kind, n_reduced = exe.build_reduced[0]
        assert kind in ("semi", "anti", "nullaware_anti", "mark")
        # the reduction really deduplicated (distinct item keys < rows)
        assert n_reduced < dist_catalog.get("web_sales").num_rows
        rw = sorted(map(str, want.to_rows()))
        assert sorted(map(str, got.to_rows())) == rw
        assert sorted(map(str, exe.execute_again().to_rows())) == rw


def test_dist_build_reduce_attempt_recovery(dist_catalog, mesh8):
    """When the LARGEST fact sits on the build side, its anchored
    candidate fails fast with NDS308 (recorded in attempt_codes), and
    the probe-anchored candidate distributes with the reduced build —
    the executor recovers instead of falling back to single-chip."""
    from ndstpu.engine import physical
    from ndstpu.engine.session import Session
    from ndstpu.parallel import dplan

    sess = Session(dist_catalog, backend="cpu")
    assert dist_catalog.get("store_sales").num_rows > \
        dist_catalog.get("web_sales").num_rows
    sql = ("select count(*) as c from web_sales where exists "
           "(select 1 from store_sales where ss_item_sk = ws_item_sk)")
    plan, _ = sess.plan(sql)
    want = physical.execute(plan, dist_catalog)
    exe = dplan.DistributedPlanExecutor(dist_catalog, mesh8,
                                        shard_threshold_rows=500)
    got = exe.execute_plan(plan)
    assert "NDS308" in exe.attempt_codes, \
        "fact-on-build-side candidate should have failed with NDS308"
    assert exe.build_reduced
    assert sorted(map(str, got.to_rows())) == \
        sorted(map(str, want.to_rows()))


def test_dist_sharded_window(dist_catalog, mesh8):
    """Ranking and whole-partition aggregate windows run sharded: rows
    colocate by partition-key hash (one all_to_all per distinct
    PARTITION BY list), ties replay the original row order."""
    # rank with a duplicate-heavy order key
    _dist_vs_cpu(dist_catalog, mesh8,
                 "select ss_store_sk, ss_item_sk, "
                 "rank() over (partition by ss_store_sk "
                 "order by ss_net_paid desc) as rnk "
                 "from store_sales where ss_net_paid > 90",
                 threshold=500)
    # two windows with DIFFERENT partition keys (two exchanges), plus
    # row_number ties broken by original row order on both paths
    _dist_vs_cpu(dist_catalog, mesh8,
                 "select ss_ticket_number, "
                 "row_number() over (partition by ss_store_sk "
                 "order by ss_sold_date_sk, ss_ticket_number) as rn, "
                 "dense_rank() over (partition by ss_item_sk "
                 "order by ss_quantity desc) as dr "
                 "from store_sales where ss_quantity > 80",
                 threshold=500)
    # whole-partition aggregates (no ORDER BY): order-independent
    _dist_vs_cpu(dist_catalog, mesh8,
                 "select ss_item_sk, ss_net_paid, "
                 "sum(ss_net_paid) over (partition by ss_item_sk) as tot, "
                 "count(*) over (partition by ss_item_sk) as n, "
                 "avg(ss_quantity) over (partition by ss_item_sk) as aq "
                 "from store_sales where ss_item_sk < 100",
                 threshold=500)


def test_dist_device_tail_topk(dist_catalog, mesh8):
    """Sort+LIMIT (or bare LIMIT) above a row spine finalizes on-device
    as a per-device top-k: only ~limit rows ever reach the host (the
    host_gather_bytes counter is the evidence), and the result must be
    bit-identical to the numpy interpreter INCLUDING row order."""
    from ndstpu import obs
    from ndstpu.engine import physical
    from ndstpu.engine.session import Session
    from ndstpu.parallel import dplan

    sess = Session(dist_catalog, backend="cpu")
    queries = [
        # ordered top-k; desc + tiebreak column
        "select ss_item_sk, ss_net_paid from store_sales "
        "where ss_quantity > 10 "
        "order by ss_net_paid desc, ss_item_sk limit 25",
        # NULLable leading key, mixed asc/desc
        "select ss_store_sk, ss_net_profit from store_sales "
        "order by ss_store_sk, ss_net_profit desc limit 17",
        # bare LIMIT: original row order, no sort keys at all
        "select ss_item_sk, ss_ticket_number from store_sales limit 40",
        # limit larger than the alive row count: dead-row padding in the
        # gather must be masked out, every alive row survives
        "select ss_item_sk from store_sales where ss_quantity > 99 "
        "order by ss_item_sk limit 1000",
    ]
    for sql in queries:
        plan, _ = sess.plan(sql)
        want = physical.execute(plan, dist_catalog)
        exe = dplan.DistributedPlanExecutor(dist_catalog, mesh8,
                                            shard_threshold_rows=500)
        before = obs.counters_snapshot()
        got = exe.execute_plan(plan)
        delta = obs.counter_delta(before)
        assert exe._tail is not None, f"tail not on-device: {sql[:50]}"
        assert want.column_names == got.column_names
        # ORDER-SENSITIVE comparison: the whole point of the tail
        assert [tuple(map(str, r)) for r in got.to_rows()] == \
            [tuple(map(str, r)) for r in want.to_rows()], sql[:60]
        assert delta.get("exchange.collective.calls", 0) >= 1
        gathered = delta.get("engine.spmd.host_gather_bytes", 0)
        assert gathered > 0
        rw = [tuple(map(str, r)) for r in want.to_rows()]
        assert [tuple(map(str, r))
                for r in exe.execute_again().to_rows()] == rw
    # evidence of the bytes DROP: the 25-row tail gathers orders of
    # magnitude less than the sharded relation it ranks (which the
    # pre-tail executor shipped to the host wholesale)
    plan, _ = sess.plan(queries[0])
    exe = dplan.DistributedPlanExecutor(dist_catalog, mesh8,
                                        shard_threshold_rows=500)
    before = obs.counters_snapshot()
    exe.execute_plan(plan)
    gathered = obs.counter_delta(before).get(
        "engine.spmd.host_gather_bytes", 0)
    n_fact = dist_catalog.get("store_sales").num_rows
    assert 0 < gathered < n_fact * 2 * 8, \
        f"tail gathered {gathered} bytes for {n_fact} fact rows"


def test_session_spmd_parameterized_plans(dist_catalog):
    """Parameterized (canonicalized) plans take the SPMD path: the
    executor cache keys on the canonical fingerprint plus the bound
    literal values (literals bake into the compiled program), where the
    old executor rejected any plan with parameters (NDS301)."""
    from ndstpu import obs
    from ndstpu.engine.session import Session

    cpu = Session(dist_catalog, backend="cpu")
    spmd = Session(dist_catalog, backend="tpu-spmd", spmd_threshold=500)
    tpl = ("select d_year, sum(ss_ext_sales_price) as s from store_sales"
           ", date_dim where ss_sold_date_sk = d_date_sk "
           "and ss_quantity > {} group by d_year order by d_year")
    a = spmd.sql(tpl.format(10)).to_rows()
    assert a == cpu.sql(tpl.format(10)).to_rows()
    assert getattr(spmd, "_spmd_used", False), "SPMD path not used"
    assert not getattr(spmd, "_spmd_errors", None)
    # a different literal binds a different value hash (new entry, still
    # distributed, still correct)
    b = spmd.sql(tpl.format(90)).to_rows()
    assert b == cpu.sql(tpl.format(90)).to_rows()
    # the same literal again is a cache hit (no re-trace)
    before = obs.counters_snapshot()
    again = spmd.sql(tpl.format(10)).to_rows()
    assert again == a
    assert obs.counter_delta(before).get("engine.cache.spmd.hit", 0) >= 1


@pytest.mark.slow
def test_dist_full_corpus_row_equal(dist_catalog, mesh8):
    """EVERY corpus query part must (a) execute under the distributed
    executor on the 8-device mesh and (b) produce rows equal to the
    numpy interpreter — the distributed analog of the reference's
    full-corpus differential validation (nds_validate.py:217-260).
    Previously only 8 templates were oracle-compared (VERDICT r3 #3)."""
    import importlib.util
    import pathlib

    spec = importlib.util.spec_from_file_location(
        "spmd_coverage",
        pathlib.Path(__file__).resolve().parent.parent / "scripts" /
        "spmd_coverage.py")
    cov = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cov)

    ok, mism, fell = cov.run_corpus(dist_catalog, mesh8,
                                    shard_threshold_rows=500,
                                    verbose=False)
    assert not fell, f"distributed fallbacks: {fell}"
    assert not mism, f"distributed row mismatches: {mism}"
    assert len(ok) >= 103
