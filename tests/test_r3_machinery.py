"""Targeted tests for the round-3 engine machinery: the
searchsorted-free join probe kernels (LUT + combined-sort paths),
segmented-compilation cache lifecycle (eviction -> rediscovery,
preloaded-record drift -> self-heal), lazy-view composition through
join chains, and the replay guard on recorded size plans.

These paths were previously covered only incidentally by the corpus
differential suite (VERDICT r3 weak #5).
"""

import warnings

import jax.numpy as jnp
import numpy as np
import pytest

from ndstpu.engine import jaxexec
from ndstpu.engine.session import Session
from ndstpu.io import loader


@pytest.fixture(scope="module")
def warehouse(sf002_warehouse):
    return sf002_warehouse


@pytest.fixture(scope="module")
def catalog(warehouse):
    return loader.load_catalog(str(warehouse))


@pytest.fixture()
def exe(catalog):
    return jaxexec.JaxExecutor(catalog)


# ---------------------------------------------------------------------------
# _probe_counts edge cases (both the LUT and the combined-sort paths)
# ---------------------------------------------------------------------------


def _check_probe(exe, pkey, bkey, bound, lut: bool):
    """Validate (lo, counts, order) against a brute-force reference:
    order[lo[i] .. lo[i]+counts[i]-1] must be exactly the build rows
    whose key equals probe key i (for valid keys)."""
    pk = jnp.asarray(np.asarray(pkey, np.int64))
    bk = jnp.asarray(np.asarray(bkey, np.int64))
    with pytest.MonkeyPatch.context() as mp:
        if not lut:     # no domain is under the cap: the combined sort
            mp.setattr(jaxexec, "_JOIN_LUT_CAP", 0)
        lo, counts, order = exe._probe_counts(pk, bk, bound)
    lo, counts, order = (np.asarray(lo), np.asarray(counts),
                         np.asarray(order))
    bkey = np.asarray(bkey)
    for i, k in enumerate(np.asarray(pkey)):
        want = sorted(np.nonzero(bkey == k)[0]) if k >= 0 else []
        got = sorted(order[lo[i]:lo[i] + counts[i]]) if counts[i] else []
        assert counts[i] == len(want), \
            f"probe {i} (key {k}): count {counts[i]} != {len(want)}"
        assert got == want, f"probe {i} (key {k}): rows {got} != {want}"


@pytest.mark.parametrize("lut", [True, False], ids=["lut", "sort"])
def test_probe_counts_basic(exe, lut):
    _check_probe(exe, [0, 1, 2, 5, 3], [1, 1, 3, 0, 2, 2, 2], 6, lut)


@pytest.mark.parametrize("lut", [True, False], ids=["lut", "sort"])
def test_probe_counts_all_dead_build(exe, lut):
    # every build row is a sentinel: no probe may match
    _check_probe(exe, [0, 1, 2], [-1, -1, -1, -1], 3, lut)


@pytest.mark.parametrize("lut", [True, False], ids=["lut", "sort"])
def test_probe_counts_bound_one(exe, lut):
    # single-slot key domain: all valid rows collide on key 0
    _check_probe(exe, [0, 0, -1], [0, -1, 0, 0], 1, lut)


@pytest.mark.parametrize("lut", [True, False], ids=["lut", "sort"])
def test_probe_counts_negative_sentinels(exe, lut):
    # negative keys on both sides: dead probes match nothing, dead
    # builds occupy order slots but never join
    _check_probe(exe, [-1, 2, -5, 0], [2, -3, 0, 2, -1, 0], 3, lut)


@pytest.mark.parametrize("lut", [True, False], ids=["lut", "sort"])
def test_probe_counts_empty_probe_matches(exe, lut):
    # probe keys entirely absent from the build side
    _check_probe(exe, [7, 8, 9], [0, 1, 2, 3], 10, lut)


def test_probe_counts_lut_sort_agree(exe):
    """The LUT and combined-sort paths must produce identical results
    at the boundary domain."""
    rng = np.random.default_rng(7)
    bkey = rng.integers(-2, 50, size=200)
    pkey = rng.integers(-2, 50, size=300)
    for lut in (True, False):
        _check_probe(exe, pkey, bkey, 50, lut)


# ---------------------------------------------------------------------------
# segmented-compilation cache lifecycle
# ---------------------------------------------------------------------------

_SEG_SQL = ("select i_category, count(*) as n, sum(ss_net_paid) as s, "
            "avg(ss_quantity) as q from store_sales "
            "join item on ss_item_sk = i_item_sk "
            "join date_dim on ss_sold_date_sk = d_date_sk "
            "where d_year >= 1998 group by i_category "
            "order by i_category")


def _fresh_tpu_session(catalog):
    return Session(catalog, backend="tpu")


def test_segment_eviction_rediscovers(catalog):
    """Evicting a shared segment must trigger rediscovery (with a
    warning), not a KeyError or a wrong result."""
    sess = _fresh_tpu_session(catalog)
    want = sess.sql(_SEG_SQL).to_rows()
    exe = sess._jax_executor()
    cp = sess.compiled_plan(_SEG_SQL)
    assert cp is not None
    if not cp.seg_fps:
        pytest.skip("plan too small to segment at this SF")
    evicted = cp.seg_fps[0]
    exe._seg_compiled.pop(evicted)
    disc = exe.n_discoveries
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        got = sess.sql(_SEG_SQL).to_rows()
    assert got == want
    assert exe.n_discoveries > disc, "eviction did not rediscover"
    assert any("rediscover" in str(w.message) for w in caught)


def test_preloaded_record_drift_self_heals(catalog, tmp_path):
    """A preloaded size-plan record whose recorded capacities no longer
    fit the data must fail its replay guard and self-heal by
    rediscovery, producing the correct result."""
    s1 = _fresh_tpu_session(catalog)
    want = s1.sql(_SEG_SQL).to_rows()
    path = str(tmp_path / "plans.pkl")
    assert s1.save_compiled(path) >= 1
    s2 = _fresh_tpu_session(catalog)
    assert s2.preload_compiled(path) >= 1
    exe2 = s2._jax_executor()
    # compiled_plan probes the canonical (fingerprint) key first, the
    # normalized-text key as fallback — same lookup _execute performs
    cp = s2.compiled_plan(_SEG_SQL)
    assert cp is not None and cp.preloaded
    # simulate drift: shrink every recorded capacity so the size-class
    # guards cannot hold at execution time
    cp.record = [(tag, (max(1, v // 16) if tag == "cap"
                        and isinstance(v, int) else v))
                 for tag, v in cp.record]
    got = s2.sql(_SEG_SQL).to_rows()
    assert got == want
    assert exe2.n_discoveries > 0, "drifted record did not self-heal"


def test_preload_makes_the_records_columns_resident_in_one_order(
        catalog, tmp_path, monkeypatch):
    """Loading records fetches the replay inputs of every column they
    scan before any statement runs, table by table, the largest first
    (bytes of the scanned columns), ties by name, a table's columns by
    name (uploaded at first use, a chip's layout followed the order of
    the statements).  One rule for every platform: what _accel_args
    returns (resident buffers on a chip, host arguments on the CPU) is
    its own."""
    s1 = _fresh_tpu_session(catalog)
    s1.sql(_SEG_SQL).to_rows()
    path = str(tmp_path / "plans.pkl")
    assert s1.save_compiled(path) >= 1
    s2 = _fresh_tpu_session(catalog)
    exe2 = s2._jax_executor()
    calls = []
    monkeypatch.setattr(exe2, "_accel_args",
                        lambda t, cols=None: calls.append((t, cols)))
    assert s2.preload_compiled(path) >= 1
    assert all(cols == sorted(set(cols)) for _t, cols in calls)
    scanned = {}
    for cp in (*exe2._compiled.values(), *exe2._seg_compiled.values()):
        for t, cols in cp.table_cols.items():
            scanned.setdefault(t, set()).update(cols)
    assert {t: set(cols) for t, cols in calls} == scanned

    def nbytes(t):
        cols = exe2._table_device(t).columns
        return sum(cols[c].data.nbytes + cols[c].valid.nbytes
                   for c in scanned[t])

    tables = [t for t, _cols in calls]
    assert len(tables) >= 2
    assert tables == sorted(scanned, key=lambda t: (-nbytes(t), t))


def test_eager_demotion_warns(catalog, monkeypatch):
    """A query demoted to eager execution after repeated replay
    failures must surface a warning (the task-failure listener
    analog), not just print."""
    sess = _fresh_tpu_session(catalog)
    sql = "select count(*) as n from store_sales where ss_quantity > 3"
    want = sess.sql(sql).to_rows()
    cp = sess.compiled_plan(sql)
    assert cp is not None and cp.compilable

    import jax as _jax

    def boom(*a, **k):
        raise _jax.errors.JaxRuntimeError("injected compile failure")

    exe = sess._jax_executor()
    cp.fn_validated = False
    monkeypatch.setattr(exe, "_replay_query", boom)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        got = sess.sql(sql).to_rows()
    assert got == want
    assert not cp.compilable, "double failure did not demote"
    assert any("demoted to eager" in str(w.message) for w in caught)


# ---------------------------------------------------------------------------
# lazy-view composition through join chains
# ---------------------------------------------------------------------------


def test_lazy_views_multi_join_chain(catalog):
    """Columns gathered through inner->left join chains compose lazy
    views; results must match the numpy interpreter exactly (NULL
    pattern included)."""
    sql = ("select i_item_id, d_year, sr_return_quantity, ss_quantity "
           "from store_sales "
           "join item on ss_item_sk = i_item_sk "
           "join date_dim on ss_sold_date_sk = d_date_sk "
           "left join store_returns on ss_ticket_number = sr_ticket_number "
           "and ss_item_sk = sr_item_sk "
           "where d_moy = 12 "
           "order by i_item_id, d_year, ss_quantity, sr_return_quantity "
           "limit 500")
    cpu = Session(catalog, backend="cpu").sql(sql).to_rows()
    tpu = _fresh_tpu_session(catalog).sql(sql).to_rows()
    assert cpu == tpu


def test_select_cols_validity_base_mismatch_no_collapse():
    """_select_cols must NOT collapse to one lazy view when the two
    columns share a data buffer but carry different validity (the
    cast-with-extra-invalid shape) — collapsing would resurrect rows
    picked from side b with side a's validity."""
    data = jnp.arange(6, dtype=jnp.int32)
    va = jnp.asarray([True] * 6)
    vb = jnp.asarray([True, False, True, False, True, False])
    from ndstpu.schema import INT32
    a = jaxexec.DCol(data, va, INT32)
    b = jaxexec.DCol(data, vb, INT32)   # same buffer, stricter validity
    idx = jnp.asarray([0, 1, 2, 3, 4, 5], jnp.int32)
    pick_a = jnp.asarray([True, False, True, False, True, False])
    out = jaxexec._select_cols({"x": a}, {"x": b}, idx, idx, pick_a)
    got_valid = np.asarray(out["x"].valid)
    want_valid = np.where(np.asarray(pick_a), np.asarray(va),
                          np.asarray(vb))
    assert (got_valid == want_valid).all()


def test_stream_and_direct_sql_share_compiled_plan(catalog):
    """The SAME query must hit one compiled record whether it arrives
    as direct template text or as stream-file text carrying the
    `-- start/end` markers and trailing semicolon (the power CLI
    previously missed every persisted record and silently re-ran
    eager discovery per query)."""
    sess = _fresh_tpu_session(catalog)
    direct = ("select count(*) as n from store_sales "
              "where ss_quantity between 1 and 20")
    streamed = ("-- start query 1 in stream 0 using template queryX.tpl\n"
                + direct +
                "\n;\n-- end query 1 in stream 0 using template queryX.tpl\n")
    want = sess.sql(direct).to_rows()
    exe = sess._jax_executor()
    disc = exe.n_discoveries
    got = sess.sql(streamed).to_rows()
    assert got == want
    assert exe.n_discoveries == disc, \
        "stream-marker text missed the compiled-plan cache"
    assert sess.compiled_plan(direct) is sess.compiled_plan(streamed)


def test_stale_out_meta_self_heals(catalog, tmp_path):
    """An engine typing change can retype an output column without
    changing the plan tree, leaving a preloaded record's out_meta
    stale; assembling under the stale meta silently corrupted values
    (r04: scaled decimal data written as x100 floats).  The replay
    trace must detect the ctype drift and rediscover."""
    from ndstpu.schema import FLOAT64
    s1 = _fresh_tpu_session(catalog)
    sql = ("select i_category, sum(ss_net_paid) as s from store_sales "
           "join item on ss_item_sk = i_item_sk group by i_category "
           "order by i_category")
    want = s1.sql(sql).to_rows()
    path = str(tmp_path / "plans.pkl")
    assert s1.save_compiled(path) >= 1
    s2 = _fresh_tpu_session(catalog)
    assert s2.preload_compiled(path) >= 1
    exe2 = s2._jax_executor()
    cp = s2.compiled_plan(sql)
    assert cp is not None and cp.preloaded
    # simulate a typing change since the record was saved: claim the
    # decimal sum column was float64
    cp.out_meta = [(n, (FLOAT64 if n == "s" else ct), d, b)
                   for n, ct, d, b in cp.out_meta]
    for fp in (cp.seg_fps or ()):
        scp = exe2._seg_compiled[fp]
        scp.out_meta = [(n, (FLOAT64 if n == "s" else ct), d, b)
                        for n, ct, d, b in scp.out_meta]
    got = s2.sql(sql).to_rows()
    assert got == want, "stale out_meta produced corrupted values"
    assert exe2.n_discoveries > 0, "drifted meta did not self-heal"
