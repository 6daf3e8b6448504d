"""The aggregate and window paths of ``nds-sf1-power-aggwindow-1chip``
(rank windows, running ROWS / RANGE frames, whole-partition windows, a
full outer join, a keyed exact sum over more slots than the segsum
kernel takes, a UNION ALL probe) through ``Session(backend="tpu")`` on
the CPU, in discovery and in replay, against the numpy engine on small
tables, each case with the operator kinds its programs tally; then the
configuration's three templates at its rehearsal scale against the
benchmark's plain reference (``benchmark/reference/
nds_templates_aggwin.py``) and the numpy engine, their tallies pinned,
and the four new kinds' readings on ``power-sf1.opclass7``'s parts.
"""

from __future__ import annotations

import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark.harness import compare, judge, traffic
from benchmark.reference import nds_templates_aggwin as ref
from ndstpu import obs
from ndstpu.engine import columnar
from ndstpu.engine.columnar import INT32, Column, decimal
from ndstpu.engine.jaxexec import _JOIN_PATHS, _OP_KINDS
from ndstpu.engine.session import Session
from ndstpu.io import loader
from ndstpu.io.loader import Catalog

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NEW_KINDS = ("window_rank", "window_running", "window_whole", "agg_wide")
# the most slots the segsum kernel takes (jaxexec._PALLAS_SEGS_MAX)
SEGS_MAX = 32768
BIG = 10 ** 12 - 1        # the largest unscaled decimal(12, 2)


def _ints(values):
    data = np.array([0 if v is None else v for v in values], dtype=np.int32)
    return Column(data, INT32, np.array([v is not None for v in values]))


def _cents(values, precision=7):
    data = np.array([0 if v is None else v for v in values], dtype=np.int64)
    return Column(data, decimal(precision, 2),
                  np.array([v is not None for v in values]))


def _table(**cols):
    return columnar.Table(cols)


def _wide_table(slots: int) -> columnar.Table:
    """(k, v): three rows a key over ``slots`` keys, v at +-(10^12 - 1)
    cents less a few, and the third row NULL on every fourth key."""
    k = np.repeat(np.arange(slots, dtype=np.int32), 3)
    sign = np.where(np.arange(len(k)) % 5 < 3, 1, -1)
    v = (sign * BIG).astype(np.int64)
    v[::3] -= (k[::3] % 7).astype(np.int64)
    valid = np.ones(len(k), bool)
    valid[2::3] = k[2::3] % 4 != 0
    return _table(k=Column(k, INT32), v=Column(v, decimal(12, 2), valid))


def _small_catalog() -> Catalog:
    cat = Catalog()
    # t: partitions g, order keys o with ties, values x with NULLs (a
    # partition whose first row is NULL, one that is all NULL)
    cat.register("t", _table(
        id=_ints(list(range(14))),
        g=_ints([1, 1, 1, 1, 1, 2, 2, 2, 2, 3, 3, None, None, 1]),
        o=_ints([1, 2, 2, 3, None, 5, 5, 5, 6, 1, 2, 1, 1, 2]),
        x=_cents([100, -250, 300, None, 75, None, 40, 40, -10, None, None,
                  7, 8, 300])))
    # a / b: full join sides on (k, d), keys on one side only, NULL keys
    cat.register("a", _table(
        ak=_ints([1, 1, 1, 2, 2, 4, None]),
        ad=_ints([10, 11, 13, 10, 12, 10, 10]),
        av=_cents([500, 700, None, 100, 900, 50, 60])))
    cat.register("b", _table(
        bk=_ints([1, 1, 2, 3, 3, 2, None]),
        bd=_ints([11, 12, 12, 10, 11, 14, 10]),
        bv=_cents([600, 200, 800, 10, 20, 5, 70])))
    cat.register("w_narrow", _wide_table(30000))
    cat.register("w_wide", _wide_table(40000))
    # two channels' sales and a day -> week table under them
    cat.register("u1", _table(s1=_ints([1, 2, 3, 3, None, 9]),
                              p1=_cents([100, 200, 300, None, 50, 70])))
    cat.register("u2", _table(s2=_ints([2, 4, 4, 5, 8]),
                              p2=_cents([1000, 2000, 3000, 4000, 9])))
    cat.register("d", _table(d_sk=_ints([1, 2, 3, 4, 5, 6, 8]),
                             d_wk=_ints([1, 1, 1, 2, 2, 2, None])))
    return cat


_RUN = ("rows between unbounded preceding and current row")
# case: (column kinds as the plain references give them, sql, its
# programs' (window_rank, window_running, window_whole, agg_wide) tallies)
CASES = {
    "rank_with_ties": (
        "iiiiii",
        "select id, g, o, rank() over (partition by g order by o) r, "
        "dense_rank() over (partition by g order by o desc) dr, "
        "row_number() over (partition by g order by o, id) rn from t",
        (3, 0, 0, 0)),
    "range_against_rows_peers": (
        "iiiddi",
        "select id, g, o, sum(x) over (partition by g order by o) s_range, "
        f"sum(x) over (partition by g order by o, id {_RUN}) s_rows, "
        "count(x) over (partition by g order by o) c_range from t",
        (0, 3, 0, 0)),
    "running_sum_and_max_over_nulls": (
        "iiddf",
        f"select id, g, sum(x) over (partition by g order by o, id {_RUN}) "
        f"s, max(x) over (partition by g order by o, id {_RUN}) m, "
        "avg(x) over (partition by g) a from t",
        (0, 2, 1, 0)),
    "running_max_over_a_full_joins_null_side": (
        "iidddd",
        "select k, d, av, bv, "
        f"max(av) over (partition by k order by d {_RUN}) am, "
        f"max(bv) over (partition by k order by d {_RUN}) bm "
        "from (select case when ak is not null then ak else bk end k, "
        "case when ak is not null then ad else bd end d, av, bv "
        "from a full outer join b on (ak = bk and ad = bd)) x",
        (0, 2, 0, 0)),
    "full_join_with_keys_on_one_side": (
        "iidiid",
        "select ak, ad, av, bk, bd, bv from a full outer join b "
        "on (ak = bk and ad = bd)",
        (0, 0, 0, 0)),
    "dense_sum_within_the_kernel": (
        "idfi",
        "select k, sum(v) s, avg(v) m, count(v) n from w_narrow group by k",
        (0, 0, 0, 0)),
    "dense_sum_over_the_kernel": (
        "idfi",
        "select k, sum(v) s, avg(v) m, count(v) n from w_wide group by k",
        (0, 0, 0, 1)),
    "union_all_probe_under_a_join": (
        "idi",
        "select d_wk, sum(p) s, count(*) n from (select s1 sk, p1 p from u1 "
        "union all select s2 sk, p2 p from u2) u, d where d_sk = sk "
        "group by d_wk",
        (0, 0, 0, 0)),
}


def _refused(got, kinds, want):
    one = compare.compare_answer(got, kinds, want)
    off = {"answers_off": int(bool(one["shape_off"]
                                   or one["exact_cells_off"])),
           "decimal_cells_off": one["decimal_cells_off"],
           "float_gap_max": one["float_gap_max"]}
    return any(v > judge.LIMITS[k] for k, v in off.items()), one


def _tallies(sess: Session, sql: str):
    """(join paths, operator kinds) summed over the statement's programs."""
    cp = sess.compiled_plan(sql)
    programs = [cp] + [sess._jax_executor()._seg_compiled[fp]
                       for fp in (cp.seg_fps or ())]
    return (tuple(sum(p.join_paths[i] for p in programs)
                  for i in range(len(_JOIN_PATHS))),
            tuple(sum(p.op_kinds[i] for p in programs)
                  for i in range(len(_OP_KINDS))))


@pytest.fixture(scope="module")
def small():
    cat = _small_catalog()
    return Session(cat, backend="tpu"), Session(cat, backend="numpy")


@pytest.mark.parametrize("case", list(CASES))
def test_small_tables_agree_with_the_numpy_engine(small, case):
    tpu, numpy_sess = small
    kinds, sql, new_kinds = CASES[case]
    want = numpy_sess.sql(sql).to_rows()
    assert want, case
    for phase in ("discovery", "replay"):
        refused, counts = _refused(tpu.sql(sql).to_rows(), kinds, want)
        assert not refused, (case, phase, counts)
    tallies = dict(zip(_OP_KINDS, _tallies(tpu, sql)[1]))
    assert tuple(tallies[k] for k in NEW_KINDS) == new_kinds, tallies


_SUMS = "select g, sum(x) sx from t where o > 1 group by g"
# case: (sql, memo_shared of its programs).  One literal of a CTE used
# twice is one parameter slot, so the two uses fingerprint equal and run
# once; the same subquery written out twice holds two literals of one
# value, two slots, and runs twice.
MEMO = {
    "cte_used_twice": (
        f"with s as ({_SUMS}) select s1.g, s1.sx, s2.sx sx2 "
        "from s s1, s s2 where s1.g = s2.g", 1),
    "subquery_written_twice": (
        f"select s1.g, s1.sx, s2.sx sx2 from ({_SUMS}) s1, ({_SUMS}) s2 "
        "where s1.g = s2.g", 0),
}


@pytest.mark.parametrize("case", list(MEMO))
def test_a_cte_used_twice_runs_once(small, case):
    tpu, numpy_sess = small
    sql, shared = MEMO[case]
    want = numpy_sess.sql(sql).to_rows()
    assert want, case
    for phase in ("discovery", "replay"):
        refused, counts = _refused(tpu.sql(sql).to_rows(), "idd", want)
        assert not refused, (case, phase, counts)
    tallies = dict(zip(_OP_KINDS, _tallies(tpu, sql)[1]))
    assert tallies["memo_shared"] == shared, tallies


@pytest.mark.parametrize("table,slots", [("w_narrow", 30000),
                                         ("w_wide", 40000)])
def test_dense_decimal_sums_are_exact(small, table, slots):
    """Each key's sum, against Python's integers: the kernel's side of
    _PALLAS_SEGS_MAX and the scatter's side of it."""
    tpu, _numpy = small
    src = _wide_table(slots)
    v = src.column("v")
    exact = {}
    for key, val, ok in zip(src.column("k").data.tolist(), v.data.tolist(),
                            v.valid.tolist()):
        if ok:
            exact[key] = exact.get(key, 0) + val
    assert max(abs(x) for x in exact.values()) > 2 * BIG
    sql = f"select k, sum(v) s from {table} group by k"
    for _phase in ("discovery", "replay"):
        got = tpu.sql(sql).to_rows()
        assert len(got) == slots and (slots > SEGS_MAX) == (table == "w_wide")
        # the system prints a decimal as its unscaled int64 over 100
        off = [r for r in got
               if r[1] != float(np.float64(exact[r[0]]) / 100)]
        assert not off, off[:3]


# -- the configuration's three templates at its rehearsal scale -------------

SF = "0.01"
# the cell's fixed_seed: its data and texts at the rehearsal scale
SEED = "3000000047"
TABLES = ["store_sales", "catalog_sales", "web_sales", "date_dim", "item",
          "store", "store_returns", "customer_demographics", "promotion",
          "time_dim", "household_demographics", "reason"]
# part: (lookup, expand, sort, compare, deferred),
#       (semi, mark, residual, full, setop, agg_sort, exists_extremes,
#        window_rank, window_running, window_whole, agg_wide, memo_shared)
# summed over the part's programs, on this data set.  A CTE used twice
# or three times runs once: its uses share their literals' parameter
# slots (one slot per source literal), so they fingerprint equal, the
# segment cut folds them into one program and the parent reads it once
# a use -- query2's pivot sum counts 1 and its second use memo_shared
# 1; query47's six-key aggregate, its two windows and the lookups under
# them count once, its two further uses memo_shared 2.
TALLIES = {
    "query2": ((1, 3, 0, 0, 0), (0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 1, 1)),
    "query47": ((3, 0, 2, 3, 0), (0, 0, 0, 0, 0, 1, 0, 1, 0, 1, 0, 2)),
    "query51": ((2, 0, 2, 2, 0), (0, 0, 0, 1, 0, 2, 0, 0, 4, 0, 0, 0)),
}
PARTS = list(TALLIES)
# power-sf1.opclass7's parts: (window_rank, window_running, window_whole,
# agg_wide)
OPCLASS7 = {"query3": (0, 0, 0, 0), "query7": (0, 0, 0, 0),
            "query96": (0, 0, 0, 0), "query12": (0, 0, 1, 0),
            "query86": (1, 0, 0, 0), "query25": (0, 0, 0, 0),
            "query9": (0, 0, 0, 0)}


class _World:
    """The data set, the two engines over it, and what each part's one
    run returned."""

    def __init__(self, home: str):
        env = dict(os.environ, PYTHONPATH=REPO_ROOT)
        raw, wh, streams = (os.path.join(home, d)
                            for d in ("raw", "wh", "streams"))
        for cmd in (
                ["ndstpu.datagen.driver", "local", SF, "2", raw, "--seed",
                 SEED],
                ["ndstpu.io.transcode", "--input_prefix", raw,
                 "--output_prefix", wh, "--report_file",
                 os.path.join(home, "load.txt"), "--tables",
                 ",".join(TABLES)],
                ["ndstpu.queries.streamgen", "--streams", "1", "--rngseed",
                 SEED, "--output_dir", streams]):
            subprocess.run([sys.executable, "-m", *cmd], check=True,
                           env=env, cwd=REPO_ROOT,
                           stdout=subprocess.DEVNULL)
        self.texts = traffic.stream_texts(
            os.path.join(streams, "query_0.sql"))
        self.raw = ref.RawTables(raw)
        catalog = loader.load_catalog(wh, use_decimal=True)
        self.tpu = Session(catalog, backend="tpu")
        self.numpy = Session(catalog, backend="numpy")
        self._ran = {}

    def ran(self, part: str) -> dict:
        if part in self._ran:
            return self._ran[part]
        sql = self.texts[part]
        before = obs.counters_snapshot()
        out = {"discovery": self.tpu.sql(sql).to_rows(),
               "replay": self.tpu.sql(sql).to_rows()}
        after = obs.counters_snapshot()
        out["fallbacks"] = {
            k: v - before.get(k, 0) for k, v in after.items()
            if k.startswith("engine.fallback.") and v != before.get(k, 0)}
        out["paths"], out["kinds"] = _tallies(self.tpu, sql)
        out["replay_counters"] = {
            k: after.get("engine.replay." + k, 0)
            - before.get("engine.replay." + k, 0) for k in _OP_KINDS}
        self._ran[part] = out
        return out


@pytest.fixture(scope="module")
def world(tmp_path_factory):
    obs.reset(enabled=True)
    yield _World(str(tmp_path_factory.mktemp("aggwindow")))
    obs.reset()


@pytest.mark.parametrize("phase", ["discovery", "replay"])
@pytest.mark.parametrize("part", PARTS)
def test_templates_agree_with_the_plain_reference(world, part, phase):
    kinds, want = ref.answer(world.raw, part, world.texts[part])
    assert want and any(v not in (None, 0) for r in want for v in r)
    refused, counts = _refused(world.ran(part)[phase], kinds, want)
    assert not refused, (part, phase, counts)
    other = world.numpy.sql(world.texts[part]).to_rows()
    assert not _refused(other, kinds, want)[0], part


@pytest.mark.parametrize("part", PARTS)
def test_template_tallies_and_no_fallback(world, part):
    ran = world.ran(part)
    assert (ran["paths"], ran["kinds"]) == TALLIES[part]
    assert ran["fallbacks"] == {}
    # the one replay added its programs' tallies to the counters
    assert tuple(ran["replay_counters"][k] for k in _OP_KINDS) \
        == ran["kinds"]
    span = [e for e in obs.tracer().events if e["name"] == "replay"]
    assert all(k in span[-1]["args"] for k in _OP_KINDS)


@pytest.mark.parametrize("part", list(OPCLASS7))
def test_opclass7_parts_new_kinds(world, part):
    kinds = dict(zip(_OP_KINDS, world.ran(part)["kinds"]))
    assert tuple(kinds[k] for k in NEW_KINDS) == OPCLASS7[part], kinds


def test_every_new_kind_is_in_some_template():
    """What the cell is there for: each of the four kinds is run by at
    least one of its parts."""
    at = {k: sum(t[1][_OP_KINDS.index(k)] for t in TALLIES.values())
          for k in NEW_KINDS}
    assert all(n > 0 for n in at.values()), at


# -- the reference's boundaries --------------------------------------------

@pytest.mark.parametrize("a,b,want", [(108, 100, 1.08), (1, 3, 0.33),
                                      (2, 3, 0.67), (-2, 3, -0.67),
                                      (5, None, None), (5, 0, None)])
def test_reference_rounds_half_up_from_the_exact_quotient(a, b, want):
    assert ref._half_up_ratio(a, b, np.float64) == want


@pytest.mark.parametrize("a,b", [(1, 200), (-3, 200),
                                 (5 * 10 ** 9 + 1, 10 ** 12)])
def test_reference_raises_on_a_half_cent_tie(a, b):
    with pytest.raises(ref.TieError):
        ref._half_up_ratio(a, b, np.float64)
