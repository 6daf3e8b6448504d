"""The six templates of ``nds-sf1-power-joinclasses-1chip`` (semi /
anti / mark / residual-semi / full joins, INTERSECT, sorted aggregates,
a twelvefold self-join) through ``Session(backend="tpu")`` on the
CPU, in discovery and in replay, against the benchmark's plain
reference (``benchmark/reference/nds_templates_joins.py``) by the
benchmark's own comparison and limits (``harness/compare.py``,
``harness/judge.py``), and against the numpy engine; each part's
operator tallies (path and kind) pinned, no fallback; and one altered
answer per class that the comparison has to refuse.

Scale factor 0.3, not the suite's usual 0.01: under 0.3 the generator
gives one warehouse, and query94 / query95 (an order shipped from two
warehouses) answer from no row.  Data and texts are the built-in
seed's.  Each part runs once (discovery, then the replay that traces
and compiles its programs); the cases read what that run kept.
"""

from __future__ import annotations

import os
import subprocess
import sys
from unittest import mock

import numpy as np
import pandas as pd
import pytest

from benchmark.harness import compare, judge, traffic
from benchmark.reference import nds_templates_joins as ref
from ndstpu import obs
from ndstpu.engine import columnar, optimizer
from ndstpu.engine.columnar import INT32, Column
from ndstpu.engine.jaxexec import _JOIN_PATHS, _OP_KINDS, _plan_fp
from ndstpu.engine.session import Session
from ndstpu.io import loader
from ndstpu.io.loader import Catalog

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SF = "0.3"
TABLES = ["store_sales", "catalog_sales", "web_sales", "web_returns",
          "date_dim", "customer", "customer_address",
          "customer_demographics", "web_site"]
# part: (lookup, expand, sort, compare, deferred),
#       (semi, mark, residual, full, setop, agg_sort, exists_extremes,
#        window_rank, window_running, window_whole, agg_wide, memo_shared)
# summed over the part's programs, on this data set (query95's two uses
# of ws_wh run once: memo_shared 1)
TALLIES = {
    "query69": ((4, 4, 0, 4, 0), (3, 0, 0, 0, 0, 1, 0, 0, 0, 0, 0, 0)),
    "query10": ((4, 4, 0, 4, 0), (1, 2, 0, 0, 0, 1, 0, 0, 0, 0, 0, 0)),
    "query94": ((3, 2, 0, 3, 2), (2, 0, 1, 0, 0, 0, 0, 0, 0, 0, 0, 0)),
    "query97": ((2, 0, 2, 2, 0), (0, 0, 0, 1, 0, 2, 0, 0, 0, 0, 0, 0)),
    "query38": ((6, 0, 0, 3, 0), (0, 0, 0, 0, 2, 0, 0, 0, 0, 0, 0, 0)),
    "query95": ((5, 2, 0, 5, 2), (2, 0, 0, 0, 0, 0, 1, 0, 0, 0, 0, 1)),
}
PARTS = list(TALLIES)
# the kinds this cell is there for
CLASSES = ("join_semi", "join_mark", "join_residual", "join_full", "setop",
           "agg_sort", "exists_extremes")


def _refused(got, kinds, want):
    """(refused, counts): the limits of ``judge.judge`` on one answer."""
    one = compare.compare_answer(got, kinds, want)
    off = {"answers_off": int(bool(one["shape_off"]
                                   or one["exact_cells_off"])),
           "decimal_cells_off": one["decimal_cells_off"],
           "float_gap_max": one["float_gap_max"]}
    return any(v > judge.LIMITS[k] for k, v in off.items()), one


class _World:
    """The data set, the three engines over it, and what each part's
    one run returned."""

    def __init__(self, home: str):
        env = dict(os.environ, PYTHONPATH=REPO_ROOT)
        raw, wh, streams = (os.path.join(home, d)
                            for d in ("raw", "wh", "streams"))
        for cmd in (
                ["ndstpu.datagen.driver", "local", SF, "2", raw],
                ["ndstpu.io.transcode", "--input_prefix", raw,
                 "--output_prefix", wh, "--report_file",
                 os.path.join(home, "load.txt"), "--tables",
                 ",".join(TABLES)],
                ["ndstpu.queries.streamgen", "--streams", "1",
                 "--output_dir", streams]):
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
        self._want = {}

    def want(self, part: str):
        if part not in self._want:
            self._want[part] = ref.answer(self.raw, part, self.texts[part])
        return self._want[part]

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
        out["discoveries"] = after.get("engine.discoveries", 0) \
            - before.get("engine.discoveries", 0)
        cp = self.tpu.compiled_plan(sql)
        programs = [cp] + [self.tpu._jax_executor()._seg_compiled[fp]
                           for fp in (cp.seg_fps or ())]
        out["paths"] = tuple(sum(p.join_paths[i] for p in programs)
                             for i in range(len(_JOIN_PATHS)))
        out["kinds"] = tuple(sum(p.op_kinds[i] for p in programs)
                             for i in range(len(_OP_KINDS)))
        out["replay_counters"] = {
            k: after.get("engine.replay." + k, 0)
            - before.get("engine.replay." + k, 0) for k in _OP_KINDS}
        self._ran[part] = out
        return out


@pytest.fixture(scope="module")
def world(tmp_path_factory):
    obs.reset(enabled=True)
    yield _World(str(tmp_path_factory.mktemp("joinclasses")))
    obs.reset()


@pytest.mark.parametrize("phase", ["discovery", "replay"])
@pytest.mark.parametrize("part", PARTS)
def test_system_agrees_with_the_plain_reference(world, part, phase):
    kinds, want = world.want(part)
    assert want, part
    got = world.ran(part)[phase]
    refused, counts = _refused(got, kinds, want)
    assert not refused, (part, phase, counts, got[:3], want[:3])


@pytest.mark.parametrize("part", PARTS)
def test_reference_answers_are_not_trivial(world, part):
    """A reference that answers from no row checks nothing: every part
    has a non-NULL cell beyond a zero count."""
    _kinds, want = world.want(part)
    cells = [v for row in want for v in row]
    assert any(v not in (None, 0) for v in cells), want[:3]


@pytest.mark.parametrize("part", PARTS)
def test_system_agrees_with_the_numpy_engine(world, part):
    kinds, _want = world.want(part)
    other = world.numpy.sql(world.texts[part]).to_rows()
    refused, counts = _refused(world.ran(part)["replay"], kinds, other)
    assert not refused, (part, counts)


@pytest.mark.parametrize("part", PARTS)
def test_tallies_by_path_and_kind_and_no_fallback(world, part):
    ran = world.ran(part)
    assert (ran["paths"], ran["kinds"]) == TALLIES[part]
    assert ran["fallbacks"] == {} and ran["discoveries"] == 1
    # the one replay added its programs' tallies to the counters
    assert tuple(ran["replay_counters"][k] for k in _OP_KINDS) \
        == ran["kinds"]
    span = [e for e in obs.tracer().events if e["name"] == "replay"]
    assert all(k in span[-1]["args"] for k in _OP_KINDS)


@pytest.mark.parametrize("part", [p for p in PARTS if p != "query95"])
def test_exists_by_extremes_leaves_the_other_parts(world, part):
    """The optimizer's plan equals the one without exists_by_extremes:
    the rule fires on query95 alone (tests/test_exists_extremes.py)."""
    sql = world.texts[part]
    with mock.patch.object(optimizer, "exists_by_extremes",
                           lambda p, catalog=None: p):
        plain = world.numpy.plan(sql)[0]
    assert _plan_fp(world.numpy.plan(sql)[0]) == _plan_fp(plain)


def test_every_class_is_in_some_part():
    """What the cell is there for: each join / set-operation kind and
    the expand and sort paths are run by at least one part (the window
    and wide-aggregate kinds are another cell's: tests/test_aggwindow.py)."""
    paths = [sum(t[0][i] for t in TALLIES.values()) for i in range(5)]
    kinds = [sum(t[1][_OP_KINDS.index(k)] for t in TALLIES.values())
             for k in CLASSES]
    assert all(n > 0 for n in paths) and all(n > 0 for n in kinds)


# -- altered answers: one per class, each refused --------------------------

def test_a_semi_join_made_an_inner_join_is_refused(world):
    """EXISTS as a join counts a customer once per sale in the window:
    a group's three counts go up together (query69)."""
    kinds, want = world.want("query69")
    first = list(want[0])
    for i, k in enumerate(kinds):
        if k == "i" and i in (3, 5, 7):
            first[i] += 1
    refused, counts = _refused([tuple(first)] + want[1:], kinds, want)
    assert refused and counts["exact_cells_off"] >= 3


def test_an_intersect_made_a_union_is_refused(world):
    kinds, want = world.want("query38")
    sql = world.texts["query38"]
    assert sql.count("intersect") == 2
    union = world.numpy.sql(sql.replace("intersect", "union")).to_rows()
    assert union[0][0] > want[0][0]
    refused, counts = _refused(union, kinds, want)
    assert refused and counts["exact_cells_off"] == 1


def _nullable(values):
    data = np.array([0 if v is None else v for v in values], dtype=np.int32)
    return Column(data, INT32, np.array([v is not None for v in values]))


def test_a_null_key_made_to_match_is_refused():
    """query97's shape over two small tables with NULL keys on both
    sides, and query69's NOT EXISTS with a NULL probe key.  In the
    six templates' own answers the fault cannot show (their probe
    keys are primary keys, and query97's sums look at the key's
    NULL-ness alone), so the semantics are held here: a NULL key
    matches nothing and its row stays; pandas' merge, which pairs NaN
    with NaN, gives the altered answer."""
    a = {"k": [1, 2, None, None, 5], "i": [1, 1, 1, 2, 1]}
    b = {"k": [2, None, None, 5, 7], "i": [1, 1, 3, 2, 1]}
    cat = Catalog()
    cat.register("a", columnar.Table({"ak": _nullable(a["k"]),
                                      "ai": _nullable(a["i"])}))
    cat.register("b", columnar.Table({"bk": _nullable(b["k"]),
                                      "bi": _nullable(b["i"])}))
    full = ("select sum(case when ak is not null and bk is null then 1 "
            "else 0 end) a_only, sum(case when ak is null and bk is not "
            "null then 1 else 0 end) b_only, sum(case when ak is not null "
            "and bk is not null then 1 else 0 end) both_sides, count(*) n "
            "from a full outer join b on (ak = bk and ai = bi)")
    anti = ("select count(*) n from a where not exists "
            "(select * from b where ak = bk)")
    # (2, 1) finds its partner; of a (1, 1) (None, 1) (None, 2) (5, 1)
    # stay alone, of b four rows: 1 + 4 + 4 = 9 rows, and of the eight
    # two a side have a key
    want_full = [(2, 2, 1, 9)]
    # a's rows 1, NULL, NULL have no equal key in b: kept
    want_anti = [(3,)]
    sess = Session(cat, backend="tpu")
    for sql, kinds, want in ((full, "iiii", want_full),
                             (anti, "i", want_anti)):
        for _phase in ("discovery", "replay"):
            refused, counts = _refused(sess.sql(sql).to_rows(), kinds, want)
            assert not refused, (sql, counts)
    fa, fb = pd.DataFrame(a), pd.DataFrame(b)
    m = fa.merge(fb, on=["k", "i"], how="outer", indicator=True)
    # NaN paired with NaN: (None, 1) of both sides becomes one row
    altered = [(int(((m._merge == "left_only") & m.k.notna()).sum()),
                int(((m._merge == "right_only") & m.k.notna()).sum()),
                int(((m._merge == "both") & m.k.notna()).sum()), len(m))]
    assert altered == [(2, 2, 1, 8)]
    assert _refused(altered, "iiii", want_full)[0]
    matched = [(int((~fa.k.isin(fb.k)).sum()),)]     # isin pairs NaN too
    assert matched == [(1,)] and _refused(matched, "i", want_anti)[0]
