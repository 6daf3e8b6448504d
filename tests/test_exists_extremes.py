"""``optimizer.exists_by_extremes``: existence over an inner equi-join
with one inequality between its sides, from per-key min / max.

Each positive case plans a query whose semi / anti / NOT IN / mark join
reads the keys of such a join, and compares four answers: the plan
optimised without the rule, on the numpy engine, against the statement
through ``Session(backend="tpu")`` (discovery, then the compiled
replay) and ``Session(backend="numpy")``, both of which plan with the
rule.  The rule-free plan is ``optimize()``'s with the rule's name bound
to the identity for the call; the rule applied to that plan by hand
gives the session's plan.  The tables hold NULL keys, NULL values on
both sides, a key whose values are all equal, keys on one side only.
Negative cases: the plan comes back unchanged.
"""

from __future__ import annotations

from unittest import mock

import numpy as np
import pytest

from ndstpu import analysis, obs
from ndstpu.engine import columnar, optimizer as opt, physical, plan as lp
from ndstpu.engine.columnar import DATE, INT32, Column, decimal
from ndstpu.engine.jaxexec import _plan_fp
from ndstpu.engine.session import Session
from ndstpu.io.loader import Catalog
from ndstpu.queries import streamgen

DEC = decimal(7, 2)


def _col(vals, ctype=INT32):
    data = np.array([0 if v is None else v for v in vals],
                    dtype=columnar.numpy_dtype(ctype))
    return Column(data, ctype, np.array([v is not None for v in vals]))


def _side(rng, keys, n, fixed):
    """A table of the rows ``fixed`` ((key, value) pairs) and ``n``
    more over ``keys`` (some NULL), whose values (int, and the same as
    date, decimal, string) are NULL now and then."""
    k = [None if rng.random() < 0.1 else int(rng.choice(keys))
         for _ in range(n)]
    x = [None if rng.random() < 0.15 else int(rng.integers(0, 4))
         for _ in range(n)]
    k, x = [*(r[0] for r in fixed), *k], [*(r[1] for r in fixed), *x]
    return {"k": _col(k),
            "x": _col(x),
            "d": _col([None if v is None else 11000 + 3 * v for v in x],
                      DATE),
            "m": _col([None if v is None else 150 * v - 99 for v in x], DEC),
            "s": Column.from_strings([None if v is None else "abcd"[v]
                                      for v in x])}


@pytest.fixture(scope="module")
def catalog():
    rng = np.random.default_rng(34)
    cat = Catalog()
    # key 1: every value 2 on both sides (no pair differs, 2 <= 2
    # holds); key 12: every value NULL in a (no pair at all); keys 9
    # and 10 only in a, 11 only in b
    cat.register("a", columnar.Table(_side(
        rng, list(range(2, 11)), 44, [(1, 2)] * 4 + [(12, None)] * 3)))
    cat.register("b", columnar.Table(_side(
        rng, [*range(2, 9), 11], 36, [(1, 2)] * 3 + [(12, 1), (12, 3)])))
    cat.register("c", columnar.Table(
        {"k": _col([1, 3, 3, 5, 7, 9, 11, 12, None]),
         "cx": _col([0, 1, 2, 3, 0, 1, 2, 3, 0])}))
    cat.register("p", columnar.Table(
        {"pk": _col([*range(0, 13), None]),
         "px": _col([0, 20, 0, 0, 0, 0, 0, 20, 0, 0, 0, 0, 0, 0])}))
    return cat


@pytest.fixture(scope="module")
def sessions(catalog):
    obs.reset(enabled=True)
    yield Session(catalog, backend="tpu"), Session(catalog, backend="numpy")
    obs.reset()


def _pair(op="<>", col="x", right="b", extra=""):
    """A CTE ``w`` of the inner join's pairs, keys and both values."""
    left = "a"
    la, ra = ("a1", "a2") if right == "a" else ("a", "b")
    return (f"with w as (select {la}.k k, {la}.{col} v1, {ra}.{col} v2 "
            f"from {left} {la}, {right} {ra} where {la}.k = {ra}.k "
            f"and {la}.{col} {op} {ra}.{col}{extra}) ")


CONSUMERS = {
    "semi": "select pk from p where pk in (select k from w)",
    "anti": "select pk from p where not exists "
            "(select * from w where w.k = p.pk)",
    "nullaware_anti": "select pk from p where pk not in (select k from w)",
    "mark": "select pk from p where px > 10 or exists "
            "(select * from w where w.k = p.pk)",
    # the step through an inner join read only through its key (q95's
    # web_returns x ws_wh)
    "inner_step": "select pk from p where pk in "
                  "(select c.k from c, w where c.k = w.k)",
}

POSITIVE = {
    **{f"semi{op}": _pair(op) + CONSUMERS["semi"]
       for op in ("<>", "<", "<=", ">", ">=")},
    **{f"{kind}<>": _pair() + sql for kind, sql in CONSUMERS.items()
       if kind != "semi"},
    "self_join<>": _pair(right="a") + CONSUMERS["semi"],
    "self_join<": _pair("<", right="a") + CONSUMERS["anti"],
    "date>": _pair(">", col="d") + CONSUMERS["inner_step"],
    "decimal<>": _pair(col="m") + CONSUMERS["nullaware_anti"],
    # the pair's value written b.x op a.x: the rule flips it
    "flipped": "with w as (select a.k k from a, b where a.k = b.k "
               "and b.x < a.x) " + CONSUMERS["semi"],
    # q95's shape: the pair used twice, directly and through a join
    "twice": _pair() + "select pk from p where pk in (select k from w) "
                       "and pk in (select c.k from c, w where c.k = w.k)",
}

NEGATIVE = {
    # the pair's value read above: it is the semi join's key
    "value_read": _pair() + "select pk from p where pk in "
                            "(select v1 from w)",
    # ... or a key of the inner join the fact would step through
    "value_read_by_step": _pair() + "select pk from p where pk in "
                                    "(select c.k from c, w where "
                                    "c.k = w.k and c.cx = w.v2)",
    # the probe row's own value in the residual (q94 / q16)
    "correlated": "select pk from p where exists (select * from b "
                  "where b.k = p.pk and b.x <> p.px)",
    "two_conjuncts": _pair(extra=" and a.d < b.d") + CONSUMERS["semi"],
    "string": _pair(col="s") + CONSUMERS["semi"],
    "aggregate_between": _pair() + "select pk from p where pk in "
                                   "(select k from w group by k "
                                   "having count(*) > 1)",
    "outer_join_between": _pair() + "select pk from p where pk in "
                                    "(select c.k from c left join w "
                                    "on c.k = w.k)",
}


def _without_rule(sess, sql):
    """``optimize()``'s plan with the rule left out."""
    with mock.patch.object(opt, "exists_by_extremes",
                           lambda p, catalog=None: p):
        return sess.plan(sql)[0]


def _rows(table):
    return sorted(table.to_rows(), key=repr)


@pytest.mark.parametrize("case", list(POSITIVE))
def test_rewrite_keeps_the_answer(sessions, catalog, case):
    tpu, numpy = sessions
    sql = POSITIVE[case]
    plain = _without_rule(numpy, sql)
    planned = numpy.plan(sql)[0]
    # the rule fired, and applied by hand to the rule-free plan it gives
    # the optimizer's
    assert _plan_fp(planned) != _plan_fp(plain)
    by_hand = opt.prune(opt.exists_by_extremes(_without_rule(numpy, sql),
                                               catalog))
    assert _plan_fp(by_hand) == _plan_fp(planned)
    want = _rows(physical.Executor(catalog).execute(plain))
    assert want, case        # some key has a pair
    got = {"tpu discovery": _rows(tpu.sql(sql)),
           "tpu replay": _rows(tpu.sql(sql)),
           "numpy": _rows(numpy.sql(sql))}
    assert got == dict.fromkeys(got, want), case


@pytest.mark.parametrize("case", list(NEGATIVE))
def test_plan_comes_back_unchanged(sessions, case):
    _tpu, numpy = sessions
    sql = NEGATIVE[case]
    assert _plan_fp(numpy.plan(sql)[0]) == \
        _plan_fp(_without_rule(numpy, sql))


def test_shared_pair_runs_and_counts_once(sessions):
    """q95's two uses of one pair compute its extremes once a replay:
    the executor's memo shares the rewritten subtree, and the counter
    ticks for the one join of the two aggregates it ran."""
    tpu, _numpy = sessions
    sql = POSITIVE["twice"]
    tpu.sql(sql)
    before = obs.counters_snapshot().get("engine.replay.exists_extremes", 0)
    tpu.sql(sql)
    after = obs.counters_snapshot().get("engine.replay.exists_extremes", 0)
    assert after - before == 1


# the parts of the accepted cells the rule must leave as they are:
# power-sf1.opclass7, serve-sf1.short4-r80, and power-sf1.joinclass6
# but query95 (tests/test_joinclasses.py repeats those five at SF0.3)
BYPASSED = ["query3", "query7", "query96", "query12", "query86", "query25",
            "query9", "query69", "query10", "query94", "query97", "query38"]


@pytest.fixture(scope="module")
def schema_session():
    return Session(analysis.schema_catalog())


def _part(name):
    sql, = [s for n, s in streamgen.render_template_parts(
        str(streamgen.TEMPLATE_DIR / f"{name}.tpl"), "07291122510", 0)
        if n == name]
    return sql


@pytest.mark.parametrize("part", BYPASSED)
def test_accepted_cells_parts_are_bypassed(schema_session, part):
    sql = _part(part)
    assert _plan_fp(schema_session.plan(sql)[0]) == \
        _plan_fp(_without_rule(schema_session, sql))


def test_query95_is_rewritten_twice(schema_session):
    """Both uses of ws_wh become the join of two per-order extremes."""
    plan = schema_session.plan(_part("query95"))[0]
    joins = [n for n in plan.walk() if isinstance(n, lp.Join) and any(
        isinstance(c, lp.Aggregate) and c.aggs[0][0].startswith(
            opt.EXTREMES) for c in n.children())]
    assert len(joins) == 2 and _plan_fp(joins[0]) == _plan_fp(joins[1])
