"""The comparison holds decimals to exact equality and measures floats
as the validator does; the input-byte function gives hand-computed bytes
for one plan."""

import dataclasses
from typing import List, Optional

import numpy as np
import pytest

from benchmark.harness import compare, judge, planbytes


def test_decimals_are_exact_and_floats_are_measured():
    want = [("a", 1, 155200.66, 0.5), ("b", 2, 20344.56, 100.0)]
    drift = [("a", 1, 155200.66000000003, 0.5000001),
             ("b", 2, 20344.56, 100.0005)]
    same = compare.compare_answer(want, "sidf", want)
    assert same == {"shape_off": 0, "exact_cells_off": 0,
                    "decimal_cells_off": 0, "float_gap_max": 0.0}
    got = compare.compare_answer(drift, "sidf", want)
    assert got["decimal_cells_off"] == 1 and got["exact_cells_off"] == 0
    # 100.0005 against 100.0 is 5e-6 relative; 1e-7 absolute below 1
    assert got["float_gap_max"] == pytest.approx(5e-6, rel=1e-3)
    assert got["float_gap_max"] > judge.LIMITS["float_gap_max"]


def test_order_among_rows_is_not_judged_but_content_is():
    want = [("a", 1), ("b", 2), (None, 3)]
    assert compare.compare_answer(list(reversed(want)), "si", want)[
        "exact_cells_off"] == 0
    assert compare.compare_answer([("a", 1), ("b", 2), (None, 4)], "si",
                                  want)["exact_cells_off"] == 1
    assert compare.compare_answer(want[:2], "si", want)["shape_off"] == 1
    assert compare.float_gap(float("nan"), float("nan")) == 0.0
    assert compare.float_gap(1.0, float("nan")) == float("inf")


def test_every_limit_is_held(monkeypatch):
    texts = ["t0"]
    monkeypatch.setattr(judge, "reference_answers",
                        lambda cfg, raw, tx, floats="float64": [
                            ("idf", [(1, 2.5, 1 / 3)])]
                        if floats == "float64" else [
                            ("idf", [(1, 2.5, float(np.float32(1 / 3)))])])
    ok, checks = judge.judge({}, "", texts, [(0, [(1, 2.5, 1 / 3)])] * 3, 0, 0, 0)
    assert ok and all(c["value"] <= c["limit"] for c in checks.values())
    for kw in ({"unanswered": 1}, {"fallbacks": 1},
               {"compiles_in_window": 1}):
        args = dict(unanswered=0, fallbacks=0, compiles_in_window=0)
        args.update(kw)
        bad, checks = judge.judge({}, "", texts, [(0, [(1, 2.5, 1 / 3)])], **args)
        assert not bad and checks[list(kw)[0]]["value"] == 1
    bad, checks = judge.judge({}, "", texts, [(0, [(1, 2.51, 1 / 3)])], 0, 0, 0)
    assert not bad and checks["decimal_cells_off"]["value"] == 1
    # a float32 average (1e-8 off) and a reordered float64 sum (1e-16)
    bad, checks = judge.judge({}, "", texts, [(0, [(1, 2.5, 1 / 3 + 1e-8)])],
                              0, 0, 0)
    assert not bad and checks["float_gap_max"]["value"] > 1e-10
    ok, _ = judge.judge({}, "", texts, [(0, [(1, 2.5, 1 / 3 + 1e-16)])],
                        0, 0, 0)
    assert ok
    # the control: the reference in float32 in the program's place
    bad, checks = judge.judge({}, "", texts, [(0, [(1, 2.5, 1 / 3)])] * 3,
                              0, 0, 0, control="ref-f32")
    assert not bad and checks["float_gap_max"]["value"] > 1e-10
    assert checks["decimal_cells_off"]["value"] == 0
    bad, _ = judge.judge({}, "", texts, [], 0, 0, 0)
    assert not bad      # a window with no answer proves nothing


@dataclasses.dataclass
class Scan:          # the shape of the program's plan nodes
    table: str
    alias: str
    columns: Optional[List[str]] = None
    predicate: object = None


@dataclasses.dataclass
class SubqueryExpr:
    plan: object


@dataclasses.dataclass
class Filter:
    child: object
    condition: object


@dataclasses.dataclass
class Join:
    left: object
    right: object
    keys: list


class _Col:
    def __init__(self, data, valid=None):
        self.data, self.valid = data, valid


class _Table:
    def __init__(self, **cols):
        self.columns = cols


def test_input_bytes_of_a_plan_by_hand():
    n = 1000
    catalog = {
        "fact": _Table(k=_Col(np.zeros(n, np.int32)),
                       price=_Col(np.zeros(n, np.int64),
                                  np.ones(n, bool)),
                       unused=_Col(np.zeros(n, np.int64))),
        "dim": _Table(sk=_Col(np.zeros(10, np.int32)),
                      name=_Col(np.zeros(10, np.int32))),
    }
    # fact is scanned twice (once inside a scalar subquery): its
    # columns count once
    plan = Filter(
        Join(Scan("fact", "f", ["k", "price"]), Scan("dim", "d", ["sk"]),
             keys=[("k", "sk")]),
        condition=SubqueryExpr(Scan("fact", "f2", ["price"])))
    cols = planbytes.scanned_columns(plan)
    assert cols == {("fact", "k"), ("fact", "price"), ("dim", "sk")}
    # k 1000 x 4; price 1000 x 8 + 1000 validity bytes; sk 10 x 4
    assert planbytes.plan_input_bytes(plan, catalog) == 4000 + 9000 + 40
    whole = Scan("dim", "d", None)
    assert planbytes.plan_input_bytes(whole, catalog) == 80
