"""The comparison that decides ``correct``: what the timed path
returned against the plain reference's answer for the same text.

The measure of a float difference is a copy of the program's
validator (``ndstpu/harness/validate.py:value_equal``, itself the NDS
reference's ``nds_validate.py``): relative to the reference where that
exceeds 1, absolute otherwise.  The limit it is held to is
``judge.LIMITS``' (the configurations state float64 averages; the
validator's own epsilon, 1e-5, would let a float32 average pass).
Beyond the validator, this comparison knows from the reference which
columns are decimals and holds those to exact equality: the
configurations guarantee exact decimal arithmetic, and a path that sums
money in floating point must not pass.

Both sides are sorted canonically (non-float columns first) before
they are compared, as the validator does under ``--ignore_ordering``.
"""

from __future__ import annotations

import math
from typing import Dict, List, Sequence


def float_gap(got: float, want: float) -> float:
    """The validator's measure of a float difference."""
    if math.isnan(got) and math.isnan(want):
        return 0.0
    if math.isnan(got) or math.isnan(want):
        return math.inf
    gap = abs(got - want)
    return gap / abs(want) if abs(want) > 1.0 else gap


def _canonical(rows: Sequence[Sequence], kinds: str) -> List[tuple]:
    exact = [i for i, k in enumerate(kinds) if k != "f"]
    floats = [i for i, k in enumerate(kinds) if k == "f"]

    def key(row):
        return tuple((row[i] is None, str(row[i]))
                     for i in exact + floats)
    return sorted((tuple(r) for r in rows), key=key)


def compare_answer(got_rows: Sequence[Sequence], kinds: str,
                   want_rows: Sequence[Sequence]) -> Dict[str, float]:
    """Counts for one answer: ``shape_off`` (1 when the row or column
    count differs, and nothing else is compared), ``exact_cells_off``
    (strings, integers), ``decimal_cells_off`` (decimals, exact),
    ``float_gap_max`` (the validator's measure, worst cell)."""
    out = {"shape_off": 0, "exact_cells_off": 0, "decimal_cells_off": 0,
           "float_gap_max": 0.0}
    if len(got_rows) != len(want_rows) or any(
            len(r) != len(kinds) for r in got_rows):
        out["shape_off"] = 1
        return out
    got = _canonical(got_rows, kinds)
    want = _canonical(want_rows, kinds)
    for g_row, w_row in zip(got, want):
        for kind, g, w in zip(kinds, g_row, w_row):
            if g is None or w is None:
                if g is not w:
                    out["decimal_cells_off" if kind == "d"
                        else "exact_cells_off"] += 1
                continue
            if kind == "f":
                out["float_gap_max"] = max(
                    out["float_gap_max"], float_gap(float(g), float(w)))
            elif kind == "d":
                if float(g) != float(w):
                    out["decimal_cells_off"] += 1
            elif kind == "i":
                if int(g) != int(w):
                    out["exact_cells_off"] += 1
            elif g != w:
                out["exact_cells_off"] += 1
    return out


def merge(total: Dict[str, float], one: Dict[str, float]) -> None:
    for k, v in one.items():
        total[k] = max(total.get(k, 0.0), v) if k.endswith("_max") \
            else total.get(k, 0) + v
