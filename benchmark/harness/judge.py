"""Decide ``correct``: every answer the timed path returned in the
window against the plain reference, and the guarantees a run can show.

Each number compared has a limit of its own; the run prints each beside
its limit (``checks`` in the result line, and the last lines of
standard error).  The limits, and the readings they were set from, are
in ``PERF.md`` section 2:

    answers_off          answers whose row/column count, a string or an
                         integer differs from the reference      limit 0
    decimal_cells_off    decimal cells not exactly the reference's
                         (the configurations guarantee exact decimal
                         arithmetic; the float-mode control reads
                         dozens here)                            limit 0
    float_gap_max        worst float cell by the validator's measure
                         (relative above 1, absolute below).  The
                         configurations state float64 averages and
                         ratios: sound runs read under 2e-14, the
                         reference computed in float32 reads 1e-7
                                                                 limit 1e-10
    unanswered           replays or requests that errored, were shed
                         or refused, or never came back          limit 0
    fallbacks            engine.fallback.* counted in the process that
                         holds the chip, window and warm-up      limit 0
    compiles_in_window   compiled-cache misses + discoveries + jit
                         builds + new persistent-cache files inside
                         the measured window                     limit 0
"""

from __future__ import annotations

import importlib
import sys
from typing import Dict, List, Optional, Sequence, Tuple

from benchmark.harness import compare, traffic

LIMITS = {
    "answers_off": 0,
    "decimal_cells_off": 0,
    "float_gap_max": 1e-10,
    "unanswered": 0,
    "fallbacks": 0,
    "compiles_in_window": 0,
}


# what must not move inside a measured window (program counters)
COMPILE_COUNTERS = ("engine.cache.compiled.miss", "engine.discoveries",
                    "engine.jit_builds")


def compile_counts(counters: Dict[str, float]) -> Dict[str, float]:
    return {k: counters.get(k, 0) for k in COMPILE_COUNTERS}


def fallback_count(counters: Dict[str, float]) -> int:
    return int(sum(v for k, v in counters.items()
                   if k.startswith("engine.fallback.")))


def reference_answers(config: dict, raw_dir: str,
                      texts: Sequence[traffic.Text],
                      floats: str = "float64") -> List[tuple]:
    """(kinds, rows) per text, from the configuration's plain
    reference (``reference``: a module under ``benchmark/``)."""
    ref = importlib.import_module(config["reference"])
    raw = ref.RawTables(raw_dir)
    return [ref.answer(raw, t.template, t.sql, floats=floats)
            for t in texts]


def judge(config: dict, raw_dir: str, texts: Sequence[traffic.Text],
          answers: Sequence[Tuple[int, Sequence[Sequence]]],
          unanswered: int, fallbacks: int, compiles_in_window: int,
          control: Optional[str] = None) -> Tuple[bool, Dict[str, dict]]:
    """``answers``: (text index, rows) for every answer of the window.
    Under ``--control ref-f32`` the reference, computed in float32,
    stands in the program's place: each answer of the window is
    replaced by it before the comparison."""
    want = reference_answers(config, raw_dir, texts)
    if control == "ref-f32":
        low = reference_answers(config, raw_dir, texts, floats="float32")
        answers = [(idx, low[idx][1]) for idx, _rows in answers]
    total: Dict[str, float] = {"shape_off": 0, "exact_cells_off": 0,
                               "decimal_cells_off": 0,
                               "float_gap_max": 0.0}
    answers_off = 0
    seen: Dict[Tuple[int, int], dict] = {}
    for idx, rows in answers:
        kinds, ref_rows = want[idx]
        # replays of one text mostly return the very same rows: compare
        # each distinct answer once
        key = (idx, hash(repr(rows)))
        one = seen.get(key)
        if one is None:
            one = seen[key] = compare.compare_answer(rows, kinds, ref_rows)
        compare.merge(total, one)
        if one["shape_off"] or one["exact_cells_off"]:
            answers_off += 1
    values = {
        "answers_off": answers_off,
        "decimal_cells_off": int(total["decimal_cells_off"]),
        "float_gap_max": float(total["float_gap_max"]),
        "unanswered": int(unanswered),
        "fallbacks": int(fallbacks),
        "compiles_in_window": int(compiles_in_window),
    }
    checks = {k: {"value": v, "limit": LIMITS[k]}
              for k, v in values.items()}
    correct = all(c["value"] <= c["limit"] for c in checks.values()) \
        and len(answers) > 0
    return correct, checks


def print_checks(correct: bool, checks: Dict[str, dict]) -> None:
    """The last lines of standard error: each number beside its limit."""
    for name, c in checks.items():
        flag = "ok" if c["value"] <= c["limit"] else "OVER"
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r}) "
              f"{flag}", file=sys.stderr)
    print(f"correct = {str(correct).lower()}", file=sys.stderr, flush=True)
