"""The one general traffic generator: a workload file's parameters and
a seed in, the texts to send and (open loop) when each is due out.

Every seed gets the SAME multiset of work — the same counts of each
template, draw and tenant, the same set of inter-arrival gaps — in
another order, so that runs with different seeds differ by order and
data, not by amount of work.  Where the workload file gives
``fixed_seed``, data and texts are that seed's on every run and the
run's seed only moves where the cycle starts: a template's parameters
and the data's sampling noise decide which size classes a compiled
program gets, so a cell whose end-to-end metric is a time per pass
fixes both.

Closed loop (``driver: closed_loop``): ``parts`` x ``draws`` texts,
replayed pass after pass, the passes starting at a text the run's seed
chooses.  ``order: stream`` keeps the order in which the parts stand in
the stream 0 (the power test's permuted order); ``order: round_robin`` interleaves draws (part A draw 0, part B
draw 0, ..., part A draw 1, ...).

Open loop (``driver: open_loop``): ``rate_rps`` x seconds requests.
Inter-arrival gaps are the quantiles of the exponential distribution at
that rate (a Poisson process's gaps, stratified), optionally warped into
on/off bursts of the same mean rate (``bursts``); templates are drawn
with Zipf weights over ``parts`` in the order the file lists them
(``template_zipf_s``: the first part is the hot one) or with explicit
``template_weights``; draws uniformly; tenants with Zipf weights
(``tenant_zipf_s``).  The workload file's ``schedule_seed`` fixes ONE
sequence of (gap, text, tenant); a run's seed starts it at another
point of the cycle.  So every seed offers the same arrivals and the
same requests in the same succession, rotated: runs differ by their
data, their parameter draws and where the window opens, not by the
luck of an arrival pattern.
"""

from __future__ import annotations

import dataclasses
import math
import random
import re
from typing import Dict, List, Optional, Sequence, Tuple

_MARKER = re.compile(
    r"^--\s*start\s+query\s+\d+\s+in\s+stream\s+\d+\s+using\s+template\s+"
    r"(?P<name>\w+)\.tpl\s*$", re.MULTILINE | re.IGNORECASE)


def stream_texts(path: str) -> "Dict[str, str]":
    """{template name: text} of one rendered stream file, in the file's
    order.  (The single-statement subset of the program's
    ``power.gen_sql_from_stream``: the whole block, markers included,
    as the power test sends it.)"""
    with open(path) as f:
        text = f.read()
    marks = list(_MARKER.finditer(text))
    out: Dict[str, str] = {}
    for m, nxt in zip(marks, marks[1:] + [None]):
        end = nxt.start() if nxt is not None else len(text)
        out[m.group("name")] = text[m.start():end]
    return out


@dataclasses.dataclass(frozen=True)
class Text:
    """One distinct text a cell sends."""
    template: str
    draw: int
    sql: str

    @property
    def label(self) -> str:
        return f"{self.template}.d{self.draw}"


@dataclasses.dataclass(frozen=True)
class Request:
    index: int
    due_s: float       # seconds after the window opens
    text: int          # index into the cell's texts
    tenant: str


def cell_texts(workload: dict, streams_dir: str) -> List[Text]:
    """The distinct texts of a cell, in closed-loop pass order."""
    parts = list(workload["parts"])
    draws = int(workload.get("draws", 1))
    per_draw = [stream_texts(f"{streams_dir}/query_{d}.sql")
                for d in range(draws)]
    for name in parts:
        for d, texts in enumerate(per_draw):
            if name not in texts:
                raise KeyError(f"stream {d} has no part {name!r}")
    if workload.get("order", "stream") == "stream":
        rank = {n: i for i, n in enumerate(per_draw[0])}
        parts.sort(key=rank.__getitem__)
    return [Text(name, d, per_draw[d][name])
            for d in range(draws) for name in parts]


def _apportion(weights: Sequence[float], n: int) -> List[int]:
    """n items over the weights by largest remainder: the same counts
    for every seed."""
    total = float(sum(weights))
    exact = [w / total * n for w in weights]
    counts = [int(math.floor(x)) for x in exact]
    by_rest = sorted(range(len(weights)),
                     key=lambda i: (exact[i] - counts[i], -i), reverse=True)
    for i in by_rest[:n - sum(counts)]:
        counts[i] += 1
    return counts


def _zipf(n: int, s: float) -> List[float]:
    return [1.0 / (k ** s) for k in range(1, n + 1)]


def _burst_warp(times: List[float], bursts: dict) -> List[float]:
    """Map a unit-mean-rate time axis onto one where arrivals come
    ``on_factor`` times as fast during the first ``on_share`` of every
    ``period_s`` and slower in the rest, the mean rate unchanged."""
    period = float(bursts["period_s"])
    share = float(bursts["on_share"])
    hi = float(bursts["on_factor"])
    if not 0 < share < 1 or hi * share > 1:
        raise ValueError("bursts: 0 < on_share < 1 and "
                         "on_share * on_factor <= 1")
    lo = (1.0 - share * hi) / (1.0 - share)
    on_work = share * period * hi     # arrivals' worth of an on phase
    out = []
    for t in times:
        k, rest = divmod(t, period)   # 'rest' counts work, mean rate 1
        if rest <= on_work:
            local = rest / hi
        elif lo > 0:
            local = share * period + (rest - on_work) / lo
        else:
            local = period
        out.append(k * period + local)
    return out


def open_loop_schedule(workload: dict, n_texts_by_template: Dict[str, List[int]],
                       seed: int, seconds: float,
                       rate_rps: Optional[float] = None) -> List[Request]:
    """The requests due in a window of ``seconds``, sorted by due time.
    ``n_texts_by_template`` maps a template to the indices of its texts
    (one per draw)."""
    rate = float(rate_rps if rate_rps is not None
                 else workload["rate_rps"])
    n = int(round(rate * seconds))
    if n < 1:
        raise ValueError(f"rate {rate}/s over {seconds}s sends nothing")
    base = random.Random(int(workload.get("schedule_seed", 1)))
    # gaps: exponential quantiles, stretched so that the n arrivals
    # span the window (the quantile set's mean is a shade under 1/rate)
    gaps = [-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)]
    scale = seconds / sum(gaps)
    gaps = [g * scale for g in gaps]
    base.shuffle(gaps)
    templates = list(workload["parts"])
    if workload.get("template_weights"):
        weights = [float(w) for w in workload["template_weights"]]
        if len(weights) != len(templates):
            raise ValueError("template_weights: one weight per part")
    else:
        weights = _zipf(len(templates),
                        float(workload.get("template_zipf_s", 0.0)))
    picks: List[int] = []
    for name, count in zip(templates, _apportion(weights, n)):
        idxs = n_texts_by_template[name]
        for j, c in enumerate(_apportion([1.0] * len(idxs), count)):
            picks += [idxs[j]] * c
    base.shuffle(picks)
    n_tenants = int(workload.get("tenants", 1))
    tenants: List[str] = []
    for k, count in enumerate(_apportion(
            _zipf(n_tenants, float(workload.get("tenant_zipf_s", 0.0))), n)):
        tenants += [f"tenant{k}"] * count
    base.shuffle(tenants)
    # the run's seed: where in the cycle this window opens
    cut = random.Random(seed).randrange(n)
    gaps, picks, tenants = (x[cut:] + x[:cut]
                            for x in (gaps, picks, tenants))
    times, t = [], 0.0
    for g in gaps:
        times.append(t)           # first arrival at 0, last before the end
        t += g
    if workload.get("bursts"):
        times = _burst_warp(times, workload["bursts"])
    return [Request(i, times[i], picks[i], tenants[i]) for i in range(n)]
