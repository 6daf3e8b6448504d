"""The per-layer metrics' readers: a small fixed vocabulary, each
taking the span, counter or op names it reads as arguments from the
metric's own file (``benchmark/metrics/<metric>.json``).

A reader gets the run's record and returns a number, or None where it
finds nothing to read — the harness then leaves the metric out of the
result line.  None is never turned into 0.

The record (``RunRecord``) holds what one run observed: the program's
finished spans and counter movement inside the measured window, the
number of operations (replays or requests) in it, the client's view of
each request (served cells), and the summary of the profiler trace of
the traced part of the window.
"""

from __future__ import annotations

import dataclasses
import statistics
from typing import Callable, Dict, List, Optional

from benchmark.harness import peaks, trace


@dataclasses.dataclass
class RunRecord:
    spans: List[dict]                 # obs events (name, wall_s, args, ...)
    counters: Dict[str, float]        # movement inside the window
    ops: int                          # replays / requests in the window
    device_kind: str = ""
    rehearsal: bool = False           # cpu-pinned: no chip, no peaks
    trace: Optional[dict] = None      # trace.summarize(...) of the traced part
    traced_ops: int = 0               # replays / requests in the traced part
    traced_input_bytes: Optional[float] = None
    # served cells: one per request due in the window
    # {"id", "due", "sent", "done", "ok"} on the client's clock (epoch s)
    requests: List[dict] = dataclasses.field(default_factory=list)


def _named(rec: RunRecord, name: str, cat: Optional[str] = None):
    return [e for e in rec.spans if e["name"] == name
            and (cat is None or e.get("cat") == cat)]


def span_mean_ms(rec: RunRecord, span: str, attr: Optional[str] = None,
                 **_kw) -> Optional[float]:
    """Mean duration of the spans of that name, or of a seconds-valued
    attribute they carry."""
    evs = _named(rec, span)
    if attr is not None:
        vals = [float(e["args"][attr]) for e in evs
                if attr in (e.get("args") or {})]
    else:
        vals = [float(e["wall_s"]) for e in evs]
    return 1e3 * statistics.fmean(vals) if vals else None


def span_diff_mean_ms(rec: RunRecord, inner: str,
                      outer: Optional[str] = None,
                      outer_cat: Optional[str] = None,
                      **_kw) -> Optional[float]:
    """Mean per outer span of its duration less the inner spans' (the
    outer layer's self time where the inner nests in it).  The outer
    spans are those of a name, or of a category (the daemon names a
    request's ``query`` span after the request)."""
    outs = _named(rec, outer) if outer is not None else [
        e for e in rec.spans if e.get("cat") == outer_cat]
    if not outs:
        return None
    total = sum(float(e["wall_s"]) for e in outs) \
        - sum(float(e["wall_s"]) for e in _named(rec, inner))
    return 1e3 * total / len(outs)


def counter_per_op(rec: RunRecord, counter: str, **_kw) -> Optional[float]:
    if counter not in rec.counters or not rec.ops:
        return None
    return float(rec.counters[counter]) / rec.ops


def trace_idle_pct(rec: RunRecord, **_kw) -> Optional[float]:
    if not rec.trace:
        return None
    return 100.0 * (1.0 - rec.trace["busy_s"] / rec.trace["window_s"])


def trace_op_ms(rec: RunRecord, pattern: str, **_kw) -> Optional[float]:
    """Device time of the ops whose name matches, per operation of the
    traced part of the window."""
    if not rec.trace or not rec.traced_ops:
        return None
    s = trace.op_seconds(rec.trace, pattern)
    return None if s is None else 1e3 * s / rec.traced_ops


def input_roofline_pct(rec: RunRecord, **_kw) -> Optional[float]:
    """Least time to read the traced replays' inputs and write their
    results at the chip's HBM peak, over the device-busy time."""
    if not rec.trace or not rec.traced_input_bytes \
            or rec.trace["busy_s"] <= 0 or rec.rehearsal:
        return None
    least_s = rec.traced_input_bytes / peaks.peak(
        rec.device_kind, "hbm_bytes_per_s")
    return 100.0 * least_s / rec.trace["busy_s"]


def client_queue_ms(rec: RunRecord, span_cat: str = "query",
                    **_kw) -> Optional[float]:
    """Mean of the client's latency (due to reply) less the server's
    span for the same request id: what a request spends outside the
    daemon's ``query`` span -- late sending, the socket, admission, the
    scheduler's hand-over, the reply.  The wait for the device gate is
    INSIDE that span (``span_diff_mean_ms`` over it reads that)."""
    served = {e["name"]: float(e["wall_s"]) for e in rec.spans
              if e.get("cat") == span_cat}
    waits = [r["done"] - r["due"] - served[r["id"]]
             for r in rec.requests if r.get("ok") and r["id"] in served]
    return 1e3 * statistics.fmean(waits) if waits else None


def generator_late_ms(rec: RunRecord, quantile: float = 0.95,
                      **_kw) -> Optional[float]:
    late = sorted(r["sent"] - r["due"] for r in rec.requests
                  if r.get("sent") is not None)
    if not late:
        return None
    return 1e3 * late[min(len(late) - 1, int(quantile * len(late)))]


READERS: Dict[str, Callable[..., Optional[float]]] = {
    "span_mean_ms": span_mean_ms,
    "span_diff_mean_ms": span_diff_mean_ms,
    "counter_per_op": counter_per_op,
    "trace_idle_pct": trace_idle_pct,
    "trace_op_ms": trace_op_ms,
    "input_roofline_pct": input_roofline_pct,
    "client_queue_ms": client_queue_ms,
    "generator_late_ms": generator_late_ms,
}


def read_metric(metric_file: dict, rec: RunRecord) -> Optional[float]:
    name = metric_file.get("reader")
    if name not in READERS:
        raise KeyError(f"unknown reader {name!r}; the vocabulary is "
                       f"{sorted(READERS)}")
    return READERS[name](rec, **(metric_file.get("arguments") or {}))
