"""From a profiler trace to numbers: device busy time, time by device
op, and the longest idle gaps with what the host was doing in each.

Two steps, so that the second can be checked on a small recorded trace
(``benchmark/fixtures/``) without JAX:

``read_xplane``   the profiler's ``.xplane.pb`` -> plain events
``summarize``     events -> busy_s, window_s, ops by name, idle gaps

The traced window is the extent of the host annotation ``bench_window``
that the benchmark's own driver opens (in the process that holds the
chip) round the part of the measured window it traces; device ops are
clipped to it.  Device ops are the events of the ``XLA Ops`` line of
each ``/device:TPU:n`` plane; busy time is the union of their
intervals, averaged over the device planes.
"""

from __future__ import annotations

import glob
import os
import re
import shutil
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW_MARK = "bench_window"
# the annotations the benchmark's drivers open (TraceAnnotation names)
ANNOTATIONS = ("bench_", "part=", "to_rows=")
OP_NAME_CHARS = 96     # device-op names are whole HLO lines: cut them
# (name, start_s, end_s) on the trace's own clock
Interval = Tuple[str, float, float]


# the program's spans worth naming an idle gap after
GAP_SPANS = ("statement", "replay", "plan", "canonicalize")


def start_profiler(jax, log_dir: str) -> None:
    """Start the profiler on a clean directory: device ops and the
    drivers' annotations, no Python call tracing."""
    shutil.rmtree(log_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def spans_on_trace_clock(events: dict, spans: Iterable[dict],
                         mark_epoch: float) -> List[Interval]:
    """The program's spans (epoch clock) moved onto the trace's clock
    through the window mark, which the driver opened at ``mark_epoch``."""
    marks = [a for n, a, _b in events["host"] if n == WINDOW_MARK]
    shift = (min(marks) - mark_epoch) if marks else 0.0
    return [(f"span:{e['name']}", e["ts_epoch_s"] + shift,
             e["ts_epoch_s"] + shift + e["wall_s"]) for e in spans
            if e["name"] in GAP_SPANS or e.get("cat") == "query"]


def newest_xplane(log_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"the profiler wrote no trace in {log_dir}")
    return files[-1]


def read_xplane(path: str, rehearsal: bool = False) -> dict:
    """{"devices": {plane: [[name, start_s, end_s], ...]},
        "host": [[name, start_s, end_s], ...]}
    A trace with no ``/device:TPU`` plane is an error, but for a
    cpu-pinned ``rehearsal``, where XLA:CPU's executions stand in."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices: Dict[str, List[list]] = {}
    host: List[list] = []
    cpu_ops: List[list] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU"):
            lines = [ln for ln in plane.lines if ln.name == "XLA Ops"]
            for ln in lines:
                devices.setdefault(plane.name, []).extend(
                    [ev.name[:OP_NAME_CHARS], ev.start_ns * 1e-9,
                     (ev.start_ns + ev.duration_ns) * 1e-9]
                    for ev in ln.events)
        elif plane.name.startswith("/host:CPU"):
            for ln in plane.lines:
                on_cpu_client = ln.name.startswith("tf_XLAPjRtCpuClient")
                for ev in ln.events:
                    row = [ev.name, ev.start_ns * 1e-9,
                           (ev.start_ns + ev.duration_ns) * 1e-9]
                    if on_cpu_client:
                        if ev.duration_ns > 0 and not ev.name.startswith(
                                ("end: ", "ThreadpoolListener")):
                            cpu_ops.append(row)
                    elif ev.name.startswith(ANNOTATIONS):
                        host.append(row)    # the benchmark's annotations
    if not devices and not rehearsal:
        raise ValueError(f"{path}: the trace has no /device:TPU plane; "
                         f"host ops never stand for device ops")
    if not devices and cpu_ops:
        # a cpu-pinned rehearsal has no device plane: XLA:CPU's own
        # executions stand in so that the whole path is walked; the
        # result line says platform cpu and is never a device number
        devices["/host:CPU (rehearsal)"] = cpu_ops
    return {"devices": devices, "host": host}


def _union_s(intervals: Iterable[Tuple[float, float]]) -> Tuple[
        float, List[Tuple[float, float]]]:
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return sum(b - a for a, b in merged), [(a, b) for a, b in merged]


def _innermost(intervals: Sequence[Interval], t: float) -> Optional[str]:
    best = None
    for name, a, b in intervals:
        if a <= t <= b and (best is None or b - a < best[1]):
            best = (name, b - a)
    return best[0] if best else None


def summarize(events: dict, host_intervals: Sequence[Interval] = (),
              top: int = 10) -> Optional[dict]:
    """Reduce plain events to the numbers the readers and the result
    line use.  ``host_intervals`` are further host-side intervals on
    the trace's clock (the program's spans, moved onto it by the
    caller).  None where the trace has no window mark or no device."""
    marks = [(a, b) for name, a, b in events["host"] if name == WINDOW_MARK]
    if not marks or not events["devices"]:
        return None
    w0 = min(a for a, _ in marks)
    w1 = max(b for _, b in marks)
    window_s = w1 - w0
    if window_s <= 0:
        return None
    busy_total = 0.0
    by_name: Dict[str, List[float]] = {}
    gaps: List[Tuple[float, float]] = []
    for plane, evs in sorted(events["devices"].items()):
        clipped = []
        for name, a, b in evs:
            a, b = max(a, w0), min(b, w1)
            if b <= a:
                continue
            clipped.append((a, b))
            slot = by_name.setdefault(name, [0, 0.0])
            slot[0] += 1
            slot[1] += b - a
        busy, merged = _union_s(clipped)
        busy_total += busy
        if not gaps:       # idle gaps of the first device
            edges = [w0] + [x for ab in merged for x in ab] + [w1]
            gaps = [(edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i]]
    n_dev = len(events["devices"])
    notes = [(n, a, b) for n, a, b in events["host"] if n != WINDOW_MARK]
    notes += list(host_intervals)
    by_host: Dict[str, float] = {}
    for a, b in gaps:
        what = _innermost(notes, 0.5 * (a + b)) or "no host span"
        by_host[what] = by_host.get(what, 0.0) + (b - a)
    # per-op totals are summed over devices; report them per device
    ops = sorted(((n, c, s / n_dev) for n, (c, s) in by_name.items()),
                 key=lambda r: -r[2])
    return {
        "window_s": window_s,
        "busy_s": busy_total / n_dev,
        "devices": n_dev,
        "ops": [[n, c, s] for n, c, s in ops],
        "device_ops": [[n, s] for n, _c, s in ops[:top]],
        "idle_gaps": [[n, s] for n, s in sorted(
            by_host.items(), key=lambda r: -r[1])[:top]],
        "window_start_s": w0,
    }


def op_seconds(summary: dict, pattern: str) -> Optional[float]:
    rx = re.compile(pattern)
    hits = [s for n, _c, s in summary["ops"] if rx.search(n)]
    return sum(hits) if hits else None
