"""The host runtime accounts for its own stalls: GC pauses and a lag
sampler, fed into the process's tracer like every other instrument.

Two instruments, started once per process by :func:`start` (every
``Session`` calls it; nothing happens where tracing is off):

* **GC pauses.**  One ``gc.callbacks`` entry times every collection
  into process-wide totals, read as the counters ``host.gc.*`` and by
  :func:`gc_seconds`.  A generation-2 collection also holds a live
  ``ndstpu:gc`` profiler annotation over its own interval, and every
  collection of generation 2 or of ``GC_SPAN_MIN_S`` or more leaves a
  ``gc`` span (cat ``host``).  The callback takes no lock and calls
  nothing that does: a collection can fire on a thread that holds
  ``Tracer._lock`` (inside the event list's growth), so the totals are
  plain module fields (one collection runs at a time, so their updates
  never interleave) and the spans wait on a pending list that the
  sampler moves into the tracer.
* **Lag sampler.**  One daemon thread, ``ndstpu-host-sampler``, sleeps
  ``SAMPLE_S`` a tick and measures how late it woke.  Lateness of
  ``STALL_MIN_S`` or more is a ``host_stall`` span from when it was due
  to when it woke, carrying what moved meanwhile: GC pause seconds, the
  process's CPU seconds, page faults and context switches, the
  machine's CPU / memory / IO pressure.  A collection shows as ``gc_s``
  near the lateness; the machine as faults or pressure, or as a process
  that used no CPU while it was held up (``cpu_s`` near 0: not
  scheduled, or a thread blocked holding the GIL); a C call computing
  under the GIL as ``cpu_s`` near the lateness and no ``gc_s`` (the
  spans open on other threads then name it).  A sandboxed kernel may
  report faults and switches as 0 and have no pressure files: there
  ``cpu_s`` is what is left.

Counters ``host.gc.collections``, ``host.gc.pause_ms``,
``host.gc.gen2.collections``, ``host.gc.gen2.pause_ms``,
``host.stall.count`` and ``host.stall_ms`` read 0 from the moment the
hook is installed (``Tracer.counters_snapshot`` merges them in).
"""

from __future__ import annotations

import collections
import gc
import os
import resource
import threading
import time
from typing import Callable, Dict, Optional, Tuple

# the sampler's period: 20 wake-ups a second, each one syscall and
# three small file reads, well under a thousandth of a core; a stall
# is named within one tick of its end
SAMPLE_S = 0.05
# lateness from which a tick is a stall: a sleeping thread on a busy
# 13-core host wakes within a few ms, and 20 ms is already 40 % of the
# served cell's p95 and 8 % of a power pass of 0.26 s
STALL_MIN_S = 0.02
# a collection under this leaves no span of its own (generation 0
# takes tens of microseconds, dozens of times a second); its pause is
# in the totals all the same
GC_SPAN_MIN_S = 0.001
# gc spans held for the sampler at most (the oldest go first): a bound
# where nothing drains
PENDING_MAX = 1024
# rusage counts a stall carries, as deltas since the previous tick
# (beside cpu_s, the process's user + system CPU seconds)
RUSAGE_FIELDS = ("majflt", "minflt", "nivcsw", "nvcsw")
PSI_KINDS = ("cpu", "memory", "io")

# -- process-wide state: written by the gc callback (one collection at a
# time) and by the one sampler thread, never under a lock --------------------
_obs = None                 # ndstpu.obs, once installed
_gc_t0 = 0.0
_gc_mark = None             # the open ndstpu:gc annotation
_gc_n = 0
_gc_s = 0.0
_gc2_n = 0
_gc2_s = 0.0
_stall_n = 0
_stall_s = 0.0
# (t0_epoch, pause_s, tid, gen, collected, uncollectable); a deque's
# append and popleft are atomic, so two drains never take one span twice
_pending: collections.deque = collections.deque(maxlen=PENDING_MAX)
_sampler: Optional[threading.Thread] = None
_start_lock = threading.Lock()


def installed() -> bool:
    return _obs is not None


def gc_seconds() -> float:
    """GC pause seconds of the whole process since the hook was
    installed (0.0 where it never was): monotonic, for differences."""
    return _gc_s


def counters() -> Dict[str, float]:
    """The host counters; empty where the hook is not installed."""
    if _obs is None:
        return {}
    return {"host.gc.collections": _gc_n,
            "host.gc.pause_ms": 1e3 * _gc_s,
            "host.gc.gen2.collections": _gc2_n,
            "host.gc.gen2.pause_ms": 1e3 * _gc2_s,
            "host.stall.count": _stall_n,
            "host.stall_ms": 1e3 * _stall_s}


def _on_gc(phase: str, info: dict) -> None:
    """``gc.callbacks`` entry.  No lock, no ``obs.inc`` / ``obs.record``:
    see the module's docstring."""
    global _gc_t0, _gc_mark, _gc_n, _gc_s, _gc2_n, _gc2_s
    gen = info["generation"]
    if phase == "start":
        if gen == 2:
            _gc_mark = _obs.tracer().annotation("gc", generation=2)
            _gc_mark.__enter__()
        _gc_t0 = time.perf_counter()
        return
    pause = time.perf_counter() - _gc_t0
    if _gc_mark is not None:
        _gc_mark.__exit__(None, None, None)
        _gc_mark = None
    _gc_n += 1
    _gc_s += pause
    if gen == 2:
        _gc2_n += 1
        _gc2_s += pause
    if gen == 2 or pause >= GC_SPAN_MIN_S:
        _pending.append((time.time() - pause, pause, threading.get_ident(),
                         gen, info["collected"], info["uncollectable"]))


def drain(tracer) -> None:
    """Move the pending ``gc`` spans into ``tracer``'s events (dropped
    where it is off)."""
    batch = []
    while True:
        try:
            batch.append(_pending.popleft())
        except IndexError:
            break
    if not tracer.enabled:
        return
    for t0_epoch, pause, tid, gen, collected, uncollectable in batch:
        tracer.log_event("gc", "host", t0_epoch, pause, tid,
                         generation=gen, collected=collected,
                         uncollectable=uncollectable,
                         pause_s=round(pause, 6))


def _rusage() -> resource.struct_rusage:
    return resource.getrusage(resource.RUSAGE_SELF)


def _pressure() -> Dict[str, int]:
    """``some total=`` (microseconds stalled) of each pressure file
    that can be read; a kind whose file is missing is left out."""
    out = {}
    for kind in PSI_KINDS:
        try:
            with open(f"/proc/pressure/{kind}") as f:
                first = f.readline()
        except OSError:
            continue
        if first.startswith("some ") and " total=" in first:
            out[kind] = int(first.rsplit("total=", 1)[1])
    return out


class Sampler:
    """The lag sampler's readings between ticks.  The clock and the
    readers are arguments so that a test can drive :meth:`tick`."""

    def __init__(self, clock: Callable[[], float] = time.perf_counter,
                 rusage: Callable[[], object] = _rusage,
                 pressure: Callable[[], Dict[str, int]] = _pressure,
                 gc_total: Callable[[], float] = gc_seconds):
        self.clock, self.rusage, self.pressure = clock, rusage, pressure
        self.gc_total = gc_total
        self._tracer = None
        self._prev: Optional[Tuple[object, Dict[str, int], float]] = None

    def tick(self, tracer, due: float) -> None:
        """One wake-up that was due at ``due`` (on ``clock``).  Does
        nothing while ``tracer`` is off; the first tick on a tracer
        (after ``obs.reset()`` too) takes readings and records no
        stall."""
        global _stall_n, _stall_s
        wake = self.clock()
        if not tracer.enabled:
            self._tracer = None
            _pending.clear()
            return
        now = (self.rusage(), self.pressure(), self.gc_total())
        prev, self._prev = self._prev, now
        fresh = tracer is not self._tracer
        self._tracer = tracer
        drain(tracer)
        late = wake - due
        if fresh or late < STALL_MIN_S:
            return
        ru, psi, gc_s = now
        attrs = {"gc_s": round(gc_s - prev[2], 6),
                 "cpu_s": round(ru.ru_utime + ru.ru_stime
                                - prev[0].ru_utime - prev[0].ru_stime, 6)}
        for k in RUSAGE_FIELDS:
            attrs[k] = getattr(ru, "ru_" + k) - getattr(prev[0], "ru_" + k)
        for k in PSI_KINDS:
            if k in psi and k in prev[1]:
                attrs[f"psi_{k}_us"] = psi[k] - prev[1][k]
        _stall_n += 1
        _stall_s += late
        tracer.record("host_stall", "host", time.time() - late, late,
                      **attrs)

    def run(self) -> None:
        while True:
            due = self.clock() + SAMPLE_S
            time.sleep(SAMPLE_S)
            self.tick(_obs.tracer(), due)


def _forget_in_child() -> None:
    """A forked child has no sampler thread and none of its parent's
    pending spans or open annotation; its next ``start`` starts one."""
    global _sampler, _gc_mark, _start_lock
    _sampler = None
    _gc_mark = None
    _start_lock = threading.Lock()
    _pending.clear()


def start() -> None:
    """Install the GC hook and start the sampler, once per process;
    nothing where tracing is off.  Idempotent."""
    global _obs, _sampler
    from ndstpu import obs
    if not obs.enabled():
        return
    with _start_lock:
        if _obs is None:
            _obs = obs
            gc.callbacks.append(_on_gc)
            os.register_at_fork(after_in_child=_forget_in_child)
        if _sampler is None:
            _sampler = threading.Thread(target=Sampler().run,
                                        name="ndstpu-host-sampler",
                                        daemon=True)
            _sampler.start()
