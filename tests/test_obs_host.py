"""The host runtime's own instruments (``ndstpu/obs/host.py``): GC pauses
timed by a callback that takes no lock, a lag sampler whose stalls say
what moved meanwhile, both off with the tracer, and a replay's share of
GC pause."""

from __future__ import annotations

import gc
import os
import subprocess
import sys
import textwrap
import threading

import pytest

from ndstpu import obs
from ndstpu.engine import jaxexec
from ndstpu.engine.session import Session
from ndstpu.obs import host
from test_obs import FIVE_QUERIES, tiny_catalog

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KEYS = ("host.gc.collections", "host.gc.pause_ms",
        "host.gc.gen2.collections", "host.gc.gen2.pause_ms",
        "host.stall.count", "host.stall_ms")


@pytest.fixture()
def hooked():
    """An enabled tracer of its own, with the hook installed."""
    obs.reset(enabled=True)
    host.start()
    yield obs.tracer()
    obs.reset()


def _python(code: str, **env) -> None:
    """Run ``code`` in a fresh interpreter from the repo root: the hook
    installs once per process."""
    full = dict(os.environ, JAX_PLATFORMS="cpu", **env)
    subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                   check=True, timeout=300, cwd=ROOT, env=full)


def test_a_full_collection_is_counted_and_leaves_a_span(hooked):
    before = obs.counters_snapshot()
    n0 = obs.finished()
    gc.collect(2)
    host.drain(hooked)
    moved = obs.counter_delta(before)
    assert moved["host.gc.gen2.collections"] == 1
    assert moved["host.gc.pause_ms"] > 0
    spans = [e for e in obs.events_since(n0)
             if e["name"] == "gc" and e["args"]["generation"] == 2]
    assert len(spans) == 1
    (span,) = spans
    assert span["cat"] == "host" and span["tid"] == threading.get_ident()
    assert span["args"]["pause_s"] == pytest.approx(span["wall_s"])
    assert {"collected", "uncollectable"} <= set(span["args"])


def test_the_gc_callback_takes_no_lock(hooked):
    """A collection on a thread that holds the tracer's lock: a callback
    that took it would never return."""
    done = []

    def hold_and_collect():
        with obs.tracer()._lock:
            gc.collect()
            done.append(True)

    th = threading.Thread(target=hold_and_collect, daemon=True)
    th.start()
    th.join(timeout=10)
    assert not th.is_alive() and done == [True]


class _Usage:
    def __init__(self, majflt=0, minflt=0, nivcsw=0, nvcsw=0, utime=0.0,
                 stime=0.0):
        self.ru_majflt, self.ru_minflt = majflt, minflt
        self.ru_nivcsw, self.ru_nvcsw = nivcsw, nvcsw
        self.ru_utime, self.ru_stime = utime, stime


def test_a_late_tick_is_a_stall_carrying_what_moved(hooked):
    now = [100.0]
    usage = iter([_Usage(1, 10, 2, 3, 1.0, 0.5),
                  _Usage(4, 15, 9, 3, 1.5, 0.75),
                  _Usage(4, 16, 9, 4, 1.5, 0.75)])
    # no io pressure file: that attribute is left out
    pressure = iter([{"cpu": 1000, "memory": 50},
                     {"cpu": 1500, "memory": 80},
                     {"cpu": 1600, "memory": 80}])
    gc_total = iter([1.0, 1.25, 1.25])
    sampler = host.Sampler(clock=lambda: now[0],
                           rusage=lambda: next(usage),
                           pressure=lambda: next(pressure),
                           gc_total=lambda: next(gc_total))
    tr = obs.trace.Tracer(enabled=True)
    stalls0 = host.counters()["host.stall.count"]

    def stalls():   # a tick also drains the process's gc spans into tr
        return [e for e in tr.events if e["name"] == "host_stall"]

    sampler.tick(tr, due=100.0)            # first tick: readings only
    assert stalls() == []
    late = host.STALL_MIN_S + 0.01
    now[0] = 100.05 + late
    sampler.tick(tr, due=100.05)
    (stall,) = stalls()
    assert stall["name"] == "host_stall" and stall["cat"] == "host"
    assert stall["wall_s"] == pytest.approx(late)
    assert stall["args"] == {"gc_s": 0.25, "cpu_s": 0.75,
                             "majflt": 3, "minflt": 5,
                             "nivcsw": 7, "nvcsw": 0, "psi_cpu_us": 500,
                             "psi_memory_us": 30}
    assert host.counters()["host.stall.count"] == stalls0 + 1

    now[0] = 100.15 + host.STALL_MIN_S / 2   # late, but under the limit
    sampler.tick(tr, due=100.15)
    assert len(stalls()) == 1


def test_a_sampler_tick_does_nothing_while_the_tracer_is_off():
    def unread():
        raise AssertionError("read while the tracer is off")

    sampler = host.Sampler(clock=lambda: 1.0, rusage=unread,
                           pressure=unread)
    sampler.tick(obs.trace.Tracer(enabled=False), due=0.0)


def test_trace_off_installs_nothing():
    """NDSTPU_TRACE=0: a Session and a replay register no callback,
    start no sampler and carry no attribute."""
    _python("""
        import gc, sys, threading
        sys.path.insert(0, "tests")
        from test_obs import FIVE_QUERIES, tiny_catalog
        from ndstpu import obs
        from ndstpu.engine import jaxexec
        from ndstpu.engine.session import Session
        from ndstpu.obs import host

        replays = []
        timed = jaxexec.CompilingExecutor._replay_query_timed

        def spy(self, cp, sp, binding=None):
            replays.append(sp)
            return timed(self, cp, sp, binding)

        jaxexec.CompilingExecutor._replay_query_timed = spy
        callbacks = list(gc.callbacks)
        sess = Session(tiny_catalog(), backend="tpu")
        first = sess.sql(FIVE_QUERIES[3]).to_rows()
        assert sess.sql(FIVE_QUERIES[3]).to_rows() == first
        assert replays and all(sp is obs.NULL_SPAN for sp in replays)
        assert obs.NULL_SPAN.attrs == {}
        assert gc.callbacks == callbacks and not host.installed()
        assert "ndstpu-host-sampler" not in [
            t.name for t in threading.enumerate()]
        assert obs.tracer().events == [] and obs.counters_snapshot() == {}
    """, NDSTPU_TRACE="0")


def test_a_replay_carries_its_gc_pause(hooked, monkeypatch):
    sess = Session(tiny_catalog(), backend="tpu")
    sql = FIVE_QUERIES[3]
    first = sess.sql(sql).to_rows()
    n0 = obs.finished()
    assert sess.sql(sql).to_rows() == first
    (replay,) = [e for e in obs.events_since(n0) if e["name"] == "replay"]
    a = replay["args"]
    assert 0 <= a["wait_gc_s"] <= a["gc_s"] <= replay["wall_s"]

    # a collection inside device_get is inside the wait and the replay
    fetch = jaxexec.jax.device_get

    def collecting_fetch(x):
        gc.collect()
        return fetch(x)

    monkeypatch.setattr(jaxexec.jax, "device_get", collecting_fetch)
    n0 = obs.finished()
    assert sess.sql(sql).to_rows() == first
    (replay,) = [e for e in obs.events_since(n0) if e["name"] == "replay"]
    a = replay["args"]
    assert 0 < a["wait_gc_s"] <= a["gc_s"] <= replay["wall_s"]
    assert a["wait_gc_s"] <= a["device_wait_s"]


def test_the_counters_read_zero_right_after_install():
    _python("""
        import gc
        gc.disable()    # no collection between install and snapshot
        from ndstpu import obs
        from ndstpu.obs import host
        keys = %r
        assert not set(keys) & set(obs.counters_snapshot())
        host.start()
        host.start()    # idempotent
        snap = obs.counters_snapshot()
        assert {k: snap[k] for k in keys} == dict.fromkeys(keys, 0), snap
        assert gc.callbacks.count(host._on_gc) == 1
    """ % (KEYS,), NDSTPU_TRACE="1")


def test_a_forked_child_starts_a_sampler_of_its_own():
    _python("""
        import os, threading
        from ndstpu.obs import host

        def samplers():
            return [t for t in threading.enumerate()
                    if t.name == "ndstpu-host-sampler"]

        host.start()
        assert len(samplers()) == 1
        pid = os.fork()
        if pid == 0:
            ok = not samplers()
            host.start()
            ok = ok and len(samplers()) == 1 and samplers()[0].is_alive()
            os._exit(0 if ok else 1)
        _, status = os.waitpid(pid, 0)
        assert os.waitstatus_to_exitcode(status) == 0
    """, NDSTPU_TRACE="1")
