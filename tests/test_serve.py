"""Serve layer: protocol, admission/overload edges, drain, warm restart.

The satellite coverage the issue names explicitly:

* drain with a hung in-flight query hits the watchdog path (abandon on
  a zombie thread + fresh-session swap) instead of blocking shutdown;
* a tenant at budget gets the typed ``Rejected`` while other tenants
  proceed;
* a tripped circuit breaker recovers after its cooldown (half-open
  probe) — tripped off the PR 5 quarantine list, per canonical key.

Plus the protocol/scheduler/lifecycle seams the server composes:
length-prefixed framing, continuous-feed StreamScheduler streams,
connection-fault taxonomy, journal replay, and the warm-restart
zero-new-compiles invariant the serve smoke proves cross-process.
"""

import json
import os
import socket
import threading
import time

import numpy as np
import pytest

from ndstpu import faults, obs
from ndstpu.engine.columnar import INT32, Column, Table
from ndstpu.engine.session import Session
from ndstpu.faults import taxonomy
from ndstpu.harness.scheduler import StreamScheduler
from ndstpu.io import atomic
from ndstpu.io.loader import Catalog
from ndstpu.obs import artifact_lint
from ndstpu.serve import lifecycle, protocol, transport
from ndstpu.serve.client import NoHealthyEndpoint, ServeClient
from ndstpu.serve.overload import (AdmissionQueue, CircuitBreaker,
                                   Overloaded, Rejected, TenantBudgets)
from ndstpu.serve.server import QueryServer, ServeConfig


def col_i32(vals):
    return Column(np.asarray(vals, dtype=np.int32), INT32, None)


def tiny_session(backend: str = "cpu") -> Session:
    cat = Catalog()
    cat.register("t", Table({
        "a": col_i32(list(range(10))),
        "b": col_i32([v % 3 for v in range(10)]),
    }))
    return Session(cat, backend=backend)


@pytest.fixture
def serve_env(tmp_path):
    """A started server over a tiny cpu session + one client; drains
    on teardown.  Yields a factory so tests can tune ServeConfig."""
    made = []

    def make(session=None, **cfg):
        defaults = dict(
            socket_path=str(tmp_path / f"s{len(made)}.sock"),
            engine="cpu",
            output_prefix=str(tmp_path / f"out{len(made)}"),
            journal_path=str(tmp_path / f"journal{len(made)}.jsonl"),
            slo_path=str(tmp_path / f"SLO{len(made)}.json"),
            ledger_path="none",
            query_timeout_s=30.0)
        defaults.update(cfg)
        srv = QueryServer(ServeConfig(**defaults),
                          session=session or tiny_session(
                              defaults["engine"]))
        srv.start()
        cli = ServeClient(defaults["socket_path"], retries=4,
                          connect_timeout_s=10.0)
        assert cli.wait_ready(10.0)
        made.append((srv, cli))
        return srv, cli

    yield make
    for srv, cli in made:
        cli.close()
        if not srv.draining:
            srv.drain(reason="teardown")


# -- protocol ----------------------------------------------------------------

def test_protocol_roundtrip_and_bounds():
    a, b = socket.socketpair()
    try:
        msg = {"op": "sql", "sql": "SELECT 1; -- '\n\x00 unicode ☃"}
        protocol.send_msg(a, msg)
        assert protocol.recv_msg(b) == msg
        a.close()
        assert protocol.recv_msg(b) is None  # clean EOF
    finally:
        b.close()
    c, d = socket.socketpair()
    try:
        c.sendall(b"\x7f\xff\xff\xff")  # absurd length prefix
        with pytest.raises(protocol.ProtocolError):
            protocol.recv_msg(d)
    finally:
        c.close()
        d.close()


def test_protocol_truncated_frame_is_error():
    a, b = socket.socketpair()
    try:
        a.sendall(b"\x00\x00\x00\x10partial")
        a.close()
        with pytest.raises(protocol.ProtocolError):
            protocol.recv_msg(b)
    finally:
        b.close()


# -- connection-fault taxonomy (satellite 1) ---------------------------------

def test_connection_faults_classify_transient():
    assert taxonomy.classify(socket.timeout("timed out")) == "transient"
    assert taxonomy.classify(
        ConnectionRefusedError("connection refused")) == "transient"
    assert taxonomy.classify(ConnectionResetError()) == "transient"
    assert taxonomy.classify(BrokenPipeError()) == "transient"
    # pre-3.10 socket.timeout pickles/paths carry the bare class name
    assert taxonomy.classify_name("timeout", "") == "transient"
    assert taxonomy.classify_name(
        "SomeWrapperError", "upstream: Connection refused") == "transient"
    assert taxonomy.classify_name(
        "SomeWrapperError", "Broken pipe on fd 7") == "transient"


# -- continuous-feed scheduler ----------------------------------------------

def test_scheduler_continuous_feed():
    sched = StreamScheduler({})
    view = sched.open_stream("c1")
    sched.feed("c1", "q1", "SELECT 1")
    sched.feed("c1", "q2", "SELECT 2")
    assert view.next(0.0) in ("q1", "q2")
    view.done("q1")
    got = []

    def drain_view():
        while True:
            n = view.next(0.0)
            if n is None:
                return
            got.append(n)
            view.done(n)

    th = threading.Thread(target=drain_view, daemon=True)
    th.start()
    time.sleep(0.1)
    sched.feed("c1", "q3", "SELECT 3")  # wakes the blocked next()
    time.sleep(0.2)
    sched.close("c1")
    th.join(5.0)
    assert not th.is_alive()
    assert set(got) == {"q2", "q3"}
    with pytest.raises(ValueError):
        sched.feed("c1", "q4", "SELECT 4")  # closed stream


def test_scheduler_feed_dedups_across_streams():
    sched = StreamScheduler(
        {}, key_fn=lambda s: " ".join(s.lower().split()))
    sched.open_stream("a")
    sched.open_stream("b")
    sched.feed("a", "qa", "SELECT * FROM t")
    sched.feed("b", "qb", "select  *  from  t")  # same normalized key
    va, vb = sched.view("a"), sched.view("b")
    assert va.next(0.0) == "qa"
    # b's identical text is classed in-flight-elsewhere, still runnable
    assert vb.next(0.0) == "qb"
    va.done("qa")
    assert sched._key[("a", "qa")] == sched._key[("b", "qb")]
    assert sched._key[("a", "qa")] in sched.compiled


# -- overload primitives -----------------------------------------------------

def test_tenant_budget_isolation():
    clock = [0.0]
    budgets = TenantBudgets(capacity=2, refill_per_s=1.0,
                            clock=lambda: clock[0])
    budgets.acquire("a")
    budgets.acquire("a")
    with pytest.raises(Rejected) as ei:
        budgets.acquire("a")
    assert ei.value.reason == "tenant-budget"
    budgets.acquire("b")  # other tenants unaffected
    clock[0] += 1.5  # refill restores tenant a
    budgets.acquire("a")


def test_admission_queue_overload_and_deadline_shed():
    q = AdmissionQueue(depth=2, est_wait_s=1.0)
    q.admit()
    q.admit(deadline_s=10.0)
    with pytest.raises(Overloaded) as ei:
        q.admit()
    assert ei.value.retry_after_s > 0
    q.release()
    with pytest.raises(Rejected) as ei:  # 1 ahead * 1s > 0.5s deadline
        q.admit(deadline_s=0.5)
    assert ei.value.reason == "deadline"
    q.admit(deadline_s=5.0)


def test_circuit_breaker_trips_and_recovers_after_cooldown():
    clock = [0.0]
    quarantine = faults.Quarantine(max_failures=1)
    cb = CircuitBreaker(quarantine, cooldown_s=10.0,
                        clock=lambda: clock[0])
    cb.check("fp1")  # closed: no-op
    quarantine.note_failure("fp1", "permanent")
    assert cb.note_failure("fp1") is True  # quarantined -> trips
    assert cb.state("fp1") == "open"
    with pytest.raises(Rejected) as ei:
        cb.check("fp1")
    assert ei.value.reason == "circuit-open"
    clock[0] += 11.0  # past cooldown: half-open, one probe admitted
    assert cb.state("fp1") == "half-open"
    cb.check("fp1")
    with pytest.raises(Rejected):
        cb.check("fp1")  # second concurrent probe rejected
    cb.note_success("fp1")  # probe succeeded -> closed
    assert cb.state("fp1") == "closed"
    cb.check("fp1")
    # and an unpoisoned failure never trips
    assert cb.note_failure("fp2") is False
    cb.check("fp2")


# -- server end-to-end -------------------------------------------------------

def test_sql_roundtrip_output_and_journal(serve_env):
    srv, cli = serve_env()
    r = cli.sql("SELECT a, b FROM t WHERE a < 4 ORDER BY a")
    assert r["rows"] == 4 and r["data"][0] == [0, 0]
    r2 = cli.sql("SELECT sum(a) AS s FROM t", name="q_out")
    assert r2["rows"] == 1
    assert os.path.exists(os.path.join(
        srv.config.output_prefix, "q_out", "part-0.csv"))
    events = [rec["event"] for rec in
              atomic.read_jsonl(srv.config.journal_path)]
    assert events[0] == lifecycle.JOURNAL_START
    assert events.count(lifecycle.JOURNAL_QUERY) == 2
    health = cli.health()
    assert health["ready"] and health["ok"] >= 2


def test_bad_sql_is_permanent_error(serve_env):
    _, cli = serve_env()
    from ndstpu.serve.client import ServeError
    with pytest.raises(ServeError) as ei:
        cli.sql("SELEKT nope")
    assert ei.value.taxonomy == "permanent"


def test_tenant_at_budget_rejected_while_others_proceed(serve_env):
    _, cli = serve_env(tenant_tokens=2, tenant_refill_per_s=0.001)
    cli.sql("SELECT count(*) AS c FROM t", tenant="greedy")
    cli.sql("SELECT count(*) AS c FROM t", tenant="greedy")
    with pytest.raises(Rejected) as ei:
        cli.sql("SELECT count(*) AS c FROM t", tenant="greedy")
    assert ei.value.reason == "tenant-budget"
    # the other tenant is untouched by greedy's exhaustion
    r = cli.sql("SELECT count(*) AS c FROM t", tenant="modest")
    assert r["status"] == "ok"


def test_dispatch_fault_is_client_visible_and_retried(serve_env):
    _, cli = serve_env()
    faults.install("serve.dispatch:transient:1:times=1")
    try:
        before = obs.counters_snapshot()
        r = cli.sql("SELECT max(a) AS m FROM t")
        assert r["status"] == "ok"
        delta = obs.counter_delta(before)
        assert delta.get(
            "faults.injected.serve.dispatch.transient") == 1
        # the CLIENT retried — the server deliberately does not absorb
        # dispatch faults (that is what distinguishes the site from
        # `execute`, which run_with_retry absorbs server-side)
        assert cli.retried >= 1
        assert delta.get("serve.errors") == 1
        assert delta.get("serve.ok") == 1
    finally:
        faults.uninstall()


def test_drain_with_hung_query_hits_watchdog(serve_env):
    """A wedged in-flight query must not block SIGTERM drain: the
    watchdog abandons it on a zombie thread, swaps a fresh session,
    and the retry completes the request — zero dropped queries."""
    srv, cli = serve_env(query_timeout_s=0.5)
    faults.install("execute:hang:1:times=1:hang=8")
    try:
        before = obs.counters_snapshot()
        got = {}

        def send():
            got["resp"] = cli.sql("SELECT min(a) AS m FROM t")

        th = threading.Thread(target=send, daemon=True)
        th.start()
        time.sleep(0.2)  # let the query wedge in the hang
        t0 = time.time()
        summary = srv.drain(reason="SIGTERM-test")
        drain_wall = time.time() - t0
        th.join(15.0)
        assert not th.is_alive()
        # the hung attempt was abandoned, the retry answered the client
        assert got["resp"]["status"] == "ok"
        assert got["resp"]["attempts"] >= 2
        delta = obs.counter_delta(before)
        assert delta.get("serve.watchdog.abandoned", 0) >= 1
        assert drain_wall < 8.0, \
            f"drain blocked {drain_wall:.1f}s behind a hung query"
        assert summary["reason"] == "SIGTERM-test"
        events = [rec["event"] for rec in
                  atomic.read_jsonl(srv.config.journal_path)]
        assert events[-1] == lifecycle.JOURNAL_CLEAN
    finally:
        faults.uninstall()


def test_draining_rejects_new_requests(serve_env):
    srv, cli = serve_env()
    cli.sql("SELECT 1 AS one FROM t")
    srv.draining = True  # admission stopped, socket still up
    from ndstpu.serve.client import ServerDraining
    with pytest.raises(ServerDraining):
        cli.sql("SELECT 2 AS two FROM t")
    srv.draining = False


# -- lifecycle: journal replay + warm restart --------------------------------

def test_journal_replay_state(tmp_path):
    j = lifecycle.ServeJournal(str(tmp_path / "j.jsonl"))
    assert j.replay_state() == {"sqls": [], "clean": True}
    j.mark_start()
    j.mark_query("q1", "SELECT 1", canon_key="k1")
    j.mark_query("q1", "SELECT 1")  # dedup
    j.mark_query("q2", "SELECT 2")
    state = lifecycle.ServeJournal(str(tmp_path / "j.jsonl")) \
        .replay_state()
    assert [r["sql"] for r in state["sqls"]] == ["SELECT 1", "SELECT 2"]
    assert state["clean"] is False  # started, never marked clean
    j.mark_clean_shutdown()
    state = lifecycle.ServeJournal(str(tmp_path / "j.jsonl")) \
        .replay_state()
    assert state["clean"] is True


def test_warm_restart_zero_new_compiles(tmp_path):
    """The serve_smoke leg-4 invariant, in-process: a restarted server
    answering a previously-seen plan shape compiles NOTHING new
    (engine.cache.compiled.miss stays flat)."""
    records = str(tmp_path / "records.json")
    journal = str(tmp_path / "j.jsonl")
    sql = "SELECT b, sum(a) AS s FROM t GROUP BY b ORDER BY b"
    cfg = dict(socket_path=str(tmp_path / "warm.sock"),
               engine="tpu", compile_records=records,
               journal_path=journal, ledger_path="none",
               query_timeout_s=60.0)

    srv1 = QueryServer(ServeConfig(**cfg), session=tiny_session("tpu"))
    srv1.start()
    cli = ServeClient(cfg["socket_path"])
    assert cli.wait_ready(10.0)
    r1 = cli.sql(sql)
    cli.close()
    # no clean drain: simulate the SIGKILL by never calling drain() —
    # the incremental persistence must already have saved the records
    assert os.path.exists(records)
    for ls in srv1._listeners:
        ls.close()

    cfg2 = dict(cfg, socket_path=str(tmp_path / "warm2.sock"))
    srv2 = QueryServer(ServeConfig(**cfg2),
                       session=tiny_session("tpu"))
    srv2.start()
    cli2 = ServeClient(cfg2["socket_path"])
    assert cli2.wait_ready(10.0)
    before = obs.counters_snapshot()
    r2 = cli2.sql(sql)
    delta = obs.counter_delta(before)
    cli2.close()
    srv2.drain(reason="test")
    assert r2["data"] == r1["data"]
    assert delta.get("engine.cache.compiled.miss", 0) == 0, \
        f"warm restart recompiled: {delta}"
    assert delta.get("engine.cache.compiled.hit", 0) >= 1


# -- SLO artifact ------------------------------------------------------------

def test_slo_tracker_percentiles_and_export(tmp_path):
    slo = lifecycle.SLOTracker()
    for ms in range(1, 101):
        slo.record("a", ms / 1000.0, "ok")
    slo.record("a", 0.0, "overloaded")
    slo.record("b", 0.005, "ok")
    doc = slo.export(str(tmp_path / "SLO.json"))
    assert doc["artifact"] == lifecycle.SLO_ARTIFACT
    a = doc["tenants"]["a"]
    assert a["count"] == 101 and a["overloaded"] == 1
    assert a["p50_ms"] == pytest.approx(50.0, abs=2.0)
    assert a["p95_ms"] == pytest.approx(95.0, abs=2.0)
    assert a["p99_ms"] == pytest.approx(99.0, abs=2.0)
    assert doc["tenants"]["b"]["p50_ms"] == pytest.approx(5.0, abs=1.0)


def test_artifact_lint_recognizes_slo_as_runtime():
    text = "the server exports `SLO.json` next to its journal"
    assert artifact_lint.lint_text(text, root="/nonexistent") == []
    assert any(p == "SLO.json" for _, p, _ in
               artifact_lint.cited_artifacts(text))


def test_artifact_lint_recognizes_fleet_health_as_runtime():
    text = "each tick rewrites `FLEET_HEALTH.json` in the run dir"
    assert artifact_lint.lint_text(text, root="/nonexistent") == []
    assert any(p == "FLEET_HEALTH.json" for _, p, _ in
               artifact_lint.cited_artifacts(text))


# -- fleet satellites: transports, failover, readiness, backpressure ---------

def test_tcp_unix_parity_same_request_same_response(serve_env):
    """Satellite 3: the SAME request sent over AF_UNIX and TCP gets
    the SAME response — shared framing, shared dispatch; only the
    volatile wall clock may differ."""
    srv, _cli = serve_env(tcp="127.0.0.1:0")
    specs = [ep.spec for ep in srv.endpoints]
    assert any(s.startswith("unix:") for s in specs), specs
    assert any(s.startswith("tcp:") for s in specs), specs

    def ask(spec, msg):
        s = transport.connect(spec, connect_timeout_s=10.0)
        try:
            protocol.send_msg(s, msg)
            return protocol.recv_msg(s)
        finally:
            s.close()

    for msg in (
            {"op": "ping", "id": "par-1"},
            {"op": "ready", "id": "par-2"},
            {"op": "sql", "id": "par-3", "tenant": "parity",
             "sql": "SELECT b, sum(a) AS s FROM t GROUP BY b "
                    "ORDER BY b"}):
        answers = []
        for spec in specs:
            resp = ask(spec, dict(msg))
            resp.pop("wall_s", None)
            answers.append(resp)
        assert answers[0] == answers[1], \
            f"transport-dependent response for {msg['op']}: {answers}"


def _tenant_for_index(idx: int, n: int) -> str:
    import zlib
    for i in range(1000):
        t = f"t{i}"
        if zlib.crc32(t.encode()) % n == idx:
            return t
    raise AssertionError("unreachable")


def test_client_fails_over_from_refused_endpoint(serve_env, tmp_path):
    """Satellite 3: first endpoint refuses -> the client silently
    moves to the next and counts the switch in ``failovers``."""
    srv, _cli = serve_env()
    live = srv.endpoints[0].spec
    dead = str(tmp_path / "nobody-listening.sock")
    cli = ServeClient(f"{dead},{live}",
                      tenant=_tenant_for_index(0, 2),
                      retries=4, connect_timeout_s=10.0)
    try:
        assert cli.endpoint.spec != live  # starts on the dead one
        assert cli.ping()["pong"] is True
        assert cli.failovers >= 1
        assert cli.endpoint.spec == live
        r = cli.sql("SELECT count(*) AS n FROM t")
        assert r["status"] == "ok" and r["data"] == [[10]]
    finally:
        cli.close()


def test_client_all_endpoints_down_raises_typed_transient(tmp_path):
    """Satellite 3: every endpoint down -> NoHealthyEndpoint naming
    the endpoints tried, classified transient for outer retry loops."""
    d1 = str(tmp_path / "d1.sock")
    d2 = str(tmp_path / "d2.sock")
    cli = ServeClient(f"{d1},{d2}", retries=0, connect_timeout_s=0.3,
                      backoff_s=0.01)
    with pytest.raises(NoHealthyEndpoint) as ei:
        cli.ping()
    assert sorted(ei.value.endpoints) == sorted(
        [f"unix:{d1}", f"unix:{d2}"])
    assert taxonomy.classify(ei.value) == "transient"
    # single endpoint keeps the PR 14 contract: the raw OSError
    solo = ServeClient(d1, retries=0, connect_timeout_s=0.3,
                       backoff_s=0.01)
    with pytest.raises(OSError) as ei2:
        solo.ping()
    assert not isinstance(ei2.value, NoHealthyEndpoint)


def test_bind_early_probe_answers_and_sql_sheds_until_ready(tmp_path):
    """Satellite 3 readiness gating: a bind_early replica answers the
    probe verb immediately, sheds sql as retryable ``overloaded``
    while warming, and flips ready only after the warm/AOT work is
    done."""
    gate = threading.Event()
    entered = threading.Event()

    class SlowBoot(QueryServer):
        def _aot_precompile(self):
            entered.set()
            assert gate.wait(30.0)
            super()._aot_precompile()

    sock = str(tmp_path / "warm_gate.sock")
    srv = SlowBoot(ServeConfig(socket_path=sock, engine="cpu",
                               journal_path=str(tmp_path / "j.jsonl"),
                               ledger_path="none", bind_early=True,
                               replica_id="r-gate"),
                   session=tiny_session())
    boot = threading.Thread(target=srv.start, daemon=True)
    boot.start()
    try:
        assert entered.wait(30.0)
        cli = ServeClient(sock, retries=0, connect_timeout_s=10.0)
        probe = cli.probe()   # probe answers while still warming
        assert probe["alive"] is True and probe["ready"] is False
        assert probe["replica_id"] == "r-gate"
        resp = cli._roundtrip({"op": "sql", "id": "w1",
                               "sql": "SELECT count(*) FROM t",
                               "tenant": "warm"})
        assert resp["status"] == "overloaded"  # retryable, NOT fatal
        assert resp["retry_after_s"] > 0
        before = obs.counters_snapshot()
        gate.set()
        boot.join(30.0)
        assert not boot.is_alive()
        assert cli.wait_ready(10.0)
        assert cli.probe()["ready"] is True
        r = cli.sql("SELECT count(*) AS n FROM t")
        assert r["data"] == [[10]]
        assert obs.counter_delta(before).get(
            "serve.warming_rejects", 0) == 0  # none after readiness
        cli.close()
    finally:
        gate.set()
        if not srv.draining:
            srv.drain(reason="test")


# -- EWMA retry hint (satellite 1) -------------------------------------------

def test_admission_queue_ewma_hint_grows_and_decays():
    q = AdmissionQueue(depth=2, est_wait_s=0.25, ewma_alpha=0.5)
    assert q.est_wait_s == pytest.approx(0.25)  # seed before data
    for _ in range(4):
        q.observe(2.0)  # slow queries: the hint must grow
    grown = q.est_wait_s
    assert grown > 1.0
    for _ in range(8):
        q.observe(0.01)  # fast again: the hint must decay back
    assert q.est_wait_s < 0.1 < grown
    snap = q.snapshot()
    assert snap["observed"] == 12
    assert snap["est_wait_s"] == pytest.approx(q.est_wait_s,
                                               abs=1e-5)


def test_admission_queue_shed_hint_tracks_ewma():
    q = AdmissionQueue(depth=1, est_wait_s=0.25, ewma_alpha=1.0)
    q.observe(3.0)  # alpha=1: est jumps straight to the observation
    q.admit()
    with pytest.raises(Overloaded) as ei:
        q.admit()
    assert ei.value.retry_after_s == pytest.approx(3.0)
    q.release()


# -- memplan admission budget (tentpole seam) --------------------------------

def test_memplan_admission_budget_clamps_and_env(monkeypatch):
    from ndstpu.engine import memplan

    doc = memplan.admission_budget(budget_bytes=8 << 30,
                                   bytes_per_query=64 << 20)
    assert doc["depth"] == (8 << 30) // 2 // (64 << 20)
    assert doc["budget_source"] == "caller"
    # starved budget clamps to the floor, never zero
    doc = memplan.admission_budget(budget_bytes=16 << 20,
                                   bytes_per_query=64 << 20)
    assert doc["depth"] == memplan.ADMISSION_MIN_DEPTH
    # huge budget clamps to the ceiling
    doc = memplan.admission_budget(budget_bytes=1 << 50,
                                   bytes_per_query=1)
    assert doc["depth"] == memplan.ADMISSION_MAX_DEPTH
    # NDSTPU_HBM_BYTES drives the budget (source: env), the serve
    # knob overrides the per-query working set
    monkeypatch.setenv("NDSTPU_HBM_BYTES", str(1 << 30))
    monkeypatch.setenv("NDSTPU_SERVE_QUERY_BYTES", str(128 << 20))
    doc = memplan.admission_budget()
    assert doc["budget_source"] == "env"
    assert doc["bytes_per_query"] == 128 << 20
    assert doc["depth"] == (1 << 30) // 2 // (128 << 20)


def test_server_auto_queue_depth_from_memplan(serve_env, monkeypatch):
    monkeypatch.setenv("NDSTPU_HBM_BYTES", str(192 << 20))
    srv, cli = serve_env(queue_depth=None)
    h = cli.health()
    assert h["admission_model"]["budget_source"] == "env"
    assert h["admission_model"]["depth"] == 1
    assert h["queue_depth"] == 1


# -- fleet supervisor units (injectable probe/launcher) ----------------------

class _FakeProc:
    def __init__(self, pid):
        self.pid = pid
        self.rc = None
        self.returncode = None

    def poll(self):
        self.returncode = self.rc
        return self.rc

    def kill(self):
        self.rc = -9

    def wait(self, timeout=None):
        self.returncode = self.rc
        return self.rc


def _fleet_cfg(tmp_path, **kw):
    from ndstpu.serve.fleet import FleetConfig
    defaults = dict(input_prefix=str(tmp_path / "wh"),
                    replicas=2, run_dir=str(tmp_path / "fleet"),
                    probe_interval_s=30.0, probe_fail_threshold=3,
                    restart_backoff_s=0.0, restart_backoff_max_s=0.0)
    defaults.update(kw)
    return FleetConfig(**defaults)


def test_fleet_adopts_live_replicas_instead_of_double_starting(
        tmp_path):
    from ndstpu.serve.fleet import FleetSupervisor
    launched = []

    def launcher(rep):
        p = _FakeProc(pid=1000 + len(launched))
        launched.append(rep.replica_id)
        return p

    def probe(rep):
        if rep.replica_id == "r0":  # r0 is already running out there
            return {"alive": True, "ready": True, "pid": 4242}
        raise ConnectionRefusedError("r1 not running")

    sup = FleetSupervisor(_fleet_cfg(tmp_path, probe_fail_threshold=99),
                          probe_fn=probe, launcher=launcher)
    sup.start()
    try:
        r0, r1 = sup.replicas
        assert r0.adopted and r0.pid == 4242 and r0.ready
        assert "r0" not in launched, "adopted replica was double-started"
        assert launched == ["r1"]
        doc = sup.health_doc()
        assert doc["artifact"] == "ndstpu-fleet-health-v1"
        assert doc["replicas"][0]["adopted"] is True
        assert os.path.exists(sup.health_path)
    finally:
        sup._stopped.set()


def test_fleet_restarts_dead_replica_and_fences_stale_lock(tmp_path):
    from ndstpu.io import commit as commit_mod
    from ndstpu.serve.fleet import FleetSupervisor
    wh = tmp_path / "wh" / "store_sales"
    wh.mkdir(parents=True)
    launched = []

    def launcher(rep):
        p = _FakeProc(pid=1000 + len(launched))
        launched.append(p)
        return p

    sup = FleetSupervisor(_fleet_cfg(tmp_path, replicas=1),
                          probe_fn=lambda rep: {"alive": True,
                                                "ready": True,
                                                "pid": None},
                          launcher=launcher)
    rep = sup.replicas[0]
    sup._start_replica(rep)
    assert len(launched) == 1 and rep.pid == 1000
    # the replica dies holding a CAS commit lease; a live stranger's
    # lease must survive the fence
    stale = wh / commit_mod.LOCK_BASENAME
    stale.write_text(json.dumps({"pid": rep.pid, "ts": 0}))
    live_dir = tmp_path / "wh" / "other"
    live_dir.mkdir()
    (live_dir / commit_mod.LOCK_BASENAME).write_text(
        json.dumps({"pid": os.getpid(), "ts": 0}))
    launched[0].rc = 9
    sup._check_one(rep)
    assert rep.restarts == 1
    assert len(launched) == 2, "death did not relaunch the replica"
    assert rep.pid == launched[1].pid, "pid not tracking the relaunch"
    assert not stale.exists(), "stale commit lease was not fenced"
    assert (live_dir / commit_mod.LOCK_BASENAME).exists(), \
        "fence broke a LIVE pid's lease"


def test_fleet_probe_failures_restart_only_at_threshold(tmp_path):
    from ndstpu.serve.fleet import FleetSupervisor
    launched = []

    def launcher(rep):
        p = _FakeProc(pid=2000 + len(launched))
        launched.append(p)
        return p

    def probe(rep):
        raise ConnectionRefusedError("injected probe failure")

    sup = FleetSupervisor(
        _fleet_cfg(tmp_path, replicas=1, probe_fail_threshold=3,
                   boot_grace_s=0.5),
        probe_fn=probe, launcher=launcher)
    rep = sup.replicas[0]
    sup._start_replica(rep)
    sup._check_one(rep)
    assert rep.consecutive_failures == 0, \
        "a probe failure during the boot grace window counted"
    rep.launched_at -= 1.0  # age the incarnation past the grace
    sup._check_one(rep)
    sup._check_one(rep)
    assert rep.restarts == 0, "restarted below the probe threshold"
    sup._check_one(rep)  # third consecutive failure crosses it
    assert rep.restarts == 1 and len(launched) == 2


def test_fleet_of_one_replica_is_one_endpoint(tmp_path):
    """``--replicas 1`` is the supervised single server: one endpoint,
    no comma spec for clients to fail over between; none is refused."""
    from ndstpu.serve import fleet as fleet_mod
    fakes = dict(probe_fn=lambda rep: {"alive": True, "ready": True},
                 launcher=lambda rep: _FakeProc(pid=1))
    sup = fleet_mod.FleetSupervisor(_fleet_cfg(tmp_path, replicas=1),
                                    **fakes)
    assert len(sup.replicas) == 1
    assert "," not in sup.endpoints_spec()
    with pytest.raises(ValueError, match=">= 1 replica"):
        fleet_mod.FleetSupervisor(_fleet_cfg(tmp_path, replicas=0),
                                  **fakes)


def test_fleet_default_endpoints_stable_and_short(tmp_path):
    from ndstpu.serve.fleet import default_endpoints
    a = default_endpoints(str(tmp_path / "fleet"), 3)
    b = default_endpoints(str(tmp_path / "fleet"), 3)
    assert a == b, "re-adoption needs stable endpoint derivation"
    assert len(set(a)) == 3
    assert all(len(p) < 100 for p in a), "AF_UNIX ~108-byte path cap"
    assert default_endpoints(str(tmp_path / "other"), 3) != a


# -- a request times its own phases (docs/OBSERVABILITY.md, serve span tree) --

def _serve_spans(since: int):
    """Spans finished since an ``obs.finished()`` reading, by name."""
    by = {}
    for e in obs.events_since(since):
        by.setdefault(e["name"], []).append(e)
    return by


def _of(spans, name, rid, cat="serve"):
    return [e for e in spans.get(name, [])
            if e["cat"] == cat and e["args"].get("id") == rid]


@pytest.mark.parametrize("watchdog_s", [30.0, 0.0],
                         ids=["watchdog-thread", "inline"])
def test_answered_request_has_every_phase_once(serve_env, watchdog_s):
    """One admit_wait and one reply_tail per answered request, one
    gate_wait + gate_hold per attempt, each with the request's id; the
    hold is its pin + statement + row conversion and little else."""
    _, cli = serve_env(query_timeout_s=watchdog_s)
    n0 = obs.finished()
    rids = [cli.sql(f"SELECT count(*) AS c FROM t WHERE a > {k}")["id"]
            for k in range(3)]
    # the reply is written inside reply_tail: let the last span close
    deadline = time.time() + 5.0
    while len(_serve_spans(n0).get("reply_tail", [])) < 3 \
            and time.time() < deadline:
        time.sleep(0.01)
    spans = _serve_spans(n0)
    for rid in rids:
        for name in ("admit_wait", "reply_tail", "gate_wait", "gate_hold"):
            assert len(_of(spans, name, rid)) == 1, (name, rid)
        assert _of(spans, "reply_tail", rid)[0]["args"]["reply_bytes"] > 0
        assert _of(spans, "gate_wait", rid)[0]["args"]["canon"]
    assert len(spans["pin"]) == len(spans["statement"]) \
        == len(spans["to_rows"]) == 3
    # requests ran one after another on one connection: pair by order
    holds = sorted(spans["gate_hold"], key=lambda e: e["ts_epoch_s"])
    for hold, pin, stmt, rows in zip(
            holds, *(sorted(evs, key=lambda e: e["ts_epoch_s"])
                     for evs in (spans["pin"], spans["statement"],
                                 spans["to_rows"]))):
        inside = pin["wall_s"] + stmt["wall_s"] + rows["wall_s"]
        assert hold["wall_s"] >= inside - 1e-5
        # the rest is the parse before the statement span, the reply's
        # slice of the rows and, with the watchdog, starting and joining
        # its thread
        assert hold["wall_s"] - inside < 0.05, (hold, inside)
    # none of the new spans takes a name or category the benchmark's
    # readers key on
    for name in ("admit_wait", "reply_tail", "gate_wait", "gate_hold",
                 "pin"):
        assert all(e["cat"] == "serve" for e in spans[name])


def test_each_attempt_waits_and_holds_once(serve_env):
    """run_with_retry makes a second attempt after a transient execute
    fault: two gate_wait + two gate_hold under one id, still one
    admit_wait and one reply_tail."""
    _, cli = serve_env()
    faults.install("execute:transient:1:times=1")
    try:
        n0 = obs.finished()
        r = cli.sql("SELECT max(b) AS m FROM t")
        assert r["status"] == "ok" and r["attempts"] == 2
        time.sleep(0.05)
        spans = _serve_spans(n0)
        rid = r["id"]
        assert len(_of(spans, "gate_wait", rid)) == 2
        holds = _of(spans, "gate_hold", rid)
        assert len(holds) == 2
        assert sum(1 for h in holds if "error" in h["args"]) == 1
        assert len(_of(spans, "admit_wait", rid)) == 1
        assert len(_of(spans, "reply_tail", rid)) == 1
    finally:
        faults.uninstall()


def test_second_request_waits_out_the_firsts_hold(serve_env):
    """Two connections, one device slot: a request sent while another
    holds the gate shows a gate_wait that lasts to the end of that
    hold."""
    srv, cli = serve_env()
    cli2 = ServeClient(srv.config.socket_path, retries=0,
                       connect_timeout_s=10.0)
    real_sql = srv.session.sql

    def slow_sql(text, pin=None):
        if "slow_marker" in text:
            time.sleep(0.4)
        return real_sql(text, pin=pin)

    srv.session.sql = slow_sql
    try:
        n0 = obs.finished()
        got = {}
        th = threading.Thread(
            target=lambda: got.update(first=cli.sql(
                "SELECT count(*) AS slow_marker FROM t")), daemon=True)
        th.start()
        time.sleep(0.15)        # the first is inside its hold by now
        second = cli2.sql("SELECT min(a) AS m FROM t")
        th.join(10.0)
        time.sleep(0.05)
        spans = _serve_spans(n0)
        hold1 = _of(spans, "gate_hold", got["first"]["id"])[0]
        wait2 = _of(spans, "gate_wait", second["id"])[0]
        hold1_end = hold1["ts_epoch_s"] + hold1["wall_s"]
        remaining = hold1_end - wait2["ts_epoch_s"]
        assert 0.1 < remaining < 0.4
        assert wait2["wall_s"] >= remaining - 0.005
        wait1 = _of(spans, "gate_wait", got["first"]["id"])[0]
        assert wait1["wall_s"] < 0.05       # nobody held it before
    finally:
        srv.session.sql = real_sql
        cli2.close()


def test_health_reports_the_bounded_buffers(serve_env):
    srv, cli = serve_env()
    cli.sql("SELECT count(*) AS c FROM t")
    h = cli.health()
    # (the reply goes out inside reply_tail, which closes after it)
    assert 0 < h["trace_events"] <= len(obs.tracer().events)
    assert h["gated_queries"] == srv.gate.gated_total == 1
