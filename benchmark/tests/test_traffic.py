"""The generator is deterministic in the seed, gives every seed the
same amount of work in another order, and the open loop times from the
instant a request was due."""

import collections
import os
import socket
import threading
import time

import pytest

from benchmark.harness import traffic

WL = {"parts": ["query55", "query96", "query3", "query86"], "draws": 4,
      "rate_rps": 5.0, "template_zipf_s": 1.0, "tenants": 4,
      "tenant_zipf_s": 1.0}
BY_TEMPLATE = {name: [4 * k + d for d in range(4)]
               for k, name in enumerate(WL["parts"])}


def _schedule(seed, **kw):
    return traffic.open_loop_schedule(dict(WL, **kw), BY_TEMPLATE, seed, 20.0)


def test_same_seed_same_schedule():
    assert _schedule(2**31 + 99) == _schedule(2**31 + 99)


def test_seeds_share_the_work_and_differ_in_order():
    a, b = _schedule(1), _schedule(2)
    assert len(a) == len(b) == 100
    assert a != b

    def gaps(s):
        return sorted(round(y.due_s - x.due_s, 9) for x, y in zip(s, s[1:]))

    def counts(s, field):
        return sorted(collections.Counter(
            getattr(r, field) for r in s).values())
    # the n - 1 gaps between n arrivals: the same set but for the one
    # gap that falls after each seed's last arrival; and the same
    # succession of requests, begun at another point of the cycle
    ga, gb = collections.Counter(gaps(a)), collections.Counter(gaps(b))
    assert sum((ga - gb).values()) <= 1 and sum((gb - ga).values()) <= 1
    ta, tb = [r.text for r in a], [r.text for r in b]
    assert any(ta[k:] + ta[:k] == tb for k in range(len(ta)))
    # the first part listed is the hot one, for every seed
    for s in (a, b):
        assert collections.Counter(r.text // 4 for r in s).most_common(
            1)[0] == (0, 48)
    # Zipf(1) over four templates, four draws each: 48/24/16/12 of 100
    for s in (a, b):
        per_template = collections.Counter(r.text // 4 for r in s)
        assert sorted(per_template.values()) == [12, 16, 24, 48]
    assert counts(a, "tenant") == counts(b, "tenant") == [12, 16, 24, 48]
    assert counts(a, "text") == counts(b, "text")


def test_arrivals_fill_the_window_in_order():
    s = _schedule(7)
    due = [r.due_s for r in s]
    assert due == sorted(due) and due[0] == 0.0 and due[-1] < 20.0
    assert [r.index for r in s] == list(range(len(s)))


def test_bursts_keep_the_count_and_bunch_the_arrivals():
    plain = _schedule(3)
    burst = _schedule(3, bursts={"period_s": 10.0, "on_share": 0.2,
                                 "on_factor": 3.0})
    assert len(burst) == len(plain)
    on = sum(1 for r in burst if (r.due_s % 10.0) < 2.0)
    assert on / len(burst) == pytest.approx(0.6, abs=0.1)


def test_explicit_weights_and_closed_loop_order(tmp_path):
    s = traffic.open_loop_schedule(
        dict(WL, template_weights=[9, 1, 0, 0]), BY_TEMPLATE, 5, 20.0)
    per_template = collections.Counter(r.text // 4 for r in s)
    assert per_template == {0: 90, 1: 10}
    for d in range(2):
        with open(tmp_path / f"query_{d}.sql", "w") as f:
            for k, name in enumerate(["query9", "query55", "query3"]):
                f.write(f"-- start query {k + 1} in stream {d} using "
                        f"template {name}.tpl\nselect {k} -- draw {d}\n;\n"
                        f"-- end query {k + 1} in stream {d} using "
                        f"template {name}.tpl\n")
    texts = traffic.cell_texts({"parts": ["query3", "query9"], "draws": 2,
                                "order": "stream"}, str(tmp_path))
    assert [t.label for t in texts] == ["query9.d0", "query3.d0",
                                       "query9.d1", "query3.d1"]
    assert "draw 1" in texts[2].sql and "select 0" in texts[2].sql
    with pytest.raises(KeyError, match="no part"):
        traffic.cell_texts({"parts": ["query77"], "draws": 1},
                           str(tmp_path))


class _SlowServer(threading.Thread):
    """Speaks the served path's framing over a unix socket and takes a
    fixed time for each sql request, one at a time."""

    def __init__(self, path, service_s):
        super().__init__(daemon=True)
        self.service_s = service_s
        self.lock = threading.Lock()
        self.sock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        self.sock.bind(path)
        self.sock.listen(16)

    def run(self):
        while True:
            try:
                conn, _ = self.sock.accept()
            except OSError:
                return
            threading.Thread(target=self.serve, args=(conn,),
                             daemon=True).start()

    def serve(self, conn):
        from ndstpu.serve import protocol
        while True:
            try:
                msg = protocol.recv_msg(conn)
            except (OSError, protocol.ProtocolError):
                return
            if msg is None:
                return
            if msg.get("op") == "sql":
                with self.lock:
                    time.sleep(self.service_s)
                protocol.send_msg(conn, {"status": "ok", "id": msg["id"],
                                         "rows": 1, "data": [[1]],
                                         "truncated": False})
            else:
                protocol.send_msg(conn, {"status": "ok", "id": msg["id"],
                                         "ready": True})


def test_open_loop_times_from_due_not_from_send(tmp_path):
    """Three requests due together at a server that takes 0.2 s each:
    the third waits for two others, and its latency says so."""
    from benchmark.harness import open_loop
    old = os.getcwd()
    os.chdir(tmp_path)      # a unix socket's path is short
    try:
        server = _SlowServer("s.sock", 0.2)
        server.start()

        class FakeDaemon:
            socket = "s.sock"
        texts = [traffic.Text("query55", 0, "select 1")]
        schedule = [traffic.Request(i, 0.0, 0, "tenant0") for i in range(3)]
        records, t0 = open_loop.offer(
            FakeDaemon(), {"client_connections": 4}, texts, schedule)
        server.sock.close()
    finally:
        os.chdir(old)
    assert all(r["ok"] for r in records)
    lat = sorted(r["done"] - r["due"] for r in records)
    assert lat[0] == pytest.approx(0.2, abs=0.08)
    assert lat[2] == pytest.approx(0.6, abs=0.12)
    assert all(r["due"] == pytest.approx(t0) for r in records)
    ms = open_loop.latencies_ms(records, t0 + 60.0)
    assert open_loop.percentile(ms, 0.95) == pytest.approx(
        1e3 * lat[2])


def test_a_failed_request_ranks_slowest():
    from benchmark.harness import open_loop
    recs = [{"ok": True, "due": 0.0, "done": 0.3},
            {"ok": False, "due": 0.1, "done": 0.2},
            {"ok": True, "due": 0.2, "done": 0.4}]
    ms = open_loop.latencies_ms(recs, penalty_done=61.0)
    assert ms[1] == max(ms) and ms[1] >= 60_000
