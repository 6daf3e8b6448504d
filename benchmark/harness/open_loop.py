"""Open-loop driver: the daemon in a child process that holds the chip,
clients here that never touch JAX.

Requests are due on a schedule fixed by the workload file and the seed
(``traffic.open_loop_schedule``), whether or not earlier ones have come
back.  A dispatcher thread hands each request, when it is due, to a
pool of client connections (``ndstpu.serve.client.ServeClient`` over
the unix socket: the served path's own client, with its retries off so
that a shed request is a failed one).  Latency is timed from the
instant a request was DUE to its reply; how late it was actually sent
is reported beside it (``generator_late_ms``).
"""

from __future__ import annotations

import json
import os
import queue
import signal
import subprocess
import sys
import threading
import time
from typing import Dict, List, Optional, Tuple

from benchmark.harness import data, judge, readers, spec, traffic

STRAGGLER_WAIT_S = 60.0
# the process that holds the chip; the tests put a faulty one in its place
CHILD = os.path.join(spec.BENCH_DIR, "harness", "serve_child.py")


class ChildDied(Exception):
    pass


class Daemon:
    """The child: start, ask, stop."""

    def __init__(self, cell: spec.Cell, args, paths: Dict[str, str],
                 sf: str):
        cfg = cell.config
        tag = (f"{cell.name}-sf{sf}-seed{paths['seed']}"
               + ("-floats" if args.control == "floats" else ""))
        self.state = os.path.join(data.CACHE_DIR, "serve", tag)
        os.makedirs(self.state, exist_ok=True)
        # a unix socket's path is short (108 bytes): keep it relative
        # to the checkout's root, where both sides run
        rel = os.path.relpath(os.path.join(self.state, "s.sock"),
                              spec.ROOT)
        self.socket = rel
        if os.path.exists(os.path.join(spec.ROOT, rel)):
            os.unlink(os.path.join(spec.ROOT, rel))
        cmd = [sys.executable, CHILD, "server", "--socket", rel,
                "--input_prefix", paths["wh"], "--engine", cfg["engine"],
                "--state_dir", self.state, "--ledger", "none",
                "--scale_factor", sf]
        cmd += [str(a) for a in cfg.get("server_arguments", [])]
        if args.control == "floats":
            cmd.append("--floats")
        env = data.child_env()
        self.log_path = os.path.join(self.state, "server.log")
        self.log = open(self.log_path, "w")
        self.proc = subprocess.Popen(
            cmd, cwd=spec.ROOT, env=env, stdin=subprocess.PIPE,
            stdout=self.log, stderr=subprocess.STDOUT, text=True)
        self._n = 0

    def log_tail(self, n: int = 3000) -> str:
        self.log.flush()
        with open(self.log_path) as f:
            return f.read()[-n:]

    def alive(self) -> bool:
        return self.proc.poll() is None

    def tell(self, cmd: str, **kw) -> str:
        """Send a command; the path its reply will be written to."""
        self._n += 1
        out = os.path.join(self.state, f"reply{self._n}.json")
        if os.path.exists(out):
            os.unlink(out)
        self.proc.stdin.write(json.dumps(dict(kw, cmd=cmd, out=out)) + "\n")
        self.proc.stdin.flush()
        return out

    def collect(self, out: str, what: str, timeout_s: float) -> dict:
        deadline = time.monotonic() + timeout_s
        while not os.path.exists(out):
            if not self.alive():
                raise ChildDied(f"the daemon exited "
                                f"({self.proc.returncode}):\n"
                                + self.log_tail())
            if time.monotonic() > deadline:
                raise TimeoutError(f"no reply to {what!r} in {timeout_s}s")
            time.sleep(0.02)
        with open(out) as f:
            doc = json.load(f)
        os.unlink(out)
        if "error" in doc:
            raise RuntimeError(f"daemon control {what!r}: {doc['error']}")
        return doc

    def ask(self, cmd: str, timeout_s: float = 120.0, **kw) -> dict:
        return self.collect(self.tell(cmd, **kw), cmd, timeout_s)

    def stop(self) -> Optional[int]:
        if self.alive():
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=90)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        try:
            self.proc.stdin.close()
        except OSError:
            pass
        self.log.close()
        return self.proc.returncode


def _client(daemon: Daemon):
    from ndstpu.serve.client import ServeClient
    return ServeClient(daemon.socket, retries=0, connect_timeout_s=10.0)


def wait_ready(daemon: Daemon, timeout_s: float) -> None:
    cli = _client(daemon)
    deadline = time.monotonic() + timeout_s
    try:
        while time.monotonic() < deadline:
            if not daemon.alive():
                raise ChildDied(
                    f"the daemon exited ({daemon.proc.returncode}) before "
                    f"it was ready:\n" + daemon.log_tail())
            if os.path.exists(os.path.join(spec.ROOT, daemon.socket)) \
                    and cli.wait_ready(timeout_s=1.0, poll_s=0.1):
                return
            time.sleep(0.2)
    finally:
        cli.close()
    raise TimeoutError(f"the daemon was not ready in {timeout_s}s:\n"
                       + daemon.log_tail())


def _send(cli, rid: str, text: traffic.Text, tenant: str,
          wl: dict) -> Tuple[bool, Optional[list], str]:
    """One request through the served path's client.  (ok, rows, note)"""
    msg = {"op": "sql", "id": rid, "sql": text.sql, "tenant": tenant,
           "max_rows": int(wl.get("max_rows", 100))}
    if wl.get("deadline_s") is not None:
        msg["deadline_s"] = float(wl["deadline_s"])
    try:
        resp = cli.request(msg)
    except Exception as e:  # noqa: BLE001 - shed, refused, error, hangup:
        cli.close()         # each a failed operation, named in the note
        return False, None, f"{type(e).__name__}: {e}"
    if resp.get("truncated"):
        return False, None, "answer truncated at max_rows"
    return True, [tuple(r) for r in resp.get("data", [])], ""


def offer(daemon: Daemon, wl: dict, texts: List[traffic.Text],
          schedule: List[traffic.Request], tag: str = "r"
          ) -> Tuple[List[dict], float]:
    """Offer the schedule; returns one record per request (client's
    epoch clock) and the epoch at which the window opened."""
    n_workers = int(wl.get("client_connections", 16))
    work: "queue.Queue" = queue.Queue()
    records: List[dict] = [None] * len(schedule)
    clients = [_client(daemon) for _ in range(n_workers)]
    for c in clients:
        c._connect()              # connections are part of set-up
    t0 = time.time() + 0.05

    def worker(cli):
        while True:
            req = work.get()
            if req is None:
                return
            due = t0 + req.due_s
            sent = time.time()
            ok, rows, note = _send(cli, f"{tag}{req.index}",
                                   texts[req.text], req.tenant, wl)
            records[req.index] = {
                "id": f"{tag}{req.index}", "text": req.text, "due": due,
                "sent": sent, "done": time.time(), "ok": ok,
                "rows": rows, "note": note}

    threads = [threading.Thread(target=worker, args=(c,), daemon=True,
                                name=f"bench-client-{i}")
               for i, c in enumerate(clients)]
    for th in threads:
        th.start()
    for req in schedule:
        delay = t0 + req.due_s - time.time()
        if delay > 0:
            time.sleep(delay)
        work.put(req)
    close = t0 + (schedule[-1].due_s if schedule else 0.0)
    for _ in threads:
        work.put(None)
    deadline = close + STRAGGLER_WAIT_S
    for th in threads:
        th.join(max(deadline - time.time(), 0.1))
    for c in clients:
        c.close()
    for req in schedule:
        if records[req.index] is None:      # never came back
            records[req.index] = {
                "id": f"{tag}{req.index}", "text": req.text,
                "due": t0 + req.due_s, "sent": None, "done": deadline,
                "ok": False, "rows": None, "note": "unanswered"}
    return records, t0


def latencies_ms(records: List[dict], penalty_done: float) -> List[float]:
    """Due-to-reply of every request due in the window; a failed, shed
    or unanswered one ranks slower than every answered one."""
    worst = max([r["done"] - r["due"] for r in records if r["ok"]]
                + [0.0])
    out = []
    for r in records:
        if r["ok"]:
            out.append(1e3 * (r["done"] - r["due"]))
        else:
            out.append(1e3 * max(penalty_done - r["due"], worst + 1.0))
    return out


def percentile(values: List[float], q: float) -> float:
    s = sorted(values)
    return s[min(len(s) - 1, int(q * len(s)))]


def cell_texts(wl: dict, streams_dir: str
               ) -> Tuple[List[traffic.Text], Dict[str, List[int]]]:
    """The cell's texts, and the indices of each template's draws."""
    texts = traffic.cell_texts(dict(wl, order="round_robin"), streams_dir)
    by_template: Dict[str, List[int]] = {}
    for i, t in enumerate(texts):
        by_template.setdefault(t.template, []).append(i)
    return texts, by_template


def warm_up(daemon: Daemon, cell: spec.Cell, texts: List[traffic.Text],
            rehearsal: bool) -> Tuple[dict, Dict[str, list]]:
    """Wait for the daemon, look at its device, and send every text,
    one at a time, round after round until a round compiles and
    discovers nothing (at least two rounds, at most five)."""
    wl = cell.workload
    wait_ready(daemon, timeout_s=float(wl.get("ready_timeout_s", 1100)))
    info0 = daemon.ask("info")
    device = dict(info0["device"])
    if not rehearsal and (device["platform"] != "tpu"
                          or device["count"] < cell.chips):
        from benchmark.harness.closed_loop import NoAccelerator
        raise NoAccelerator(f"the daemon runs on {device}")
    cli = _client(daemon)
    warm_s: Dict[str, list] = {}
    seen = judge.compile_counts(info0["counters"])
    for rnd in range(5):
        for t in texts:
            a = time.perf_counter()
            ok, _rows, note = _send(cli, f"warm{rnd}-{t.label}", t,
                                    "warmup", wl)
            if not ok:
                raise RuntimeError(f"warm-up of {t.label}: {note}")
            warm_s.setdefault(t.label, []).append(
                round(time.perf_counter() - a, 4))
        now = judge.compile_counts(daemon.ask("info")["counters"])
        if rnd >= 1 and now == seen:
            break       # a whole round compiled nothing
        seen = now
    cli.close()
    return device, warm_s


def run(cell: spec.Cell, args, t_start: float, paths: Dict[str, str],
        sf: str) -> dict:
    cfg, wl = cell.config, cell.workload
    texts, by_template = cell_texts(wl, paths["streams"])
    daemon = Daemon(cell, args, paths, sf)
    try:
        device, warm_s = warm_up(daemon, cell, texts, args.rehearse_cpu)
        info1 = daemon.ask("info")
        setup_s = time.time() - t_start

        schedule = traffic.open_loop_schedule(wl, by_template, args.seed,
                                              args.seconds)
        trace_reply = None
        if args.trace:
            # trace a few seconds in the middle of the window, in the
            # process that holds the chip
            seconds = float(wl.get("trace_seconds", 4.0))
            start_at = max(0.25 * args.seconds, 0.0)
            trace_dir = os.path.join(data.CACHE_DIR, "trace", cell.name)

            def later():
                time.sleep(start_at)
                nonlocal trace_reply
                trace_reply = daemon.tell("trace", seconds=seconds,
                                          dir=trace_dir)
            threading.Thread(target=later, daemon=True).start()
        records, t0 = offer(daemon, wl, texts, schedule)
        info2 = daemon.ask("info")
        spans = daemon.ask("spans", since_epoch=t0 - 0.01)["spans"]
        summary = None
        if args.trace and trace_reply is not None:
            daemon.collect(trace_reply, "trace", timeout_s=120)
            summary = daemon.ask("trace_summary", timeout_s=200,
                                 rehearsal=bool(args.rehearse_cpu))
    finally:
        rc = daemon.stop()

    window_s = float(args.seconds)
    close = t0 + window_s
    lat = latencies_ms(records, close + STRAGGLER_WAIT_S)
    answered = [r for r in records if r["ok"]]
    delta = {k: info2["counters"].get(k, 0) - info1["counters"].get(k, 0)
             for k in info2["counters"]}
    compiles = int(sum(judge.compile_counts(delta).values())) + max(
        info2["xla_cache_files"] - info1["xla_cache_files"], 0)
    fallbacks = judge.fallback_count(info2["counters"])
    device["memory_peak_bytes"] = info2["memory_peak_bytes"]
    rec = readers.RunRecord(spans=spans, counters=delta, ops=len(records),
                            device_kind=device["kind"],
                            rehearsal=bool(args.rehearse_cpu),
                            requests=[{k: r[k] for k in (
                                "id", "due", "sent", "done", "ok")}
                                for r in records])
    breakdown = None
    if summary and summary.get("summary"):
        s = summary["summary"]
        rec.trace = s
        rec.traced_ops = sum(
            1 for e in spans if e.get("cat") == "query"
            and summary["epoch0"] <= e["ts_epoch_s"] <= summary["epoch1"])
        device["busy_s"] = s["busy_s"]
        device["window_s"] = s["window_s"]
        breakdown = {"device_ops": s["device_ops"],
                     "idle_gaps": s["idle_gaps"]}

    failed = len(records) - len(answered)
    correct, checks = judge.judge(
        cfg, paths["raw"], texts,
        [(r["text"], r["rows"]) for r in answered],
        unanswered=failed, fallbacks=fallbacks,
        compiles_in_window=compiles, control=args.control)
    if rc != 0:
        correct = False
        checks["daemon_exit_code"] = {"value": rc, "limit": 0}
    end_to_end = {
        "setup_s": setup_s,
        "serve_p50_ms": percentile(lat, 0.50),
        "serve_p95_ms": percentile(lat, 0.95),
    }
    notes = [r["note"] for r in records if r["note"]]
    return {"correct": correct, "attempted": len(records),
            "failed": failed, "end_to_end": end_to_end, "record": rec,
            "device": device, "breakdown": breakdown, "checks": checks,
            "notes": {"warmup_s": warm_s, "failures": notes[:5],
                      "data_made": bool(paths.get("made")),
                      "offered_rps": len(records) / window_s,
                      "daemon_exit_code": rc}}
