"""The process that holds the chip in a served cell: the daemon,
unchanged, plus a side thread that answers the benchmark's questions.

Runs ``ndstpu.harness.serve.main([...])`` with the arguments it is
given after ``--``, in the main thread (the daemon installs signal
handlers).  A daemon thread reads one JSON command per line from
standard input and writes each reply to the file the command names:

    info            device, peak device memory, the program's counters,
                    files in the persistent compile cache
    spans           the program's finished spans since an epoch time
    trace           start the profiler, hold the ``bench_window`` mark
                    for some seconds, stop (only this process can trace
                    the chip it holds)
    trace_summary   reduce that trace (benchmark/harness/trace.py)
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def _reply(path: str, doc: dict) -> None:
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f, default=str)
    os.replace(tmp, path)


class Control(threading.Thread):
    def __init__(self):
        super().__init__(name="bench-control", daemon=True)
        self.trace_info: dict = {}

    def run(self) -> None:
        for line in sys.stdin:
            line = line.strip()
            if not line:
                continue
            try:
                cmd = json.loads(line)
                doc = getattr(self, "do_" + cmd["cmd"])(cmd)
            except Exception as e:  # noqa: BLE001 - reply, keep serving
                doc = {"error": f"{type(e).__name__}: {e}"}
            if cmd.get("out"):
                _reply(cmd["out"], doc)

    def do_info(self, cmd: dict) -> dict:
        import jax
        from benchmark.harness import data, closed_loop
        from ndstpu import obs
        devs = jax.devices()
        cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
        return {"device": {"platform": devs[0].platform,
                           "kind": devs[0].device_kind,
                           "count": len(devs)},
                "memory_peak_bytes": closed_loop.memory_peak(jax),
                "counters": obs.counters_snapshot(),
                "xla_cache_files": data.dir_file_count(cache_dir)
                if cache_dir else 0,
                "pid": os.getpid(), "epoch": time.time()}

    def do_spans(self, cmd: dict) -> dict:
        from ndstpu import obs
        since = float(cmd.get("since_epoch", 0.0))
        return {"spans": [e for e in list(obs.tracer().events)
                          if e["ts_epoch_s"] >= since]}

    def do_trace(self, cmd: dict) -> dict:
        import jax
        from benchmark.harness import trace
        trace.start_profiler(jax, cmd["dir"])
        epoch0 = time.time()
        with jax.profiler.TraceAnnotation(trace.WINDOW_MARK):
            time.sleep(float(cmd["seconds"]))
        jax.profiler.stop_trace()
        self.trace_info = {"dir": cmd["dir"], "epoch0": epoch0,
                           "epoch1": time.time()}
        return dict(self.trace_info)

    def do_trace_summary(self, cmd: dict) -> dict:
        from benchmark.harness import trace
        from ndstpu import obs
        info = self.trace_info
        events = trace.read_xplane(trace.newest_xplane(info["dir"]),
                                   rehearsal=bool(cmd.get("rehearsal")))
        near = [e for e in list(obs.tracer().events)
                if info["epoch0"] - 5 <= e["ts_epoch_s"] <= info["epoch1"]]
        host = trace.spans_on_trace_clock(events, near, info["epoch0"])
        summary = trace.summarize(events, host)
        return {"summary": summary, "epoch0": info["epoch0"],
                "epoch1": info["epoch1"]}


def main(argv=None) -> int:
    """``argv``: the daemon's own arguments."""
    Control().start()
    from ndstpu.harness import serve
    return serve.main(list(sys.argv[1:] if argv is None else argv))


if __name__ == "__main__":
    sys.exit(main())
