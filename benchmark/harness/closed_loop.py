"""Closed-loop driver: one client replays a cell's texts, pass after
pass, through ``Session.sql(text)`` + ``Table.to_rows()`` in this
process — what ``ndstpu.harness.power.run_one_query`` does without an
output path.  This process holds the chip.
"""

from __future__ import annotations

import gc
import os
import random
import sys
import threading
import time
from typing import Dict, List, Tuple

from benchmark.harness import data, judge, planbytes, readers, spec, trace, traffic


HOLDS_CHIP = True    # run.py looks for the chip before data set-up


class NoAccelerator(Exception):
    pass


def device_block(jax, chips: int, pinned_cpu: bool) -> dict:
    devs = jax.devices()
    dev = devs[0]
    if not pinned_cpu and (dev.platform != "tpu" or len(devs) < chips):
        raise NoAccelerator(
            f"the cell asks for {chips} TPU chip(s); JAX reports "
            f"{len(devs)} x {dev.platform} ({dev.device_kind})")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs)}


def memory_peak(jax) -> int:
    peak = 0
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def run(cell: spec.Cell, args, t_start: float, paths: Dict[str, str],
        sf: str) -> dict:
    cfg, wl = cell.config, cell.workload
    import jax
    device = device_block(jax, cell.chips, args.rehearse_cpu)

    from ndstpu import obs
    from ndstpu.engine.session import Session
    from ndstpu.harness import power
    from ndstpu.io import loader

    if cfg.get("properties"):
        power.apply_engine_properties(power.load_properties(
            os.path.join(spec.ROOT, cfg["properties"])))
    texts = traffic.cell_texts(wl, paths["streams"])
    # the run's seed: at which text of the cycle the passes start
    cut = random.Random(args.seed).randrange(len(texts))
    texts = texts[cut:] + texts[:cut]
    phases = {"before_load_s": round(time.time() - t_start, 3)}
    t_phase = time.time()
    catalog = loader.load_catalog(
        paths["wh"], use_decimal=(args.control != "floats"))
    sess = Session(catalog, backend=cfg["engine"])
    records = os.path.join(
        data.CACHE_DIR, "records",
        f"{cell.name}-sf{sf}-seed{paths['seed']}"
        + ("-floats" if args.control == "floats" else "") + ".pkl")
    os.makedirs(os.path.dirname(records), exist_ok=True)
    preloaded = sess.preload_compiled(records) \
        if os.path.exists(records) else 0

    phases["load_and_preload_s"] = round(time.time() - t_phase, 3)
    t_phase = time.time()

    def replay(text: traffic.Text, annotate: bool = False) -> list:
        if annotate:
            with jax.profiler.TraceAnnotation(f"part={text.label}"):
                table = sess.sql(text.sql)
            with jax.profiler.TraceAnnotation(f"to_rows={text.label}"):
                return table.to_rows()
        return sess.sql(text.sql).to_rows()

    # warm-up: every text twice.  The first replay of a text in a
    # process discovers + compiles (cold) or re-traces, loads the
    # executable and uploads its columns (warm); the second is what the
    # window repeats.
    # Rounds go on (at most five) until one compiles and discovers
    # nothing: a later draw of a template can outgrow the size classes
    # an earlier one discovered, and the rediscovery belongs to set-up.
    warm_s: Dict[str, list] = {}
    for rnd in range(5):
        before = judge.compile_counts(obs.counters_snapshot())
        for t in texts:
            t0 = time.perf_counter()
            replay(t)
            warm_s.setdefault(t.label, []).append(
                round(time.perf_counter() - t0, 4))
        if rnd >= 1 and judge.compile_counts(obs.counters_snapshot()) == before:
            break
    phases["warmup_s"] = round(time.time() - t_phase, 3)
    if sess.compiled_count() > preloaded:
        sess.save_compiled(records)
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    files_before = data.dir_file_count(cache_dir) if cache_dir else 0
    c_before = obs.counters_snapshot()
    n_events_before = len(obs.tracer().events)
    gc.collect()
    setup_s = time.time() - t_start

    # the measured window: whole passes, ending at the first pass
    # boundary at or after --seconds
    tracing = bool(args.trace)
    trace_dir = os.path.join(data.CACHE_DIR, "trace", cell.name)
    trace_seconds = float(wl.get("trace_seconds", 4.0))
    answers: List[Tuple[int, list]] = []
    clients = int(wl.get("clients", 1))
    tracer = _Tracing(jax, trace_dir, trace_seconds) if tracing else None
    errors: List[str] = []
    passes = [0] * clients
    stop = threading.Event()
    pass_ends: List[float] = []     # client 0's pass boundaries
    w0 = time.perf_counter()

    def client(k: int) -> None:
        """Whole passes over the texts (client k starts k/clients of
        the way round) until client 0 sees --seconds gone by."""
        shift = (k * len(texts)) // clients
        order = list(enumerate(texts))
        order = order[shift:] + order[:shift]
        while not stop.is_set():
            if k == 0 and tracer is not None and passes[0] == 1:
                tracer.start()     # from the second pass on
            for i, t in order:
                live = tracer is not None and tracer.live
                try:
                    rows = replay(t, annotate=live)
                    answers.append((i, rows))
                    if live:
                        tracer.texts.append(i)
                except Exception as e:  # noqa: BLE001 - a failed replay
                    errors.append(t.label)   # is a failed operation
                    print(f"replay of {t.label} failed: "
                          f"{type(e).__name__}: {e}", file=sys.stderr)
            passes[k] += 1
            if k == 0:
                pass_ends.append(time.perf_counter() - w0)
                if tracer is not None:
                    tracer.pass_done()
                if time.perf_counter() - w0 >= args.seconds:
                    stop.set()

    others = [threading.Thread(target=client, args=(k,),
                               name=f"bench-client-{k}")
              for k in range(1, clients)]
    for th in others:
        th.start()
    client(0)
    for th in others:
        th.join()
    window_s = time.perf_counter() - w0
    if tracer is not None:
        tracer.stop()

    c_after = obs.counters_snapshot()
    files_after = data.dir_file_count(cache_dir) if cache_dir else 0
    spans = list(obs.tracer().events[n_events_before:])
    delta = {k: c_after.get(k, 0) - c_before.get(k, 0) for k in c_after}
    compiles = int(sum(judge.compile_counts(delta).values())) \
        + max(files_after - files_before, 0)
    fallbacks = judge.fallback_count(c_after)
    device["memory_peak_bytes"] = memory_peak(jax)

    # per-layer record (before the session goes: the byte function
    # reads the plan and the catalog)
    rec = readers.RunRecord(spans=spans, counters=delta,
                            ops=len(answers) + len(errors),
                            device_kind=device["kind"],
                            rehearsal=bool(args.rehearse_cpu))
    breakdown = None
    if tracing:
        by_idx = dict(answers[:len(texts)])
        text_bytes = []
        for i, t in enumerate(texts):
            plan, _cols = sess.plan(t.sql)
            text_bytes.append(
                planbytes.plan_input_bytes(plan, catalog.tables)
                + _rows_bytes(by_idx.get(i, [])))
        events = trace.read_xplane(trace.newest_xplane(trace_dir),
                                   rehearsal=bool(args.rehearse_cpu))
        host = trace.spans_on_trace_clock(events, spans, tracer.epoch0)
        summary = trace.summarize(events, host)
        if summary is not None:
            rec.trace = summary
            rec.traced_ops = len(tracer.texts)
            rec.traced_input_bytes = sum(text_bytes[i]
                                         for i in tracer.texts)
            device["busy_s"] = summary["busy_s"]
            device["window_s"] = summary["window_s"]
            breakdown = {"device_ops": summary["device_ops"],
                         "idle_gaps": summary["idle_gaps"]}

    # free the program's state before the reference runs
    del sess, catalog
    jax.clear_caches()
    gc.collect()

    correct, checks = judge.judge(
        cfg, paths["raw"], texts, answers, unanswered=len(errors),
        fallbacks=fallbacks, compiles_in_window=compiles,
        control=args.control)
    end_to_end = {"setup_s": setup_s,
                  # elapsed of a pass as a client sees it
                  "power_pass_s": window_s * clients / max(sum(passes), 1)}
    return {"correct": correct, "attempted": len(answers) + len(errors),
            "failed": len(errors), "end_to_end": end_to_end, "record": rec,
            "device": device, "breakdown": breakdown, "checks": checks,
            "notes": {"passes": sum(passes),
                      "pass_s": [round(b - a, 4) for a, b in zip(
                          [0.0] + pass_ends, pass_ends)],
                      "window_s": window_s,
                      "warmup_s": warm_s, "setup_phases": phases,
                      "preloaded_records": preloaded,
                      "data_made": bool(paths.get("made")),
                      "xla_cache_files": files_before}}


class _Tracing:
    """The profiler round whole passes of the window: started by
    client 0 at a pass boundary, stopped at the first boundary some
    seconds later, the ``bench_window`` mark held in between."""

    def __init__(self, jax, trace_dir: str, seconds: float):
        self.jax, self.dir, self.seconds = jax, trace_dir, seconds
        self.live = False
        self.done = False
        self.texts: List[int] = []     # indices of the traced replays
        self.epoch0 = 0.0
        self._t0 = 0.0
        self._mark = None

    def start(self) -> None:
        if self.live or self.done:
            return
        trace.start_profiler(self.jax, self.dir)
        self._mark = self.jax.profiler.TraceAnnotation(trace.WINDOW_MARK)
        self.epoch0 = time.time()
        self._t0 = time.perf_counter()
        self._mark.__enter__()
        self.live = True

    def pass_done(self) -> None:
        if self.live and time.perf_counter() - self._t0 >= self.seconds:
            self.stop()

    def stop(self) -> None:
        if not self.live:
            return
        self.live = False
        self.done = True
        self._mark.__exit__(None, None, None)
        self.jax.profiler.stop_trace()


def _rows_bytes(rows: list) -> int:
    """Result bytes at 8 bytes a numeric cell and a string's length."""
    n = 0
    for r in rows:
        for v in r:
            n += len(v) if isinstance(v, str) else 8
    return n
