"""Benchmark entry point: NDS power-run elapsed, TPU backend vs CPU.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N,
   "device": {"platform": ..., "device_kind": ..., "count": N}, ...}

The metric's name carries the platform the "tpu" engine actually ran on
as JAX reports it.  With no chip the run is refused before any phase
(ndstpu/engine/device.py) unless the platform is pinned with
JAX_PLATFORMS=cpu, and then the name says ``cpu``.

Pipeline (mirrors the reference power run, nds/nds_power.py:183-304):
generate raw data (cached) -> transcode to parquet warehouse (cached) ->
render the query stream -> execute every query serially on the numpy CPU
reference interpreter (the baseline — the analog of the reference's
power_run_cpu Spark path, measured on the same host) and on the JAX/TPU
backend (wall-clock around each result materialization).

value       = TPU-backend power-run elapsed seconds (best complete run)
vs_baseline = CPU elapsed / TPU elapsed over the common measured queries
              (>1 means TPU wins); geomean of per-query speedups is also
              reported.

Robustness contract (the driver kills this process at an unknown wall
limit): EVERY phase runs under one global deadline, and SIGTERM/SIGINT/
SIGALRM or an unhandled exception still emit the JSON line built from
whatever completed — the reference's report always gets written
(nds/nds_power.py:251-288); so does ours.  The exit code still tells:
non-zero after an exception or a signal.
"""

from __future__ import annotations

import atexit
import json
import math
import os
import shutil
import signal
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
CACHE = os.path.join(REPO, ".bench_cache")
SF = float(os.environ.get("NDSTPU_BENCH_SF", "1"))
# default calibrated against an earlier driver's kill point (SIGTERM at
# ~1800 s): a longer default meant the deadline machinery (early stop,
# steady-subset pass, clean _emit) never engaged — leave ~60 s of slack
BUDGET_S = float(os.environ.get("NDSTPU_BENCH_BUDGET_S", "1740"))
T0 = time.time()
DEADLINE = T0 + BUDGET_S

# -- partial-result state, emitted exactly once ------------------------------

STATE = {
    "sf": SF,
    "n_queries": 0,
    "cpu_times": {},     # name -> seconds (numpy interpreter baseline)
    "cpu_failed": [],
    "tpu_runs": [],      # list of {"times": {name: s}, "failed": [...],
                         #          "complete": bool}
    "phase": "init",
    "device": None,      # device.describe("tpu") once the backend is up
}
_EMITTED = False


def _remaining() -> float:
    return DEADLINE - time.time()


def _build_result() -> dict:
    nq = STATE["n_queries"]
    cpu_times = STATE["cpu_times"]
    runs = STATE["tpu_runs"]
    complete = [r for r in runs if r["complete"] and not r["failed"]]
    pool = complete or [r for r in runs if r["times"]]
    # coverage first, then time: a deadline-cut 10-query run must never
    # shadow a full run as the headline number
    best = min(pool, key=lambda r: (-len(r["times"]),
                                    sum(r["times"].values()))) \
        if pool else None
    tpu_times = best["times"] if best else {}
    common = [q for q in tpu_times if q in cpu_times]
    tpu_s = sum(tpu_times.values())
    cpu_common = sum(cpu_times[q] for q in common)
    tpu_common = sum(tpu_times[q] for q in common)
    dev = STATE["device"] or {}
    result = {
        "metric": f"nds_power_run_sf{SF:g}_{nq}q_"
                  f"{dev.get('platform', 'nodevice')}_vs_numpy_cpu",
        "device": STATE["device"],
        "value": round(tpu_s, 4) if tpu_times else 0.0,
        "unit": "s",
        "vs_baseline": round(cpu_common / tpu_common, 4)
        if tpu_common > 0 and common else 0.0,
        "baseline": "numpy CPU interpreter, same host, serial power run",
        "queries_measured_tpu": len(tpu_times),
        "queries_measured_cpu": len(cpu_times),
        "phase_reached": STATE["phase"],
        "elapsed_s": round(time.time() - T0, 1),
    }
    if common:
        ratios = [cpu_times[q] / tpu_times[q] for q in common
                  if tpu_times[q] > 0 and cpu_times[q] > 0]
        if ratios:
            result["geomean_speedup"] = round(
                math.exp(sum(math.log(r) for r in ratios) / len(ratios)), 4)
        result["cpu_elapsed_common_s"] = round(cpu_common, 4)
    if best and best["failed"]:
        result["failed_queries"] = sorted(best["failed"])
    if STATE["cpu_failed"]:
        result["cpu_failed_queries"] = sorted(STATE["cpu_failed"])
    partial = (not complete) or len(cpu_times) < nq or nq == 0
    if partial:
        result["partial"] = True
    return result


def _emit(trailer: str = "") -> None:
    global _EMITTED
    if _EMITTED:
        return
    _EMITTED = True
    result = _build_result()
    if trailer:
        result["note"] = trailer
    print(json.dumps(result), flush=True)
    # per-query detail for the record, not on the contract line
    detail = {"cpu_times": STATE["cpu_times"],
              "tpu_runs": STATE["tpu_runs"]}
    try:
        with open(os.path.join(CACHE, f"last_run_sf{SF:g}.json"), "w") as f:
            json.dump(detail, f, indent=1)
    except OSError:
        pass


def _on_signal(signum, frame):  # noqa: ARG001
    _emit(f"terminated by signal {signum} in phase {STATE['phase']}")
    os._exit(128 + signum)


def _install_handlers() -> None:
    for s in (signal.SIGTERM, signal.SIGINT):
        signal.signal(s, _on_signal)
    if hasattr(signal, "SIGALRM"):
        signal.signal(signal.SIGALRM, _on_signal)
        # backstop: fire shortly after the soft deadline so a stuck
        # native call can't ride past the driver's own kill
        signal.alarm(int(BUDGET_S + 120))
    atexit.register(_emit)


# -- phases ------------------------------------------------------------------

def _src_fingerprint(rels) -> str:
    import hashlib
    h = hashlib.sha256()
    for rel in rels:
        try:
            with open(os.path.join(REPO, rel), "rb") as f:
                h.update(f.read())
        except OSError:
            h.update(rel.encode())
    return h.hexdigest()[:16]


# identity of the build pipeline per artifact: raw data depends only on
# the generator; the warehouse additionally on the transcoder/schema.
# An SF-only tag silently kept pre-change data alive across generator
# changes (e.g. the r04 distribution skew); a single shared stamp would
# waste a full datagen phase on transcoder-only edits.
_GEN_SRCS = ("ndstpu/datagen/ndsgen.cpp", "ndstpu/datagen/driver.py")
_WH_SRCS = _GEN_SRCS + ("ndstpu/io/transcode.py", "ndstpu/schema.py")
# the CPU baseline is a function of (data, queries, interpreter): cached
# times must not survive interpreter changes, or vs_baseline silently
# compares against a stale denominator
_CPU_SRCS = ("ndstpu/engine/physical.py", "ndstpu/engine/expr.py",
             "ndstpu/engine/columnar.py", "ndstpu/engine/optimizer.py",
             "ndstpu/engine/planner.py", "ndstpu/engine/plan.py")


def _stamp_ok(d: str, fp: str) -> bool:
    try:
        with open(os.path.join(d, ".genfp")) as f:
            return f.read().strip() == fp
    except OSError:
        return False


def ensure_warehouse(sf: float, datagen_timeout=None,
                     transcode_timeout=None, quiet: bool = True,
                     on_phase=None) -> str:
    """Build (or reuse) the warehouse for one SF.  Each phase writes
    into a _tmp_ dir renamed only on success: a timeout/SIGTERM
    mid-build must not leave a truncated dir that later runs mistake
    for a complete cache (and silently benchmark forever).  Dirs carry
    a .genfp stamp of the generator sources; a stamp mismatch forces a
    rebuild.  Shared artifact contract for bench.py (deadline-capped,
    quiet) and scripts/build_wh.py (uncapped, verbose)."""
    tag = f"sf{sf:g}"
    raw = os.path.join(CACHE, f"raw_{tag}")
    wh = os.path.join(CACHE, f"wh_{tag}")
    raw_fp = _src_fingerprint(_GEN_SRCS)
    wh_fp = _src_fingerprint(_WH_SRCS)
    for d, fp in ((raw, raw_fp), (wh, wh_fp)):
        if os.path.isdir(d) and os.listdir(d) and not _stamp_ok(d, fp):
            if not quiet:
                print(f"stale stamp: rebuilding {d}", flush=True)
            shutil.rmtree(d, ignore_errors=True)
    # the datagen and transcode children import no jax
    # (ndstpu.datagen.driver, ndstpu.io.transcode): they never want the
    # chip, so they may run while this process holds it.  Keep it so.
    pp = os.environ.get("PYTHONPATH", "")
    env = dict(os.environ,
               PYTHONPATH=f"{REPO}{os.pathsep}{pp}" if pp else REPO)
    for d in (raw + "_tmp_", wh + "_tmp_"):   # stale partials from kills
        shutil.rmtree(d, ignore_errors=True)
    out = subprocess.DEVNULL if quiet else None

    def _limit(t):   # timeouts may be callables (deadline-relative)
        return t() if callable(t) else t

    if not os.path.isdir(wh) or not os.listdir(wh):
        if not os.path.isdir(raw) or not os.listdir(raw):
            if on_phase:
                on_phase("datagen")
            tmp = raw + "_tmp_"
            os.makedirs(tmp, exist_ok=True)
            try:
                subprocess.run(
                    [sys.executable, "-m", "ndstpu.datagen.driver",
                     "local", f"{sf:g}", "2", tmp, "--overwrite_output"],
                    check=True, env=env, stdout=out, cwd=REPO,
                    timeout=_limit(datagen_timeout))
            except BaseException:
                shutil.rmtree(tmp, ignore_errors=True)
                raise
            with open(os.path.join(tmp, ".genfp"), "w") as f:
                f.write(raw_fp)
            os.rename(tmp, raw)
        if on_phase:
            on_phase("transcode")
        tmp = wh + "_tmp_"
        os.makedirs(tmp, exist_ok=True)
        try:
            subprocess.run(
                [sys.executable, "-m", "ndstpu.io.transcode",
                 "--input_prefix", raw, "--output_prefix", tmp,
                 "--report_file", os.path.join(tmp, "load.txt")],
                check=True, env=env, stdout=out, cwd=REPO,
                timeout=_limit(transcode_timeout))
        except BaseException:
            shutil.rmtree(tmp, ignore_errors=True)
            raise
        with open(os.path.join(tmp, ".genfp"), "w") as f:
            f.write(wh_fp)
        os.rename(tmp, wh)
    return wh


def _ensure_warehouse() -> str:
    def _phase(p):
        STATE["phase"] = p

    return ensure_warehouse(
        SF,
        datagen_timeout=lambda: max(60.0, min(_remaining() - 300.0,
                                              900.0)),
        transcode_timeout=lambda: max(60.0, _remaining() - 240.0),
        quiet=True, on_phase=_phase)


def _corpus_fingerprint(wh: str, queries) -> str:
    """Identity of (warehouse data, rendered query corpus, interpreter
    sources): the CPU baseline is a pure function of these, so cache it
    by this key."""
    import hashlib
    h = hashlib.sha256()
    h.update(_src_fingerprint(_CPU_SRCS).encode())
    for name, sql in queries:
        h.update(name.encode())
        h.update(hashlib.sha256(sql.encode()).digest())
    for root, dirs, files in sorted(os.walk(wh)):
        dirs.sort()
        for fn in sorted(files):
            st = os.stat(os.path.join(root, fn))
            h.update(f"{os.path.relpath(os.path.join(root, fn), wh)}:"
                     f"{st.st_size}".encode())
    return h.hexdigest()


def _load_cpu_cache(path: str, fp: str):
    try:
        with open(path) as f:
            d = json.load(f)
        if d.get("fingerprint") == fp:
            return d["cpu_times"], d["cpu_failed"]
    except (OSError, ValueError, KeyError):
        pass
    return None


def _save_cpu_cache(path: str, fp: str, times: dict, failed: list):
    try:
        with open(path, "w") as f:
            json.dump({"fingerprint": fp, "cpu_times": times,
                       "cpu_failed": failed}, f)
    except OSError:
        pass


_BACKEND_DEAD = ("UNAVAILABLE", "worker process crashed", "DATA_LOSS")
# a compile that never returns blocks the stream forever: abandon the
# query in its daemon thread and keep the stream moving
QUERY_TIMEOUT_S = float(os.environ.get("NDSTPU_BENCH_QUERY_TIMEOUT_S",
                                       "900"))


def _run_one(sess, sql: str, slot: dict) -> None:
    try:
        out = sess.sql(sql)
        out.to_rows()  # materialize like collect() (nds_power.py:124-134)
        slot["ok"] = True
    except Exception as e:  # noqa: BLE001
        slot["err"] = e


def _power_run(sess, queries, times: dict, failed: list,
               stop_at: float, rebuild=None, watchdog=None,
               per_query_timeout=None, progress: bool = False,
               hang_abort: int = 3, reasons=None) -> bool:
    """Run the stream serially; returns True iff every query ran.
    ``rebuild()`` returns a FRESH session after a hang, so the
    abandoned zombie thread keeps only the old session's state and
    cannot race the rest of the stream.  ``watchdog`` defaults to on
    for accelerator runs; pass True to also bound CPU queries (SF10+
    interpreter passes, where one pathological numpy query could
    otherwise blow through the whole budget).  ``hang_abort`` bounds
    consecutive-run hang tolerance: N hangs mean a wedged backend on
    accelerators, but independent slow queries on a CPU interpreter —
    pass 0 to never abort (each hang still costs at most the per-query
    timeout).  ``reasons`` (dict) collects a per-query failure reason
    alongside the bare names in ``failed``."""
    import threading
    accel = sess.backend != "cpu"
    qto = per_query_timeout if per_query_timeout else QUERY_TIMEOUT_S
    if watchdog is None:
        watchdog = accel
    hangs = 0
    for name, sql in queries:
        if time.time() >= stop_at:
            return False
        t0 = time.time()
        slot: dict = {}
        if watchdog:
            th = threading.Thread(target=_run_one, args=(sess, sql, slot),
                                  daemon=True)
            th.start()
            waited = min(qto, max(30.0, stop_at - time.time()))
            th.join(waited)
            if th.is_alive():
                if waited < qto:
                    # deadline cut an ordinary query, not a hang
                    return False
                print(f"BENCH-ERROR {name}: hang (> "
                      f"{qto:.0f}s), abandoned",
                      file=sys.stderr, flush=True)
                failed.append(name)
                if reasons is not None:
                    reasons[name] = f"hang>{qto:.0f}s"
                hangs += 1
                if hang_abort and hangs >= hang_abort:
                    # backend wedged, not one bad program
                    print("BENCH-WARNING: repeated hangs, aborting run",
                          file=sys.stderr, flush=True)
                    return False
                if rebuild is not None:
                    # the zombie thread stays blocked inside its jax
                    # call — on the OLD session; a fresh one isolates
                    # the remaining stream from any late completion
                    try:
                        sess = rebuild()
                    except Exception as e:  # noqa: BLE001
                        print(f"BENCH-WARNING: session rebuild failed "
                              f"({e}); continuing on shared session",
                              file=sys.stderr, flush=True)
                continue
        else:
            _run_one(sess, sql, slot)
        if slot.get("ok"):
            times[name] = round(time.time() - t0, 4)
            if progress:
                print(f"{name}: {times[name]:.3f}s", flush=True)
            continue
        e = slot.get("err")
        # a failed query must not zero the whole 99-query benchmark
        print(f"BENCH-ERROR {name}: {type(e).__name__}: {e}",
              file=sys.stderr, flush=True)
        failed.append(name)
        if reasons is not None:
            reasons[name] = str(e)
        if accel and any(tok in str(e) for tok in _BACKEND_DEAD):
            # the TPU worker died: every further query would fail the
            # same way — abort this run so the report stays scoped to
            # what actually executed
            print("BENCH-WARNING: backend unavailable, aborting run",
                  file=sys.stderr, flush=True)
            return False
    return True


def main() -> None:
    global SF, DEADLINE
    if "--quick" in sys.argv:
        SF = min(SF, 0.01)
        STATE["sf"] = SF
    _install_handlers()
    sys.path.insert(0, REPO)
    import jax

    from ndstpu.engine import device
    # before any phase: no chip (and no explicit cpu pin), no benchmark
    device.require_accelerator("tpu")
    STATE["device"] = device.describe("tpu")
    # persist only the expensive whole-query replay programs: eager
    # host ops compile in well under 2 s and never reach the cache
    # (its directory is resolved by ndstpu/engine/device.py)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 2.0)

    wh = _ensure_warehouse()

    STATE["phase"] = "stream-render"
    from ndstpu.engine.session import Session
    from ndstpu.io import loader
    from ndstpu.queries import streamgen

    queries = streamgen.render_power_corpus()
    STATE["n_queries"] = len(queries)

    STATE["phase"] = "load-catalog"
    catalog = loader.load_catalog(wh)

    # CPU baseline first: it is bounded (~minutes at SF1) while a
    # cold-cache TPU pass may not finish inside the budget — the
    # vs_baseline denominator must exist even when the TPU pass is cut.
    # The measured times are CACHED keyed by (SF, corpus fingerprint):
    # re-measuring minutes of numpy every invocation eats the budget.
    # NDSTPU_BENCH_CPU=0 skips it entirely.
    STATE["phase"] = "cpu-baseline"
    if os.environ.get("NDSTPU_BENCH_CPU", "1") != "0":
        corpus_fp = _corpus_fingerprint(wh, queries)
        cpu_cache = os.path.join(CACHE, f"cpu_times_sf{SF:g}.json")
        cached = _load_cpu_cache(cpu_cache, corpus_fp)
        if cached is not None:
            STATE["cpu_times"], STATE["cpu_failed"] = cached
        else:
            cpu_sess = Session(catalog, backend="cpu")
            cpu_stop = time.time() + max(60.0, _remaining() * 0.45)
            complete = _power_run(cpu_sess, queries, STATE["cpu_times"],
                                  STATE["cpu_failed"], cpu_stop)
            # never cache a deadline-cut run NOR one with failures — a
            # transient failure would otherwise be replayed forever
            if complete and not STATE["cpu_failed"]:
                _save_cpu_cache(cpu_cache, corpus_fp,
                                STATE["cpu_times"], STATE["cpu_failed"])
    if STATE["cpu_failed"]:
        print(f"BENCH-WARNING: {len(STATE['cpu_failed'])} baseline "
              f"queries failed: {sorted(STATE['cpu_failed'])}",
              file=sys.stderr, flush=True)

    STATE["phase"] = "tpu-runs"
    rec_path = os.path.join(CACHE, f"plans_sf{SF:g}.pkl")

    def make_tpu_sess():
        s = Session(catalog, backend="tpu")
        try:  # persisted size-plan records: skip eager discovery
            s.preload_compiled(rec_path)
        except Exception as e:  # noqa: BLE001
            # stale/corrupt records: discovery still works, but the run
            # is then cold — say so
            print(f"BENCH-WARNING: compile records {rec_path} not "
                  f"preloaded ({type(e).__name__}: {e}); this run "
                  f"rediscovers every query", file=sys.stderr, flush=True)
        return s

    holder = {"s": make_tpu_sess()}

    def rebuild():
        holder["s"] = make_tpu_sess()
        return holder["s"]

    n_runs = int(os.environ.get("NDSTPU_BENCH_RUNS", "3"))
    # run1 = discovery/compile (+persistent-cache replay), later runs =
    # compiled replay — the steady-state number.  Every run honors the
    # global deadline; a cut run is recorded as incomplete.
    for ri in range(n_runs):
        if _remaining() < 120.0:
            break
        run = {"times": {}, "failed": [], "complete": False}
        STATE["tpu_runs"].append(run)
        run["complete"] = _power_run(
            holder["s"], queries, run["times"], run["failed"],
            DEADLINE - 60.0, rebuild=rebuild)
        try:  # persist incrementally: a later crash must not lose them
            holder["s"].save_compiled(rec_path)
        except Exception:
            pass
        if not run["complete"]:
            break
        # stop early if another full run cannot fit
        est = sum(run["times"].values())
        if ri + 1 < n_runs and _remaining() - 60.0 < est:
            break

    # a deadline-cut first run mixes compile time into its per-query
    # numbers; if no complete run exists but some queries compiled,
    # spend whatever budget is left on a steady-state pass over that
    # subset so the headline measures execution, not compilation
    runs = STATE["tpu_runs"]
    if runs and not any(r["complete"] and not r["failed"] for r in runs):
        done = [(n, s) for n, s in queries
                if n in runs[-1]["times"] and
                n not in runs[-1]["failed"]]
        if done and _remaining() > 60.0:
            STATE["phase"] = "tpu-steady-subset"
            run = {"times": {}, "failed": [], "complete": False}
            STATE["tpu_runs"].append(run)
            _power_run(holder["s"], done, run["times"], run["failed"],
                       DEADLINE - 20.0, rebuild=rebuild)

    STATE["phase"] = "done"
    _emit()


if __name__ == "__main__":
    try:
        main()
    except Exception as e:  # noqa: BLE001
        import traceback
        traceback.print_exc()
        _emit(f"exception in phase {STATE['phase']}: "
              f"{type(e).__name__}: {e}")
        sys.exit(1)
