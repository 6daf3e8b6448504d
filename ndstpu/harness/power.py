"""Power run: execute a query stream serially on the engine, timed.

Parity with the reference's power runner (/root/reference/nds/nds_power.py):
stream-file parsing on the `-- start` marker contract incl. two-part query
splitting (nds_power.py:49-76), per-query BenchReport JSON summaries, the
`application_id,query,time/milliseconds` CSV time log with Power Start/End/
Test/Total rows (nds_power.py:247-299), `--sub_queries` subsets, and query
output collection or writing (with output column-name sanitization,
nds_power.py:136-173).

The Spark-submit + session-build layer maps to: load the warehouse catalog
(TempView registration analog, nds_power.py:78-121), optional property file
of engine knobs, and `--engine cpu|tpu` to pick the numpy interpreter or the
JAX/XLA path.
"""

from __future__ import annotations

import argparse
import csv
import json
import os
import re
import time
import uuid
from collections import OrderedDict
from typing import Dict, List, Optional

from ndstpu import faults, obs
from ndstpu.check import check_json_summary_folder, check_query_subset_exists
from ndstpu.engine import columnar, device
from ndstpu.engine.session import Session
from ndstpu.harness import progress
from ndstpu.harness.report import BenchReport
from ndstpu.io import atomic, loader
from ndstpu.obs import ledger as ledger_mod
from ndstpu.obs import sentinel


# One `-- start query N in stream M using template queryX.tpl` marker
# opens each query block (the spark.tpl dialect contract the stream
# generator reproduces; cf. reference nds_power.py:49-76).
_STREAM_MARKER = re.compile(
    r"^--\s*start\s+query\s+\d+\s+in\s+stream\s+\d+\s+using\s+template\s+"
    r"(?P<name>\w+)\.tpl\s*$",
    re.MULTILINE | re.IGNORECASE)


def _sql_statements(block: str) -> List[str]:
    """Non-empty SQL statements in a query block, split on semicolons
    that are real statement terminators — a ``;`` inside a quoted
    literal or a ``--`` line comment does not split.  Fragments with no
    code outside comments (e.g. the trailing ``-- end query`` marker
    after the final semicolon) are not statements."""
    frags: List[str] = []
    cur: List[str] = []
    has_code = False
    in_str = in_comment = False
    for i, ch in enumerate(block):
        if in_comment:
            in_comment = ch != "\n"
        elif in_str:
            in_str = ch != "'"
        elif ch == "'":
            in_str = True
            has_code = True
        elif ch == "-" and block[i + 1:i + 2] == "-":
            in_comment = True
        elif ch == ";":
            if has_code:
                frags.append("".join(cur))
            cur, has_code = [], False
            continue
        elif not ch.isspace():
            has_code = True
        cur.append(ch)
    if has_code:
        frags.append("".join(cur))
    return frags


def gen_sql_from_stream(query_stream_file_path: str) -> "OrderedDict[str, str]":
    """Split a stream file into {query_name: sql}, splitting the
    multi-statement templates (14/23/24/39) into `_part1`/`_part2`
    entries (contract: nds_power.py:49-76)."""
    with open(query_stream_file_path) as f:
        text = f.read()
    markers = list(_STREAM_MARKER.finditer(text))
    queries: "OrderedDict[str, str]" = OrderedDict()
    for marker, nxt in zip(markers, markers[1:] + [None]):
        name = marker.group("name")
        block_end = nxt.start() if nxt is not None else len(text)
        body = text[marker.end():block_end]
        stmts = _sql_statements(body)
        if len(stmts) > 1:
            for k, stmt in enumerate(stmts, start=1):
                queries[f"{name}_part{k}"] = stmt + ";"
        else:
            # single-statement: keep the whole block, markers included
            queries[name] = text[marker.start():block_end]
    return queries


def ensure_valid_column_names(table: columnar.Table) -> columnar.Table:
    """Sanitize output column names for file formats
    (reference: nds_power.py:136-173)."""
    def ok(name: str) -> bool:
        return re.fullmatch(r"[A-Za-z_][A-Za-z0-9_]*", name) is not None

    cols = {}
    for i, (n, c) in enumerate(table.columns.items()):
        cols[n if ok(n) else f"column_{i}"] = c
    return columnar.Table(cols)


def get_query_subset(query_dict, subset: List[str]):
    check_query_subset_exists(query_dict, subset)
    return OrderedDict((q, query_dict[q]) for q in subset)


def static_check(sess: Session, query_dict, engine: str,
                 scale_factor=None) -> List[str]:
    """``--static_check`` gate: run the static analyzer over every queued
    query (plan-only — no data, no XLA compile) and return the queries
    with error-severity lowering diagnostics, printing each diagnostic's
    code and plan location.  Error-severity NDS2xx means jaxexec WILL
    fall back mid-run after paying the compile, so accel engines reject
    the stream up front; the cpu interpreter executes everything, so
    nothing gates there."""
    from ndstpu import analysis

    if engine not in ("tpu", "tpu-spmd"):
        print("static check: cpu engine lowers everything; skipping")
        return []
    try:
        sf = float(scale_factor)
    except (TypeError, ValueError):
        sf = None
    tables = analysis.schema_tables()
    offenders: List[str] = []
    for name, sql in query_dict.items():
        try:
            plan, _cols = sess.plan(sql)
        except Exception as e:
            # parse/plan/optimize rejection: the run would die on this
            # statement anyway, so it gates
            offenders.append(name)
            print(f"STATIC CHECK {name}: NDS000 at plan: {e}")
            continue
        try:
            res = analysis.analyze_plan(plan, tables=tables, query=name,
                                        scale_factor=sf)
        except Exception as e:  # analyzer gaps must not block a run
            print(f"WARNING: static check could not analyze {name}: {e}")
            continue
        gating = [d for d in res.diagnostics if d.severity == "error"
                  and "/subquery[" not in d.path]
        if gating:
            offenders.append(name)
            for d in gating:
                print(f"STATIC CHECK {name}: {d.code} at {d.path}: "
                      f"{d.message}")
    return offenders


def run_one_query(session: Session, query: str, query_name: str,
                  output_path: Optional[str], output_format: str) -> None:
    result = session.sql(query)
    if result is None:
        return
    # observed output cardinality on the query span -> ledger extra:
    # the calibration source for the static cost model
    # (scripts/cost_lint.py --calibrate, NDS604)
    from ndstpu import obs
    obs.annotate(result_rows=int(result.num_rows))
    if not output_path:
        result.to_rows()  # the collect() analog — materialize to host
        return
    out = ensure_valid_column_names(result)
    dest = os.path.join(output_path, query_name)
    os.makedirs(dest, exist_ok=True)
    at = columnar.to_arrow(out)
    if output_format == "parquet":
        import pyarrow.parquet as pq
        pq.write_table(at, os.path.join(dest, "part-0.parquet"))
    elif output_format == "csv":
        import pyarrow.csv as pacsv
        pacsv.write_csv(at, os.path.join(dest, "part-0.csv"))
    else:
        raise ValueError(f"unsupported output format {output_format}")


def load_properties(filename: str) -> Dict[str, str]:
    """java-properties style engine config (reference: nds_power.py:306-312)."""
    props = {}
    with open(filename) as f:
        for line in f:
            line = line.strip()
            if not line or line.startswith("#"):
                continue
            if "=" in line:
                k, v = line.split("=", 1)
                props[k.strip()] = v.strip()
    return props


def apply_engine_properties(engine_conf: Dict[str, str]) -> None:
    """Apply `jax.*` properties to jax.config (the engine-knob channel —
    the analog of Spark conf flowing from the submit template into the
    SparkSession, nds_power.py:221-237).  The persistent compile-cache
    DIRECTORY is not a property: engine/device.py resolves it, once,
    for every process."""
    jax_keys = {k: v for k, v in engine_conf.items() if k.startswith("jax.")}
    if "jax.compilation_cache_dir" in jax_keys:
        del jax_keys["jax.compilation_cache_dir"]
        print(f"WARNING: engine property jax.compilation_cache_dir is "
              f"ignored; the cache lives at {device.compile_cache_dir()} "
              f"(set {device.CACHE_ENV} to move it)")
    if not jax_keys:
        return
    import jax
    for k, v in jax_keys.items():
        name = "jax_" + k[len("jax."):]
        val: object = v
        for conv in (int, float):
            try:
                val = conv(v)
                break
            except ValueError:
                continue
        if v.lower() in ("true", "false"):
            val = v.lower() == "true"
        try:
            jax.config.update(name, val)
        except Exception as e:  # unknown knob: record, don't abort the run
            print(f"WARNING: engine property {k}={v} not applied: {e}")


def _dir_file_count(path: Optional[str]) -> int:
    """Recursive file count of the XLA persistent compile cache — the
    before/after gauge that distinguishes a genuinely warm run (no new
    cache entries) from one that recompiled behind preloaded records."""
    if not path or not os.path.isdir(path):
        return 0
    return sum(len(files) for _, _, files in os.walk(path))


def run_stream(query_dict, *, queue, runner,
               heartbeat: Optional[progress.Heartbeat] = None,
               engine: str = "cpu", app_id: Optional[str] = None,
               stream_name: str = "stream",
               engine_conf: Optional[Dict[str, str]] = None,
               gate=None, pre_query=None, post_query=None,
               json_summary_folder: Optional[str] = None,
               summary_prefix: str = "",
               t0: Optional[float] = None,
               span_attrs: Optional[dict] = None,
               retry_policy: Optional[faults.RetryPolicy] = None,
               quarantine: Optional[faults.Quarantine] = None,
               completed: Optional[set] = None) -> dict:
    """Run one query stream's per-query loop against an already-built
    execution context.  This is the reusable core the power CLI and the
    in-process throughput scheduler share: the CLI wraps it with its own
    session/watchdog/admission setup (one stream per OS process), the
    scheduler calls it once per stream THREAD against one shared session
    (ndstpu/harness/scheduler.py).

    * ``queue``      — BudgetedQueue or a scheduler stream view: needs
      ``next(elapsed_s)``, ``projected_s()``, ``skipped``; an optional
      ``done(name, failed=...)`` is called after each query (the
      scheduler uses it to publish compile-once state across streams).
    * ``runner``     — ``runner(sql, query_name)`` executes one query
      (the CLI passes its watchdog-guarded closure).
    * ``gate``       — admission with ``acquire()``/``release()``
      (DeviceAdmission or InprocAdmission), or None.
    * ``pre_query``  — optional hook returning a dict merged into the
      query summary (the CLI's zombie-thread bookkeeping).
    * ``post_query`` — optional ``post_query(name, summary, failed)``
      hook called after each query completes or fails (the resume
      journal appends its per-query record here).
    * ``retry_policy`` / ``quarantine`` — failure handling
      (ndstpu/faults/retry.py): transient failures retry with bounded
      deterministic backoff; a key that keeps failing is quarantined
      and later occurrences skip with an explicit ``partial_reason``.
    * ``completed``  — query names already finished by a previous run
      of the same fingerprint (crash-safe resume); skipped up front
      and reported under ``resumed``.

    Returns ``{"app_id", "rows", "executed", "skipped", "failures",
    "start_epoch_s", "end_epoch_s", "taxonomy", "quarantined",
    "resumed"}`` where ``rows`` are ``(app_id, query, millis)``
    time-log tuples.
    """
    t0 = time.time() if t0 is None else t0
    app_id = app_id or f"ndstpu-{uuid.uuid4().hex[:12]}"
    engine_conf = engine_conf or {}
    # the before/after file gauge reads the ONE resolved cache dir
    xla_cache_dir = device.compile_cache_dir() \
        if device.is_accel(engine) else None
    mark_done = getattr(queue, "done", None)
    rows: List[tuple] = []
    executed: List[str] = []
    failures = 0
    taxonomy_counts: Dict[str, int] = {}
    taxonomy_queries: Dict[str, str] = {}
    resumed: List[str] = []
    base_runner = runner
    if retry_policy is not None or quarantine is not None:
        # run_with_retry classifies + annotates even at max_attempts=1
        def runner(sql, qname):  # noqa: F811 — deliberate shadowing
            faults.run_with_retry(lambda: base_runner(sql, qname),
                                  qname, policy=retry_policy,
                                  quarantine=quarantine)
    start_epoch = time.time()
    stream_span = obs.span(stream_name, cat="stream", collect=True,
                           engine=engine, n_queries=len(query_dict),
                           **(span_attrs or {}))
    stream_span.__enter__()
    try:
        while True:
            query_name = queue.next(time.time() - t0)
            if query_name is None:
                break
            if completed and query_name in completed:
                # crash-safe resume: finished by a previous run of the
                # same fingerprint — skip without touching the engine
                print(f"====== Skip {query_name} (resume: already "
                      f"completed) ======")
                resumed.append(query_name)
                if mark_done is not None:
                    mark_done(query_name, failed=False)
                continue
            if quarantine is not None and \
                    quarantine.is_quarantined(query_name):
                reason = quarantine.reason(query_name)
                print(f"====== Skip {query_name} ({reason}) ======")
                queue.skipped[query_name] = reason
                obs.inc("harness.quarantine.skips")
                if mark_done is not None:
                    # failed=True: a quarantined key must never publish
                    # to the shared compile/plan caches (PR-4 invariant)
                    mark_done(query_name, failed=True)
                continue
            q_content = query_dict[query_name]
            if heartbeat is not None:
                heartbeat.beat(len(executed) + 1, query_name,
                               time.time() - t0,
                               eta_s=queue.projected_s())
            print(f"====== Run {query_name} ======")
            summary_extra = pre_query(query_name) if pre_query else None
            xla_files_before = _dir_file_count(xla_cache_dir)
            q_report = BenchReport(engine_conf)
            # NOTE metric difference vs the reference: its
            # concurrentGpuTasks semaphore is acquired inside task
            # execution, so queue wait is part of each reported query
            # time; here the gate sits outside report_on, so queryTimes
            # is pure execution and the wait is reported separately
            # (admissionWaitMs) to keep stream comparisons honest.
            wait_ms = 0
            if gate is not None:
                wait_start = time.time()
                gate.acquire()
                wait_ms = int((time.time() - wait_start) * 1000)
            try:
                summary = q_report.report_on(runner, q_content,
                                             query_name,
                                             query_name=query_name,
                                             span_attrs=span_attrs)
            finally:
                if gate is not None:
                    gate.release()
            if gate is not None:
                summary["admissionWaitMs"] = wait_ms
            if summary_extra:
                summary.update(summary_extra)
            failed = bool(summary["queryStatus"]) and \
                summary["queryStatus"][-1] == "Failed"
            if failed:
                failures += 1
                for tx in summary.get("failureTaxonomy", []):
                    if tx.get("query") == query_name:
                        taxonomy_counts[tx["class"]] = \
                            taxonomy_counts.get(tx["class"], 0) + 1
                        taxonomy_queries[query_name] = tx["class"]
            if mark_done is not None:
                mark_done(query_name, failed=failed)
            if xla_cache_dir:
                xla_files_after = _dir_file_count(xla_cache_dir)
                obs.set_gauge("xla.persistent_cache.files",
                              xla_files_after)
                if xla_files_after > xla_files_before:
                    obs.inc("xla.persistent_cache.new_entries",
                            xla_files_after - xla_files_before)
                if summary.get("metrics"):
                    summary["metrics"][-1]["xla_cache_files"] = {
                        "before": xla_files_before,
                        "after": xla_files_after}
            print(f"Time taken: {summary['queryTimes']} millis for "
                  f"{query_name}")
            rows.append((app_id, query_name, summary["queryTimes"][0]))
            if json_summary_folder:
                q_report.write_summary(query_name,
                                       prefix=summary_prefix)
            executed.append(query_name)
            if post_query is not None:
                post_query(query_name, summary, failed)
    finally:
        stream_span.__exit__(None, None, None)
    if queue.skipped:
        budget = getattr(queue, "budget_s", None)
        print(f"WARNING: {getattr(queue, 'phase', 'run')} run partial "
              f"- {len(queue.skipped)} queries cut by the "
              f"{budget:g}s budget; per-query partial_reason recorded "
              f"in the metrics sidecar" if budget else
              f"WARNING: {len(queue.skipped)} queries skipped")
        obs.inc("harness.budget.queries_skipped", len(queue.skipped))
    return {
        "app_id": app_id,
        "rows": rows,
        "executed": executed,
        "skipped": dict(queue.skipped),
        "failures": failures,
        "taxonomy": {"counts": taxonomy_counts,
                     "queries": taxonomy_queries},
        "quarantined": quarantine.snapshot() if quarantine else {},
        "resumed": resumed,
        "start_epoch_s": start_epoch,
        "end_epoch_s": time.time(),
    }


def power_fingerprint(args) -> str:
    """Identity of a power run for crash-safe resume: two runs with the
    same fingerprint execute the same queries against the same data, so
    a query completed by one needn't re-run in the other."""
    import hashlib
    parts = [
        str(getattr(args, "engine", "")),
        str(getattr(args, "scale_factor", "")),
        str(getattr(args, "run_seed", "")),
        os.path.basename(getattr(args, "query_stream_file", "") or ""),
        str(getattr(args, "sub_queries", "") or ""),
        os.path.abspath(getattr(args, "input_prefix", "") or ""),
    ]
    return hashlib.sha256("|".join(parts).encode()).hexdigest()


def run_query_stream(args) -> None:
    total_start = time.time()
    execution_times = []
    app_id = f"ndstpu-{uuid.uuid4().hex[:12]}"

    engine_conf: Dict[str, str] = {}
    if args.property_file:
        engine_conf.update(load_properties(args.property_file))
    engine_conf.setdefault("engine", args.engine)
    engine_conf.setdefault("input_format", args.input_format)
    # before the (slow) catalog load: no chip, no run
    device.require_accelerator(args.engine)
    apply_engine_properties(engine_conf)

    query_dict = gen_sql_from_stream(args.query_stream_file)

    # catalog load == table registration (TempView analog)
    load_start = time.time()
    with obs.span("load_catalog", cat="phase"):
        catalog = loader.load_catalog(args.input_prefix,
                                      use_decimal=not args.floats)
        sess = Session(catalog, backend=args.engine)
    # distributed-engine knobs via the property channel (the analog of
    # spark.sql.shuffle.partitions etc. flowing from the template)
    if engine_conf.get("spmd.threshold_rows"):
        sess.spmd_threshold = int(engine_conf["spmd.threshold_rows"])
    if engine_conf.get("spmd.chunk_rows"):
        raw = engine_conf["spmd.chunk_rows"]
        sess.spmd_chunk_rows = raw if raw == "auto" else int(raw)
    if engine_conf.get("spmd.prefetch_depth"):
        sess.spmd_prefetch_depth = int(engine_conf["spmd.prefetch_depth"])
    execution_times.append(
        (app_id, "CreateTempView all tables",
         int((time.time() - load_start) * 1000)))
    if args.compile_records and args.engine in ("tpu", "tpu-spmd"):
        # after the load-time row: preload re-plans every saved query and
        # must not be charged to table registration
        preload_start = time.time()
        obs.set_gauge("harness.compile_records.present",
                      1 if os.path.exists(args.compile_records) else 0)
        try:
            with obs.span("preload_compile_records", cat="phase"):
                n = sess.preload_compiled(args.compile_records)
            obs.inc("harness.compile_records.preloaded", n)
            print(f"preloaded {n} compile records")
        except Exception as e:  # stale records must never kill the run
            print(f"WARNING: compile records not loaded: {e}")
        execution_times.append(
            (app_id, "Preload compile records",
             int((time.time() - preload_start) * 1000)))

    check_json_summary_folder(args.json_summary_folder)
    if args.sub_queries:
        query_dict = get_query_subset(query_dict,
                                      args.sub_queries.split(","))

    if getattr(args, "static_check", False):
        with obs.span("static_check", cat="phase"):
            offenders = static_check(
                sess, query_dict, args.engine,
                scale_factor=getattr(args, "scale_factor", None))
        if offenders:
            raise SystemExit(
                "static check failed: query part(s) "
                f"{', '.join(offenders)} cannot lower on "
                f"{args.engine} (diagnostics above); fix the query "
                "or drop --static_check to run with runtime fallback")

    # concurrent-stream admission: at most N streams execute on the
    # device at once (the concurrentGpuTasks analog; set by the
    # throughput runner via env, see ndstpu.harness.admission)
    from ndstpu.harness import admission as adm
    gate = adm.from_env()

    # per-query watchdog (accel engines): a compile or execution that
    # never returns otherwise blocks the stream forever — abandon such
    # a query in a daemon thread.  The abandoned thread keeps only the
    # OLD session, so the stream continues on a fresh one (records
    # preloaded again).
    #
    # Device-sharing hazard: the abandoned thread still drives the old
    # session on the SAME TPU runtime the fresh session uses; a late
    # completion can contend for HBM, and warnings it raises are
    # captured by whichever later query's report window is open
    # (process-global warning capture).  Mitigation below: abandoned
    # threads are tracked in `zombies`; before each query the stream
    # grants them a short grace join, and any still-alive zombie is
    # recorded in the query's summary (`zombieQueries`) so a
    # CompletedWithTaskFailures status can be adjudicated.
    accel = args.engine in ("tpu", "tpu-spmd")
    watchdog_s = float(os.environ.get(
        "NDSTPU_POWER_QUERY_TIMEOUT_S", "1200")) if accel else 0.0
    sess_holder = {"s": sess}
    zombies: List[dict] = []  # abandoned runs: {th, name, graced}

    def live_zombies(grace_s: float = 0.0) -> List[str]:
        # each zombie gets ONE grace join — a permanently-wedged thread
        # must not charge every remaining query the full grace window
        for z in zombies:
            if not z["graced"]:
                z["th"].join(grace_s)
                z["graced"] = True
        zombies[:] = [z for z in zombies if z["th"].is_alive()]
        return [z["name"] for z in zombies]

    def run_guarded(q_content, query_name):
        if watchdog_s <= 0:
            return run_one_query(sess_holder["s"], q_content, query_name,
                                 args.output_prefix, args.output_format)
        import threading
        slot: dict = {}

        def work(s=sess_holder["s"]):
            t_body = time.perf_counter()
            try:
                run_one_query(s, q_content, query_name,
                              args.output_prefix, args.output_format)
                slot["ok"] = True
            except Exception as e:  # noqa: BLE001
                slot["err"] = e
            finally:
                slot["body_s"] = time.perf_counter() - t_body

        th = threading.Thread(target=work, daemon=True)
        t_handed = time.perf_counter()
        th.start()
        th.join(watchdog_s)
        if "body_s" in slot:
            # starting the watchdog's thread and waking from its join
            # is part of running the query: on a busy host it was a
            # fifth of a 20 ms query's wall, attributed to nothing
            obs.add_time("execute_s", max(
                time.perf_counter() - t_handed - slot["body_s"], 0.0))
        if th.is_alive():
            zombies.append({"th": th, "name": query_name, "graced": False})
            old = sess_holder["s"]
            try:
                fresh = Session(old.catalog, backend=args.engine,
                                views=dict(old.views),
                                warehouse=old.warehouse)
                fresh.spmd_threshold = old.spmd_threshold
                fresh.spmd_chunk_rows = old.spmd_chunk_rows
                fresh.spmd_prefetch_depth = old.spmd_prefetch_depth
                # swap FIRST: preload failure is non-fatal, but the
                # stream must never continue on the session the
                # zombie thread still drives
                sess_holder["s"] = fresh
                if args.compile_records:
                    fresh.preload_compiled(args.compile_records)
            except Exception as e:  # noqa: BLE001
                print(f"WARNING: fresh session setup after hang "
                      f"incomplete: {e}")
            raise TimeoutError(
                f"{query_name} hung > {watchdog_s:.0f}s; abandoned "
                f"(stream continues on a fresh session)")
        if "err" in slot:
            raise slot["err"]

    stream_name = os.path.splitext(
        os.path.basename(args.query_stream_file))[0]
    if accel:
        obs.set_gauge("xla.persistent_cache.files",
                      _dir_file_count(device.compile_cache_dir()))

    # -- run ledger + budget heartbeat (docs/OBSERVABILITY.md) --------
    # priors feed the per-query ETA and the cheapest-first deadline
    # degradation; the ledger itself is appended to after the stream.
    # getattr: callers that build a Namespace by hand (tests, older
    # drivers) predate these flags
    run_scale_factor = getattr(args, "scale_factor", "unknown")
    run_seed = getattr(args, "run_seed", "unknown")
    led = None
    ledger_path = getattr(args, "ledger", None)
    if ledger_path is None:
        ledger_path = ledger_mod.default_path()
    if ledger_path and ledger_path.lower() != "none":
        try:
            led = ledger_mod.Ledger(ledger_path)
        except Exception as e:  # a corrupt ledger must not kill a run
            print(f"WARNING: ledger {ledger_path} not loaded: {e}")
    # expected warmth for ETA priors: accel engines pay compile unless
    # the size-plan records exist; the cpu interpreter never compiles
    expected_warmth = "warm"
    if args.engine in ("tpu", "tpu-spmd") and not (
            args.compile_records and
            os.path.exists(args.compile_records)):
        expected_warmth = "cold"
    budget_s = getattr(args, "budget_s", None)
    budget_s = budget_s if budget_s and budget_s > 0 else None
    est = progress.ledger_estimator(led, engine=args.engine,
                                    scale_factor=run_scale_factor,
                                    warmth=expected_warmth)
    queue = progress.BudgetedQueue(list(query_dict), budget_s, est,
                                   phase="power")
    hb = progress.Heartbeat("power", total=len(query_dict),
                            budget_s=budget_s)

    # -- failure handling + crash-safe resume -------------------------
    # transient failures retry (NDSTPU_RETRY_MAX attempts, deterministic
    # backoff); a per-query progress journal rides next to the time log
    # so a killed run can --resume past every query it already finished
    retry_policy = faults.RetryPolicy.from_env()
    quarantine = faults.Quarantine()
    progress_log = args.time_log + ".progress.jsonl"
    run_fp = power_fingerprint(args)
    completed: set = set()
    resumed_rows: List[tuple] = []
    if getattr(args, "resume", False):
        for rec in atomic.read_jsonl(progress_log):
            if rec.get("fp") == run_fp and not rec.get("failed") and \
                    rec.get("query") in query_dict and \
                    rec["query"] not in completed:
                completed.add(rec["query"])
                resumed_rows.append((rec.get("app_id", app_id),
                                     rec["query"],
                                     rec.get("millis") or 0))
        if completed:
            print(f"====== Resume: skipping {len(completed)} queries "
                  f"already completed (fingerprint {run_fp[:12]}) "
                  f"======")
            obs.inc("harness.resume.queries_skipped", len(completed))
    elif os.path.exists(progress_log):
        os.unlink(progress_log)  # fresh run: the old journal is stale

    def post_query(name, summary, failed):
        try:
            atomic.append_jsonl(progress_log, {
                "fp": run_fp, "query": name, "failed": bool(failed),
                "millis": summary["queryTimes"][0]
                if summary["queryTimes"] else None,
                "app_id": app_id, "ts_epoch_s": time.time()})
        except Exception as e:  # journal must never fail the run
            print(f"WARNING: progress journal append failed: {e}")

    def pre_query(query_name):
        # abandoned-thread gate: give zombies a short grace window to
        # drain before sharing the device with the next query
        active_zombies = live_zombies(grace_s=10.0) if zombies else []
        if not active_zombies:
            return None
        print(f"WARNING: abandoned query threads still running: "
              f"{active_zombies} — device contention possible; "
              f"captured warnings may belong to them")
        return {"zombieQueries": active_zombies}

    if args.json_summary_folder and args.property_file:
        summary_prefix = os.path.join(
            args.json_summary_folder,
            os.path.basename(args.property_file).split(".")[0])
    else:
        summary_prefix = os.path.join(args.json_summary_folder or "", "")

    power_start = int(time.time())
    res = run_stream(query_dict, queue=queue, runner=run_guarded,
                     heartbeat=hb, engine=args.engine, app_id=app_id,
                     stream_name=stream_name, engine_conf=engine_conf,
                     gate=gate, pre_query=pre_query,
                     post_query=post_query,
                     json_summary_folder=args.json_summary_folder,
                     summary_prefix=summary_prefix,
                     t0=total_start,
                     span_attrs={"stream": stream_name},
                     retry_policy=retry_policy, quarantine=quarantine,
                     completed=completed)
    execution_times.extend(resumed_rows)
    execution_times.extend(res["rows"])
    executed = res["executed"]
    power_end = int(time.time())
    power_elapse = int((power_end - power_start) * 1000)
    total_elapse = int((time.time() - total_start) * 1000)
    print(f"====== Power Test Time: {power_elapse} milliseconds ======")
    print(f"====== Total Time: {total_elapse} milliseconds ======")
    execution_times.append((app_id, "Power Start Time", power_start))
    execution_times.append((app_id, "Power End Time", power_end))
    execution_times.append((app_id, "Power Test Time", power_elapse))
    execution_times.append((app_id, "Total Time", total_elapse))

    if args.compile_records and args.engine in ("tpu", "tpu-spmd"):
        try:
            sess_holder["s"].save_compiled(args.compile_records)
        except Exception as e:
            print(f"WARNING: compile records not saved: {e}")

    header = ["application_id", "query", "time/milliseconds"]
    with atomic.atomic_writer(args.time_log, "w",
                              encoding="UTF8", newline="") as f:
        w = csv.writer(f)
        w.writerow(header)
        w.writerows(execution_times)
    if args.extra_time_log:
        with atomic.atomic_writer(args.extra_time_log, "w",
                                  encoding="UTF8", newline="") as f:
            w = csv.writer(f)
            w.writerow(header)
            w.writerows(execution_times)

    if obs.enabled():
        # one JSONL event log + one Perfetto-loadable Chrome trace per
        # run, next to the time log (NDSTPU_TRACE_DIR overrides), plus a
        # machine-readable metrics sidecar the bench driver aggregates
        trace_dir = os.environ.get("NDSTPU_TRACE_DIR") or \
            (os.path.dirname(args.time_log) or ".")
        base = os.path.basename(args.time_log)
        # sentinel verdicts are judged against the PRE-run ledger, then
        # this run's measurements are appended so the next run has
        # priors; failed queries never contribute baselines
        sentinel_block = None
        ledger_block = None
        qsums = [q for q in obs.tracer().query_summaries()
                 if q["query"] in set(executed)]
        if led is not None and qsums:
            try:
                # data-version identity of the warehouse this run read:
                # the sentinel only compares warm walls within one
                # epoch (verdict data-changed across epochs), and rows
                # appended here carry the stamp for future runs
                run_epoch = None
                try:
                    from ndstpu.io import lake as lake_mod
                    run_epoch = lake_mod.warehouse_epoch(
                        args.input_prefix)
                except Exception:  # noqa: BLE001 — stamp is best-effort
                    pass
                sentinel_block = sentinel.classify_run(
                    qsums, led, engine=args.engine,
                    scale_factor=run_scale_factor,
                    snapshot_epoch=run_epoch)
                entries = [ledger_mod.make_entry(
                    q["query"], q["wall_s"], q["compile_s"],
                    q["execute_s"], engine=args.engine,
                    scale_factor=run_scale_factor, seed=run_seed,
                    source=os.path.basename(args.time_log),
                    # why the engine left the device path, as
                    # "NDSxxx:Node" analyzer codes (engine-annotated);
                    # plus the stream tag so a shared ledger stays
                    # attributable per stream
                    extra={k: v for k, v in {
                        "stream": stream_name,
                        "snapshot_epoch": run_epoch,
                        "fallback_codes":
                            (q.get("attrs") or {}).get("fallback_codes"),
                        "spmd_fallback":
                            (q.get("attrs") or {}).get("spmd_fallback"),
                        "retry_attempts":
                            (q.get("attrs") or {}).get("retry_attempts"),
                        "spine_hits":
                            (q.get("attrs") or {}).get("spine_hits"),
                        "spine_bytes_saved":
                            (q.get("attrs") or {}).get(
                                "spine_bytes_saved"),
                        # cost-model consumers: the advisor's exchange
                        # decisions and the observed output cardinality
                        # (NDS604 calibration, scripts/cost_lint.py)
                        "cost_decisions":
                            (q.get("attrs") or {}).get("cost_decisions"),
                        "result_rows":
                            (q.get("attrs") or {}).get("result_rows"),
                    }.items() if v})
                    for q in qsums
                    if not (q.get("attrs") or {}).get("error")]
                led.append(entries)
                ledger_block = {"path": led.path,
                                "appended": len(entries)}
                if sentinel_block["regressions"]:
                    print(f"WARNING: sentinel flagged warm-path "
                          f"regressions: "
                          f"{sentinel_block['regressions']} "
                          f"(scripts/regression_check.py exits "
                          f"nonzero on these)")
            except Exception as e:  # ledger must never fail the run
                print(f"WARNING: ledger/sentinel update failed: {e}")
        try:
            paths = obs.export_run(trace_dir, base)
            sidecar = args.time_log + ".metrics.json"
            with atomic.atomic_writer(sidecar, "w") as f:
                json.dump(obs.run_metrics({
                    "app_id": app_id,
                    "engine": args.engine,
                    "device": device.describe(args.engine),
                    "stream": stream_name,
                    "power_elapse_ms": power_elapse,
                    "total_elapse_ms": total_elapse,
                    "budget_s": budget_s,
                    "partial": bool(queue.skipped),
                    "partial_reasons": queue.skipped,
                    "faultTaxonomy": res["taxonomy"],
                    "quarantined": res["quarantined"] or None,
                    "resumed": res["resumed"] or None,
                    "ledger": ledger_block,
                    "sentinel": sentinel_block,
                }), f, indent=2)
            print(f"====== Trace: {paths['jsonl']} | {paths['chrome']} "
                  f"| {sidecar} ======")
        except Exception as e:  # observability must never fail the run
            print(f"WARNING: trace export failed: {e}")


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description="NDS power run (TPU engine)")
    p.add_argument("query_stream_file",
                   help="query stream file (query_N.sql)")
    p.add_argument("input_prefix", help="warehouse directory")
    p.add_argument("time_log", help="per-query CSV time log output path")
    p.add_argument("--input_format", default="parquet",
                   choices=["parquet", "orc", "avro", "csv", "json",
                            "ndslake", "ndsdelta"],
                   help="warehouse table format")
    p.add_argument("--engine", default="cpu",
                   choices=["cpu", "tpu", "tpu-spmd"],
                   help="execution backend (tpu-spmd distributes over "
                        "the device mesh, falling back per-query)")
    p.add_argument("--output_prefix",
                   help="write per-query results under this dir "
                        "(for validation); default = collect only")
    p.add_argument("--output_format", default="parquet",
                   choices=["parquet", "csv"])
    p.add_argument("--property_file",
                   help="engine properties file (knobs recorded in reports)")
    p.add_argument("--json_summary_folder",
                   help="folder for per-query JSON summaries")
    p.add_argument("--sub_queries",
                   help="comma-separated query-name subset, e.g. "
                        "query1,query3_part1")
    p.add_argument("--extra_time_log",
                   help="secondary location for the CSV time log")
    p.add_argument("--compile_records",
                   help="path for persisted whole-query size-plan "
                        "records (skip per-query discovery on repeat "
                        "power runs; tpu engines only)")
    p.add_argument("--budget_s", type=float,
                   default=float(os.environ.get(
                       "NDSTPU_PHASE_BUDGET_S", "0") or 0),
                   help="phase deadline budget in seconds (0 = none; "
                        "default from NDSTPU_PHASE_BUDGET_S). On "
                        "projected overrun the run degrades "
                        "explicitly: remaining queries reorder "
                        "cheapest-first by ledger prior and cut "
                        "queries get a per-query partial_reason in "
                        "the metrics sidecar")
    p.add_argument("--ledger",
                   help="run-ledger JSONL path (default "
                        "$NDSTPU_LEDGER or .bench_cache/ledger.jsonl; "
                        "'none' disables). Serves ETA priors and "
                        "regression-sentinel baselines; executed "
                        "queries are appended after the run")
    p.add_argument("--scale_factor", default="unknown",
                   help="scale factor for ledger fingerprinting "
                        "(the bench driver passes it)")
    p.add_argument("--run_seed", default="unknown",
                   help="stream rngseed for ledger fingerprinting "
                        "(the bench driver passes the resolved seed)")
    p.add_argument("--floats", action="store_true",
                   help="double mode (no decimals)")
    p.add_argument("--resume", action="store_true",
                   help="crash-safe resume: replay the per-query "
                        "progress journal (<time_log>.progress.jsonl) "
                        "and skip queries already completed by a "
                        "previous run of the same fingerprint (engine, "
                        "scale factor, seed, stream, subset, "
                        "warehouse); their time-log rows are carried "
                        "over")
    p.add_argument("--static_check", action="store_true",
                   help="run the static plan analyzer over the stream "
                        "before executing anything; on accel engines, "
                        "reject queries with error-severity lowering "
                        "diagnostics (code + plan path printed) before "
                        "any compile")
    return p


if __name__ == "__main__":
    run_query_stream(build_parser().parse_args())
