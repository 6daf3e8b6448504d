"""Full-benchmark orchestrator (the nds_bench analog).

Runs the five NDS phases end-to-end from a YAML config and computes the
composite TPC-DS-style metric (reference: /root/reference/nds/nds_bench.py):

  data gen -> load test (transcode) -> stream gen (RNGSEED chained from the
  load report, spec 4.3.1) -> Power Test -> Throughput Test 1 -> Data
  Maintenance 1 -> Throughput Test 2 -> Data Maintenance 2 -> metric

Phase parity details: per-phase `skip:` flags reusing prior reports
(nds_bench.py:368-399), throughput elapsed = max(end)-min(start) over the
stream time logs rounded up to 0.1s (nds_bench.py:138-157,207-208), stream
ranges split across the two throughput tests (nds_bench.py:120-135), and
metric = int(SF * Sq*Q / (Tpt*Ttt*Tdm*Tld)^(1/4)) in decimal hours
(nds_bench.py:334-357).
"""

from __future__ import annotations

import argparse
import json
import math
import os
import subprocess
import sys
import time
from contextlib import contextmanager

import yaml

from ndstpu import faults, obs
from ndstpu.harness import runstate
from ndstpu.io import atomic

PY = [sys.executable, "-m"]


@contextmanager
def _phase(name: str, walls: dict, budget_s=None):
    """Time one bench phase: a tracer span (cat='phase') plus a wall
    entry for the HW metrics artifact.  Phases run as subprocesses, so
    per-query spans live in the power runner's own trace; the driver
    records the phase envelope and stitches the power sidecar in.
    ``budget_s`` (per-phase ``budget_s:`` in the YAML) makes the
    deadline visible: a start heartbeat, and an explicit overrun line +
    counter when the phase blows its budget — never a silent burn."""
    t0 = time.time()
    if budget_s:
        print(f"[heartbeat] phase {name} start budget={budget_s:g}s")
    with obs.span(name, cat="phase", budget_s=budget_s):
        yield
    wall = time.time() - t0
    walls[name] = round(wall, 3)
    if budget_s:
        if wall > budget_s:
            print(f"[budget] phase {name} OVERRAN: {wall:.1f}s > "
                  f"{budget_s:g}s budget (+{wall - budget_s:.1f}s)")
            obs.inc("harness.budget.phase_overruns")
        else:
            print(f"[heartbeat] phase {name} done {wall:.1f}s of "
                  f"{budget_s:g}s budget")


def round_up_to_nearest_10_percent(num: float) -> float:
    return math.ceil(num * 10) / 10


def get_load_time(load_report_file: str) -> str:
    with open(load_report_file) as f:
        for line in f:
            if "Load Test Time" in line:
                return line.split(":")[1].split(" ")[1]
    raise RuntimeError(f"Load Test Time not found in {load_report_file}")


def get_load_end_timestamp(load_report_file: str) -> str:
    with open(load_report_file) as f:
        for line in f:
            if "RNGSEED used:" in line:
                return line.split(":")[1].strip()
    raise RuntimeError(f"RNGSEED not found in {load_report_file}")


def resolve_stream_rngseed(stream_cfg: dict, load_report_file: str) -> str:
    """Seed for the query streams: an explicit ``rngseed:`` in the
    generate_query_stream config wins; otherwise it chains from the load
    end timestamp (spec 4.3.1, nds_bench.py:249-261).  The override is
    the orchestrated form of the reference stream generator's explicit
    ``--rngseed`` flag (nds_gen_query_stream.py:42-89, "for
    reproducibility"): a pinned seed renders the same stream corpus
    every run, so a pre-warmed compile-record/XLA cache can serve the
    power phase.  The sentinel ``rngseed: bench`` resolves to
    ``streamgen.BENCH_RNGSEED`` — the one seed every warm/bench script
    renders with — so configs cannot drift from the warmed corpus by
    duplicating the literal."""
    seed = stream_cfg.get("rngseed")
    if seed is None:
        return get_load_end_timestamp(load_report_file)
    if seed == "bench":
        from ndstpu.queries.streamgen import BENCH_RNGSEED
        return BENCH_RNGSEED
    if not isinstance(seed, str):
        # yaml parses unquoted digit seeds as ints.  PyYAML octal-parses
        # an unquoted 0-prefixed seed ONLY when its digits are all 0-7
        # (YAML 1.1 resolver `0[0-7]+`) — such a timestamp resolves to a
        # DIFFERENT number; a 0-prefixed seed containing an 8 or 9
        # matches neither the octal nor the decimal form and safely
        # stays a string.  Any seed that reached here as an int has at
        # minimum lost its leading zeros, so the pin would silently
        # render the wrong corpus.  Refuse instead of guessing.
        raise ValueError(
            f"generate_query_stream.rngseed must be a quoted string "
            f"(got {type(seed).__name__} {seed!r}; unquoted seeds lose "
            f"leading zeros, and 0-prefixed seeds whose digits are all "
            f"0-7 parse as octal) or the sentinel 'bench'")
    return seed


def get_power_time(power_report_file: str) -> str:
    with open(power_report_file) as f:
        for line in f:
            if "Power Test Time" in line:
                return line.split(",")[2].strip()
    raise RuntimeError(f"Power Test Time not found in {power_report_file}")


def get_start_end_time(report_file: str):
    start = end = None
    with open(report_file) as f:
        for line in f:
            if "Power Start Time" in line:
                start = line.split(",")[2].strip()
            if "Power End Time" in line:
                end = line.split(",")[2].strip()
    if start is None or end is None:
        raise RuntimeError(f"start/end time not found in {report_file}")
    return start, end


def get_stream_range(num_streams: int, first_or_second: int):
    if first_or_second == 1:
        return list(range(1, num_streams // 2 + 1))
    return list(range(num_streams // 2 + 1, num_streams))


def get_throughput_time(report_base: str, num_streams: int,
                        first_or_second: int) -> float:
    starts, ends = [], []
    for i in get_stream_range(num_streams, first_or_second):
        s, e = get_start_end_time(f"{report_base}_{i}.csv")
        starts.append(float(s))
        ends.append(float(e))
    return round_up_to_nearest_10_percent(max(ends) - min(starts))


def get_refresh_time(report_file: str) -> float:
    with open(report_file) as f:
        for line in f:
            if "Data Maintenance Time" in line:
                return float(line.split(",")[2].strip())
    raise RuntimeError(f"Data Maintenance Time not found in {report_file}")


def get_maintenance_time(report_base: str, num_streams: int,
                         first_or_second: int) -> float:
    tdm = 0.0
    for i in get_stream_range(num_streams, first_or_second):
        tdm += get_refresh_time(f"{report_base}_{i}.csv")
    return round_up_to_nearest_10_percent(tdm)


def get_perf_metric(scale_factor, num_streams_in_throughput, queries_per_stream,
                    Tload, Tpower, Ttt1, Ttt2, Tdm1, Tdm2) -> int:
    """Composite metric, times in decimal hours (nds_bench.py:334-357).
    Each component is clamped to the 0.1s rounding floor so a phase that
    measures 0 elapsed at tiny scale factors cannot zero the product
    (unreachable at spec-scale; the reference rounds to 0.1s upstream)."""
    Q = num_streams_in_throughput * queries_per_stream
    Tpt = max(Tpower * num_streams_in_throughput, 0.1) / 3600
    Ttt = max(Ttt1 + Ttt2, 0.1) / 3600
    Tdm = max(Tdm1 + Tdm2, 0.1) / 3600
    Tld = max(0.01 * num_streams_in_throughput * Tload, 0.1) / 3600
    return int(float(scale_factor) * Q / (Tpt * Ttt * Tdm * Tld) ** (1 / 4))


def write_metrics_report(path: str, metrics: dict) -> None:
    text = "".join(f"{k},{v}\n" for k, v in metrics.items())
    atomic.atomic_write_text(path, text)


def run(cmd, **kw):
    print("====", " ".join(str(c) for c in cmd))
    faults.check("phase.subprocess", key=str(cmd[0]) if cmd else None)
    subprocess.run([str(c) for c in cmd], check=True, **kw)


def run_full_bench(yaml_params: dict, resume: bool = False) -> None:
    d = yaml_params["data_gen"]
    l = yaml_params["load_test"]
    g = yaml_params["generate_query_stream"]
    p = yaml_params["power_test"]
    t = yaml_params["throughput_test"]
    m = yaml_params["maintenance_test"]
    mtr = yaml_params["metrics"]
    sf = str(d["scale_factor"])
    num_streams = int(g["num_streams"])
    sq = max(len(get_stream_range(num_streams, 1)), 1)
    phase_walls: dict = {}
    obs_cfg = yaml_params.get("observability") or {}
    ledger_path = obs_cfg.get("ledger")
    if ledger_path:
        ledger_path = os.path.abspath(ledger_path)

    # crash-safe resume: the RUN_STATE.json journal records each phase
    # completed under this config fingerprint; --resume auto-skips them
    # (replacing hand-edited per-phase skip: flags after a crash)
    state = runstate.RunState.for_bench(yaml_params)
    if resume:
        done = state.completed_phases()
        if done:
            print(f"[resume] {state.path}: skipping completed phases "
                  f"{sorted(done)} (fingerprint "
                  f"{state.fingerprint[:12]})")
            obs.inc("harness.resume.phases_skipped", len(done))
    else:
        state.reset()
        done = set()

    def phase_done(name: str) -> bool:
        if name in done:
            phase_walls[name] = 0.0
            print(f"[resume] phase {name} already completed — skipping")
            return True
        return False

    # seed policy: a pinned `rngseed:` breaks spec 4.3.1's unconditional
    # chaining (reference nds_bench.py:413-414 always chains from the
    # load end timestamp).  Publish which policy this run used so
    # report.py / the artifacts can carry the non-compliance flag.
    seed_pinned = g.get("rngseed") is not None
    os.environ["NDSTPU_SEED_POLICY"] = \
        "pinned" if seed_pinned else "chained"

    # 1. data generation (+ per-stream refresh sets)
    if not d.get("skip") and not phase_done("data_gen"):
        with _phase("data_gen", phase_walls, d.get("budget_s")):
            run(PY + ["ndstpu.datagen.driver", "local", sf,
                      str(d["parallel"]), d["data_path"],
                      "--overwrite_output"])
            for i in range(1, num_streams):
                run(PY + ["ndstpu.datagen.driver", "local", sf,
                          str(d["parallel"]), d["data_path"] + f"_{i}",
                          "--overwrite_output", "--update", str(i)])
        state.mark("data_gen", artifacts=[d["data_path"]])

    # 2. load test
    if not l.get("skip") and not phase_done("load_test"):
        with _phase("load_test", phase_walls, l.get("budget_s")):
            cmd = PY + ["ndstpu.io.transcode",
                        "--input_prefix", d["data_path"],
                        "--output_prefix", l["warehouse_path"],
                        "--report_file", l["report_file"],
                        "--output_format",
                        l.get("warehouse_format", "parquet")]
            if resume:
                # per-table _SUCCESS markers: finished tables skip
                cmd += ["--resume"]
            run(cmd)
        state.mark("load_test", artifacts=[l["warehouse_path"],
                                           l["report_file"]])
    load_elapse = get_load_time(l["report_file"])

    # 3. query streams (RNGSEED = load end timestamp, spec 4.3.1, or a
    #    pinned `rngseed:` override — see resolve_stream_rngseed)
    if not g.get("skip") and not phase_done("generate_query_stream"):
        with _phase("generate_query_stream", phase_walls,
                    g.get("budget_s")):
            rngseed = resolve_stream_rngseed(g, l["report_file"])
            cmd = PY + ["ndstpu.queries.streamgen",
                        "--output_dir", g["stream_output_path"],
                        "--rngseed", rngseed,
                        "--streams", str(num_streams)]
            if g.get("template_dir"):
                cmd += ["--template_dir", g["template_dir"]]
            run(cmd)
        state.mark("generate_query_stream",
                   artifacts=[g["stream_output_path"]])
    try:
        run_seed = resolve_stream_rngseed(g, l["report_file"])
    except Exception:
        run_seed = "unknown"

    # 4. power test
    if not p.get("skip") and not phase_done("power_test"):
        with _phase("power_test", phase_walls, p.get("budget_s")):
            if p.get("json_summary_folder") and not resume:
                import shutil
                shutil.rmtree(p["json_summary_folder"], ignore_errors=True)
            cmd = PY + ["ndstpu.harness.power",
                        os.path.join(g["stream_output_path"],
                                     "query_0.sql"),
                        l["warehouse_path"], p["report_file"],
                        "--engine", p.get("engine", "cpu"),
                        "--scale_factor", sf,
                        "--run_seed", run_seed]
            if p.get("budget_s"):
                cmd += ["--budget_s", str(p["budget_s"])]
            if ledger_path:
                cmd += ["--ledger", ledger_path]
            if p.get("json_summary_folder"):
                cmd += ["--json_summary_folder", p["json_summary_folder"]]
            if p.get("output_prefix"):
                cmd += ["--output_prefix", p["output_prefix"]]
            if p.get("compile_records"):
                # persisted size-plan records (+ the persistent XLA
                # cache engine/device.py resolves): accel engines skip
                # per-query discovery.  Absolutized so subprocess cwd
                # can't silently miss it.
                rec = os.path.abspath(p["compile_records"])
                p["compile_records"] = rec
                if not os.path.exists(rec):
                    print(f"WARNING: compile_records {rec} does not "
                          f"exist yet — accel power runs will pay full "
                          f"discovery")
                cmd += ["--compile_records", rec]
            if resume:
                # mid-phase kill recovery: the power runner replays its
                # per-query progress journal and skips finished queries
                cmd += ["--resume"]
            run(cmd)
        state.mark("power_test", artifacts=[p["report_file"]])
    power_elapse = float(get_power_time(p["report_file"])) / 1000

    # 5./6. throughput + maintenance, twice
    ttt, tdm = {}, {}
    for fs in (1, 2):
        if not t.get("skip") and \
                not phase_done(f"throughput_test_{fs}"):
            with _phase(f"throughput_test_{fs}", phase_walls,
                        t.get("budget_s")):
                ids = ",".join(str(x) for x in
                               get_stream_range(num_streams, fs))
                tcmd = PY + ["ndstpu.harness.throughput", ids]
                if t.get("concurrent"):
                    # device admission: at most N streams on the chip at
                    # a time (the concurrentGpuTasks analog)
                    tcmd += ["--concurrent", str(t["concurrent"])]
                if t.get("budget_s"):
                    tcmd += ["--budget_s", str(t["budget_s"])]
                if t.get("mode"):
                    # inproc = shared-engine fast path (one warehouse
                    # load, compile-once across streams); process =
                    # spec-faithful N-driver fan-out (default)
                    tcmd += ["--mode", str(t["mode"])]
                # overlap evidence artifact: proves the streams really
                # ran concurrently under the admission cap
                overlap = t.get("overlap_report") or \
                    t["report_base"] + f"_overlap_{fs}.json"
                tcmd += ["--overlap_report",
                         overlap.replace("{}", str(fs))]
                pcmd = PY + ["ndstpu.harness.power",
                             os.path.join(g["stream_output_path"],
                                          "query_{}.sql"),
                             l["warehouse_path"],
                             t["report_base"] + "_{}.csv",
                             "--engine", p.get("engine", "cpu"),
                             "--scale_factor", sf,
                             "--run_seed", run_seed]
                if ledger_path:
                    pcmd += ["--ledger", ledger_path]
                if p.get("compile_records"):
                    pcmd += ["--compile_records", p["compile_records"]]
                run(tcmd + ["--"] + pcmd)
            state.mark(f"throughput_test_{fs}",
                       artifacts=[t["report_base"]])
        ttt[fs] = get_throughput_time(t["report_base"], num_streams, fs)
        if not m.get("skip") and \
                not phase_done(f"maintenance_test_{fs}"):
            with _phase(f"maintenance_test_{fs}", phase_walls,
                        m.get("budget_s")):
                for i in get_stream_range(num_streams, fs):
                    run(PY + ["ndstpu.harness.maintenance",
                              l["warehouse_path"],
                              d["data_path"] + f"_{i}",
                              m["report_base"] + f"_{i}.csv"])
            state.mark(f"maintenance_test_{fs}",
                       artifacts=[m["report_base"]])
        tdm[fs] = get_maintenance_time(m["report_base"], num_streams, fs)

    qps = len(__import__("ndstpu.queries.streamgen",
                         fromlist=["list_templates"])
              .list_templates(g.get("template_dir")))
    metric = get_perf_metric(sf, sq, qps, float(load_elapse), power_elapse,
                             ttt[1], ttt[2], tdm[1], tdm[2])
    metrics = {
        "scale_factor": sf,
        "num_streams": num_streams,
        "queries_per_stream": qps,
        "Tload(s)": load_elapse,
        "Tpower(s)": power_elapse,
        "Ttt1(s)": ttt[1], "Ttt2(s)": ttt[2],
        "Tdm1(s)": tdm[1], "Tdm2(s)": tdm[2],
        "metric": metric,
    }
    print(metrics)
    write_metrics_report(mtr["metrics_report"], metrics)
    write_hw_metrics(yaml_params, metrics, phase_walls)


def write_hw_metrics(yaml_params: dict, metrics: dict,
                     phase_walls: dict) -> str:
    """Phase-level hardware-run artifact (docs/HW_METRICS_*.json):
    driver phase walls + the composite metric + the power runner's
    per-query attribution sidecar (written by ndstpu.harness.power next
    to its time log when tracing is on).  Path from ``metrics:
    hw_metrics`` in the config; defaults to ``hw_metrics.json`` next to
    the metrics report."""
    p = yaml_params["power_test"]
    mtr = yaml_params["metrics"]
    power_sidecar = p["report_file"] + ".metrics.json"
    power_metrics = None
    if os.path.exists(power_sidecar):
        try:
            with open(power_sidecar) as f:
                power_metrics = json.load(f)
        except Exception as e:  # artifact is best-effort, never fatal
            print(f"WARNING: power metrics sidecar unreadable: {e}")
    g = yaml_params["generate_query_stream"]
    seed_pinned = g.get("rngseed") is not None
    phase_budgets = {
        ph: (yaml_params.get(ph) or {}).get("budget_s")
        for ph in ("data_gen", "load_test", "generate_query_stream",
                   "power_test", "throughput_test", "maintenance_test")
        if (yaml_params.get(ph) or {}).get("budget_s")}
    hw = {
        "format": "ndstpu-hw-metrics-v1",
        "scale_factor": yaml_params["data_gen"]["scale_factor"],
        "engine": p.get("engine", "cpu"),
        "num_streams": yaml_params["generate_query_stream"]["num_streams"],
        "phases": phase_walls,
        "phase_budgets": phase_budgets,
        "seed_policy": "pinned" if seed_pinned else "chained",
        # spec 4.3.1 chains RNGSEED from the load end timestamp
        # unconditionally (reference nds_bench.py:413-414); a pinned
        # seed is a deliberate cache-warm trade and the artifact says so
        "spec_compliant_seed": not seed_pinned,
        "summary": metrics,
        "power": power_metrics,
        "counters": obs.counters_snapshot(),
        "gauges": obs.gauges_snapshot(),
    }
    hw_path = mtr.get("hw_metrics") or os.path.join(
        os.path.dirname(mtr["metrics_report"]) or ".", "hw_metrics.json")
    atomic.atomic_write_json(hw_path, hw)
    print(f"HW metrics artifact: {hw_path}")
    return hw_path


if __name__ == "__main__":
    parser = argparse.ArgumentParser(description="NDS full benchmark")
    parser.add_argument("yaml_config", help="yaml config file (bench.yml)")
    parser.add_argument("--resume", action="store_true",
                        help="crash-safe resume: replay the "
                             "RUN_STATE.json journal (next to the "
                             "metrics report) and skip phases already "
                             "completed under the same config "
                             "fingerprint; the power and load phases "
                             "additionally resume mid-phase via their "
                             "own journals/markers")
    cli = parser.parse_args()
    with open(cli.yaml_config) as f:
        params = yaml.safe_load(f)
    run_full_bench(params, resume=cli.resume)
