#!/usr/bin/env python3
"""Prove that the power/serve path runs on the attached TPU.

Drives the system's main path once, through the entry points a user
calls, each as its own process (this parent never imports jax — a chip
belongs to one process at a time, so every stage that needs the chip is
ONE child, and the next starts only after the last has exited):

    device      python -m ndstpu.engine.device          what JAX sees
    segsum      the Pallas limb kernel alone, interpret=False, at
                power-run shapes, against exact numpy int64 sums
    datagen     python -m ndstpu.datagen.driver local <sf> 4 ...
                (native generator built here from the committed source)
    transcode   python -m ndstpu.io.transcode ...
    streamgen   python -m ndstpu.queries.streamgen --streams 1 ...
    power_cold  python -m ndstpu.harness.power ... --engine tpu
    power_warm  the same command again in a new process: compile
                records + persistent XLA cache, nothing recompiled
    power_cpu   the same sub-queries with --engine cpu
    validate    python -m ndstpu.harness.validate out_tpu out_cpu ...
                for the cold and for the warm outputs
    serve       ndstpu-serve server --engine tpu --queue_depth auto,
                three client requests byte-identical to power_warm's
                outputs, health/stats, a second process refused the
                chip, SIGTERM -> exit 0 + clean-shutdown journal
    spmd        (more than one chip) three of the parts with --engine
                tpu-spmd under NDSTPU_SPMD_STRICT=1, validated

Pass/fail is decided from the artifacts (per-query JSON summaries,
metrics sidecars, validation status, journal), not from exit codes
alone.  The default mode DEMANDS a TPU: if the children's default
backend is not ``tpu`` the run fails at stage ``device``, before any
query, whatever the environment says, and prints no result.

The last line of stdout is one JSON object with exactly these keys,
the device as JAX reports it:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

The line before it is the full report (also ``<out>/result.json``): each
stage's outcome and wall time, compile seconds as set-up time.
``"ok": true`` appears only for a complete run on a TPU.
``--rehearse-cpu`` (tiny ``--sf``, platform pinned to cpu, Pallas
interpreted) and ``--stages`` runs say ``"ok": false`` and report under
``"rehearsal"`` / ``"partial"``: they debug this script, they are not a
pass.

Large data (raw files, warehouse, query outputs) lives in a work
directory outside the checkout and is removed at the end; only small
reports go to ``--out`` (default ``chiprun_out/chip_smoke``).
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.abspath(__file__))
PROPS = os.path.join(REPO, "ndstpu", "harness", "properties")

# about ten parts, cheap to compile and wide in operators: small-domain
# decimal group-bys where the Pallas limb kernel engages (3/52/55/42/7),
# count over joins (96), window over partition (12), rollup + rank (86),
# fact-fact-fact join (25), scalar subqueries (9)
SUB_QUERIES = ["query3", "query52", "query55", "query42", "query7",
               "query96", "query12", "query86", "query25", "query9"]
SERVE_QUERIES = ["query3", "query96", "query12"]
# a distributed program takes minutes to compile on four chips (50-200 s
# a part, my chip run, PR 21), so the spmd stage runs three parts, not
# ten: the count over joins, a decimal group-by and the fact-fact-fact
# join, whose shuffle must count a collective.  (PR 21, four chips, SF1:
# query25 leaves the distributed path — "build key runs too long" — so
# this stage fails there until parallel/dplan.py distributes it.)
SPMD_QUERIES = ["query96", "query7", "query25"]
STAGES = ["device", "segsum", "datagen", "transcode", "streamgen",
          "power_cold", "power_warm", "power_cpu", "validate", "serve",
          "spmd"]
# the whole run, compilation included, has 1200 s; stop before that
DEADLINE_S = 1150.0


class StageFailed(Exception):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise StageFailed(msg)


class Smoke:
    def __init__(self, args):
        self.args = args
        self.t0 = time.time()
        self.rehearsal = args.rehearse_cpu
        self.want_platform = "cpu" if self.rehearsal else "tpu"
        self.out = os.path.abspath(args.out)
        self.work = args.work or tempfile.mkdtemp(
            prefix="ndstpu_chip_smoke_")
        self.keep_work = bool(args.work)
        self.device: dict = {}
        self.stages: dict = {}
        self.children: list = []
        self.env = dict(os.environ, PYTHONPATH=os.pathsep.join(
            p for p in (REPO, os.environ.get("PYTHONPATH")) if p))
        # power runs append to a ledger by default; keep the smoke's
        # rows out of the developer's
        self.env["NDSTPU_LEDGER"] = "none"
        if self.rehearsal:
            # the explicit pin is what lets the accelerator engines run
            # on the CPU at all (ndstpu/engine/device.py); four virtual
            # devices rehearse the spmd stage; the kernels run in the
            # Pallas interpreter inside whole-query programs
            self.env["JAX_PLATFORMS"] = "cpu"
            self.env["XLA_FLAGS"] = (
                self.env.get("XLA_FLAGS", "") +
                " --xla_force_host_platform_device_count=4").strip()
            # a cpu-pinned process sets no cache directory in code;
            # give the rehearsal one so the warm checks have a cache
            self.env.setdefault("JAX_COMPILATION_CACHE_DIR",
                                os.path.join(self.work, "xla_cache"))
        os.makedirs(self.out, exist_ok=True)
        os.makedirs(self.work, exist_ok=True)

    # -- process plumbing ----------------------------------------------------

    def remaining(self) -> float:
        return DEADLINE_S - (time.time() - self.t0)

    def run(self, tag: str, cmd: list, env: dict = None,
            ok_codes=(0,)) -> subprocess.CompletedProcess:
        """One child to the end; its output goes to <out>/<tag>.log."""
        log_path = os.path.join(self.out, f"{tag}.log")
        left = self.remaining()
        check(left > 5, f"{tag}: no time left before the deadline")
        print(f"+ [{tag}] {_shown(cmd)}", flush=True)
        with open(log_path, "w") as log:
            p = subprocess.Popen([str(c) for c in cmd], stdout=log,
                                 stderr=subprocess.STDOUT,
                                 env=env or self.env, cwd=REPO)
            self.children.append(p)
            try:
                p.wait(timeout=left)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()
                raise StageFailed(
                    f"{tag}: killed at the {DEADLINE_S:.0f}s deadline")
            finally:
                self.children.remove(p)
        if p.returncode not in ok_codes:
            raise StageFailed(
                f"{tag}: exit code {p.returncode}; tail of {log_path}:\n"
                + _tail(log_path))
        return p

    def py(self, *mod_and_args) -> list:
        return [sys.executable, "-m", *mod_and_args]

    def path(self, *parts) -> str:
        return os.path.join(self.work, *parts)

    def kill_children(self) -> None:
        for p in list(self.children):
            if p.poll() is None:
                p.kill()
                p.wait()

    # -- stages --------------------------------------------------------------

    def stage_device(self) -> dict:
        self.run("device", self.py("ndstpu.engine.device"))
        doc = json.loads(_last_line(os.path.join(self.out, "device.log")))
        self.device = {k: doc[k] for k in
                       ("platform", "device_kind", "count")}
        check(doc["default_backend"] == self.want_platform
              and doc["platform"] == self.want_platform,
              f"the children's default backend is "
              f"{doc['default_backend']!r}, not {self.want_platform!r} "
              f"(JAX_PLATFORMS={doc['JAX_PLATFORMS']!r}): "
              + ("no TPU is attached to this machine, or another "
                 "process holds it" if not self.rehearsal else
                 "the rehearsal pins the platform to cpu"))
        return doc

    def stage_segsum(self) -> dict:
        """The default group-by kernel, alone: a Mosaic rejection is
        reported as that and not as a slow or demoted query."""
        self.run("segsum", [sys.executable, __file__, "--child-segsum",
                            "1" if self.rehearsal else "0"])
        doc = json.loads(_last_line(os.path.join(self.out, "segsum.log")))
        check(doc["platform"] == self.want_platform, f"segsum ran on {doc}")
        check(doc["interpret"] == self.rehearsal,
              f"segsum ran with interpret={doc['interpret']}")
        for shape, r in doc["shapes"].items():
            check(r.get("exact"), f"segsum {shape}: {r}")
        return doc

    def stage_datagen(self) -> dict:
        self.run("datagen", self.py(
            "ndstpu.datagen.driver", "local", f"{self.args.sf:g}", "4",
            self.path("raw"), "--seed", str(self.args.seed)))
        stamp = os.path.join(REPO, "ndstpu", "datagen", "_build",
                             "ndsgen.stamp")
        check(os.path.exists(stamp),
              "the native generator was not built on this machine")
        return {"raw_bytes": _dir_bytes(self.path("raw"))}

    def stage_transcode(self) -> dict:
        self.run("transcode", self.py(
            "ndstpu.io.transcode", "--input_prefix", self.path("raw"),
            "--output_prefix", self.path("wh"),
            "--report_file", os.path.join(self.out, "load.txt")))
        shutil.rmtree(self.path("raw"), ignore_errors=True)
        return {"warehouse_bytes": _dir_bytes(self.path("wh"))}

    def stage_streamgen(self) -> dict:
        self.run("streamgen", self.py(
            "ndstpu.queries.streamgen", "--streams", "1", "--rngseed",
            str(self.args.seed), "--output_dir", self.path("streams")))
        check(os.path.exists(self.stream()), "no query_0.sql rendered")
        return {}

    def stream(self) -> str:
        return self.path("streams", "query_0.sql")

    def power_cmd(self, tag: str, engine: str, props: str = None,
                  records: bool = False, queries=SUB_QUERIES) -> list:
        shutil.rmtree(os.path.join(self.out, f"js_{tag}"),
                      ignore_errors=True)
        cmd = self.py(
            "ndstpu.harness.power", self.stream(), self.path("wh"),
            os.path.join(self.out, f"time_{tag}.csv"),
            "--engine", engine, "--sub_queries", ",".join(queries),
            "--scale_factor", f"{self.args.sf:g}",
            "--run_seed", str(self.args.seed),
            "--output_prefix", self.path(f"out_{tag}"),
            "--json_summary_folder", os.path.join(self.out, f"js_{tag}"))
        if props:
            cmd += ["--property_file", self.props(props)]
        if records:
            cmd += ["--compile_records", self.path("plans.pkl")]
        return cmd

    def props(self, name: str) -> str:
        """The shipped properties file; the rehearsal runs on a copy
        cut to its size: tiny CPU programs compile in under the 1 s
        cache threshold and tiny facts sit under the 65536-row shard
        threshold, so neither the cache checks nor the distributed code
        would be walked."""
        path = os.path.join(PROPS, name)
        if not self.rehearsal:
            return path
        with open(path) as f:
            text = f.read()
        text = text.replace("min_compile_time_secs=1.0",
                            "min_compile_time_secs=0.0")
        text = text.replace("spmd.threshold_rows=65536",
                            "spmd.threshold_rows=500")
        with open(self.path(name), "w") as f:
            f.write(text)
        return self.path(name)

    def power_reports(self, tag: str) -> tuple:
        """(per-query JSON summaries by name, metrics sidecar)."""
        sums = {}
        for path in glob.glob(os.path.join(self.out, f"js_{tag}",
                                           "*.json")):
            with open(path) as f:
                s = json.load(f)
            sums[s["query"]] = s
        with open(os.path.join(self.out,
                               f"time_{tag}.csv.metrics.json")) as f:
            sidecar = json.load(f)
        return sums, sidecar

    def check_accel_run(self, tag: str, warm: bool,
                        n_devices: int = None,
                        queries=SUB_QUERIES) -> tuple:
        """Every part Completed on the expected device, nothing fell
        back, nothing was demoted; a warm process recompiled nothing.
        Returns (stage report, summaries by query, sidecar)."""
        sums, sidecar = self.power_reports(tag)
        check(set(sums) == set(queries),
              f"{tag}: summaries for {sorted(sums)}, wanted "
              f"{sorted(queries)}")
        dev = sidecar.get("device") or {}
        check(dev.get("platform") == self.want_platform,
              f"{tag}: sidecar names device {dev}")
        if n_devices is not None:
            check(dev.get("count") == n_devices,
                  f"{tag}: ran over {dev.get('count')} devices, "
                  f"not {n_devices}")
        attrs = _span_attrs(sidecar)
        compile_s = 0.0
        for name in queries:
            s = sums[name]
            check(s["queryStatus"] == ["Completed"],
                  f"{tag}/{name}: queryStatus {s['queryStatus']}, "
                  f"taskFailures {s.get('taskFailures')}, "
                  f"exceptions {s.get('exceptions')}")
            check((s["env"].get("device") or {}).get("platform")
                  == self.want_platform,
                  f"{tag}/{name}: summary names device "
                  f"{s['env'].get('device')}")
            m = s["metrics"][0]
            compile_s += m["compile_s"]
            bad = [k for k in m["counters"]
                   if k.startswith("engine.fallback.")
                   or k.startswith("engine.spmd.fallback.")
                   or k in ("engine.spmd.unsupported_fallbacks",
                            "engine.spmd.error_fallbacks")]
            check(not bad, f"{tag}/{name}: fallback counters {bad}")
            hidden = [k for k in ("fallback_codes", "spmd_fallback",
                                  "chunk_fallthrough")
                      if k in attrs.get(name, {})]
            check(not hidden, f"{tag}/{name}: span attributes "
                  f"{ {k: attrs[name][k] for k in hidden} }")
            if warm:
                miss = m["counters"].get("engine.cache.compiled.miss", 0)
                check(miss == 0 and m["counters"].get(
                    "engine.cache.compiled.hit", 0) >= 1,
                    f"{tag}/{name}: compiled-cache miss={miss} in the "
                    f"second process: {m['counters']}")
                files = m.get("xla_cache_files") or {}
                check(files.get("before") == files.get("after")
                      and files.get("after", 0) > 0,
                      f"{tag}/{name}: persistent cache files {files} "
                      f"(wanted before == after, non-zero)")
        report = {"compile_s_setup": round(compile_s, 3),
                  "execute_s": round(sum(
                      sums[n]["metrics"][0]["execute_s"]
                      for n in queries), 3)}
        return report, sums, sidecar

    def stage_power_cold(self) -> dict:
        self.run("power_cold", self.power_cmd(
            "tpu", "tpu", "tpu-default.properties", records=True))
        r, sums, _ = self.check_accel_run("tpu", warm=False)
        check(os.path.exists(self.path("plans.pkl")),
              "no compile records were saved")
        # the kernel engages where a group-by's key domain is small
        # and host-known (which parts, depends on the data): at least
        # one whole-query program must have been traced with it
        kernel = {n: sums[n]["metrics"][0]["counters"].get(
            "engine.pallas.segsum_calls", 0) for n in SUB_QUERIES}
        r["pallas_segsum_calls"] = {n: c for n, c in kernel.items() if c}
        check(r["pallas_segsum_calls"], "no compiled program contains "
              "the Pallas segment-sum kernel")
        # the second process runs the SAME command and overwrites all
        # of this: set the cold run's outputs and reports aside.  (A
        # cold answer comes from eager discovery, a warm one from the
        # compiled replay: float averages may differ in the last bit,
        # so both are validated, and neither is compared bytewise.)
        for name in ("time_tpu.csv", "time_tpu.csv.metrics.json"):
            os.replace(os.path.join(self.out, name), os.path.join(
                self.out, name.replace("time_tpu", "time_tpu_cold")))
        for src, dst in ((os.path.join(self.out, "js_tpu"),
                          os.path.join(self.out, "js_tpu_cold")),
                         (self.path("out_tpu"),
                          self.path("out_tpu_cold"))):
            shutil.rmtree(dst, ignore_errors=True)
            os.replace(src, dst)
        return r

    def stage_power_warm(self) -> dict:
        self.run("power_warm", self.power_cmd(
            "tpu", "tpu", "tpu-default.properties", records=True))
        return self.check_accel_run("tpu", warm=True)[0]

    def stage_power_cpu(self) -> dict:
        self.run("power_cpu", self.power_cmd("cpu", "cpu"))
        sums, sidecar = self.power_reports("cpu")
        for name in SUB_QUERIES:
            check(sums[name]["queryStatus"] == ["Completed"],
                  f"cpu/{name}: {sums[name]['queryStatus']} "
                  f"{sums[name].get('exceptions')}")
        return {}

    def validate(self, tag: str, queries=SUB_QUERIES) -> dict:
        self.run(f"validate_{tag}", self.py(
            "ndstpu.harness.validate", self.path(f"out_{tag}"),
            self.path("out_cpu"), self.stream(), "--ignore_ordering",
            "--sub_queries", ",".join(queries), "--json_summary_folder",
            os.path.join(self.out, f"js_{tag}")))
        sums, _ = self.power_reports(tag)
        status = {n: sums[n].get("queryValidationStatus")
                  for n in queries}
        check(all(v == ["Pass"] for v in status.values()),
              f"validation of out_{tag} against the cpu engine: {status}")
        return {"validated": len(status)}

    def stage_validate(self) -> dict:
        cold = self.validate("tpu_cold")
        warm = self.validate("tpu")
        return {"validated": cold["validated"] + warm["validated"]}

    def stage_serve(self) -> dict:
        from ndstpu.harness.power import gen_sql_from_stream
        queries = gen_sql_from_stream(self.stream())
        sock = self.path("serve.sock")
        state = os.path.join(self.out, "serve_state")
        shutil.rmtree(state, ignore_errors=True)
        server_log = open(os.path.join(self.out, "serve_server.log"), "w")
        cmd = self.py(
            "ndstpu.harness.serve", "server", "--socket", sock,
            "--input_prefix", self.path("wh"), "--engine", "tpu",
            "--queue_depth", "auto",
            "--output_prefix", self.path("out_serve"),
            "--output_format", "parquet", "--state_dir", state,
            # the power run's records: the daemon boots warm
            "--compile_records", self.path("plans.pkl"),
            "--ledger", "none", "--scale_factor", f"{self.args.sf:g}")
        print(f"+ [serve_server] {_shown(cmd)}", flush=True)
        server = subprocess.Popen(cmd, stdout=server_log,
                                  stderr=subprocess.STDOUT, env=self.env,
                                  cwd=REPO)
        self.children.append(server)
        try:
            client = self.py("ndstpu.harness.serve", "client",
                             "--socket", sock)
            for i, name in enumerate(SERVE_QUERIES):
                check(server.poll() is None,
                      f"the server exited (code {server.returncode}):\n"
                      + _tail(server_log.name))
                self.run(f"serve_client_{name}", client + [
                    "--sql", queries[name], "--name", name,
                    "--wait_ready_s", f"{max(self.remaining() - 60, 30):g}"
                    if i == 0 else "30"])
            self.run("serve_health", client + ["--op", "health"])
            self.run("serve_stats", client + ["--op", "stats"])
            health = _json_doc(os.path.join(
                self.out, "serve_health.log"))["health"]
            stats = _json_doc(os.path.join(self.out, "serve_stats.log"))
            second = self.second_process()
        finally:
            if server.poll() is None:
                server.send_signal(signal.SIGTERM)
            try:
                rc = server.wait(timeout=max(self.remaining(), 30))
            except subprocess.TimeoutExpired:
                server.kill()
                server.wait()
                rc = "killed: no exit after SIGTERM"
            self.children.remove(server)
            server_log.close()
        check(rc == 0, f"server exit code after SIGTERM: {rc}\n"
              + _tail(server_log.name))
        with open(os.path.join(state, "serve_journal.jsonl")) as f:
            events = [json.loads(ln).get("event") for ln in f if ln.strip()]
        check(events and events[-1] == "clean-shutdown",
              f"journal ends with {events[-3:]}, not clean-shutdown")
        check((health.get("device") or {}).get("platform")
              == self.want_platform,
              f"server health names device {health.get('device')}")
        model = health.get("admission_model") or {}
        want_src = "default" if self.rehearsal else "memory_stats"
        check(model.get("budget_source") == want_src,
              f"admission budget came from {model.get('budget_source')!r}"
              f", not {want_src!r}: {model}")
        check(health["ok"] >= len(SERVE_QUERIES) and not health["errors"],
              f"server health: ok={health['ok']} errors="
              f"{health['errors']}")
        fell = [k for k in stats["counters"]
                if k.startswith("engine.fallback.")]
        check(not fell, f"server fallback counters: {fell}")
        got = _parquet_tree(self.path("out_serve"))
        want = _parquet_tree(self.path("out_tpu"))
        for name in SERVE_QUERIES:
            rel = os.path.join(name, "part-0.parquet")
            check(rel in got, f"the server wrote no output for {name}")
            check(got[rel] == want[rel], f"server output for {name} is "
                  f"not byte-identical to the power run's")
        return {"requests": health["ok"],
                "admission_model": model,
                "compiled_miss": stats["counters"].get(
                    "engine.cache.compiled.miss", 0),
                "second_process": second}

    def second_process(self) -> dict:
        """While the daemon holds the chip: what a second process that
        wants it gets.  It must not end up quietly on the CPU."""
        if self.rehearsal:
            return {"not_run": "cpu rehearsal: no chip to hold"}
        out = {}
        for label, plat in (("as_exported", None), ("no_platform_list", "")):
            env = dict(self.env)
            if plat is not None:
                env["JAX_PLATFORMS"] = plat
            p = self.run(f"second_{label}", self.py(
                "ndstpu.harness.power", self.stream(), self.path("wh"),
                os.path.join(self.out, f"time_second_{label}.csv"),
                "--engine", "tpu", "--sub_queries", "query96"),
                env=env, ok_codes=range(0, 256))
            log = os.path.join(self.out, f"second_{label}.log")
            text = open(log).read()
            check(p.returncode != 0 and "Run query96" not in text,
                  f"a second process ran a query while the daemon held "
                  f"the chip (JAX_PLATFORMS={plat!r}, exit "
                  f"{p.returncode}):\n" + _tail(log))
            out[label] = {"exit": p.returncode,
                          "refused_by": "NoAcceleratorError"
                          if "NoAcceleratorError" in text else "jax"}
        return out

    def stage_spmd(self) -> dict:
        n = self.device.get("count", 1)
        if n < 2:
            return {"not_run": f"{n} device visible"}
        env = dict(self.env, NDSTPU_SPMD_STRICT="1")
        self.run("power_spmd", self.power_cmd(
            "spmd", "tpu-spmd", "tpu-spmd.properties",
            queries=SPMD_QUERIES), env=env)
        r, sums, sidecar = self.check_accel_run(
            "spmd", warm=False, n_devices=n, queries=SPMD_QUERIES)
        counters = sidecar.get("counters", {})
        check(counters.get("engine.spmd.traces", 0) > 0,
              f"no distributed program was traced: {counters}")
        q25 = sums["query25"]["metrics"][0]["counters"]
        check(q25.get("exchange.collective.calls", 0) > 0,
              f"query25 counted no collective: {q25}")
        placed = {q: a.get("spmd_fact_placement")
                  for q, a in _span_attrs(sidecar).items()
                  if a.get("spmd_fact_placement")}
        check(placed and all(v == f"{n}x{self.want_platform}"
                             for v in placed.values()),
              f"fact shards were placed on {placed}, wanted "
              f"{n}x{self.want_platform} everywhere")
        r.update(self.validate("spmd", SPMD_QUERIES))
        r["queries"] = SPMD_QUERIES
        r["fact_placement"] = placed
        r["collective_calls"] = counters.get(
            "exchange.collective.calls", 0)
        return r

    # -- driver --------------------------------------------------------------

    def main(self) -> int:
        wanted = self.args.stages.split(",") if self.args.stages \
            else STAGES
        unknown = [s for s in wanted if s not in STAGES]
        if unknown:
            print(f"unknown stages {unknown}; known: {STAGES}",
                  file=sys.stderr)
            return 2
        if "device" not in wanted:
            wanted = ["device"] + wanted   # never run blind
        failed = None
        try:
            for name in STAGES:
                if name not in wanted:
                    continue
                t = time.time()
                try:
                    detail = getattr(self, f"stage_{name}")()
                    ok = True
                except StageFailed as e:
                    detail, ok = {"error": str(e)}, False
                except Exception as e:  # noqa: BLE001 — a report this
                    # script could not read is a failed stage, with its
                    # traceback, not a crash without a result line
                    import traceback
                    traceback.print_exc()
                    detail = {"error": f"{type(e).__name__}: {e}"}
                    ok = False
                rec = {"ok": ok, "wall_s": round(time.time() - t, 2)}
                if isinstance(detail, dict) and "not_run" in detail:
                    rec = {"ok": True, "ran": False,
                           "why": detail["not_run"]}
                else:
                    rec.update(detail or {})
                self.stages[name] = rec
                print(f"= [{name}] {'ok' if ok else 'FAILED'} "
                      f"{rec.get('wall_s', 0)}s", flush=True)
                if not ok:
                    failed = name
                    print(rec["error"], file=sys.stderr, flush=True)
                    break
        finally:
            self.kill_children()
            if not self.keep_work:
                shutil.rmtree(self.work, ignore_errors=True)
        if "jax" in sys.modules:
            print("BUG: the parent imported jax", file=sys.stderr)
            return 1
        if failed == "device":
            # no accelerator (or the wrong platform): no result line
            return 1
        all_ok = failed is None
        result = {
            "ok": False,
            "device": self.device,
            "sf": self.args.sf, "seed": self.args.seed,
            "sub_queries": SUB_QUERIES,
            "elapsed_s": round(time.time() - self.t0, 1),
            "failed_stage": failed,
            "stages": self.stages,
        }
        if self.rehearsal:
            result["rehearsal"] = {"ok": all_ok, "note":
                                   "cpu rehearsal: not a chip pass"}
        elif wanted != STAGES:
            result["partial"] = {"ok": all_ok, "stages": wanted}
        else:
            result["ok"] = all_ok
        with open(os.path.join(self.out, "result.json"), "w") as f:
            json.dump(result, f, indent=1)
        print(json.dumps(result), flush=True)
        # the last line: these keys and no others
        print(json.dumps({"ok": result["ok"], "device": {
            "platform": self.device["platform"],
            "kind": self.device["device_kind"],
            "count": self.device["count"]}}), flush=True)
        return 0 if all_ok else 1


# -- helpers ------------------------------------------------------------------

def _shown(cmd: list) -> str:
    """A command for the log, with long arguments (SQL text) cut."""
    return " ".join(a if len(a) <= 160 else a[:60].strip() + " ...'"
                    for a in map(str, cmd))


def _span_attrs(sidecar: dict) -> dict:
    """query name -> the attributes its span collected."""
    return {q["query"]: q.get("attrs") or {} for q in sidecar["queries"]}


def _tail(path: str, n: int = 3000) -> str:
    try:
        with open(path, errors="replace") as f:
            return f.read()[-n:]
    except OSError as e:
        return f"<{e}>"


def _last_line(path: str) -> str:
    with open(path) as f:
        lines = [ln for ln in f.read().splitlines() if ln.strip()]
    check(lines, f"{path} is empty")
    return lines[-1]


def _json_doc(path: str) -> dict:
    """The JSON document a client call printed (it may be preceded by
    log lines)."""
    text = open(path).read()
    return json.loads(text[text.index("{"):])


def _dir_bytes(path: str) -> int:
    return sum(os.path.getsize(os.path.join(d, f))
               for d, _dirs, files in os.walk(path) for f in files)


def _parquet_tree(prefix: str) -> dict:
    """relpath -> bytes of every query output under prefix."""
    out = {}
    for path in glob.glob(os.path.join(prefix, "**", "part-0.parquet"),
                          recursive=True):
        with open(path, "rb") as f:
            out[os.path.relpath(path, prefix)] = f.read()
    return out


# -- the kernel stage's child (the only code here that imports jax) -----------

def child_segsum(interpret: bool) -> int:
    """``segsum.segment_sum_decimal`` against exact numpy int64 sums at
    power-run shapes: SF1 store_sales rows x a brand-sized and an
    item-sized segment domain (tiny shapes under the interpreter)."""
    import numpy as np

    import jax
    jax.config.update("jax_enable_x64", True)
    import jax.numpy as jnp

    from ndstpu.ops import segsum

    shapes = ((20_000, 100), (20_000, 700)) if interpret else \
        ((2_880_404, 1_000), (2_880_404, 18_000))
    rng = np.random.default_rng(7)
    doc = {"platform": jax.devices()[0].platform,
           "interpret": interpret, "shapes": {}}
    for n, segs in shapes:
        vals = rng.integers(-10 ** 9, 10 ** 9, n).astype(np.int64)
        gid = rng.integers(0, segs, n).astype(np.int32)
        mask = rng.random(n) < 0.8
        want = np.zeros(segs, np.int64)
        np.add.at(want, gid[mask], vals[mask])
        want_n = np.bincount(gid[mask], minlength=segs)
        t = time.time()
        try:
            sums, counts = segsum.segment_sum_decimal(
                jnp.asarray(vals), jnp.asarray(gid), jnp.asarray(mask),
                num_segments=segs, interpret=interpret)
            sums, counts = np.asarray(sums), np.asarray(counts)
            rec = {"exact": bool((sums == want).all()
                                 and (counts == want_n).all()),
                   "compile_and_run_s": round(time.time() - t, 3)}
        except Exception as e:  # noqa: BLE001 — the compiler's verdict
            rec = {"exact": False,
                   "error": f"{type(e).__name__}: {str(e)[-2000:]}"}
        doc["shapes"][f"{n}x{segs}"] = rec
    print(json.dumps(doc))
    return 0


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--sf", type=float, default=1.0,
                   help="scale factor (default 1: the smallest the "
                        "TPC-DS specification defines)")
    p.add_argument("--seed", type=int, default=20260926,
                   help="seeds the generated data and the query stream")
    p.add_argument("--out", default=os.path.join(
        os.getcwd(), "chiprun_out", "chip_smoke"),
        help="small reports go here")
    p.add_argument("--work", default=None,
                   help="work directory for the large data (kept when "
                        "given; default: a temp dir, removed at the end)")
    p.add_argument("--rehearse-cpu", action="store_true",
                   help="debug this script on the CPU at a tiny --sf; "
                        "its result is never a pass")
    p.add_argument("--stages", default=None,
                   help="comma-separated subset of: " + ",".join(STAGES)
                        + " (a partial run is never a pass)")
    p.add_argument("--child-segsum", default=None, help=argparse.SUPPRESS)
    return p


if __name__ == "__main__":
    _args = build_parser().parse_args()
    if not os.path.isdir(os.path.join(REPO, "ndstpu")):
        print(f"chip_smoke.py: no ndstpu package beside it in {REPO}; "
              f"run it from the root of a checkout", file=sys.stderr)
        sys.exit(2)
    if _args.child_segsum is not None:
        sys.path.insert(0, REPO)
        sys.exit(child_segsum(_args.child_segsum == "1"))
    sys.path.insert(0, REPO)
    _smoke = Smoke(_args)
    signal.signal(signal.SIGTERM, lambda *_: (_smoke.kill_children(),
                                              sys.exit(143)))
    sys.exit(_smoke.main())
