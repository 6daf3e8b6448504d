"""Regression sentinel CLI (ndstpu/obs/sentinel.py).

Classifies power-run sidecars (``<time_log>.metrics.json``) against the
run ledger's best-known-warm baselines and exits nonzero on genuine
warm-path regressions.  The compile/execute split means a first compile
is classified ``cold-compile``, never ``regressed``.

    # judge one or more runs, write the artifact trail
    python scripts/regression_check.py /tmp/nds_hw/power_time.csv.metrics.json \\
        --ledger .bench_cache/ledger.jsonl --out REGRESSIONS.json

    # no-hardware CI mode: verify ingest + classifier on a synthetic
    # history
    python scripts/regression_check.py --selftest
"""
from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from ndstpu.obs import ledger as ledger_mod  # noqa: E402
from ndstpu.obs import sentinel  # noqa: E402


def selftest() -> int:
    """Classifier checks that need no hardware: write a synthetic run
    history (a cold and a warm power-run sidecar), replay it through
    ingest + classify and assert the invariants the sentinel promises
    (a warm steady-state rerun of the same data is never flagged; cold
    compiles are never regressions)."""
    import tempfile
    steady = {f"query{i}": 0.2 + 0.05 * i for i in range(1, 21)}
    with tempfile.TemporaryDirectory() as root:
        for tag, compile_s in (("cold", 30.0), ("warm", 0.0)):
            with open(os.path.join(root, f"{tag}.csv.metrics.json"),
                      "w") as f:
                json.dump({"engine": "tpu", "queries": [
                    {"query": q, "wall_s": w + compile_s,
                     "compile_s": compile_s, "execute_s": w,
                     "mode": tag} for q, w in steady.items()]}, f)
        led = ledger_mod.Ledger(path=None, load=False)
        ingested = {
            p: led.ingest_file(p, scale_factor="1") for p in sorted(
                os.path.join(root, n) for n in os.listdir(root))}
    print(f"selftest: ingested {sum(ingested.values())} synthetic "
          f"entries from {len(ingested)} sidecars "
          f"({len(led.queries())} distinct queries)")
    assert sum(ingested.values()) == 2 * len(steady), ingested
    qsums = [{"query": q, "wall_s": w, "compile_s": 0.0,
              "execute_s": w} for q, w in steady.items()]
    res = sentinel.classify_run(qsums, led, engine="tpu",
                                scale_factor="1")
    counts = res["counts"]
    print(f"selftest: steady-state replay counts: {counts}")
    assert not res["regressions"], (
        f"replaying the steady state against its own ledger flagged "
        f"regressions: {res['regressions']}")
    assert counts.get("cold-compile", 0) == 0, counts
    # synthetic verdict table
    v = sentinel.classify_query("q", 60.0, 55.0, 5.0, 1.0)
    assert v["verdict"] == "cold-compile", v
    v = sentinel.classify_query("q", 2.0, 0.0, 2.0, 1.0)
    assert v["verdict"] == "regressed", v
    v = sentinel.classify_query("q", 0.5, 0.0, 0.5, 1.0)
    assert v["verdict"] == "improved", v
    v = sentinel.classify_query("q", 1.1, 0.0, 1.1, 1.0)
    assert v["verdict"] == "flat", v
    v = sentinel.classify_query("q", 1.0, 0.0, 1.0, None)
    assert v["verdict"] == "new", v
    print("selftest: OK")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("sidecars", nargs="*",
                    help="power sidecar(s): <time_log>.metrics.json")
    ap.add_argument("--ledger", default=None,
                    help="ledger JSONL (default $NDSTPU_LEDGER or "
                         ".bench_cache/ledger.jsonl)")
    ap.add_argument("--ingest-history", action="store_true",
                    help="also ingest the power-run sidecars "
                         "(*.metrics.json) at the repo root and under "
                         "docs/ as baselines")
    ap.add_argument("--engine", default=None,
                    help="baseline scope override (default: from each "
                         "sidecar)")
    ap.add_argument("--scale_factor", default=None)
    ap.add_argument("--out", default="REGRESSIONS.json",
                    help="JSON verdict artifact ('' disables)")
    ap.add_argument("--md", default="REGRESSIONS.md",
                    help="markdown verdict table ('' disables)")
    ap.add_argument("--selftest", action="store_true",
                    help="no-hardware classifier checks (CI)")
    args = ap.parse_args(argv)
    if args.selftest:
        return selftest()
    if not args.sidecars:
        ap.error("no sidecars given (or use --selftest)")
    led = ledger_mod.Ledger(args.ledger or ledger_mod.default_path(REPO))
    if args.ingest_history:
        ingested = led.ingest_history(REPO)
        print(f"ingested {sum(ingested.values())} historical entries "
              f"from {len(ingested)} artifacts")
    all_verdicts = []
    engine = args.engine
    scale_factor = args.scale_factor
    for path in args.sidecars:
        with open(path) as f:
            sc = json.load(f)
        queries = sc.get("queries") or []
        res = sentinel.classify_run(
            queries, led,
            engine=engine or sc.get("engine"),
            scale_factor=scale_factor or sc.get("scale_factor"))
        engine = engine or sc.get("engine")
        all_verdicts.extend(res["verdicts"])
    counts: dict = {}
    for v in all_verdicts:
        counts[v["verdict"]] = counts.get(v["verdict"], 0) + 1
    result = {
        "format": "ndstpu-regressions-v1",
        "engine": engine,
        "scale_factor": scale_factor,
        "rel_tol": sentinel.REL_TOL,
        "abs_floor_s": sentinel.ABS_FLOOR_S,
        "counts": counts,
        "regressions": [v["query"] for v in all_verdicts
                        if v["verdict"] == "regressed"],
        "verdicts": all_verdicts,
    }
    paths = sentinel.write_reports(result, args.out or None,
                                   args.md or None)
    print(sentinel.markdown_table(result))
    for k, p in paths.items():
        print(f"wrote {k}: {p}")
    if result["regressions"]:
        print(f"REGRESSIONS: {result['regressions']}", file=sys.stderr)
        return 1
    print("no warm-path regressions")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
