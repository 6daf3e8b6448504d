"""Distributed full-corpus differential + NDS3xx coverage gate.

Every corpus query part must execute under the tpu-spmd executor on an
8-device virtual mesh AND produce rows equal to the single-process numpy
interpreter — the distributed analog of the reference's differential
validation loop (/root/reference/nds/nds_validate.py:217-260): outputs
are compared for EVERY query, not merely executed.

On top of the differential, the script emits **per-code NDS3xx counts**
(the DistUnsupported raise-site codes from the shared registry in
ndstpu/analysis/lowering.py) and gates them against a committed baseline
(docs/spmd_coverage_baseline.json): a part that distributed at the
baseline may never silently fall back again, and no NDS3xx code's count
may grow.  Accept intentional changes with --write-baseline.

Usage:  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
            python scripts/spmd_coverage.py [warehouse_dir] [--no-assert]
                [--baseline] [--write-baseline]
                [--sub_queries query1,query10,...]

Prints a per-part verdict (OK/ROWDIFF/FALL/ERR) and exits nonzero when
any part falls back or mismatches (unless --no-assert), or when
--baseline finds a regression.  The same row comparison is enforced in
CI by tests/test_parallel.py::test_dist_full_corpus_row_equal; the
--baseline gate is its own CI step over a corpus subset.
"""

import collections
import json
import os
import pathlib
import subprocess
import sys
import tempfile

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

BASELINE_PATH = REPO / "docs" / "spmd_coverage_baseline.json"

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()


def rows_match(want, got, eps=1e-5):
    """Validator-semantics comparison: row sets equal within epsilon
    (nds_validate.py:194-215 analog), order-insensitive."""
    if len(want) != len(got):
        return False

    def key(r):
        return tuple((v is None, str(v)) for v in r)

    for rw, rg in zip(sorted(want, key=key), sorted(got, key=key)):
        if len(rw) != len(rg):
            return False
        for vw, vg in zip(rw, rg):
            if vw is None or vg is None:
                if not (vw is None and vg is None):
                    return False
            elif isinstance(vw, float) or isinstance(vg, float):
                fw, fg = float(vw), float(vg)
                if fw != fg and abs(fw - fg) > \
                        eps * max(1.0, abs(fw), abs(fg)):
                    return False
            elif vw != vg:
                return False
    return True


def run_corpus(catalog, mesh, shard_threshold_rows=500, verbose=True,
               sub_queries=None, extras=None):
    """(ok, mismatched, fell) lists over every corpus part.  Fallbacks
    carry the NDS3xx diagnostic code of the DistUnsupported raise site
    (the shared registry in ndstpu/analysis/lowering.py names them),
    so the per-reason summary groups by analyzer code.

    `extras`, when a dict, receives: per-part status map ("ok" |
    "<NDS3xx>" | "mismatch" | "error"), attempt-code counts over parts
    that DID distribute (failed-candidate codes the executor recovered
    from), and the count of existence-join build sides reduced
    distributed (dplan._reduce_build engagements)."""
    from ndstpu.engine import physical
    from ndstpu.engine.session import Session
    from ndstpu.parallel import dplan
    from ndstpu.queries import streamgen

    sess = Session(catalog, backend="cpu")
    dev_cache: dict = {}
    ok, mism, fell = [], [], []
    statuses = {}
    attempt_codes = collections.Counter()
    build_reduced = 0
    for name, sql in streamgen.render_power_corpus(
            rngseed="07291122510", stream=0):
        if sub_queries is not None and name not in sub_queries:
            continue
        try:
            plan, _ = sess.plan(sql)
        except Exception as e:  # planner issue, not a dist gap
            fell.append((name, f"PLAN: {e}"))
            statuses[name] = "error"
            continue
        try:
            want = physical.execute(plan, catalog).to_rows()
        except Exception as e:  # oracle (numpy interpreter) defect
            fell.append((name, f"ORACLE: {type(e).__name__}: {e}"))
            statuses[name] = "error"
            continue
        try:
            exe = dplan.DistributedPlanExecutor(
                catalog, mesh,
                shard_threshold_rows=shard_threshold_rows,
                dev_cache=dev_cache)
            got = exe.execute_plan(plan).to_rows()
        except dplan.DistUnsupported as e:
            code = getattr(e, "code", None) or "uncoded"
            fell.append((name, f"{code}: {e}"))
            statuses[name] = code
            if verbose:
                print(f"  FALL {name}: {code}: {e}", flush=True)
            continue
        except Exception as e:
            fell.append((name, f"ERROR {type(e).__name__}: {e}"))
            statuses[name] = "error"
            if verbose:
                print(f"  ERR  {name}: {type(e).__name__}: {e}",
                      flush=True)
            continue
        attempt_codes.update(exe.attempt_codes)
        build_reduced += len(exe.build_reduced)
        if rows_match(want, got):
            ok.append(name)
            statuses[name] = "ok"
            if verbose:
                print(f"  OK   {name} ({len(got)} rows)", flush=True)
        else:
            mism.append((name, len(want), len(got)))
            statuses[name] = "mismatch"
            if verbose:
                print(f"  ROWDIFF {name}: {len(want)} vs {len(got)}",
                      flush=True)
    if extras is not None:
        extras["statuses"] = statuses
        extras["attempt_codes"] = dict(attempt_codes)
        extras["build_reduced"] = build_reduced
    return ok, mism, fell


def code_counts(statuses):
    """Per-NDS3xx-code fallback counts (plus mismatch/error buckets)."""
    return dict(collections.Counter(
        st for st in statuses.values() if st != "ok"))


def check_baseline(statuses, baseline):
    """Regressions of `statuses` vs the committed per-part baseline,
    restricted to the probed parts (subset runs gate their subset):

    * a part that was "ok" at the baseline must stay "ok";
    * "mismatch"/"error" are regressions regardless of the baseline;
    * a probed part missing from the baseline must be "ok" (anything
      else needs a conscious --write-baseline);
    * per-code totals over probed parts may not exceed the baseline's.
    """
    problems = []
    base_parts = baseline.get("parts", {})
    for name, st in sorted(statuses.items()):
        was = base_parts.get(name)
        if st in ("mismatch", "error"):
            problems.append(f"{name}: {st} (baseline {was or 'absent'})")
        elif was == "ok" and st != "ok":
            problems.append(f"{name}: fell back with {st}, was ok")
        elif was is None and st != "ok":
            problems.append(f"{name}: {st} not in baseline")
    probed = set(statuses)
    base_sub = {n: s for n, s in base_parts.items() if n in probed}
    now = collections.Counter(code_counts(statuses))
    was = collections.Counter(code_counts(base_sub))
    for code in sorted(now):
        if now[code] > was.get(code, 0):
            problems.append(
                f"{code}: {now[code]} part(s), baseline {was.get(code, 0)}")
    return problems


def main():
    from ndstpu.io import loader
    from ndstpu.parallel import mesh as pmesh

    assert_ok = "--no-assert" not in sys.argv
    use_baseline = "--baseline" in sys.argv
    write_baseline = "--write-baseline" in sys.argv
    sub_queries = None
    argv = sys.argv[1:]
    skip = set()
    for i, a in enumerate(argv):
        if a == "--sub_queries" and i + 1 < len(argv):
            sub_queries = set(argv[i + 1].split(","))
            skip.add(i + 1)
        elif a.startswith("--sub_queries="):
            sub_queries = set(a.split("=", 1)[1].split(","))
    args = [a for i, a in enumerate(argv)
            if not a.startswith("--") and i not in skip]
    if args:
        wh = args[0]
    else:
        tmp = tempfile.mkdtemp(prefix="spmdcov")
        data = os.path.join(tmp, "raw")
        wh = os.path.join(tmp, "wh")
        env = dict(os.environ, PYTHONPATH=os.getcwd())
        subprocess.run(["python", "-m", "ndstpu.datagen.driver", "local",
                        "0.002", "2", data], check=True, env=env)
        subprocess.run(["python", "-m", "ndstpu.io.transcode",
                        "--input_prefix", data, "--output_prefix", wh,
                        "--report_file", os.path.join(wh, "load.txt")],
                       check=True, env=env, stdout=subprocess.DEVNULL)

    catalog = loader.load_catalog(wh)
    mesh = pmesh.make_mesh(8)
    extras: dict = {}
    ok, mism, fell = run_corpus(catalog, mesh, sub_queries=sub_queries,
                                extras=extras)

    total = len(ok) + len(mism) + len(fell)
    print(f"\n== {len(ok)}/{total} parts distributed AND row-equal ==")
    reasons = collections.Counter(r for _, r in fell)
    for reason, cnt in reasons.most_common():
        print(f"{cnt:4d}  {reason}")
    for name, nw, ng in mism:
        print(f"  ROWDIFF {name}: want {nw} rows, got {ng}")
    counts = code_counts(extras["statuses"])
    print("\nper-code NDS3xx fallback counts:",
          json.dumps(counts, sort_keys=True) or "{}")
    print("attempt codes on distributed parts (recovered candidates):",
          json.dumps(extras["attempt_codes"], sort_keys=True))
    print(f"existence-join build sides reduced distributed: "
          f"{extras['build_reduced']}")

    if write_baseline:
        BASELINE_PATH.parent.mkdir(parents=True, exist_ok=True)
        BASELINE_PATH.write_text(json.dumps(
            {"parts": extras["statuses"], "code_counts": counts,
             "distributed": len(ok), "total": total},
            indent=2, sort_keys=True) + "\n")
        print(f"baseline written: {BASELINE_PATH}")
        return
    if use_baseline:
        if not BASELINE_PATH.exists():
            print(f"no baseline at {BASELINE_PATH}; run with "
                  "--write-baseline first", file=sys.stderr)
            sys.exit(2)
        baseline = json.loads(BASELINE_PATH.read_text())
        problems = check_baseline(extras["statuses"], baseline)
        if problems:
            print("\nSPMD coverage regressions vs baseline:")
            for p in problems:
                print(f"  {p}")
            sys.exit(1)
        print("\nbaseline ok: no SPMD coverage regression")
        return
    if assert_ok and (mism or fell):
        sys.exit(1)


if __name__ == "__main__":
    main()
