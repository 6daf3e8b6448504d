"""Global-dictionary audit: sidecar inventory + corpus coverage gate.

Two halves, one artifact pair:

* **inventory** — every ``_GLOBAL_DICTS.json`` sidecar in the
  warehouse, per column: version count, latest entry size (values and
  encoded UTF-8 bytes, the engine/spine.py byte model), content hash.
  This is the ground truth for "which string columns have a frozen
  warehouse-wide code space" (ndstpu/io/gdict.py).
* **coverage sweep** — every corpus part (all 103) is planned
  statically and its base-table scans walked (plan.Scan); a part is
  ``covered`` when every string column of every table it scans holds a
  frozen global dictionary, ``nostrings`` when it touches none.  An
  ``uncovered`` part is one that would still hit the per-call
  dictionary paths: build-side translation on string joins (NDS307),
  string-table streaming rejection, unbound string literals.

Artifacts: ``DICT_AUDIT.json`` / ``DICT_AUDIT.md`` (repo root,
deterministic — no timestamps).  Baseline gate
(``docs/dict_audit_baseline.json``): a part that was covered may not
regress to uncovered/error, and the uncovered total may not grow;
accept intentional changes with ``--write-baseline``.

Usage::

    JAX_PLATFORMS=cpu python scripts/dict_audit.py [warehouse_dir]
        [--baseline] [--write-baseline] [--sub_queries query1,...]

Without a warehouse argument a tiny SF-0.002 warehouse is generated
and transcoded (the spmd_coverage.py pattern).  Exits nonzero on
baseline regression.  A warehouse without sidecars has an empty
inventory and every string-touching part uncovered.
"""

import argparse
import json
import os
import pathlib
import subprocess
import sys
import tempfile

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

BASELINE_PATH = REPO / "docs" / "dict_audit_baseline.json"

os.environ.setdefault("JAX_PLATFORMS", "cpu")


def sidecar_inventory(warehouse: str) -> dict:
    """Per-table, per-column dictionary stats from the sidecars."""
    from ndstpu.io import gdict

    inv = {}
    for table in sorted(os.listdir(warehouse)):
        tdir = os.path.join(warehouse, table)
        if not os.path.isdir(tdir):
            continue
        doc = gdict._read_sidecar(tdir)
        if doc is None:
            continue
        cols = {}
        for col, entries in sorted((doc.get("columns") or {}).items()):
            ent = gdict._select_entry(entries, None)
            if ent is None:
                continue
            cols[col] = {
                "versions": len(entries),
                "values": len(ent["values"]),
                "bytes": gdict.dictionary_nbytes(ent["values"]),
                "hash": ent.get("hash"),
                "table_version": ent.get("table_version"),
            }
        if cols:
            inv[table] = cols
    return inv


def string_columns(catalog) -> dict:
    """table -> {column -> has frozen dict} over the resident catalog.
    A column counts as covered when the loader attached a GlobalDict
    to it (columnar.Column.gdict), i.e. resident codes ARE the global
    code space."""
    out = {}
    for name, t in sorted(catalog.tables.items()):
        cols = {}
        for cn, c in t.columns.items():
            if c.ctype.kind == "string":
                cols[cn.split(".")[-1]] = c.gdict is not None
        if cols:
            out[name] = cols
    return out


def sweep(catalog, sub_queries=None, verbose=True):
    """Per-part coverage statuses: covered | nostrings |
    uncovered:<table.col,...> | error."""
    from ndstpu.engine import plan as plan_mod
    from ndstpu.engine.session import Session
    from ndstpu.queries import streamgen

    strs = string_columns(catalog)
    sess = Session(catalog, backend="cpu")
    statuses = {}
    for name, sql in streamgen.render_power_corpus(
            rngseed="07291122510", stream=0):
        if sub_queries is not None and name not in sub_queries:
            continue
        try:
            plan, _ = sess.plan(sql)
        except Exception as e:
            statuses[name] = f"error: {type(e).__name__}: {e}"
            continue
        scanned = {n.table for n in plan.walk()
                   if isinstance(n, plan_mod.Scan)}
        missing = sorted(
            f"{t}.{c}" for t in scanned
            for c, covered in strs.get(t, {}).items() if not covered)
        if missing:
            statuses[name] = "uncovered:" + ",".join(missing)
        elif any(t in strs for t in scanned):
            statuses[name] = "covered"
        else:
            statuses[name] = "nostrings"
        if verbose:
            print(f"  {statuses[name].split(':')[0].upper():9s} {name}",
                  flush=True)
    return statuses


def summarize(statuses: dict) -> dict:
    buckets = {"covered": 0, "nostrings": 0, "uncovered": 0, "error": 0}
    for st in statuses.values():
        buckets[st.split(":")[0]] += 1
    return buckets


def check_baseline(statuses: dict, inv: dict, baseline: dict) -> list:
    """Regressions vs the committed baseline, restricted to probed
    parts: covered parts must stay covered, errors are regressions
    outright, the uncovered count may not grow, and no audited column's
    dictionary may disappear."""
    problems = []
    base_parts = baseline.get("parts", {})
    for name, st in sorted(statuses.items()):
        kind = st.split(":")[0]
        was = (base_parts.get(name) or "").split(":")[0]
        if kind == "error":
            problems.append(f"{name}: {st}")
        elif was in ("covered", "nostrings") and kind == "uncovered":
            problems.append(f"{name}: {st}, was {was}")
        elif not was and kind == "uncovered":
            problems.append(f"{name}: {st}, not in baseline")
    probed = set(statuses)
    now_unc = summarize(statuses)["uncovered"]
    was_unc = sum(1 for n, s in base_parts.items()
                  if n in probed and s.split(":")[0] == "uncovered")
    if now_unc > was_unc:
        problems.append(
            f"uncovered parts grew: {now_unc} vs baseline {was_unc}")
    for table, cols in sorted((baseline.get("inventory") or {}).items()):
        for col in sorted(cols):
            if col not in (inv.get(table) or {}):
                problems.append(
                    f"dictionary lost: {table}.{col} in baseline "
                    f"inventory but no sidecar entry now")
    return problems


def write_artifacts(inv: dict, statuses: dict, json_path, md_path):
    buckets = summarize(statuses)
    doc = {
        "meta": {"tool": "scripts/dict_audit.py"},
        "summary": buckets,
        "inventory": inv,
        "parts": statuses,
    }
    pathlib.Path(json_path).write_text(
        json.dumps(doc, indent=2, sort_keys=True) + "\n")

    lines = ["# Global-dictionary audit", ""]
    lines.append("- parts: " + ", ".join(
        f"{buckets[k]} {k}" for k in sorted(buckets)))
    lines += ["", "## Sidecar inventory", "",
              "| table | column | versions | values | bytes | hash |",
              "|---|---|---|---|---|---|"]
    for table, cols in sorted(inv.items()):
        for col, st in sorted(cols.items()):
            lines.append(f"| {table} | {col} | {st['versions']} "
                         f"| {st['values']} | {st['bytes']} "
                         f"| `{st['hash']}` |")
    lines += ["", "## Corpus coverage", "",
              "| part | status |", "|---|---|"]
    for name, st in sorted(statuses.items()):
        lines.append(f"| {name} | {st} |")
    lines.append("")
    pathlib.Path(md_path).write_text("\n".join(lines))


def build_tiny_warehouse() -> str:
    tmp = tempfile.mkdtemp(prefix="dictaudit")
    data = os.path.join(tmp, "raw")
    wh = os.path.join(tmp, "wh")
    env = dict(os.environ, PYTHONPATH=str(REPO))
    subprocess.run(["python", "-m", "ndstpu.datagen.driver", "local",
                    "0.002", "2", data], check=True, env=env)
    subprocess.run(["python", "-m", "ndstpu.io.transcode",
                    "--input_prefix", data, "--output_prefix", wh,
                    "--report_file", os.path.join(wh, "load.txt")],
                   check=True, env=env, stdout=subprocess.DEVNULL)
    return wh


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="global-dictionary sidecar inventory + corpus "
                    "coverage gate")
    ap.add_argument("warehouse", nargs="?")
    ap.add_argument("--baseline", action="store_true")
    ap.add_argument("--write-baseline", action="store_true")
    ap.add_argument("--sub_queries")
    ap.add_argument("--json", default=str(REPO / "DICT_AUDIT.json"))
    ap.add_argument("--md", default=str(REPO / "DICT_AUDIT.md"))
    args = ap.parse_args(argv)

    from ndstpu.io import loader

    wh = args.warehouse or build_tiny_warehouse()
    sub = set(args.sub_queries.split(",")) if args.sub_queries else None

    inv = sidecar_inventory(wh)
    catalog = loader.load_catalog(wh)
    statuses = sweep(catalog, sub_queries=sub)

    buckets = summarize(statuses)
    n_cols = sum(len(c) for c in inv.values())
    n_bytes = sum(st["bytes"] for c in inv.values() for st in c.values())
    print(f"\n== {n_cols} dictionary columns over {len(inv)} tables, "
          f"{n_bytes} encoded bytes ==")
    print("parts:", json.dumps(buckets, sort_keys=True))

    write_artifacts(inv, statuses, args.json, args.md)
    print(f"artifacts: {args.json} {args.md}")

    if args.write_baseline:
        BASELINE_PATH.parent.mkdir(parents=True, exist_ok=True)
        BASELINE_PATH.write_text(json.dumps(
            {"parts": statuses, "summary": buckets,
             "inventory": {t: sorted(c) for t, c in inv.items()}},
            indent=2, sort_keys=True) + "\n")
        print(f"baseline written: {BASELINE_PATH}")
        return 0
    if args.baseline:
        if not BASELINE_PATH.exists():
            print(f"no baseline at {BASELINE_PATH}; run with "
                  "--write-baseline first", file=sys.stderr)
            return 2
        baseline = json.loads(BASELINE_PATH.read_text())
        problems = check_baseline(statuses, inv, baseline)
        if problems:
            print("\ndict-audit regressions vs baseline:")
            for p in problems:
                print(f"  {p}")
            return 1
        print("\nbaseline ok: no dictionary-coverage regression")
    return 0


if __name__ == "__main__":
    sys.exit(main())
