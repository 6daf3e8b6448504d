"""Doc/artifact honesty lint CLI (ndstpu/obs/artifact_lint.py).

Fails (exit 1) when committed prose cites an artifact that is not in
the tree (including the root ``PLAN_LINT.*`` / ``CANON_AUDIT.*`` /
``MQO_AUDIT.*`` / ``DICT_AUDIT.*`` sweeps).

    python scripts/doc_lint.py [--root PATH]

Runs in CI after the functional suite (.github/workflows/test.yml) and
as a tier-1 test (tests/test_doc_lint.py), so a doc that cites a ghost
artifact cannot merge.
"""
from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from ndstpu.obs import artifact_lint  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--root", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), help="repo root to lint")
    args = ap.parse_args(argv)
    findings = artifact_lint.lint_repo(args.root)
    for f in findings:
        print(f"doc-lint: {f}")
    if findings:
        print(f"doc-lint: {len(findings)} finding(s)", file=sys.stderr)
        return 1
    print("doc-lint: clean")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
