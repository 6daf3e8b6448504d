"""Machine check that committed docs cite artifacts that exist.

One lint (CLI wrapper: scripts/doc_lint.py; wired into the test
suite via tests/test_doc_lint.py):

**Citation lint** — scan ``docs/*.md`` (and README.md / a root
STATUS.md) for cited artifact paths (``docs/*.json``/``docs/*.csv``
and root ``BENCH_*.json`` / ``PLAN_LINT.json`` / ``PLAN_LINT.md`` /
``CANON_AUDIT.json`` / ``CANON_AUDIT.md`` / ``MQO_AUDIT.json`` /
``MQO_AUDIT.md`` / ``DICT_AUDIT.json`` / ``DICT_AUDIT.md`` /
``COST_LINT.json`` / ``COST_LINT.md``) and fail when a cited file is
absent from the tree.  A citation whose line carries an explicit
not-here-yet marker (``pending``, ``uncommitted``, ``not committed``)
is exempt — docs may *promise* an artifact, they may not *cite* a
ghost.  ``RUN_STATE.json`` citations are recognized but exempt from the
existence check: it is a per-run resume journal (docs/ROBUSTNESS.md),
never a committed file.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Iterable, List, Optional, Tuple

CITED_RE = re.compile(
    r"\bdocs/[A-Za-z0-9_.\-/]*\.(?:json|csv)\b"
    r"|\bBENCH_[A-Za-z0-9_.\-]*\.json\b"
    r"|\bPLAN_LINT\.(?:json|md)\b"
    r"|\bCANON_AUDIT\.(?:json|md)\b"
    r"|\bMQO_AUDIT\.(?:json|md)\b"
    r"|\bDICT_AUDIT\.(?:json|md)\b"
    r"|\bCOST_LINT\.(?:json|md)\b"
    r"|\bRUN_STATE\.json\b"
    r"|\bINGEST_DIFF\.json\b"
    r"|\bSLO\.json\b"
    r"|\bFLEET_HEALTH\.json\b")

EXEMPT_MARKERS = ("pending", "uncommitted", "not committed")

# recognized per-run journals/artifacts: docs cite these by name (they
# define the resume/differential/SLO/fleet-health contracts,
# docs/ROBUSTNESS.md and docs/OBSERVABILITY.md) but every run writes
# its own next to its artifacts — there is never a committed copy to
# point at
RUNTIME_ARTIFACTS = ("RUN_STATE.json", "INGEST_DIFF.json", "SLO.json",
                     "FLEET_HEALTH.json")

def cited_artifacts(text: str) -> Iterable[Tuple[int, str, str]]:
    """(lineno, cited path, line) for every artifact citation."""
    for lineno, line in enumerate(text.splitlines(), start=1):
        for m in CITED_RE.finditer(line):
            yield lineno, m.group(0), line


def lint_text(text: str, root: str, doc: str = "<doc>") -> List[str]:
    findings = []
    for lineno, path, line in cited_artifacts(text):
        low = line.lower()
        if any(mk in low for mk in EXEMPT_MARKERS):
            continue
        if os.path.basename(path) in RUNTIME_ARTIFACTS:
            continue
        if not os.path.exists(os.path.join(root, path)):
            findings.append(
                f"{doc}:{lineno}: cites missing artifact {path} "
                f"(commit it, or mark the citation 'pending')")
    return findings


def lint_docs(root: str = ".",
              docs: Optional[Iterable[str]] = None) -> List[str]:
    """Citation-lint the committed prose: docs/*.md, README.md, and a
    root-level STATUS.md when present."""
    if docs is None:
        docs = sorted(glob.glob(os.path.join(root, "docs", "*.md")))
        for extra in ("README.md", "STATUS.md"):
            p = os.path.join(root, extra)
            if os.path.exists(p):
                docs.append(p)
    findings: List[str] = []
    for p in docs:
        with open(p) as f:
            text = f.read()
        findings.extend(lint_text(text, root,
                                  doc=os.path.relpath(p, root)))
    return findings


def lint_repo(root: str = ".") -> List[str]:
    """Empty list means the committed tree is honest."""
    return lint_docs(root)
