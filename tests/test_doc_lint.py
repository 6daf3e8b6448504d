"""Doc/artifact citation lint tests (ndstpu/obs/artifact_lint.py,
scripts/doc_lint.py) — the committed tree must never cite a ghost
artifact, and stale perf artifacts must say so."""

import os
import subprocess
import sys

from ndstpu.obs import artifact_lint

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_missing_citation_fails(tmp_path):
    (tmp_path / "docs").mkdir()
    text = "See `docs/GHOST_BENCH.json` for the numbers.\n"
    findings = artifact_lint.lint_text(text, str(tmp_path), doc="d.md")
    assert len(findings) == 1
    assert "docs/GHOST_BENCH.json" in findings[0]


def test_present_artifact_and_pending_marker_pass(tmp_path):
    (tmp_path / "docs").mkdir()
    (tmp_path / "docs" / "REAL.json").write_text("{}")
    text = ("cites `docs/REAL.json` (committed)\n"
            "and `docs/FUTURE.json` is pending a hardware run\n"
            "plus an uncommitted `BENCH_r99.json` snapshot\n")
    assert artifact_lint.lint_text(text, str(tmp_path)) == []


def test_bench_root_citations_checked(tmp_path):
    text = "headline in `BENCH_r42.json`\n"
    assert artifact_lint.lint_text(text, str(tmp_path)) != []
    (tmp_path / "BENCH_r42.json").write_text("{}")
    assert artifact_lint.lint_text(text, str(tmp_path)) == []


def test_plan_lint_root_citations_checked(tmp_path):
    text = "static verdicts in `PLAN_LINT.json` and `PLAN_LINT.md`\n"
    findings = artifact_lint.lint_text(text, str(tmp_path))
    assert len(findings) == 2
    (tmp_path / "PLAN_LINT.json").write_text("{}")
    (tmp_path / "PLAN_LINT.md").write_text("# lint\n")
    assert artifact_lint.lint_text(text, str(tmp_path)) == []


def test_canon_audit_root_citations_checked(tmp_path):
    text = "collapse sweep in `CANON_AUDIT.json` and `CANON_AUDIT.md`\n"
    findings = artifact_lint.lint_text(text, str(tmp_path))
    assert len(findings) == 2
    (tmp_path / "CANON_AUDIT.json").write_text("{}")
    (tmp_path / "CANON_AUDIT.md").write_text("# canon\n")
    assert artifact_lint.lint_text(text, str(tmp_path)) == []


def test_cost_lint_root_citations_checked(tmp_path):
    text = "cost sweep in `COST_LINT.json` and `COST_LINT.md`\n"
    findings = artifact_lint.lint_text(text, str(tmp_path))
    assert len(findings) == 2
    (tmp_path / "COST_LINT.json").write_text("{}")
    (tmp_path / "COST_LINT.md").write_text("# cost\n")
    assert artifact_lint.lint_text(text, str(tmp_path)) == []


def test_committed_tree_is_clean():
    assert artifact_lint.lint_repo(REPO) == []


def test_cli_exit_codes(tmp_path):
    env = dict(os.environ, PYTHONPATH=REPO)
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "doc_lint.py")],
        capture_output=True, text=True, env=env)
    assert r.returncode == 0, r.stdout + r.stderr
    # a tree citing a ghost artifact fails
    (tmp_path / "docs").mkdir()
    (tmp_path / "docs" / "bad.md").write_text(
        "numbers in `docs/NOT_THERE.json`\n")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "doc_lint.py"),
         "--root", str(tmp_path)],
        capture_output=True, text=True, env=env)
    assert r.returncode == 1
    assert "NOT_THERE" in r.stdout


def test_run_state_citation_is_recognized_but_runtime_exempt(tmp_path):
    """`RUN_STATE.json` is a per-run resume journal
    (docs/ROBUSTNESS.md): citing it must never demand a committed
    file — while ghost doc artifacts in the same text still flag."""
    text = ("the bench driver journals phases to `RUN_STATE.json`\n"
            "and cites `docs/GHOST.json` for numbers\n")
    (tmp_path / "docs").mkdir()
    findings = artifact_lint.lint_text(text, str(tmp_path), doc="d.md")
    assert len(findings) == 1
    assert "GHOST" in findings[0]
    assert not any("RUN_STATE" in f for f in findings)


def test_ingest_diff_citation_is_recognized_but_runtime_exempt(tmp_path):
    """`INGEST_DIFF.json` is the ingest differential's per-run artifact
    (scripts/ingest_smoke.py): recognized as a citation, exempt from
    the committed-file existence check."""
    text = ("the ingest smoke writes `INGEST_DIFF.json` per run\n"
            "and cites `docs/GHOST.json` for numbers\n")
    (tmp_path / "docs").mkdir()
    findings = artifact_lint.lint_text(text, str(tmp_path), doc="d.md")
    assert len(findings) == 1 and "GHOST" in findings[0]
    assert not any("INGEST_DIFF" in f for f in findings)
    assert any("INGEST_DIFF.json" in m.group(0)
               for m in artifact_lint.CITED_RE.finditer(text))


def test_fleet_health_citation_is_recognized_but_runtime_exempt(tmp_path):
    """`FLEET_HEALTH.json` is the fleet supervisor's per-run artifact
    (serve/fleet.py): recognized as a citation, exempt from the
    committed-file existence check."""
    text = ("the supervisor writes `FLEET_HEALTH.json` per monitor pass\n"
            "and cites `docs/GHOST.json` for numbers\n")
    (tmp_path / "docs").mkdir()
    findings = artifact_lint.lint_text(text, str(tmp_path), doc="d.md")
    assert len(findings) == 1 and "GHOST" in findings[0]
    assert not any("FLEET_HEALTH" in f for f in findings)
    assert any("FLEET_HEALTH.json" in m.group(0)
               for m in artifact_lint.CITED_RE.finditer(text))
