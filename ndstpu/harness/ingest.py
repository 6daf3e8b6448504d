"""Crash-consistent micro-batch ingest: LF_*/DF_* refresh functions
applied to a LIVE session while query streams keep serving.

The reference treats data maintenance as a quiesced batch phase
between benchmark runs (nds_maintenance.py); the ROADMAP north star is
a service that ingests while serving.  This module is the write side
of that HTAP shape, gated by the differential in
scripts/ingest_smoke.py: interleaved ingest+query must be bit-exact,
per snapshot epoch, against the same refresh functions replayed
quiesced.

Mechanics (docs/ROBUSTNESS.md "Ingest commit protocol"):

* **one micro-batch = one refresh function** (or one synthetic batch),
  applied wholly under the session's execution lock — concurrent query
  pins (Session.pin_snapshot takes the same lock) only ever observe
  batch boundaries, never half a refresh function;
* an **intent/done journal** (append-only JSONL via
  io/atomic.append_jsonl, the RUN_STATE idiom) brackets every batch:
  *intent* records the per-table lake pre-versions before the first
  statement, *done* the post-versions after the last commit.  A
  SIGKILL mid-batch leaves intent-without-done; :meth:`resume`
  retracts the touched tables to the recorded pre-versions
  (lake.abort_to_version — history-rewriting, sound because no pin can
  hold an un-done batch's commits), GCs unpublished manifest orphans,
  reloads the catalog, and the batch re-applies from scratch — atomic
  under crash;
* a **CommitConflict** (io/commit.py) or any transient fault inside a
  batch triggers the same retract-and-retry via faults/retry.py.
  Because retraction rewrites (rather than rolls forward over) the
  aborted commits, a retried or killed-and-resumed run ends on the
  SAME per-table snapshot versions as an uninterrupted one — which is
  what lets the differential compare epochs across chaos and clean
  runs.
  ``ingest.apply`` is the batch-level fault-injector site;
  ``ingest.commit`` fires inside the lake commit protocol itself.

Counters: ``engine.ingest.commits`` / ``engine.ingest.conflicts``
tick in the io layer; ``engine.ingest.retries`` ticks here per
re-applied attempt (docs/OBSERVABILITY.md).

CLI (the smoke's SIGKILL target — killable between batches via
``--batch_pause_s``, resumable with ``--resume``)::

    python -m ndstpu.harness.ingest WAREHOUSE \
        --refresh_data_path DIR --funcs LF_SS,DF_SS \
        [--resume] [--batch_pause_s S]
    python -m ndstpu.harness.ingest WAREHOUSE --synthetic N ...
"""

from __future__ import annotations

import argparse
import os
import time
from typing import Callable, Dict, List, Optional, Tuple

from ndstpu.faults import retry
from ndstpu.io import atomic, gdict, lake

JOURNAL_RELPATH = os.path.join("_ingest", "INGEST_STATE.jsonl")


class _NullLock:
    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NULL_LOCK = _NullLock()


class MicroBatchIngestor:
    """Applies micro-batches to a lake warehouse (and, when a session
    is attached, its live in-memory catalog) with crash atomicity and
    conflict retry.  See the module docstring for the protocol."""

    def __init__(self, warehouse: str, sess=None,
                 journal_path: Optional[str] = None,
                 policy: Optional[retry.RetryPolicy] = None):
        self.warehouse = warehouse
        self.sess = sess
        self.journal_path = journal_path or os.path.join(
            warehouse, JOURNAL_RELPATH)
        self.policy = policy or retry.RetryPolicy.from_env()

    # -- journal ---------------------------------------------------------

    def records(self) -> List[dict]:
        return atomic.read_jsonl(self.journal_path)

    def pending_intent(self) -> Optional[dict]:
        """The last intent with no matching done/rolled_back — the
        signature of a crash mid-batch."""
        pend = None
        for r in self.records():
            ev = r.get("event")
            if ev == "intent":
                pend = r
            elif ev in ("done", "rolled_back"):
                pend = None
        return pend

    def done_funcs(self) -> List[str]:
        return [r["fn"] for r in self.records()
                if r.get("event") == "done"]

    # -- restore ---------------------------------------------------------

    def _versions(self) -> Dict[str, int]:
        return lake.versions_vector(self.warehouse)

    def _restore(self, pre_versions: Dict[str, int]) -> List[str]:
        """Retract every table that advanced past its recorded
        pre-batch version (lake.abort_to_version — history-rewriting,
        sound here because the aborted commits belong to a batch whose
        intent never reached done and no pin can hold them: pins only
        form at batch boundaries), GC unpublished manifest orphans,
        and reload touched tables into the live catalog.  Retraction —
        not a rollback snapshot — is what keeps a killed-and-resumed
        run's version trajectory identical to a clean run's, which the
        differential (scripts/ingest_smoke.py) depends on."""
        touched = []
        for table, pre in sorted(pre_versions.items()):
            root = os.path.join(self.warehouse, table)
            try:
                cur = lake.current_version(root)
            except (OSError, ValueError):
                continue
            if cur != pre:
                lake.abort_to_version(root, pre)
                # drop dictionary versions stamped past the retracted
                # snapshot — a re-applied batch regrows them, keeping the
                # dict-version trajectory identical to a clean run's
                gdict.retract(root, pre)
                touched.append(table)
                self._reload(table)
        lake.gc_orphans(self.warehouse)
        return touched

    def _reload(self, table: str) -> None:
        if self.sess is None:
            return
        from ndstpu import schema as nds_schema
        from ndstpu.engine import columnar
        root = os.path.join(self.warehouse, table)
        at = lake.read(root)
        try:
            sch = nds_schema.get_schema(table)
        except KeyError:
            sch = None
        gds = gdict.table_dicts(root, table)
        self.sess.catalog.register(
            table, columnar.from_arrow(at, sch, gdicts=gds or None))

    def _grow_dicts(self, pre: Dict[str, int],
                    post: Dict[str, int]) -> None:
        """Append-only global-dictionary growth for every table whose
        lake version advanced in this batch.  Runs before the done
        record inside the batch lock: a crash between commit and grow
        leaves intent-without-done, and :meth:`_restore` retracts both
        the lake commits and the dict versions stamped past them, so
        dict versions ride snapshot versions exactly.  Pinned readers
        keep selecting the dict entry matching their pinned snapshot;
        only new loads see the grown value set."""
        for table, cur in sorted(post.items()):
            if pre.get(table) == cur:
                continue
            root = os.path.join(self.warehouse, table)
            grown = gdict.grow_for_table(root, table, table_version=cur)
            if self.sess is not None and any(
                    e.get("table_version") == cur
                    for e in grown.values()):
                # re-encode the live catalog entry against the grown
                # dict so new (unpinned) queries shard on its codes
                self._reload(table)

    # -- apply -----------------------------------------------------------

    def apply_batch(self, name: str, apply_fn: Callable[[], None]) -> dict:
        """Apply one micro-batch crash-consistently.  ``apply_fn()``
        performs the batch's writes (SQL statements through the
        session, or direct lake ops).  Returns the journal done
        record."""
        from ndstpu import faults as faults_mod, obs
        lock = self.sess._exec_lock if self.sess is not None \
            else _NULL_LOCK
        seq = len([r for r in self.records()
                   if r.get("event") == "intent"])
        batch = f"{seq:04d}-{name}"
        with lock:
            pre = self._versions()
            atomic.append_jsonl(self.journal_path, {
                "event": "intent", "batch": batch, "fn": name,
                "pre_versions": pre, "ts": round(time.time(), 3)})

            tries = [0]

            def attempt():
                tries[0] += 1
                if tries[0] > 1:
                    # a prior attempt failed: retract any partial
                    # commits and GC unpublished manifest orphans so
                    # the re-apply starts from exactly the recorded
                    # pre-batch state — applied exactly once overall,
                    # with the same version numbering as a clean run
                    self._restore(pre)
                faults_mod.check("ingest.apply", key=name)
                apply_fn()

            _res, attempts = retry.run_with_retry(
                attempt, f"ingest:{batch}", policy=self.policy)
            if attempts > 1:
                obs.inc("engine.ingest.retries", attempts - 1)
            post = self._versions()
            self._grow_dicts(pre, post)
            rec = {"event": "done", "batch": batch, "fn": name,
                   "post_versions": post,
                   "attempts": attempts, "ts": round(time.time(), 3)}
            atomic.append_jsonl(self.journal_path, rec)
        return rec

    def resume(self) -> Optional[str]:
        """Recover the journal after a crash: an intent without a done
        means the process died mid-batch — roll the touched tables
        back to the recorded pre-versions and journal the rollback.
        Returns the rolled-back batch's function name (it must be
        re-applied), or None when the journal is clean."""
        pend = self.pending_intent()
        if pend is None:
            return None
        restored = self._restore(pend.get("pre_versions") or {})
        atomic.append_jsonl(self.journal_path, {
            "event": "rolled_back", "batch": pend["batch"],
            "fn": pend.get("fn"), "restored": restored,
            "ts": round(time.time(), 3)})
        return pend.get("fn")

    def run(self, batches: List[Tuple[str, Callable[[], None]]],
            resume: bool = False,
            batch_pause_s: float = 0.0) -> List[dict]:
        """Apply named batches in order.  With ``resume``, first repair
        a crashed batch, then skip batches already journaled done (the
        RUN_STATE phase-skip idiom applied per micro-batch)."""
        done = set()
        if resume:
            rolled = self.resume()
            if rolled:
                print(f"[ingest] rolled back crashed batch {rolled}; "
                      f"re-applying")
            done = set(self.done_funcs())
        out = []
        for name, fn in batches:
            if name in done:
                print(f"[ingest] skip {name}: journaled done")
                continue
            rec = self.apply_batch(name, fn)
            print(f"[ingest] batch {rec['batch']} done "
                  f"(attempts={rec['attempts']})", flush=True)
            out.append(rec)
            if batch_pause_s:
                time.sleep(batch_pause_s)
        return out


def synthetic_batch(warehouse: str, i: int) -> Callable[[], None]:
    """One deterministic session-free micro-batch over every lake
    table: even batches re-append the table's first rows, odd batches
    delete a content-keyed slice (first column mod 7).  Exercises the
    commit/journal machinery without a generated dataset — the chaos
    smoke's SIGKILL-mid-ingest scenario and the unit tests both drive
    this.  Deterministic given the prior table state, so a killed-and-
    resumed run converges on the same snapshots as an uninterrupted
    one."""
    import numpy as np

    def apply():
        for name in lake.lake_tables(warehouse):
            root = os.path.join(warehouse, name)
            if i % 2 == 0:
                at = lake.read(root)
                lake.append(root, at.slice(0, min(3, at.num_rows)))
            else:
                def pred(at):
                    col = at.column(0).to_numpy(zero_copy_only=False)
                    return (col.astype(np.int64) % 7) == (i % 7)
                lake.delete_rows(root, pred)
    return apply


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        description="crash-consistent micro-batch ingest over a lake "
                    "warehouse")
    p.add_argument("warehouse_path")
    p.add_argument("--refresh_data_path",
                   help="transcoded refresh (staging) data dir for "
                        "LF_*/DF_* functions")
    p.add_argument("--funcs",
                   help="comma-separated refresh-function subset "
                        "(default: all)")
    p.add_argument("--synthetic", type=int, default=0, metavar="N",
                   help="apply N synthetic micro-batches instead of "
                        "refresh functions (no refresh data needed)")
    p.add_argument("--journal",
                   help=f"journal path (default: "
                        f"WAREHOUSE/{JOURNAL_RELPATH})")
    p.add_argument("--resume", action="store_true",
                   help="repair a crashed batch and skip completed ones")
    p.add_argument("--batch_pause_s", type=float, default=0.0,
                   help="sleep between batches (gives chaos harnesses "
                        "a deterministic kill window)")
    return p


def main(argv: Optional[List[str]] = None) -> int:
    args = build_parser().parse_args(argv)
    journal = args.journal or os.path.join(
        args.warehouse_path, JOURNAL_RELPATH)
    if not args.resume and os.path.exists(journal):
        os.unlink(journal)
    if args.synthetic:
        ing = MicroBatchIngestor(args.warehouse_path,
                                 journal_path=journal)
        batches = [(f"syn{i}", synthetic_batch(args.warehouse_path, i))
                   for i in range(args.synthetic)]
    else:
        if not args.refresh_data_path:
            raise SystemExit(
                "--refresh_data_path is required without --synthetic")
        from ndstpu.engine.session import Session
        from ndstpu.harness import maintenance
        from ndstpu.io import loader
        catalog = loader.load_catalog(args.warehouse_path)
        sess = Session(catalog, warehouse=args.warehouse_path)
        maintenance.register_staging_views(sess, args.refresh_data_path)
        funcs = args.funcs.split(",") if args.funcs \
            else list(maintenance.DM_FUNCS)
        queries = maintenance.get_maintenance_queries(sess, funcs)
        ing = MicroBatchIngestor(args.warehouse_path, sess=sess,
                                 journal_path=journal)

        def sql_batch(stmts):
            def apply():
                for s in stmts:
                    sess.sql(s)
            return apply

        batches = [(fn, sql_batch(queries[fn])) for fn in funcs]
    ing.run(batches, resume=args.resume,
            batch_pause_s=args.batch_pause_s)
    print(f"[ingest] final versions: {lake.versions_vector(args.warehouse_path)} "
          f"epoch: {lake.warehouse_epoch(args.warehouse_path)}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
