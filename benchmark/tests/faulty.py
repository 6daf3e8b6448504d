#!/usr/bin/env python3
"""The benchmark's command with the timed path broken underneath, for
the tests: every answer has one cell altered where it is produced.

    python3 benchmark/tests/faulty.py <run.py's arguments>

A closed-loop cell's answers are altered in ``Table.to_rows`` of this
process; a served cell's in the daemon's ``QueryServer._run_query``,
by starting this file in the place of
``benchmark/harness/serve_child.py`` (``BENCH_FAULTY_CHILD`` in the
environment tells it so).  The harness itself has no fault switch.
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def altered(rows: list) -> list:
    """One cell of an answer changed."""
    if not rows or not rows[0]:
        return [("altered",)]
    first = list(rows[0])
    for j, v in enumerate(first):
        if isinstance(v, (int, float)) and not isinstance(v, bool):
            first[j] = v + 1
            break
        if isinstance(v, str):
            first[j] = v + "x"
            break
    return [tuple(first)] + list(rows[1:])


def main() -> int:
    if os.environ.get("BENCH_FAULTY_CHILD"):
        from benchmark.harness import serve_child
        from ndstpu.serve import server as srv
        inner = srv.QueryServer._run_query

        def run_query(self, session, req):
            out = inner(self, session, req)
            if out.get("data"):
                out["data"] = [list(r) for r in altered(
                    [tuple(r) for r in out["data"]])]
            return out
        srv.QueryServer._run_query = run_query
        return serve_child.main()
    from benchmark import run
    from benchmark.harness import open_loop
    from ndstpu.engine import columnar
    open_loop.CHILD = os.path.abspath(__file__)
    os.environ["BENCH_FAULTY_CHILD"] = "1"
    inner = columnar.Table.to_rows
    columnar.Table.to_rows = lambda self: altered(inner(self))
    return run.main()


if __name__ == "__main__":
    sys.exit(main())
