"""The least bytes a query has to read: every base-table column its
optimized plan scans, at stored width, read once, plus its result.

Computed from the plan and the catalog — never from a compiled program,
a kernel or an operator's implementation — so the scan roofline built
on it reads the same work whatever later implements the operators.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Iterable, Optional, Set, Tuple


def _children(node) -> Iterable:
    """Every value hanging off a plan or expression node: dataclass
    fields, walked through lists, tuples and dicts."""
    if dataclasses.is_dataclass(node) and not isinstance(node, type):
        for f in dataclasses.fields(node):
            yield getattr(node, f.name, None)
    elif isinstance(node, (list, tuple)):
        yield from node
    elif isinstance(node, dict):
        yield from node.values()


def scanned_columns(plan) -> Set[Tuple[str, Optional[str]]]:
    """(table, column) of every base-table scan in the plan, scalar
    subqueries inside expressions included.  A scan that names no
    columns reads them all: (table, None)."""
    out: Set[Tuple[str, Optional[str]]] = set()
    seen: Set[int] = set()
    stack = [plan]
    while stack:
        node = stack.pop()
        if node is None or isinstance(node, (str, bytes, int, float, bool)):
            continue
        if id(node) in seen:
            continue
        seen.add(id(node))
        if type(node).__name__ == "Scan" and hasattr(node, "table"):
            cols = getattr(node, "columns", None)
            if cols is None:
                out.add((node.table, None))
            else:
                out.update((node.table, c) for c in cols)
        if type(node).__name__ == "InlineTable":
            continue        # literal rows, not a base table
        stack.extend(_children(node))
    return out


def column_bytes(column) -> int:
    """Stored width: the data array plus a validity byte per row where
    the column has NULLs."""
    n = int(column.data.nbytes)
    if getattr(column, "valid", None) is not None:
        n += int(column.valid.nbytes)
    return n


def plan_input_bytes(plan, catalog_tables: Dict[str, object]) -> int:
    total = 0
    cols = scanned_columns(plan)
    whole = {t for t, c in cols if c is None}
    for table in whole:
        total += sum(column_bytes(c)
                     for c in catalog_tables[table].columns.values())
    for table, name in cols:
        if name is None or table in whole:
            continue
        total += column_bytes(catalog_tables[table].columns[name])
    return total
