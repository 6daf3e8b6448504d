"""Warehouse-wide frozen global string dictionaries.

Per-column string dictionaries used to be an accident of whatever rows
a ``from_arrow`` call happened to see: two chunks of one table, or two
snapshots of one lake table, encoded the same string to different
codes.  That per-call scope was the single wall across three
north-star axes (ROADMAP item 3): SPMD string join keys needed a
build-dictionary translation, chunk sources rejected string tables
outright, and string binds could not ride the parameterized compile
cache.

This module gives every string column of a transcoded table ONE
authoritative sorted dictionary, persisted as a sidecar artifact next
to the table's data files (``_GLOBAL_DICTS.json`` — invisible to the
loaders, which glob by extension, exactly like ``_SUCCESS``):

* **frozen + content-hashed** — a dictionary version never mutates;
  its identity is the hash of its value list, so two columns (or two
  processes) holding the same hash hold the same code space and codes
  compare directly with no translation;
* **sorted per version** — the engine's string machinery assumes
  ``code order == lexical order`` everywhere (searchsorted
  translation, ORDER BY on codes, range predicates, merged-dict
  literals), so growth produces a NEW fully sorted version rather than
  appending values to the old one.  Codes are stable *within* a
  version; the value SET grows append-only across versions;
* **versioned with the table** — each entry is stamped with the lake
  table version whose commit introduced it (``table_version``; None
  for non-ACID layouts written once at transcode).  A snapshot-pinned
  reader selects the newest entry at-or-before its pin, so pinned
  queries decode with the dictionary matching their pin, and
  ``lake.warehouse_epoch`` — a hash over per-table CURRENT versions —
  already keys every epoch-invalidated cache, so dict growth rides
  the existing invalidation for free.

A table with no sidecar (a warehouse transcoded before the layer, or
hand-written parquet) takes the per-call paths: loaders build per-call
dictionaries, chunk sources reject its string columns, joins translate
through merged dictionaries.  ``scripts/dict_audit.py`` sweeps sidecar
sizes + corpus coverage into the ``DICT_AUDIT.*`` artifacts.

Counters (docs/OBSERVABILITY.md): ``engine.dict.lookups`` /
``engine.dict.misses`` per bind-time value lookup,
``engine.dict.bytes`` encoded bytes of loaded dictionaries,
``engine.dict.version_loads`` per sidecar entry materialized.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
from typing import Dict, List, Optional, Sequence

import numpy as np

#: sidecar file name inside a table directory (next to _SUCCESS)
GDICT_FILE = "_GLOBAL_DICTS.json"

#: sidecar schema version
FORMAT = 1


def _obs_inc(name: str, value: float = 1) -> None:
    from ndstpu import obs
    obs.inc(name, value)


def content_hash(values: Sequence[str]) -> str:
    """Stable identity of a dictionary's value list.  Equal hashes mean
    equal code spaces: codes compare across tables with no translation."""
    h = hashlib.sha256()
    for v in values:
        h.update(str(v).encode("utf-8"))
        h.update(b"\x1f")
    return "d" + h.hexdigest()[:16]


def dictionary_nbytes(values) -> int:
    """Actual encoded byte size of a dictionary's text (UTF-8) — what
    the strings really cost, vs the 8 B/entry object-pointer estimate
    that undercounted wide string columns (engine/spine.py)."""
    if values is None:
        return 0
    return int(sum(len(str(v).encode("utf-8")) for v in values))


@dataclasses.dataclass(frozen=True)
class GlobalDict:
    """One frozen, sorted dictionary version for one table column."""

    table: str
    column: str
    values: np.ndarray            # sorted object array of unique strings
    hash: str                     # content_hash(values)
    version: int                  # ordinal in the sidecar journal
    table_version: Optional[int]  # lake version that introduced it

    def __len__(self) -> int:
        return len(self.values)

    def lookup(self, value) -> Optional[int]:
        """Code of ``value`` in this dictionary, or None when absent.
        This is the bind-time path for scalar dict-code params, so it
        ticks the lookup/miss counters."""
        _obs_inc("engine.dict.lookups")
        v = str(value)
        n = len(self.values)
        if n:
            pos = int(np.searchsorted(self.values.astype(str), v))
            if pos < n and str(self.values[pos]) == v:
                return pos
        _obs_inc("engine.dict.misses")
        return None

    @property
    def nbytes(self) -> int:
        return dictionary_nbytes(self.values)


# ---------------------------------------------------------------------------
# sidecar I/O
# ---------------------------------------------------------------------------


def sidecar_path(table_dir: str) -> str:
    return os.path.join(table_dir, GDICT_FILE)


def _read_sidecar(table_dir: str) -> Optional[dict]:
    path = sidecar_path(table_dir)
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError):
        return None
    if not isinstance(doc, dict) or doc.get("format") != FORMAT:
        return None
    return doc


def _write_sidecar(table_dir: str, doc: dict) -> None:
    from ndstpu.io import atomic
    atomic.atomic_write_text(sidecar_path(table_dir),
                             json.dumps(doc, indent=1, sort_keys=True))


def has_sidecar(table_dir: str) -> bool:
    return _read_sidecar(table_dir) is not None


def _select_entry(entries: List[dict],
                  pin_table_version: Optional[int]) -> Optional[dict]:
    """Newest entry visible at ``pin_table_version`` (None = newest
    overall).  Entries without a table stamp (plain-parquet transcode)
    are visible at every pin."""
    best = None
    for ent in entries:
        tv = ent.get("table_version")
        if pin_table_version is not None and tv is not None \
                and tv > pin_table_version:
            continue
        if best is None or ent["version"] > best["version"]:
            best = ent
    return best


def table_dicts(table_dir: str, table: Optional[str] = None,
                pin_table_version: Optional[int] = None
                ) -> Dict[str, GlobalDict]:
    """Load the frozen dictionaries for one table, selecting per column
    the version matching ``pin_table_version`` (snapshot-pinned chunk
    sources) or the newest (live loads)."""
    doc = _read_sidecar(table_dir)
    if doc is None:
        return {}
    tname = table or doc.get("table") or os.path.basename(
        os.path.normpath(table_dir))
    out: Dict[str, GlobalDict] = {}
    for col, entries in sorted((doc.get("columns") or {}).items()):
        ent = _select_entry(entries, pin_table_version)
        if ent is None:
            continue
        values = np.asarray(ent["values"], dtype=object)
        gd = GlobalDict(table=tname, column=col, values=values,
                        hash=ent.get("hash") or content_hash(values),
                        version=int(ent["version"]),
                        table_version=ent.get("table_version"))
        _obs_inc("engine.dict.version_loads")
        _obs_inc("engine.dict.bytes", gd.nbytes)
        out[col] = gd
    return out


# ---------------------------------------------------------------------------
# build / growth
# ---------------------------------------------------------------------------


def string_uniques_arrow(at) -> Dict[str, np.ndarray]:
    """Sorted unique non-null values per string column of a pyarrow
    Table (the transcode-time build input)."""
    import pyarrow as pa
    import pyarrow.compute as pc

    out: Dict[str, np.ndarray] = {}
    for i, name in enumerate(at.column_names):
        arr = at.column(i)
        typ = arr.type
        if pa.types.is_dictionary(typ):
            typ = typ.value_type
        if not (pa.types.is_string(typ) or pa.types.is_large_string(typ)):
            continue
        col = at.column(i)
        if isinstance(col, pa.ChunkedArray):
            col = col.combine_chunks()
        if pa.types.is_dictionary(col.type):
            col = col.cast(col.type.value_type)
        uniq = pc.unique(col.drop_null()).to_pylist()
        vals = np.asarray(sorted(str(v) for v in uniq), dtype=object)
        out[name] = vals
    return out


def update_sidecar(table_dir: str, table: str,
                   values_by_col: Dict[str, np.ndarray],
                   table_version: Optional[int] = None) -> Dict[str, dict]:
    """Merge new column values into the sidecar: each column whose
    value SET actually grew gets a fresh sorted version entry stamped
    with ``table_version``; unchanged columns keep their newest entry.
    Idempotent — re-running with the same inputs writes nothing new."""
    doc = _read_sidecar(table_dir) or {
        "format": FORMAT, "table": table, "columns": {}}
    cols = doc.setdefault("columns", {})
    changed = False
    applied: Dict[str, dict] = {}
    for col, vals in sorted(values_by_col.items()):
        new_vals = [str(v) for v in vals]
        entries = cols.setdefault(col, [])
        latest = _select_entry(entries, None)
        if latest is not None:
            union = sorted(set(latest["values"]) | set(new_vals))
            if union == list(latest["values"]):
                applied[col] = latest
                continue
            new_vals = union
        else:
            new_vals = sorted(set(new_vals))
        ent = {"version": len(entries),
               "table_version": table_version,
               "hash": content_hash(new_vals),
               "values": new_vals}
        entries.append(ent)
        applied[col] = ent
        changed = True
    if changed or not os.path.exists(sidecar_path(table_dir)):
        os.makedirs(table_dir, exist_ok=True)
        _write_sidecar(table_dir, doc)
    return applied


def grow_for_table(table_dir: str, table: Optional[str] = None,
                   table_version: Optional[int] = None) -> Dict[str, dict]:
    """Grow the sidecar to cover the table's CURRENT committed rows —
    the post-commit ingest hook (harness/ingest.py).  Append-only per
    commit: only columns whose value set actually grew get a new
    version, stamped with the commit's lake version.  Idempotent, so a
    retried or resumed batch converges on the same sidecar."""
    from ndstpu.io import lake
    tname = table or os.path.basename(os.path.normpath(table_dir))
    if not lake.is_lake(table_dir):
        return {}
    if table_version is None:
        table_version = lake.current_version(table_dir)
    at = lake.read(table_dir)
    vals = string_uniques_arrow(at)
    if not vals:
        return {}
    return update_sidecar(table_dir, tname, vals,
                          table_version=table_version)


def retract(table_dir: str, table_version: int) -> int:
    """Drop dictionary versions introduced after ``table_version`` —
    the crash-recovery twin of ``lake.abort_to_version`` (ingest
    restore).  Sound for the same reason the lake retraction is: no
    pin can hold an un-done batch's commits, so nothing can still
    reference the dropped versions.  Returns the number of entries
    dropped."""
    doc = _read_sidecar(table_dir)
    if doc is None:
        return 0
    dropped = 0
    for col, entries in list((doc.get("columns") or {}).items()):
        keep = [e for e in entries
                if e.get("table_version") is None
                or e["table_version"] <= table_version]
        dropped += len(entries) - len(keep)
        doc["columns"][col] = keep
    if dropped:
        _write_sidecar(table_dir, doc)
    return dropped
