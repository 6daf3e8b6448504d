"""Load test: transcode raw pipe-CSV into the warehouse format, timed.

Parity with the reference transcoder (/root/reference/nds/nds_transcode.py):
per-table conversion timing, date-sk partitioning + within-partition sort for
the 7 fact tables (nds_transcode.py:44-53,123-131), single output file for
dimensions (the coalesce(1) analog), `--floats` decimal switch, `--update`
refresh-data mode, append/overwrite/ignore output modes, and a load report
whose "Load Test Time" / "RNGSEED used:" lines follow the same parse contract
(nds_transcode.py:196-220, consumed by nds_bench.py:60-90).  RNGSEED is the
load end-timestamp `%m%d%H%M%S%f` truncated — TPC-DS spec 4.3.1 chaining.

Output formats: parquet (primary TPU path), orc, avro, csv, json, and `ndslake` —
this framework's ACID snapshot table format (Iceberg/Delta analog, see
ndstpu.io.acid) used by the data-maintenance phase.
"""

from __future__ import annotations

import argparse
import os
import shutil
import time
from collections import OrderedDict
from datetime import datetime

import pyarrow as pa
import pyarrow.compute as pc
import pyarrow.parquet as pq

from ndstpu import faults
from ndstpu import schema as nds_schema
from ndstpu.io import atomic, csvio

FACT_PARTITION = nds_schema.TABLE_PARTITIONING


def _write_partitioned(at: pa.Table, out_dir: str, part_col: str,
                       compression: str) -> None:
    """Date-partitioned parquet write: sort by the partition key, then one
    file per key directory (hive-style `col=value/`), nulls in `col=__NULL__/`.
    Unique basenames make repeated appends additive rather than clobbering."""
    import uuid

    import pyarrow.dataset as ds

    at = at.sort_by([(part_col, "ascending")])
    ds.write_dataset(
        at, out_dir,
        format="parquet",
        partitioning=ds.partitioning(
            pa.schema([at.schema.field(part_col)]), flavor="hive"),
        existing_data_behavior="overwrite_or_ignore",
        basename_template="part-" + uuid.uuid4().hex + "-{i}.parquet",
        max_partitions=4096,  # day-grain partitioning: ~1800+NULL dirs
        file_options=ds.ParquetFileFormat().make_write_options(
            compression=compression),
    )


def _write_single(at: pa.Table, out_dir: str, table: str, fmt: str,
                  compression: str) -> None:
    import uuid

    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"{table}.{fmt}")
    if os.path.exists(path):  # append mode: add a second uniquely-named file
        path = os.path.join(out_dir, f"{table}-{uuid.uuid4().hex}.{fmt}")
    if fmt == "parquet":
        pq.write_table(at, path, compression=compression)
    elif fmt == "orc":
        import pyarrow.orc as paorc
        paorc.write_table(at, path)
    elif fmt == "csv":
        import pyarrow.csv as pacsv
        pacsv.write_csv(at, path)
    elif fmt == "json":
        import pandas as pd  # noqa: F401
        at.to_pandas().to_json(path, orient="records", lines=True,
                               date_format="iso")
    elif fmt == "avro":
        from ndstpu.io import avroio
        avroio.write_table(at, path, name=table)
    else:
        raise ValueError(f"unsupported format {fmt}")


def _success_marker(args, table: str) -> str:
    return os.path.join(args.output_prefix, table, "_SUCCESS")


def transcode_table(args, table: str, tschema) -> float:
    """Convert one table; returns elapsed seconds (cf. reference
    nds_transcode.py:179-194 timeit loop).

    Crash safety: a ``_SUCCESS`` marker is written inside the table dir
    only after the full write completes (loaders glob by extension, so
    the marker is invisible to them).  ``--resume`` skips marked tables;
    an UNMARKED existing dir on resume is a torn write from a killed
    run and is rebuilt from scratch."""
    start = time.time()
    out_root = os.path.join(args.output_prefix, table)
    marker = _success_marker(args, table)
    resume = getattr(args, "resume", False)
    if resume and os.path.exists(marker):
        print(f"[resume] {table}: _SUCCESS marker present — skipping")
        return 0.0
    faults.check("io.write", key=table)
    at = csvio.read_table_dir(args.input_prefix, table, tschema)
    if resume and os.path.exists(out_root) and \
            not os.path.exists(marker):
        # torn write from the killed run: rebuild the whole table
        print(f"[resume] {table}: incomplete output (no _SUCCESS) — "
              f"rebuilding")
        shutil.rmtree(out_root)
    if os.path.exists(out_root):
        if args.output_mode == "overwrite":
            shutil.rmtree(out_root)
        elif args.output_mode == "ignore":
            return 0.0
        elif args.output_mode == "errorifexists":
            raise RuntimeError(f"output for {table} already exists")
        # append: fall through, dataset write adds files
    if args.output_format in ("ndslake", "ndsdelta"):
        from ndstpu.io import lake
        if os.path.exists(out_root) and lake.is_lake(out_root):
            have = lake.detect(out_root)
            if have is not lake.module_for(args.output_format):
                raise RuntimeError(
                    f"{out_root} already holds the other ACID format; "
                    f"refusing to append {args.output_format} data into "
                    f"it (use --output_mode overwrite)")
            lake.append(out_root, at)  # append mode
        else:
            lake.create_table(args.output_format, out_root, at,
                              partition_col=FACT_PARTITION.get(table))
    elif table in FACT_PARTITION and args.output_format == "parquet":
        _write_partitioned(at, out_root, FACT_PARTITION[table],
                           args.compression)
    else:
        _write_single(at, out_root, table, args.output_format,
                      args.compression)
    _build_global_dicts(args, table, out_root, at)
    atomic.atomic_write_text(marker, "")
    return time.time() - start


def _build_global_dicts(args, table: str, out_root: str, at) -> None:
    """Build/grow the table's global string-dictionary sidecar
    (ndstpu/io/gdict.py) after the data write, before the _SUCCESS
    marker — so a marked table always has a sidecar covering it.
    Append mode unions with the existing sidecar (value set grows
    append-only); ACID formats stamp entries with the commit version
    so snapshot-pinned readers can select the dict matching their
    pin."""
    from ndstpu.io import gdict
    uniques = gdict.string_uniques_arrow(at)
    if not uniques:
        return
    table_version = None
    if args.output_format in ("ndslake", "ndsdelta"):
        from ndstpu.io import lake
        table_version = lake.current_version(out_root)
    gdict.update_sidecar(out_root, table, uniques,
                         table_version=table_version)


def transcode(args) -> None:
    start_time = datetime.now()
    use_decimal = not args.floats
    if args.update:
        schemas = nds_schema.get_maintenance_schemas(use_decimal)
        # delete-date tables stay raw CSV; DM reads them directly
        schemas = {t: s for t, s in schemas.items()
                   if t not in ("delete", "inventory_delete")}
    else:
        schemas = nds_schema.get_schemas(use_decimal)
    if args.tables:
        keep = args.tables.split(",")
        missing = [t for t in keep if t not in schemas]
        if missing:
            raise ValueError(f"unknown tables: {missing}")
        schemas = {t: schemas[t] for t in keep}

    results: "OrderedDict[str, float]" = OrderedDict()
    for table, tschema in schemas.items():
        print(f"transcoding {table} ...")
        results[table] = transcode_table(args, table, tschema)

    end_time = datetime.now()
    delta = (end_time - start_time).total_seconds()
    end_time_formatted = end_time.strftime("%m%d%H%M%S%f")[:-5]
    report = []
    report.append(f"Load Test Time: {delta} seconds")
    report.append(f"Load Test Finished at: {end_time}")
    report.append(f"RNGSEED used: {end_time_formatted}")
    for table, duration in results.items():
        report.append("Time to convert '%s' was %.04fs" % (table, duration))
    report.append("")
    report.append("Engine configuration follows:")
    report.append(f"output_format={args.output_format}")
    report.append(f"compression={args.compression}")
    report.append(f"use_decimal={use_decimal}")
    text = "\n".join(report) + "\n"
    print(text)
    if args.report_file:
        atomic.atomic_write_text(args.report_file, text)


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description="NDS load test (CSV -> warehouse)")
    p.add_argument("--input_prefix", required=True,
                   help="directory holding per-table raw .dat dirs")
    p.add_argument("--output_prefix", required=True,
                   help="warehouse output directory")
    p.add_argument("--report_file", default="load_report.txt",
                   help="load test report path")
    p.add_argument("--output_format", default="parquet",
                   choices=["parquet", "orc", "avro", "csv", "json",
                            "ndslake", "ndsdelta"])
    p.add_argument("--output_mode", default="overwrite",
                   choices=["overwrite", "append", "ignore", "errorifexists"])
    p.add_argument("--tables", help="comma-separated subset of tables")
    p.add_argument("--compression", default="snappy",
                   help="parquet compression codec")
    p.add_argument("--floats", action="store_true",
                   help="use double instead of decimal for money columns")
    p.add_argument("--update", action="store_true",
                   help="transcode refresh (maintenance staging) data")
    p.add_argument("--resume", action="store_true",
                   help="crash-safe resume: skip tables whose _SUCCESS "
                        "marker exists; rebuild tables whose output dir "
                        "exists without one (torn write from a killed "
                        "run)")
    return p


if __name__ == "__main__":
    transcode(build_parser().parse_args())
