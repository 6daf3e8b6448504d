#!/usr/bin/env python3
"""Find a served cell's knee once: offer the cell's mix at each of
``--rates`` for ``--seconds``, in one process, and print one line per
rate.  Not a result.  The knee is the highest rate at which the backlog
does not grow through the sub-window (last third no slower than the
first) and nothing is shed; the cell then offers 0.8 x that, written
into its workload file.

    python3 benchmark/sweep.py --workload <cell> --seed <n> --seconds 15 \\
        --rates 8,10,12,14,16,18
"""

from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run as bench  # noqa: E402
from benchmark.harness import traffic  # noqa: E402


def main(argv=None) -> int:
    p = bench.build_parser()
    p.add_argument("--rates", required=True)
    args = p.parse_args(argv)
    cell, drv, paths, sf = bench.set_up(args)
    wl = cell.workload
    texts, by_template = drv.cell_texts(wl, paths["streams"])
    daemon = drv.Daemon(cell, args, paths, sf)
    try:
        drv.warm_up(daemon, cell, texts, args.rehearse_cpu)
        for k, rate in enumerate(float(x) for x in args.rates.split(",")):
            schedule = traffic.open_loop_schedule(
                wl, by_template, args.seed + k, args.seconds, rate_rps=rate)
            records, t0 = drv.offer(daemon, wl, texts, schedule,
                                    tag=f"s{k}r")
            close = t0 + args.seconds
            lat = drv.latencies_ms(records, close + drv.STRAGGLER_WAIT_S)
            third = max(len(lat) // 3, 1)
            print("SWEEP " + json.dumps({
                "rate_rps": rate, "n": len(records),
                "ok": sum(1 for r in records if r["ok"]),
                "p50_ms": drv.percentile(lat, 0.5),
                "p95_ms": drv.percentile(lat, 0.95),
                # a backlog that grows shows as a last third slower
                # than the first
                "p50_first_third_ms": drv.percentile(lat[:third], 0.5),
                "p50_last_third_ms": drv.percentile(lat[-third:], 0.5),
                "drained_after_close_s": max(
                    r["done"] for r in records) - close}), flush=True)
    finally:
        daemon.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
