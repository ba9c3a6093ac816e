#!/usr/bin/env python3
"""Sweep the burst rate of an open-loop mix on the chip, to find the knee:
the highest rate at which the backlog does not grow.

    python3 bench/knee.py --workload lofar_station_pfb.bursts \
        --rates 10,11,12,13,14,15 --seconds 30 --seed 1

The workload (an entry of ``BENCHMARK.json``) names the configuration,
the open-loop mix and the chips.  One service, warmed once, serves each rate in turn for ``--seconds``.
Each rate prints one JSON line: latency p50/p95 from due time, the share
of due requests completed inside the window, and the growth of latency
from the first to the last quarter of the window (a growing backlog).
The knee found is written into the traffic file by hand, as a number,
with ``bursts_per_s`` set to 0.8 of it.  Sweep with windows as long as a
run's (``run_seconds``): a host stall backlogs the service, and a short
window can end before it shows whether the backlog drains.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    os.environ["TINA_AUTOTUNE"] = "off"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from repro import compile_cache
    compile_cache.enable()
    import numpy as np

    from bench import harness, loadgen

    cell = harness.load_cell(args.workload)
    config, traffic = cell.config, cell.traffic
    harness.require_chips(cell.chips)
    svc = harness.build_service(config)
    pool = harness.make_pool(config, args.seed)
    harness.warm(svc)
    with svc:
        for rate in [float(r) for r in args.rates.split(",")]:
            mix = dict(traffic, bursts_per_s=rate)
            tally = loadgen.Tally(time.perf_counter(), args.seed, 0)
            loadgen.drive(svc.submit, mix, pool, seed=args.seed,
                          seconds=args.seconds,
                          batch_size=int(config["batch_size"]), tally=tally)
            tally.wait_all(harness.DRAIN_S)
            r = tally.arrays()
            lat = (r["done"] - r["due"]) * 1e3
            q = len(lat) // 4
            print(json.dumps({
                "bursts_per_s": rate, "requests": len(lat),
                "p50_ms": float(np.percentile(lat, 50)),
                "p95_ms": float(np.percentile(lat, 95)),
                "done_in_window": float(np.mean(r["done"] <= args.seconds)),
                "growth_ms": float(lat[-q:].mean() - lat[:q].mean()),
                "lateness_max_ms": float(((r["sub"] - r["due"]) * 1e3).max()),
            }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
