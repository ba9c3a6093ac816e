#!/usr/bin/env python3
"""Read the correctness check's control on the chip: the plain reference
put in the program's place, one precision step below what the
configuration states (``control`` of ``bench/reference/<pipeline>.py``).

    python3 bench/control.py --config lofar_station_pfb --seeds 11,12,13

For each seed it builds the run's input pool, takes as many requests as a
run compares (``check.sample``, drawn from the seed as a run draws its
requests), computes them through the control in batches of the service's
per-chip batch, and prints the worst relative error against the float64
reference, the number a run compares with ``check.max_rel_err``.  A limit
is sound only if every seed's reading here lies above it.  The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import itertools
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def readings(config: dict, seeds, batch: int) -> list[dict]:
    import numpy as np

    from bench import harness, loadgen
    ctl = harness.module("reference", config["pipeline"]).control(
        config["args"])
    out = []
    for seed in seeds:
        pool = harness.make_pool(config, seed)
        idx = list(itertools.islice(loadgen.pool_order(seed, len(pool)),
                                    int(config["check"]["sample"])))
        sample = []
        for i in range(0, len(idx), batch):
            rows = idx[i:i + batch]
            got = np.asarray(ctl(pool[rows]))
            sample.extend(zip(rows, got))
        out.append({"seed": seed, "rel_err": harness.compare(sample, pool,
                                                             config),
                    "limit": config["check"]["max_rel_err"],
                    "compared": len(sample)})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from repro import compile_cache
    compile_cache.enable()
    from bench import harness

    config = harness._json(harness.ROOT / "bench" / "configs"
                           / f"{args.config}.json")
    harness.require_chips(1)
    mesh = config["compile_options"].get("mesh") or 1
    batch = int(config["batch_size"]) // int(mesh)
    for r in readings(config, [int(s) for s in args.seeds.split(",")],
                      batch):
        print(json.dumps(r), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
