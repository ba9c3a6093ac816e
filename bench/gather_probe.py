#!/usr/bin/env python3
"""Time the host side of gathering one sharded batch result, apart from
the service.

    python3 bench/gather_probe.py --config lofar_station_pfb_x4 [--repeats 10]

One batch of the configuration's output (``batch_size`` rows shaped as
the reference's result for one request), sharded along the batch axis
over the configuration's mesh, is made on the chips and pulled back to
the host ``--repeats`` times: ``np.asarray`` of the whole, and apart,
after ``copy_to_host_async``, the wait for each shard (the first, then
the rest) and the copy of the row blocks into a fresh ``np.empty`` and
into one reused array.  Prints one JSON line of the milliseconds of
every try and one of their medians.  The benchmark's own runs never run
this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def probe(shape, devices, repeats: int):
    """Milliseconds per try of each way to pull a ``shape`` f32 array,
    sharded along its first axis over ``devices``, back to the host; and
    the reused host array as the last try left it."""
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    sharding = NamedSharding(Mesh(np.array(devices), ("b",)),
                             PartitionSpec("b"))
    step = jax.jit(lambda x, i: x + i, out_shardings=sharding)
    x = jax.device_put(np.zeros(shape, np.float32), sharding)
    keep = np.empty(shape, np.float32)
    times = {k: [] for k in ("asarray", "wait_first", "wait_rest",
                             "fill_fresh", "fill_reused")}

    def ms(t0):
        return (time.perf_counter() - t0) * 1e3

    for i in range(repeats):
        y = step(x, float(i)).block_until_ready()
        t0 = time.perf_counter()
        np.asarray(y)
        times["asarray"].append(ms(t0))
        y = step(x, i + 0.5).block_until_ready()
        y.copy_to_host_async()
        blocks = []
        for k, s in enumerate(y.addressable_shards):
            t0 = time.perf_counter()
            rows = np.asarray(s.data)
            times["wait_first" if k == 0 else "wait_rest"].append(ms(t0))
            blocks.append((s.index, rows))
        t0 = time.perf_counter()
        host = np.empty(shape, np.float32)
        for index, rows in blocks:
            host[index] = rows
        times["fill_fresh"].append(ms(t0))
        del host
        t0 = time.perf_counter()
        for index, rows in blocks:
            keep[index] = rows
        times["fill_reused"].append(ms(t0))
    return times, keep


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--repeats", type=int, default=10)
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import numpy as np

    from bench import harness

    config = harness._json(harness.ROOT / "bench" / "configs"
                           / f"{args.config}.json")
    chips = int(config["compile_options"].get("mesh") or 1)
    devices, _ = harness.require_chips(chips)
    row = harness.module("reference", config["pipeline"]).reference(
        np.zeros(int(config["signal_len"])), config["args"]).shape
    times, _ = probe((int(config["batch_size"]), *row), devices[:chips],
                     args.repeats)
    print(json.dumps(times), flush=True)
    print(json.dumps({k: float(np.median(v)) for k, v in times.items()}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
