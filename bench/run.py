#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``.  The run
needs a TPU with at least the cell's chips and an entry for its
``device_kind`` in ``bench/peaks.json``; without them it exits non-zero
and prints no result.  The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer metrics),
``device``, with ``--trace 1`` a ``breakdown``, and last ``check``: each
number compared with the reference beside its limit.  The line before
it holds the load generator's books.  JAX's compilation cache is kept by
:mod:`repro.compile_cache` (``<checkout>/.jax_cache`` unless
``JAX_COMPILATION_CACHE_DIR`` is set), so only a checkout's first run of
a cell compiles.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    os.environ["TINA_AUTOTUNE"] = "off"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # not /tmp/tpu_logs
    from repro import compile_cache

    import jax
    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from bench import harness

    cell = harness.load_cell(args.workload)
    try:
        load, result = harness.run_cell(
            cell, seed=args.seed, seconds=args.seconds,
            trace=bool(args.trace), t_start=T_START)
    except harness.NoChip as e:
        harness.log(f"not run: {e}")
        return 2
    print(json.dumps({"load": load}), flush=True)
    for name, c in result["check"].items():
        harness.log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
