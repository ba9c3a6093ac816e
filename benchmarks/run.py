"""Benchmark entrypoint: ``python -m benchmarks.run``.

One section per paper table/figure (DESIGN.md §1):
  * Fig. 1 — arithmetic functions (elementwise mult/add, matmul, summation)
  * Fig. 2 — signal functions (DFT, IDFT, FIR, unfold)
  * Fig. 3 — PFB use case (frontend + full), speedups vs NumPy
  * Fig. 4 — compiled pipeline plans vs per-op dispatch (graph subsystem)
  * kernels — Pallas kernel structural metrics (VMEM footprint per block,
    arithmetic intensity) from the kernel specs; wall-clock kernel timing
    is meaningless in interpret mode, so the TPU story is carried by the
    dry-run roofline (EXPERIMENTS.md §Roofline).

``--quick`` shrinks sizes/repeats for CI.
"""
from __future__ import annotations

import argparse
import time

from benchmarks import (fig1_arithmetic, fig2_signal, fig3_pfb,
                        fig4_pipelines, kernel_specs)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None,
                    choices=[None, "fig1", "fig2", "fig3", "fig4", "kernels"])
    args = ap.parse_args(argv)

    sizes = (64, 256) if args.quick else (64, 256, 1024)
    rep = 5 if args.quick else 20
    pfb_sizes = (2 ** 12, 2 ** 14) if args.quick else (2 ** 14, 2 ** 16, 2 ** 18)
    pipe_sizes = (2 ** 12,) if args.quick else (2 ** 13, 2 ** 15)

    t0 = time.time()
    if args.only in (None, "fig1"):
        print(fig1_arithmetic.run(sizes, include_pallas=False, repeats=rep))
        print()
    if args.only in (None, "fig2"):
        print(fig2_signal.run(sizes, repeats=rep))
        print()
    if args.only in (None, "fig3"):
        print(fig3_pfb.run(pfb_sizes, repeats=max(3, rep // 2)))
        print()
    if args.only in (None, "fig4"):
        # --quick skips the block-tuning columns: tuning measures every
        # valid config per node in interpret mode (minutes on CPU)
        table, _ = fig4_pipelines.run(pipe_sizes, repeats=max(3, rep // 2),
                                      tuned=not args.quick)
        print(table)
        print()
    if args.only in (None, "kernels"):
        print(kernel_specs.run())
    print(f"\n[benchmarks done in {time.time() - t0:.1f}s]")


if __name__ == "__main__":
    from repro import compile_cache
    compile_cache.enable()
    main()
