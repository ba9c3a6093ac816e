"""Work of one request of the PFB power pipeline, counted from its shapes.

The count is that of the algorithm, not of an implementation, so that a
factorised DFT, a lower-precision path or a fused transfer cannot read
above 100% of the roofline:

* bytes: each input sample read once and each output power written once,
  as float32;
* operations: the FIR multiply-adds (2 per tap per branch per frame), a
  real-input radix-2 FFT of the P branches (2.5 P log2 P per frame) and
  the power |z|^2 (3 per bin).
"""
from __future__ import annotations

import math


def work(config: dict) -> dict:
    p = int(config["args"]["n_branches"])
    m = int(config["args"]["n_taps"])
    n = int(config["signal_len"])
    frames = n // p - m + 1
    flops = frames * (2 * m * p + 2.5 * p * math.log2(p) + 3 * p)
    return {"flops": float(flops), "bytes": float(4 * n + 4 * frames * p)}
