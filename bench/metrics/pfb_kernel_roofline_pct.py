"""The fused PFB kernel's share of its roofline: the least device time
the kernel needs for the requests completed in the window, over the
device time of the trace's ops whose instruction starts with
``pfb_fused`` (the f32 kernel and the int8 one), summed over chips.

The least time of one request is the larger of the kernel's operations
over the chip's bf16 peak and its bytes over the peak memory bandwidth,
counted from the configuration as the algorithm needs them: the FIR's
multiply-adds (2 per tap per branch per frame) and a real-input radix-2
FFT (2.5 P log2 P per frame); each input sample read once as float32,
and the real and imaginary part of each output bin written once as
float32.  None without a trace or without such ops in its top entries."""
import math

import numpy as np

KERNEL = "pfb_fused"


def read(run):
    if run.trace is None:
        return None
    chips = len(run.trace["busy_s"])
    kernel_s = chips * sum(t for name, t in run.trace["device_ops"]
                           if name.startswith(KERNEL))
    if kernel_s <= 0:
        return None
    p = int(run.config["args"]["n_branches"])
    m = int(run.config["args"]["n_taps"])
    n = int(run.config["signal_len"])
    frames = n // p - m + 1
    flops = frames * (2 * m * p + 2.5 * p * math.log2(p))
    nbytes = 4 * n + 8 * frames * p
    least = max(flops / run.peaks["bf16_flops_per_s"],
                nbytes / run.peaks["hbm_bytes_per_s"])
    r = run.requests
    done = int(np.sum(r["ok"] & (r["done"] <= run.window_s)))
    return 100.0 * done * least / kernel_s
