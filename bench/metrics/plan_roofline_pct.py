"""Least device time the requests completed in the window need, over the
device busy time in the window (profiler trace), summed over chips.  The
least time of one request is the larger of its operations over the
chip's peak rate and its bytes over the peak memory bandwidth, with the
work counted by ``bench/work`` whatever implements the pipeline."""
import numpy as np


def read(run):
    if run.trace is None or sum(run.trace["busy_s"]) <= 0:
        return None
    r = run.requests
    n = int(np.sum(r["ok"] & (r["done"] <= run.window_s)))
    least = max(run.work["flops"] / run.peaks["bf16_flops_per_s"],
                run.work["bytes"] / run.peaks["hbm_bytes_per_s"])
    return 100.0 * n * least / sum(run.trace["busy_s"])
