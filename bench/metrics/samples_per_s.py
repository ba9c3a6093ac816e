"""Samples of the requests completed in the window, over the window, in
millions per second (host clock)."""
import numpy as np


def read(run):
    r = run.requests
    n = int(np.sum(r["ok"] & (r["done"] <= run.window_s)))
    return n * run.signal_len / run.window_s / 1e6
