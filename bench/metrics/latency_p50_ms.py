"""Median latency of all requests due in the window, from each one's due
time to when its future resolved (host clock).  A failed request counts
as never served."""
import numpy as np


def read(run):
    lat = run.latency_ms()
    if not len(lat):
        return None
    with np.errstate(invalid="ignore"):   # inf - inf where requests failed
        return float(np.percentile(lat, 50))
