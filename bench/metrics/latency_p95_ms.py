"""95th percentile of the latencies that latency_p50_ms reads."""
import numpy as np


def read(run):
    lat = run.latency_ms()
    if not len(lat):
        return None
    with np.errstate(invalid="ignore"):   # inf - inf where requests failed
        return float(np.percentile(lat, 95))
