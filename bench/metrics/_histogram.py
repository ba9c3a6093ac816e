"""The window's share of one of the service's ``stats()["latency_ms"]``
histograms, for the readers of per-batch phase times.  Count and sum are
exact, so the window's mean is their difference between the opening and
the close."""


def window_mean(run, key: str):
    """Mean of histogram ``key`` over the window's records; None where
    the window holds none or the service keeps no such histogram."""
    a = run.stats_open["latency_ms"].get(key)
    b = run.stats_close["latency_ms"].get(key)
    if a is None or b is None or b["count"] <= a["count"]:
        return None
    n = b["count"] - a["count"]
    return (b["mean"] * b["count"] - (a["mean"] or 0.0) * a["count"]) / n
