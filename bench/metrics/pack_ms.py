"""Mean host packing time per batch over the window's batches, from the
service's ``stats()["latency_ms"]["pad"]`` histogram (count and sum are
exact, so the window's share is their difference)."""


def read(run):
    a = run.stats_open["latency_ms"]["pad"]
    b = run.stats_close["latency_ms"]["pad"]
    n = b["count"] - a["count"]
    if n <= 0:
        return None
    return (b["mean"] * b["count"] - (a["mean"] or 0.0) * a["count"]) / n
