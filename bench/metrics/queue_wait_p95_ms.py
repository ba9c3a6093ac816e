"""95th percentile of the service's queued phase (submit to dispatch, per
request) over the window's requests, from the service's own histogram
``stats()["latency_ms"]["queued"]``.  The histogram keeps its last 4096
samples, so a window with more requests reads its last 4096."""


def read(run):
    q = run.stats_close["latency_ms"]["queued"]
    return q["p95"] if q["count"] > run.stats_open["latency_ms"]["queued"][
        "count"] else None
