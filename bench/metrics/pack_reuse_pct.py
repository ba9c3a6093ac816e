"""Share of the window's batches packed into a reused host staging
buffer rather than a newly allocated one, from the service's
``stats()["pack_buffers"]`` counters.  None where the service keeps no
such counters or packed no batch in the window."""


def read(run):
    a = run.stats_open.get("pack_buffers")
    b = run.stats_close.get("pack_buffers")
    if a is None or b is None:
        return None
    reused = b["reused"] - a["reused"]
    n = reused + b["allocated"] - a["allocated"]
    if n <= 0:
        return None
    return 100.0 * reused / n
