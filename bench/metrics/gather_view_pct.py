"""Share of the window's mesh batches whose result rows were delivered
as views of their shards' own host blocks rather than assembled into
one host array, from the service's ``stats()["gather"]`` counters.
None where the service keeps no such counters or served no batch on a
mesh in the window."""


def read(run):
    a = run.stats_open.get("gather")
    b = run.stats_close.get("gather")
    if a is None or b is None:
        return None
    views = b["views"] - a["views"]
    n = views + b["assembled"] - a["assembled"]
    if n <= 0:
        return None
    return 100.0 * views / n
