"""Share of the window's batches packed block by block, each shard's
rows on its own host thread, rather than whole on one thread, from the
service's ``stats()["pack_split"]`` counters.  None where the service
keeps no such counters or packed no batch in the window."""


def read(run):
    a = run.stats_open.get("pack_split")
    b = run.stats_close.get("pack_split")
    if a is None or b is None:
        return None
    sharded = b["sharded"] - a["sharded"]
    n = sharded + b["whole"] - a["whole"]
    if n <= 0:
        return None
    return 100.0 * sharded / n
