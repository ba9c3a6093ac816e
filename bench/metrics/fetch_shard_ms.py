"""Mean wait per shard for the copy to the host of a batch served on a
mesh, from the service's ``stats()["latency_ms"]["fetch_shard"]``
histogram.  Every shard's copy starts before the first wait, so copies
that overlap leave the first shard's wait holding them and the others'
near 0, and copies in series give each shard one copy's time; the rest
of ``fetch_ms``, ``fetch_ms`` - 4 x ``fetch_shard_ms`` on four chips,
is the host array's layout.  None on an unsharded service, whose
histogram stays empty, or one without it."""
from bench.metrics._histogram import window_mean


def read(run):
    return window_mean(run, "fetch_shard")
