"""Mean device-to-host pull-back time per batch (the copy and the host's
layout of the result) over the window's batches, from the service's
``stats()["latency_ms"]["fetch"]`` histogram.  None where the service
keeps no such histogram."""
from bench.metrics._histogram import window_mean


def read(run):
    return window_mean(run, "fetch")
