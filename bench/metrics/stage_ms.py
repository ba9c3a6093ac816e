"""Mean host-to-device staging time per batch over the window's batches,
from the service's ``stats()["latency_ms"]["stage"]`` histogram.  None
where the service keeps no such histogram."""
from bench.metrics._histogram import window_mean


def read(run):
    return window_mean(run, "stage")
