"""Share of the window in which no operation ran on the device, from the
profiler trace, averaged over the cell's chips."""


def read(run):
    if run.trace is None:
        return None
    busy = run.trace["busy_s"]
    return 100.0 * (1.0 - sum(busy) / len(busy) / run.trace["window_s"])
