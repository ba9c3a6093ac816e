"""Spread of device busy time over the cell's chips in the window, from
the profiler trace: 100 x (max - min) / mean of the busy seconds per
chip.  Near 0 when the chips stay in step; a starved chip raises it.
None without a trace or with fewer than two chips."""


def read(run):
    if run.trace is None or len(run.trace["busy_s"]) < 2:
        return None
    busy = run.trace["busy_s"]
    mean = sum(busy) / len(busy)
    if mean <= 0:
        return None
    return 100.0 * (max(busy) - min(busy)) / mean
