"""Share of the window's batches whose bucket plan ran every node that
has an int8 implementation at int8, rather than falling back to f32,
from the service's ``stats()["tier"]`` counters.  None where the service
keeps no such counters or booked no batch in the window (a service whose
options do not ask for int8 books none)."""


def read(run):
    a = run.stats_open.get("tier")
    b = run.stats_close.get("tier")
    if a is None or b is None:
        return None
    int8 = b["int8"] - a["int8"]
    n = int8 + b["fallback"] - a["fallback"]
    if n <= 0:
        return None
    return 100.0 * int8 / n
