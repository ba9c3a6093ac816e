#!/usr/bin/env python3
"""Device idle time put down to the program's spans, from a profiler
trace.

    python3 bench/span_idle.py <trace_dir>
    python3 bench/span_idle.py --workload <cell> --seed <n> --seconds <s>

The first form reads the ``.xplane.pb`` that ``jax.profiler`` wrote under
``trace_dir`` (``dsp_serve --jax-profiler DIR`` makes one).  The second
runs one cell as ``bench/run.py --trace 1`` does, prints the same two
lines, and then the summary of that run's trace (which the harness
deletes once it is reduced).

While a profiler records, every :mod:`repro.obs` span is also a
``TraceAnnotation`` on the host plane, on the device's clock; a program
span is a host event named ``service.*`` or ``python.*``.  Each instant
in which a chip runs no operation goes to the shortest program span open
at that instant on any host thread, or to ``"none"``.  The window is
the harness's :data:`trace_reduce.WINDOW` span where the trace has one,
else first to last device operation.

Prints one JSON object: ``window_s``; ``idle_s`` and ``idle_by_span``
(``[[span, seconds], ...]``, largest first), averaged over chips;
``idle_unattributed_pct``, the share of the idle time under no program
span; ``spans``, each program span's ``[count, seconds, longest
seconds]`` in the window; ``long_spans``, ``[span, at_s, seconds]`` of
every program span of :data:`LONG_S` or more other than
``service.idle`` (where the batcher waits for traffic), in time order;
and ``longest_idle``, the longest idle intervals of any chip, each with
its own ``idle_by_span``.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
from pathlib import Path

import numpy as np

if __name__ == "__main__":
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import trace_reduce  # noqa: E402

PROGRAM = ("service.", "python.")
NONE = "none"
LONG_S = 0.1
WAITING = "service.idle"


def program_spans(planes: list[dict]) -> tuple[np.ndarray, list[str]]:
    """([[start_ns, end_ns], ...], names) of the program spans on every
    host thread."""
    devs = {id(p) for p in trace_reduce.device_planes(planes)}
    iv, names = [], []
    for p in planes:
        if id(p) in devs:
            continue
        for events in p["lines"].values():
            for name, start, dur in events:
                if name.startswith(PROGRAM):
                    iv.append([start, start + dur])
                    names.append(name)
    return np.asarray(iv, float).reshape(-1, 2), names


def attribute(a: float, b: float, spans: np.ndarray,
              names: list[str]) -> dict[str, float]:
    """Nanoseconds of [a, b) under each name: every instant goes to the
    shortest program span open over it, else to :data:`NONE`."""
    hit = np.flatnonzero((spans[:, 0] < b) & (spans[:, 1] > a))
    cuts = np.unique(np.clip(np.concatenate(
        [[a, b], spans[hit].ravel()]), a, b))
    out: dict[str, float] = {}
    for x, y in zip(cuts[:-1], cuts[1:]):
        best, best_dur = NONE, np.inf
        for k in hit:
            s, e = spans[k]
            if s <= x and e >= y and e - s < best_dur:
                best, best_dur = names[k], e - s
        out[best] = out.get(best, 0.0) + (y - x)
    return out


def _window(planes: list[dict]) -> tuple[float, float]:
    try:
        return trace_reduce.window(planes)
    except RuntimeError:
        ev = [e for p in trace_reduce.device_planes(planes)
              for e in p["lines"].get(trace_reduce.OPS_LINE, [])]
        if not ev:
            raise
        return (float(min(e[1] for e in ev)),
                float(max(e[1] + e[2] for e in ev)))


def _idle(plane: dict, t0: float, t1: float) -> np.ndarray:
    """[[start_ns, end_ns], ...] in which the chip runs no operation,
    inside [t0, t1)."""
    ev = plane["lines"].get(trace_reduce.OPS_LINE, [])
    iv = np.clip(np.asarray([[s, s + d] for _, s, d in ev], float)
                 .reshape(-1, 2), t0, t1)
    busy = trace_reduce.merge(iv[iv[:, 1] > iv[:, 0]])
    edges = np.concatenate([[t0], busy.ravel(), [t1]]).reshape(-1, 2)
    return edges[edges[:, 1] > edges[:, 0]]


def _ranked(ns: dict[str, float], scale: float) -> list:
    return [[k, v / scale] for k, v in
            sorted(ns.items(), key=lambda kv: kv[1], reverse=True)]


def summarize(planes: list[dict], top: int = 10) -> dict:
    """The printed object (see the module's docstring)."""
    t0, t1 = _window(planes)
    devs = trace_reduce.device_planes(planes)
    if not devs:
        raise RuntimeError("no /device:TPU:<n> plane in the trace")
    spans, names = program_spans(planes)
    total: dict[str, float] = {}
    gaps = []
    for plane in devs:
        for a, b in _idle(plane, t0, t1):
            part = attribute(a, b, spans, names)
            for k, v in part.items():
                total[k] = total.get(k, 0.0) + v
            gaps.append((b - a, a, part))
    idle_ns = sum(total.values())
    per_span: dict[str, list] = {}
    long = []
    for (s, e), n in zip(spans, names):
        inside = (min(e, t1) - max(s, t0)) / 1e9
        if inside <= 0:
            continue
        c = per_span.setdefault(n, [0, 0.0, 0.0])
        c[0] += 1
        c[1] += inside
        c[2] = max(c[2], inside)
        if inside >= LONG_S and n != WAITING:
            long.append([n, (max(s, t0) - t0) / 1e9, inside])
    gaps.sort(key=lambda g: g[0], reverse=True)
    return {
        "window_s": (t1 - t0) / 1e9,
        "idle_s": idle_ns / len(devs) / 1e9,
        "idle_by_span": _ranked(total, len(devs) * 1e9),
        "idle_unattributed_pct": (100.0 * total.get(NONE, 0.0) / idle_ns
                                  if idle_ns > 0 else None),
        "spans": dict(sorted(per_span.items())),
        "long_spans": sorted(long, key=lambda x: x[1]),
        "longest_idle": [{"at_s": (a - t0) / 1e9, "idle_s": d / 1e9,
                          "idle_by_span": _ranked(part, 1e9)}
                         for d, a, part in gaps[:top]],
    }


@contextlib.contextmanager
def summaries():
    """While open, every trace that :func:`trace_reduce.reduce` reduces
    is also summarised; yields the list of summaries."""
    out: list[dict] = []
    reduce = trace_reduce.reduce

    def both(planes, *args, **kw):
        out.append(summarize(planes))
        return reduce(planes, *args, **kw)

    trace_reduce.reduce = both
    try:
        yield out
    finally:
        trace_reduce.reduce = reduce


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(usage=__doc__.split("\n\n")[1])
    ap.add_argument("trace_dir", nargs="?")
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int)
    ap.add_argument("--seconds", type=float)
    args = ap.parse_args(argv)
    if (args.trace_dir is None) == (args.workload is None):
        ap.print_usage(sys.stderr)
        return 2
    if args.trace_dir is not None:
        print(json.dumps(summarize(trace_reduce.load(args.trace_dir))))
        return 0
    if args.seed is None or args.seconds is None:
        ap.print_usage(sys.stderr)
        return 2
    from bench import run
    with summaries() as out:
        rc = run.main(["--workload", args.workload, "--seed",
                       str(args.seed), "--seconds", str(args.seconds),
                       "--trace", "1"])
    if rc == 0:
        print(json.dumps({"span_idle": out[0]}), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
