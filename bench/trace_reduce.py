"""Profiler trace -> device busy time per chip, top device ops, and the
longest idle gaps labelled by what the host was doing.

A trace is reduced from a plain structure, so the reduction can be
checked on a hand-built trace:

    [{"name": "/device:TPU:0",
      "lines": {"XLA Ops": [[op_name, start_ns, duration_ns], ...], ...}},
     {"name": "/host:CPU", "lines": {thread_name: [[...], ...]}}, ...]

:func:`load` builds it from the ``.xplane.pb`` that ``jax.profiler``
writes.  Busy time is the union of the intervals in which an operation
of the ``XLA Ops`` line runs on a chip, clipped to the measured window;
the window is the host span named :data:`WINDOW` that the harness
records around it.
"""
from __future__ import annotations

import glob
import os
import re

import numpy as np

OPS_LINE = "XLA Ops"
WINDOW = "bench.window"
_DEVICE = re.compile(r"/device:TPU:(\d+)$")
_OP = re.compile(r"%?(\S+) = \(?(\w+\[[\d,]*\])")


def load(trace_dir: str) -> list[dict]:
    """Planes of the one ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {paths}")
    planes = []
    for plane in ProfileData.from_file(paths[0]).planes:
        lines = {}
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                [e.name, e.start_ns, e.duration_ns] for e in line.events)
        planes.append({"name": plane.name, "lines": lines})
    return planes


def device_planes(planes: list[dict]) -> list[dict]:
    devs = [p for p in planes if _DEVICE.match(p["name"])]
    return sorted(devs, key=lambda p: int(_DEVICE.match(p["name"])[1]))


def window(planes: list[dict]) -> tuple[float, float]:
    """(start_ns, end_ns) of the host span named :data:`WINDOW`."""
    for p in planes:
        if _DEVICE.match(p["name"]):
            continue
        for events in p["lines"].values():
            for name, start, dur in events:
                if name == WINDOW:
                    return float(start), float(start + dur)
    raise RuntimeError(f"no {WINDOW!r} span in the trace")


def merge(intervals: np.ndarray) -> np.ndarray:
    """Union of [start, end) intervals, as sorted disjoint rows."""
    if len(intervals) == 0:
        return np.zeros((0, 2))
    iv = intervals[np.argsort(intervals[:, 0], kind="stable")]
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out, float)


def op_name(hlo: str) -> str:
    """``%pfb_fused.1 = (f32[8,1024,1024]{...}, ...) custom-call(...)`` ->
    ``pfb_fused.1 f32[8,1024,1024]``: the instruction and its first
    result shape, without the operands."""
    m = _OP.match(hlo)
    return f"{m[1]} {m[2]}" if m else hlo[:120]


def _ops(plane: dict, t0: float, t1: float):
    ev = plane["lines"].get(OPS_LINE, [])
    names = [op_name(e[0]) for e in ev]
    iv = np.asarray([[e[1], e[1] + e[2]] for e in ev], float).reshape(-1, 2)
    iv = np.clip(iv, t0, t1)
    keep = iv[:, 1] > iv[:, 0]
    return [n for n, k in zip(names, keep) if k], iv[keep]


def _host_label(planes: list[dict], a: float, b: float) -> str:
    """What the host was doing in [a, b): the shortest host event that
    covers at least half of it, else the one that covers most of it."""
    best, best_key = "no host event", None
    for p in planes:
        if _DEVICE.match(p["name"]):
            continue
        for line, events in p["lines"].items():
            for name, start, dur in events:
                if name == WINDOW:
                    continue
                cover = min(b, start + dur) - max(a, start)
                if cover <= 0:
                    continue
                half = cover >= 0.5 * (b - a)
                key = (half, -dur if half else cover)
                if best_key is None or key > best_key:
                    best, best_key = f"{line}: {name}"[:120], key
    return best


def reduce(planes: list[dict], top: int = 10) -> dict:
    """Busy seconds per chip inside the window, the window's length, the
    ``top`` device ops by mean time per chip, and the ``top`` longest
    idle gaps (any chip), each labelled by host activity."""
    t0, t1 = window(planes)
    devs = device_planes(planes)
    if not devs:
        raise RuntimeError("no /device:TPU:<n> plane in the trace")
    busy, per_op, gaps = [], {}, []
    for plane in devs:
        names, iv = _ops(plane, t0, t1)
        for n, (s, e) in zip(names, iv):
            per_op[n] = per_op.get(n, 0.0) + (e - s) / len(devs)
        u = merge(iv)
        busy.append(float((u[:, 1] - u[:, 0]).sum()) / 1e9)
        edges = np.concatenate([[t0], u.ravel(), [t1]]).reshape(-1, 2)
        gaps.extend((float(s), float(e)) for s, e in edges if e - s > 1e3)
    gaps.sort(key=lambda g: g[1] - g[0], reverse=True)
    ops = sorted(per_op.items(), key=lambda kv: kv[1], reverse=True)[:top]
    return {
        "window_s": (t1 - t0) / 1e9,
        "busy_s": busy,
        "device_ops": [[n, t / 1e9] for n, t in ops],
        "idle_gaps": [[_host_label(planes, s, e), (e - s) / 1e9]
                      for s, e in gaps[:top]],
    }
