"""The benchmark's harness: one run of one cell.

Everything that belongs to one cell is found by name, from
``BENCHMARK.json`` at the root of the checkout:

* ``bench/configs/<config>.json`` (the ``file`` of the configuration):
  the pipeline builder and its arguments, the request length and dtype,
  the ``CompileOptions``, the service batch size, the pool size and the
  correctness check (``sample``, ``max_rel_err``);
* ``bench/traffic/<traffic>.json``: the mix that :mod:`bench.loadgen`
  offers;
* ``bench/metrics/<metric>.py``: one reader per metric, ``read(run)``,
  returning a number or None when the run has nothing for it to read;
* ``bench/work/<pipeline>.py``: ``work(config)``, the operations and
  bytes of one request;
* ``bench/reference/<pipeline>.py``: ``reference(x, args)``, the plain
  reference the responses are compared with;
* ``bench/peaks.json``: the chip's peaks, keyed by ``device_kind``.

The timed entry is ``PipelineService.submit`` with continuous batching and
the default overlapped scheduler, so a response passes the admission
queue, packing and bucket choice, staging (sharded on a mesh), the
compiled plan and delivery before it is compared.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

from bench import loadgen, trace_reduce

ROOT = Path(__file__).resolve().parents[1]
DRAIN_S = 60.0     # how long past the close a response may still come


class NoChip(RuntimeError):
    """The run cannot be measured here: no TPU, too few chips, or a chip
    without an entry in the peaks table."""


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def module(kind: str, name: str, root: Path = ROOT):
    """``bench/<kind>/<name>.py``, loaded from its file."""
    path = root / "bench" / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}",
                                                  path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(f"no {kind} module {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = _json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]

    def mine(metrics):
        return [m for m in metrics
                if "workloads" not in m or name in m["workloads"]]

    return Cell(name=name, chips=int(w["chips"]),
                config=_json(root / conf["file"]),
                traffic=_json(root / "bench" / "traffic"
                              / f"{w['traffic']}.json"),
                end_to_end=mine(bench["end_to_end"]),
                per_layer=mine(bench["per_layer"]))


def peaks_for(kind: str, root: Path = ROOT) -> dict:
    peaks = _json(root / "bench" / "peaks.json")
    if kind not in peaks:
        raise NoChip(f"device_kind {kind!r} has no entry in bench/peaks.json "
                     f"(have {sorted(peaks)})")
    return peaks[kind]


def require_chips(chips: int, root: Path = ROOT):
    """The TPU devices and their peaks; never falls back to the CPU."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX's first device is {devices[0].platform!r} "
                     f"({devices[0].device_kind}), not a TPU")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees "
                     f"{len(devices)}")
    return devices, peaks_for(devices[0].device_kind, root)


def build_service(config: dict):
    from repro import graph
    g = getattr(graph, config["builder"])(**config["args"])
    opts = graph.CompileOptions(dtype=config["dtype"],
                                **config["compile_options"])
    return graph.PipelineService(g, int(config["signal_len"]),
                                 batch_size=int(config["batch_size"]),
                                 batching="continuous", options=opts)


def make_pool(config: dict, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return rng.standard_normal(
        (int(config["pool_requests"]), int(config["signal_len"])),
        dtype=np.dtype(config["dtype"]))


def warm(svc) -> None:
    """Run every bucket plan of the service as the batcher stages it, and
    pull the result back, without going through ``submit``."""
    import jax.numpy as jnp
    for b, plan in svc.plans.items():
        for _ in range(2):
            z = np.zeros((b, svc.signal_len), svc.dtype)
            x = plan.shard_inputs(z) if plan.input_shardings \
                else jnp.asarray(z)
            np.asarray(plan(x))


def compare(sample: list, pool: np.ndarray, config: dict,
            root: Path = ROOT) -> float:
    """Worst relative error, ||got - ref|| / ||ref||, over the sampled
    responses (inf for a wrong shape or a non-finite value)."""
    ref = module("reference", config["pipeline"], root)
    refs: dict[int, np.ndarray] = {}
    worst = 0.0
    for idx, out in sample:
        if idx not in refs:
            refs[idx] = ref.reference(pool[idx], config["args"])
        want = refs[idx]
        got = np.asarray(out, np.float64)
        if got.shape != want.shape or not np.isfinite(got).all():
            return math.inf
        worst = max(worst, float(np.linalg.norm(got - want)
                                 / np.linalg.norm(want)))
    return worst


@dataclasses.dataclass
class Run:
    """What the metric readers read.  Times are host-clock seconds; the
    request arrays are indexed by submission order, with times counted
    from the window's opening."""
    config: dict
    traffic: dict
    signal_len: int
    window_s: float
    setup_s: float
    requests: dict            # due, sub, done (nan: never), ok
    stats_open: dict          # PipelineService.stats() at the opening
    stats_close: dict         # ... once every window request resolved
    work: dict                # flops, bytes of one request
    peaks: dict
    trace: dict | None        # trace_reduce.reduce(), --trace 1 only

    def latency_ms(self) -> np.ndarray:
        r = self.requests
        lat = (r["done"] - r["due"]) * 1e3
        return np.where(r["ok"], lat, np.inf)


def _memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks, default=0)


def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool,
             t_start: float, root: Path = ROOT, require_chip: bool = True,
             peaks: dict | None = None, hook=None) -> tuple[dict, dict]:
    """One run: set-up, the measured window, the drain, the comparison
    with the reference.  Returns (load, result): the generator's books and
    the contract's result object.  ``require_chip=False`` (with ``peaks``)
    is for rehearsals on the CPU; ``hook(service)`` runs after warm-up,
    for tests that break the timed path."""
    import jax
    from jax import monitoring

    config, traffic = cell.config, cell.traffic
    if require_chip:
        devices, peaks = require_chips(cell.chips, root)
    else:
        devices = jax.devices()
    used = devices[:cell.chips]

    compiles = {"window": 0, "open": False}

    def on_event(event, *_a, **_k):
        if compiles["open"] and event.startswith("/jax/core/compile/"):
            compiles["window"] += 1

    monitoring.register_event_duration_secs_listener(on_event)
    try:
        svc = build_service(config)
        pool = make_pool(config, seed)
        warm(svc)
        if hook is not None:
            hook(svc)
        svc.start()
        stats_open = svc.stats()
        trace_dir = None
        if trace:
            trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
            # the Python tracer would slow the batcher the run measures;
            # the window is a TraceAnnotation, which needs none
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        t_open = time.perf_counter()
        setup_s = t_open - t_start
        compiles["open"] = True
        tally = loadgen.Tally(t_open, seed,
                              int(config["check"]["sample"]))
        kw = dict(seed=seed, seconds=seconds,
                  batch_size=int(config["batch_size"]), tally=tally)
        if trace:
            with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
                loadgen.drive(svc.submit, traffic, pool, **kw)
        else:
            loadgen.drive(svc.submit, traffic, pool, **kw)
        window_s = time.perf_counter() - t_open
        compiles["open"] = False
        if trace:
            jax.profiler.stop_trace()
        drained = tally.wait_all(DRAIN_S)
        stats_close = svc.stats()
        memory_peak = _memory_peak(used)
        svc.close()
    finally:
        monitoring.unregister_event_duration_listener(on_event)

    reduced = None
    if trace_dir is not None:
        try:
            reduced = trace_reduce.reduce(trace_reduce.load(trace_dir))
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)

    requests = tally.arrays()
    attempted = len(requests["ok"])
    failed = attempted - int(requests["ok"].sum())
    t_check = time.perf_counter()
    rel_err = compare(tally.sample, pool, config, root)
    limit = config["check"]["max_rel_err"]
    correct = (drained and attempted > 0 and failed == 0
               and len(tally.sample) > 0 and limit is not None
               and rel_err <= limit)
    check_s = time.perf_counter() - t_check

    run = Run(config=config, traffic=traffic,
              signal_len=int(config["signal_len"]), window_s=window_s,
              setup_s=setup_s, requests=requests, stats_open=stats_open,
              stats_close=stats_close,
              work=module("work", config["pipeline"], root).work(config),
              peaks=peaks, trace=reduced)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = module("metrics", m["name"], root).read(run)
        if v is not None and math.isfinite(v):
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    lateness = (requests["sub"] - requests["due"]) * 1e3
    load = {
        "attempted": attempted, "failed": failed,
        "completed_in_window": int(np.sum(requests["ok"]
                                          & (requests["done"] <= window_s))),
        "window_s": window_s, "compiles_in_window": compiles["window"],
        "lateness_ms": ({"p50": float(np.percentile(lateness, 50)),
                         "p99": float(np.percentile(lateness, 99)),
                         "max": float(lateness.max())}
                        if attempted else None),
        "batches": stats_close["batches"] - stats_open["batches"],
        "bucket_batches": stats_close.get("bucket_batches"),
        "compared": len(tally.sample), "check_s": check_s,
        "failures": sorted(set(tally.failed.values()))[:3],
    }
    device = {"platform": used[0].platform, "kind": used[0].device_kind,
              "count": jax.device_count(), "memory_peak_bytes": memory_peak}
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = sum(reduced["busy_s"]) / len(reduced["busy_s"])
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["check"] = {
        "rel_err": {"value": min(rel_err, 1e300), "limit": limit},
        "failed": {"value": failed, "limit": 0},
    }
    return load, result
