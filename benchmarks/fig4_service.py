"""Fig.4-service: fixed vs continuous batching under Poisson arrival
load — the serving-layer companion to fig4_pipelines.

GPTPU's lesson (and TINA's serving north star): sustained accelerator
utilization by non-NN workloads is won or lost in the request-staging
layer.  This benchmark drives the same Poisson arrival trace through a
``PipelineService`` in both batching modes and records what the staging
policy costs each request:

  * fixed       — every batch pads to ``--batch`` behind a
                  ``--max-wait-ms`` fill deadline: a request landing
                  just after a batch closed waits out the deadline, and
                  partial load pads most slots
  * continuous  — the scheduler dispatches the largest queued batch the
                  moment the device goes idle, through the pre-compiled
                  bucket-plan ladder (padding only to the next bucket)

Offered load is expressed as a fraction of the service's measured
full-batch capacity (``--load 0.5`` = half the request rate a saturated
device could sustain), so runs are comparable across machines.  Every
plan is warmed before the clock starts — the numbers are steady-state
staging policy, not XLA compile time.

Correctness is asserted, not assumed: the continuous run records every
batch packing and replays it through the same bucket plan, requiring
each delivered response to be **bit-for-bit** the replayed row
(:func:`repro.graph.service.replay_batches`); a sample of responses
from both modes is additionally checked against the pipeline's numpy
oracle.

Appends a run record (git rev + timestamp, p50/p99 latency +
throughput per mode) to ``BENCH_service.json`` via
:func:`benchmarks.common.append_bench_json`, so the serving-latency
trajectory accumulates across PRs like the pipeline one.  Each record
also carries the service's own telemetry as flat numeric fields — the
phase-attributed latency split (``<mode>_queued_ms_p50``,
``<mode>_pad_ms_p50``, ``<mode>_wait_ms_p50``, from
``service.stats()``) and the run's plan-cache hit/miss delta — so
``check_regression.py --metric continuous_wait_ms_p50`` can gate an
*attributed* phase, not just the end-to-end number.

The continuous mode is additionally run **twice** — once with the
blocking scheduler (``overlap=False``: pack, run, wait, repeat) and
once double-buffered (the service default: batch N+1 packs on the host
while N runs on the device); ``noverlap_p50_ms``/``noverlap_p99_ms``
are recorded for the comparison.  Device idle time is not measured
here: it comes from the profiler trace of a chip run (``bench/``).

Each record also carries a **multi-tenant priority point**: a second
pipeline served as a named tenant of the same service, requests
offered as one interleaved burst with the aux tenant on the ``rt``
priority class — per-class p50/p99 (``mt_rt_*``, ``mt_batch_*``) show
the rt class jumping the queue, and replay is verified bit-for-bit
per tenant (``mt_replayed``).

Each record also carries an **overload point**: the same trace offered
at ``--overload-load`` (default 1.5x) times capacity against a bounded
queue (``queue_limit = 2 * batch``) with ``on_full="shed"`` — served
p50/p99, shed ratio, and served throughput (``overload_*`` fields).
That is the admission-control claim in numbers: at offered load above
capacity the served latency distribution stays bounded because the
queue does, and exactly the shed requests pay for it (every shed future
fails typed with ``Overloaded``; anything else failing fails the
benchmark).
"""
from __future__ import annotations

import argparse
import time

import jax.numpy as jnp
import numpy as np

from benchmarks.common import append_bench_json, fmt_table
from repro.core.registry import PIPELINES, pipelines as _load_pipelines
from repro.graph import plan as plan_lib
from repro.graph.errors import Overloaded
from repro.graph.service import PipelineService, replay_batches


def drive(svc: PipelineService, signals, gaps, *, timeout=180.0,
          allow_shed=False, tenants=None, priorities=None):
    """Submit ``signals`` on the ``gaps`` inter-arrival schedule against
    a started service; returns (per-request latencies [s], makespan [s],
    served mask).

    Latency is submit -> future-done, stamped in the future's done
    callback (the batcher thread), so one slow consumer of a result
    can't inflate another request's number.

    ``allow_shed``: an overload drive against a bounded shedding queue —
    ``Overloaded`` futures are an expected outcome (masked out of
    ``served``); any *other* failure still raises, so a fault that isn't
    admission control fails the benchmark loudly.
    """
    n = len(signals)
    done_t = np.zeros(n)
    lat = np.zeros(n)
    ok = np.ones(n, dtype=bool)
    futs = []
    svc.start()
    t_start = time.perf_counter()
    next_t = t_start
    for i, (x, gap) in enumerate(zip(signals, gaps)):
        next_t += gap
        delay = next_t - time.perf_counter()
        if delay > 0:
            time.sleep(delay)        # the Poisson arrival process
        t_sub = time.perf_counter()
        fut = svc.submit(
            x,
            priority=priorities[i] if priorities else "batch",
            tenant=tenants[i] if tenants else None)

        def _done(f, i=i, t_sub=t_sub):
            done_t[i] = time.perf_counter()
            lat[i] = done_t[i] - t_sub

        fut.add_done_callback(_done)
        futs.append(fut)
    for i, f in enumerate(futs):
        try:
            f.result(timeout=timeout)   # every future must resolve
        except Overloaded:
            if not allow_shed:
                raise
            ok[i] = False
    svc.close()
    return lat, float(done_t.max() - t_start), ok


def _warm(svc: PipelineService) -> None:
    """Execute each bucket plan once so XLA compiles outside the
    measured window (steady-state serving, not cold start)."""
    for t in svc.tenants.values():
        for b, p in t.plans.items():
            np.asarray(p(jnp.zeros((b, t.signal_len), t.dtype)))


def run(pipeline="spectrogram", *, requests=200, max_batch=8,
        signal_len=4096, load=0.5, max_wait_ms=10.0, mesh=None,
        lowering="native", check=8, seed=0, overload_load=1.5):
    _load_pipelines()
    spec = PIPELINES[pipeline]
    g = spec.build()
    n = spec.valid_len(signal_len)
    rng = np.random.default_rng(seed)
    signals = [rng.standard_normal(n).astype(np.float32)
               for _ in range(requests)]

    opts = plan_lib.CompileOptions(lowering=lowering, mesh=mesh)

    # capacity: how fast a saturated device turns over full batches
    probe = PipelineService(g, signal_len=n, batch_size=max_batch,
                            batching="fixed", options=opts)
    _warm(probe)
    # tile if requests < max_batch: the probe must time a FULL batch or
    # capacity comes out ~2x high and the offered load lands in overload
    xb = jnp.asarray(np.stack([signals[i % len(signals)]
                               for i in range(max_batch)]))
    ts = []
    for _ in range(5):
        t0 = time.perf_counter()
        np.asarray(probe.plan(xb))
        ts.append(time.perf_counter() - t0)
    # min, not mean: a contention spike in the probe inflates the
    # offered rate into an overload regime and poisons the whole trace
    t_full = min(ts)
    probe.close()
    capacity = max_batch / t_full              # req/s at saturation
    rate = load * capacity
    # one shared arrival trace: "equal offered load" means equal traces
    gaps = rng.exponential(1.0 / rate, size=requests)

    results = {}
    cache0 = plan_lib.cache_stats()
    # three schedulers against ONE arrival trace: fixed packing,
    # blocking continuous (each batch packs only after the previous one
    # retires), and overlapped continuous (the service default: batch
    # N+1 packs while N runs)
    for mode, overlap in (("fixed", False), ("noverlap", False),
                          ("continuous", True)):
        batching = "fixed" if mode == "fixed" else "continuous"
        svc = PipelineService(g, signal_len=n, batch_size=max_batch,
                              batching=batching, options=opts,
                              overlap=overlap,
                              max_wait_ms=max_wait_ms,
                              record_batches=(batching == "continuous"))
        _warm(svc)
        lat, makespan, _ = drive(svc, signals, gaps)
        if batching == "continuous":
            checked = replay_batches(svc)      # bit-for-bit vs packing
            assert checked == requests, (checked, requests)
        s = svc.stats()
        results[mode] = {
            "p50_ms": float(np.percentile(lat, 50) * 1e3),
            "p99_ms": float(np.percentile(lat, 99) * 1e3),
            "mean_ms": float(lat.mean() * 1e3),
            "throughput_req_s": requests / makespan,
            "batches": s["batches"],
            "fill": s["fill_ratio"],
            "bucket_batches": s.get("bucket_batches"),
            # the service's own phase attribution: where each request's
            # wall clock went (queue wait vs padding vs device wait)
            **{f"{phase}_ms_{q}": s["latency_ms"][phase][q]
               for phase in ("queued", "pad", "wait")
               for q in ("p50", "p99")},
        }
        del svc
    cache1 = plan_lib.cache_stats()

    # the overload point: offered load ABOVE capacity against a bounded
    # queue with shedding on — what the latency distribution and shed
    # ratio look like when admission control is doing its job (an
    # unbounded queue here would show runaway p99, not a policy)
    ov_limit = 2 * max_batch
    ov = PipelineService(g, signal_len=n, batch_size=max_batch,
                         batching="continuous", options=opts,
                         queue_limit=ov_limit, on_full="shed",
                         record_batches=True)
    _warm(ov)
    rate_ov = overload_load * capacity
    gaps_ov = rng.exponential(1.0 / rate_ov, size=requests)
    lat_ov, makespan_ov, ok = drive(ov, signals, gaps_ov, allow_shed=True)
    served = int(ok.sum())
    assert replay_batches(ov) == served      # admitted rows stay bitwise
    s_ov = ov.stats()
    assert s_ov["shed"] == requests - served, (s_ov["shed"], served)
    served_lat = lat_ov[ok] if served else np.zeros(1)
    overload = {
        "overload_offered_load": float(overload_load),
        "overload_queue_limit": int(ov_limit),
        "overload_served": served,
        "overload_shed": int(s_ov["shed"]),
        "overload_shed_ratio": float(s_ov["shed"]) / requests,
        "overload_p50_ms": float(np.percentile(served_lat, 50) * 1e3),
        "overload_p99_ms": float(np.percentile(served_lat, 99) * 1e3),
        "overload_throughput_req_s": served / makespan_ov,
    }
    del ov

    # the multi-tenant priority point: a second pipeline served as a
    # named tenant of the same device pool, requests offered as one
    # interleaved burst (a queue forms instantly) with the aux tenant on
    # the rt class — rt jumps the queue order, so its latency
    # distribution should sit below the batch class's, and replay must
    # stay bit-for-bit PER TENANT (each tenant packs its own batches)
    aux_name = "pfb_power" if pipeline != "pfb_power" else "spectrogram"
    aux = PIPELINES[aux_name]
    g2 = aux.build()
    n2 = aux.valid_len(signal_len)
    mt = PipelineService(g, signal_len=n, batch_size=max_batch,
                         batching="continuous", options=opts,
                         record_batches=True)
    mt.add_tenant("aux", g2, n2, record_batches=True)
    rng2 = np.random.default_rng(seed + 1)
    pairs = max(max_batch, min(requests // 2, 64))
    xs, tns, prs = [], [], []
    for i in range(pairs):
        xs.append(signals[i % len(signals)])
        tns.append(None)                       # default tenant
        prs.append("batch")
        xs.append(rng2.standard_normal(n2).astype(np.float32))
        tns.append("aux")
        prs.append("rt")
    lat_mt, _, _ = drive(mt, xs, [0.0] * len(xs),
                         tenants=tns, priorities=prs)
    mt_replayed = (replay_batches(mt, tenant="default")
                   + replay_batches(mt, tenant="aux"))
    assert mt_replayed == len(xs), (mt_replayed, len(xs))
    multi_tenant = {
        "mt_requests": len(xs),
        "mt_replayed": int(mt_replayed),
        "mt_batch_p50_ms": float(np.percentile(lat_mt[0::2], 50) * 1e3),
        "mt_batch_p99_ms": float(np.percentile(lat_mt[0::2], 99) * 1e3),
        "mt_rt_p50_ms": float(np.percentile(lat_mt[1::2], 50) * 1e3),
        "mt_rt_p99_ms": float(np.percentile(lat_mt[1::2], 99) * 1e3),
    }
    del mt

    # oracle spot-check outside the timed window: the numerics path is
    # identical to the driven services (same bucket plans), and the
    # continuous packing replay above already pinned responses bitwise
    ref = PipelineService(g, signal_len=n, batch_size=max_batch,
                          batching="continuous", options=opts)
    futs = [ref.submit(signals[i]) for i in range(min(check, requests))]
    ref.flush()
    for i, f in enumerate(futs):
        np.testing.assert_allclose(f.result(timeout=30),
                                   spec.oracle(signals[i]),
                                   rtol=2e-3, atol=2e-3)
    ref.close()

    rec = {"pipeline": pipeline, "n": int(n), "max_batch": int(max_batch),
           "requests": int(requests), "offered_load": float(load),
           "rate_req_s": float(rate), "capacity_req_s": float(capacity),
           "max_wait_ms": float(max_wait_ms), "lowering": lowering,
           **{f"{m}_{k}": v for m in results for k, v in results[m].items()
              if k != "bucket_batches"},
           "continuous_bucket_batches":
               results["continuous"]["bucket_batches"],
           # plan-cache churn across both driven services: steady-state
           # serving should be all hits after the ladders compile
           "plan_cache_hits": cache1["hits"] - cache0["hits"],
           "plan_cache_misses": cache1["misses"] - cache0["misses"],
           "p50_speedup": (results["fixed"]["p50_ms"]
                           / results["continuous"]["p50_ms"]),
           "p99_speedup": (results["fixed"]["p99_ms"]
                           / results["continuous"]["p99_ms"]),
           **multi_tenant, **overload}
    rows = [[m, f"{r['p50_ms']:.2f}", f"{r['p99_ms']:.2f}",
             f"{r['throughput_req_s']:.1f}", r["batches"],
             f"{r['fill']:.0%}"] for m, r in results.items()]
    rows.append([f"shed@{overload_load:g}x",
                 f"{overload['overload_p50_ms']:.2f}",
                 f"{overload['overload_p99_ms']:.2f}",
                 f"{overload['overload_throughput_req_s']:.1f}",
                 f"{served}/{requests}",
                 f"{overload['overload_shed_ratio']:.0%} shed"])
    rows.append(["mt rt|batch",
                 f"{multi_tenant['mt_rt_p50_ms']:.2f}|"
                 f"{multi_tenant['mt_batch_p50_ms']:.2f}",
                 f"{multi_tenant['mt_rt_p99_ms']:.2f}|"
                 f"{multi_tenant['mt_batch_p99_ms']:.2f}",
                 "-", f"{len(xs)} req", "2 tenants"])
    table = fmt_table(
        f"Fig.4-service: {pipeline} n={n} batch<= {max_batch} "
        f"Poisson load {load:.0%} of capacity ({rate:.1f} req/s), "
        f"overload row at {overload_load:g}x with queue_limit={ov_limit}",
        ["batching", "p50_ms", "p99_ms", "req/s", "batches", "fill"], rows)
    return table, rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--pipeline", default="spectrogram")
    ap.add_argument("--requests", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--signal-len", type=int, default=4096)
    ap.add_argument("--load", type=float, default=0.5,
                    help="offered load as a fraction of measured "
                         "full-batch capacity (partial load is where "
                         "the staging policy matters)")
    ap.add_argument("--max-wait-ms", type=float, default=10.0,
                    help="fixed-mode fill deadline (continuous ignores)")
    ap.add_argument("--lowering", default="native",
                    choices=["native", "conv", "pallas", "auto"])
    ap.add_argument("--mesh", type=int, default=0,
                    help="shard each bucket across N devices")
    ap.add_argument("--check", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--overload-load", type=float, default=1.5,
                    help="offered load (x capacity) for the overload-"
                         "point row driven against a bounded shedding "
                         "queue (must exceed 1 to mean anything)")
    ap.add_argument("--out", default="BENCH_service.json")
    args = ap.parse_args(argv)
    table, rec = run(args.pipeline, requests=args.requests,
                     max_batch=args.batch, signal_len=args.signal_len,
                     load=args.load, max_wait_ms=args.max_wait_ms,
                     mesh=args.mesh or None, lowering=args.lowering,
                     check=args.check, seed=args.seed,
                     overload_load=args.overload_load)
    print(table)
    path = append_bench_json(args.out, [rec], figure="fig4_service",
                             requests=args.requests, load=args.load)
    print(f"\n[fig4_service] p50 {rec['fixed_p50_ms']:.2f} ms (fixed) -> "
          f"{rec['continuous_p50_ms']:.2f} ms (continuous), "
          f"{rec['p50_speedup']:.2f}x; overload {args.overload_load:g}x: "
          f"p50/p99 {rec['overload_p50_ms']:.2f}/"
          f"{rec['overload_p99_ms']:.2f} ms at "
          f"{rec['overload_shed_ratio']:.0%} shed; "
          f"2-tenant rt/batch p99 {rec['mt_rt_p99_ms']:.2f}/"
          f"{rec['mt_batch_p99_ms']:.2f} ms "
          f"({rec['mt_replayed']} replayed); appended run to {path}")


if __name__ == "__main__":
    from repro import compile_cache
    compile_cache.enable()
    main()
