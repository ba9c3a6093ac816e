"""DSP pipeline serving launcher: batched requests through cached plans.

    PYTHONPATH=src python -m repro.launch.dsp_serve \\
        --pipeline spectrogram --requests 64 --batch 8 --signal-len 4096

Spins up a :class:`repro.graph.service.PipelineService` for one built-in
pipeline, drives it with synthetic requests from a background batcher
thread, validates a sample of responses against the pipeline's numpy
oracle, and reports throughput + batching efficiency.  ``--lowering
auto`` engages the measurement-based autotuner (winners persist to the
on-disk tuning cache, so a second launch skips the measurements).

The service's batcher dispatches the largest queued batch the moment
the device goes idle, through a ladder of pre-compiled bucket plans
(1/2/4/…/--batch), padding only up to the next bucket, and packs batch
N+1 on the host while the device runs batch N.  ``--prewarm`` tunes
every bucket shape, not just the full batch.

Mesh serving: ``--mesh N`` shards every batch across N devices (batch
must divide evenly); ``--devices N`` forces the host platform to expose
N virtual devices (CPU dev boxes / CI — set before jax initializes, so
it must be a flag here, not an afterthought env var).

Deploy-time cache pre-warm: ``--prewarm`` runs the measurement-based
autotuner for the exact serving shape ``(batch, signal_len)`` *before*
the service accepts traffic, regardless of the ambient
``TINA_AUTOTUNE`` mode — so a production launch with
``TINA_AUTOTUNE=cached`` still serves tuned kernels: the pre-warm pass
persists winners to the on-disk cache and the (cached-mode) service
plan compiles against them.

Robustness: ``--queue-limit N --on-full shed|block|raise`` bounds the
admission queue, ``--deadline-ms`` stamps a per-request scheduling
deadline, ``--max-retries`` caps transient-failure retries, and
``--validate strict`` rejects non-finite payloads at submit.  The drive
loop is outcome-tolerant — every future resolves with a result or a
typed exception, and a robustness counter summary (shed / expired /
retried / quarantined / degraded + injected-fault counts) is printed
when anything non-nominal happened.  The launch exits non-zero when a
request that was not deliberately poisoned failed (other than by
shedding or deadline expiry) or a bucket degraded to the reference
lowering.  ``--poison K`` deliberately
corrupts K requests with NaNs and **asserts** they all fail typed (and
that no healthy request was harmed) — pair it with
``TINA_FAULTS="device_run:nan"`` to exercise the service's bisection
quarantine end to end (chaos CI does exactly this).

Multi-tenant serving: ``--tenants pfb_power,fir_decimate`` adds extra
pipelines as named tenants of the same service — one shared device
pool, one priority-aware queue, per-tenant plans/stats/replay.
Requests round-robin across every tenant.  ``--priority mix``
alternates rt/batch priority classes across requests (rt jumps the
queue but never preempts a running batch).

Asyncio front door: ``--async`` drives the whole request load through
``await service.submit_async(...)`` under ``async with`` — the same
futures, batching, and robustness machinery, natively awaitable.

Observability: ``--trace out.json`` turns span collection on
(equivalent to ``TINA_TELEMETRY=on``) and writes a Chrome trace of the
whole run — plan compilation, autotune selection, each batch's host
phases, per-thread tracks — openable at ``chrome://tracing`` or
https://ui.perfetto.dev.  ``--metrics-interval S`` prints a JSON
metrics snapshot (service stats + plan-cache + autotuner counters) to
stderr every S seconds while serving.  ``--jax-profiler DIR`` brackets
the serving window with jax's profiler: the operator's one-file view,
since the ``.xplane.pb`` under DIR holds the device's ops and, on the
same clock, every program span of the window (``service.pack``,
``service.stage``, ``service.wait``, ``service.fetch``, ``python.gc``,
...), whether or not ``--trace`` is given (TensorBoard / Perfetto,
or ``jax.profiler.ProfileData``).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

import numpy as np    # jax-free: safe before the --devices flag lands


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--pipeline", default="spectrogram")
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--signal-len", type=int, default=4096)
    ap.add_argument("--lowering", default="native",
                    choices=["native", "conv", "pallas", "auto"])
    ap.add_argument("--precision", default="f32",
                    choices=["f32", "bf16", "int8", "auto"],
                    help="execution tier for every bucket plan: int8 "
                         "runs the quantized kernels (weights quantized "
                         "once at plan build), bf16 rounds through "
                         "bfloat16 around f32 accumulate, auto lets the "
                         "autotuner pick per node under each OpDef's "
                         "accuracy budget (responses are oracle-checked "
                         "by SQNR instead of allclose below f32)")
    ap.add_argument("--tune-blocks", action="store_true",
                    help="autotune Pallas block sizes for the chosen "
                         "lowering (lowering=auto already tunes them "
                         "jointly)")
    ap.add_argument("--mesh", type=int, default=0, metavar="N",
                    help="shard each batch across N devices (0 = "
                         "single-device plan)")
    ap.add_argument("--devices", type=int, default=0, metavar="N",
                    help="force the host platform to expose N virtual "
                         "devices (must run before jax initializes; "
                         "for CPU dev boxes and CI mesh jobs)")
    ap.add_argument("--tenants", metavar="P1,P2", default=None,
                    help="comma-separated extra pipelines to serve as "
                         "named tenants of the same service (shared "
                         "device pool, per-tenant plans/stats/replay); "
                         "requests round-robin across all tenants")
    ap.add_argument("--priority", default="batch",
                    choices=["batch", "rt", "mix"],
                    help="priority class for submitted requests; mix "
                         "alternates rt/batch so the rt class "
                         "demonstrably jumps the queue")
    ap.add_argument("--async", dest="use_async", action="store_true",
                    help="drive the load through the asyncio front "
                         "door: async with PipelineService(...) + "
                         "await submit_async(...)")
    ap.add_argument("--check", type=int, default=4,
                    help="responses to validate against the numpy oracle")
    ap.add_argument("--queue-limit", type=int, default=0, metavar="N",
                    help="bound the admission queue at N requests "
                         "(0 = unbounded); see --on-full")
    ap.add_argument("--on-full", default="block",
                    choices=["block", "shed", "raise"],
                    help="policy when the bounded queue is full: block "
                         "the submitter, shed (the future fails with "
                         "Overloaded immediately), or raise from submit")
    ap.add_argument("--deadline-ms", type=float, default=0.0,
                    help="per-request scheduling deadline; requests "
                         "still queued past it fail with "
                         "DeadlineExceeded before consuming a device "
                         "slot (0 = no deadline)")
    ap.add_argument("--max-retries", type=int, default=2,
                    help="transient batch-failure retries (capped "
                         "exponential backoff) before the batch is "
                         "bisected to isolate poison rows")
    ap.add_argument("--validate", default="off",
                    choices=["off", "strict"],
                    help="strict: reject non-finite payloads at submit "
                         "(the future fails with InvalidRequest)")
    ap.add_argument("--poison", type=int, default=0, metavar="K",
                    help="corrupt K requests with NaNs and assert they "
                         "all fail with typed exceptions while healthy "
                         "requests are unaffected; arm "
                         "TINA_FAULTS=device_run:nan (or --validate "
                         "strict) so the poison actually faults")
    ap.add_argument("--prewarm", action="store_true",
                    help="run the autotuner for the serving shape "
                         "(batch, signal_len) before accepting traffic, "
                         "persisting winners to the tuning cache — the "
                         "deploy-time pre-warm for TINA_AUTOTUNE=cached "
                         "production serving")
    ap.add_argument("--tune-repeats", type=int, default=2,
                    help="per-candidate repeats inside the pre-warm "
                         "autotune pass")
    ap.add_argument("--trace", metavar="OUT.json", default=None,
                    help="collect telemetry spans (forces span "
                         "collection on for this run) and write a "
                         "Chrome trace-event JSON viewable in "
                         "chrome://tracing or Perfetto")
    ap.add_argument("--metrics-interval", type=float, default=0.0,
                    metavar="SEC",
                    help="print a JSON metrics snapshot (service stats "
                         "+ plan cache + autotuner counters) to stderr "
                         "every SEC seconds while serving (0 = off)")
    ap.add_argument("--jax-profiler", metavar="DIR", default=None,
                    help="bracket the serving window with "
                         "jax.profiler.start_trace/stop_trace writing "
                         "to DIR one trace of the device's ops and the "
                         "program's spans on the same clock")
    return ap


def _result_or_exception(fut, timeout: float = 120.0):
    try:
        return fut.result(timeout=timeout)
    except Exception as e:   # noqa: BLE001 — typed failures ARE outcomes
        return e


def _metrics_snapshot(svc) -> dict:
    """Everything a scrape wants in one dict: the service's consistent
    stats snapshot plus the process-wide plan-cache/autotuner/obs
    counters."""
    from repro import obs
    from repro.graph import autotune, plan as plan_lib
    return {"time": time.time(), "service": svc.stats(),
            "plan_cache": plan_lib.cache_stats(),
            "autotune": autotune.stats(),
            "gauges": obs.snapshot()["gauges"]}


def _start_metrics_thread(svc, interval: float):
    """Emit one JSON metrics line to stderr every ``interval`` seconds
    until the returned event is set (daemon thread — a hung service
    doesn't keep the process alive)."""
    stop = threading.Event()

    def loop():
        while not stop.wait(interval):
            print(json.dumps(_metrics_snapshot(svc)), file=sys.stderr,
                  flush=True)

    threading.Thread(target=loop, daemon=True).start()
    return stop


def prewarm(graph_obj, batch: int, signal_len: int, *, lowering: str,
            precision: str = "f32", mesh=None, repeats: int = 2) -> dict:
    """Measure-and-persist autotune entries for the serving shape.

    Temporarily forces ``TINA_AUTOTUNE=on`` (the whole point is to
    measure ahead of traffic even when serving runs ``cached``),
    compiles the serving-shaped plan with the tuner engaged, and
    returns the tuner's stats delta.  ``lowering="auto"`` tunes
    lowering + tiling jointly; a fixed lowering tunes its tiling only;
    ``precision="auto"`` adds the budget-gated precision dimension to
    whichever search runs.
    """
    from repro.graph import autotune, plan as plan_lib

    prev = os.environ.get("TINA_AUTOTUNE")
    os.environ["TINA_AUTOTUNE"] = "on"
    try:
        before = autotune.stats()
        opts = plan_lib.CompileOptions(
            lowering=lowering,
            block_configs=None if lowering == "auto" else "auto",
            mesh=mesh, precision=precision,
            autotune_kwargs={"repeats": repeats})
        plan_lib.compile(graph_obj,
                         {graph_obj.inputs[0]: (batch, signal_len)},
                         options=opts)
        after = autotune.stats()
        return {k: after[k] - before[k] for k in after}
    finally:
        if prev is None:
            os.environ.pop("TINA_AUTOTUNE", None)
        else:
            os.environ["TINA_AUTOTUNE"] = prev


def main(argv=None, *, persistent_cache: bool = False):
    """Run one launch.  ``persistent_cache`` places JAX's compilation
    cache (:mod:`repro.compile_cache`) — the command line turns it on;
    in-process callers keep whatever cache they configured."""
    args = build_parser().parse_args(argv)
    if args.devices:
        # must precede the first jax import: jax locks the device count
        # at backend init, which is why the imports below are deferred
        if "jax" in sys.modules:
            raise SystemExit(
                "--devices has no effect once jax is imported (the "
                "device count locks at backend init); set XLA_FLAGS="
                f"--xla_force_host_platform_device_count={args.devices} "
                "in the environment instead")
        flag = f"--xla_force_host_platform_device_count={args.devices}"
        os.environ["XLA_FLAGS"] = \
            (os.environ.get("XLA_FLAGS", "") + " " + flag).strip()

    if persistent_cache:
        from repro import compile_cache
        compile_cache.enable()
    from repro import obs
    from repro.core.registry import PIPELINES, pipelines
    from repro.graph.plan import CompileOptions
    from repro.graph.service import PipelineService

    if args.trace:
        # span collection on for the whole run (compile + tune + serve),
        # whatever $TINA_TELEMETRY says — asking for a trace IS the
        # opt-in
        obs.enable()
    pipelines()
    if args.pipeline not in PIPELINES:
        raise SystemExit(f"unknown pipeline {args.pipeline!r}; "
                         f"choices: {sorted(PIPELINES)}")
    spec = PIPELINES[args.pipeline]
    g = spec.build()
    n = spec.valid_len(args.signal_len)   # e.g. PFB branch divisibility
    if n != args.signal_len:
        print(f"[dsp_serve] signal-len {args.signal_len} -> {n} "
              f"({args.pipeline} length constraint)")
    rng = np.random.default_rng(0)

    if args.prewarm:
        from repro.graph import autotune
        from repro.graph.service import bucket_ladder
        t0 = time.perf_counter()
        # the service executes every bucket shape in its ladder: tune
        # them all, or the sub-max buckets would serve default kernels
        # under TINA_AUTOTUNE=cached
        sizes = bucket_ladder(args.batch, args.mesh or 1)
        delta: dict = {}
        for b in sizes:
            d = prewarm(g, b, n, lowering=args.lowering,
                        precision=args.precision,
                        mesh=args.mesh or None, repeats=args.tune_repeats)
            delta = {k: delta.get(k, 0) + v for k, v in d.items()}
        print(f"[dsp_serve] prewarm: tuned {len(sizes)} serving shape(s) "
              f"{[(b, n) for b in sizes]} in "
              f"{time.perf_counter() - t0:.2f}s — "
              f"measured {delta['measured']} node(s), "
              f"{delta['cache_hits']} already cached "
              f"(cache: {autotune.cache_path()})")
        # the pre-warm measured block configs for this lowering; make the
        # service actually read them (a fixed-lowering service without
        # --tune-blocks would otherwise serve kernel defaults)
        args.tune_blocks = args.tune_blocks or args.lowering != "auto"

    t0 = time.perf_counter()
    opts = CompileOptions(
        lowering=args.lowering,
        precision=args.precision,
        block_configs="auto" if args.tune_blocks else None,
        mesh=args.mesh or None)
    svc = PipelineService(g, signal_len=n, batch_size=args.batch,
                          options=opts,
                          queue_limit=args.queue_limit or None,
                          on_full=args.on_full,
                          deadline_ms=args.deadline_ms or None,
                          max_retries=args.max_retries,
                          validate=args.validate)
    tenant_specs = {"default": spec}
    tenant_lens = {"default": n}
    if args.tenants:
        for tn in [t.strip() for t in args.tenants.split(",") if t.strip()]:
            if tn not in PIPELINES:
                raise SystemExit(f"--tenants: unknown pipeline {tn!r}; "
                                 f"choices: {sorted(PIPELINES)}")
            if tn in tenant_specs:
                continue
            tspec = PIPELINES[tn]
            tlen = tspec.valid_len(args.signal_len)
            svc.add_tenant(tn, tspec.build(), tlen,
                           batch_size=args.batch)
            tenant_specs[tn] = tspec
            tenant_lens[tn] = tlen
    t_compile = time.perf_counter() - t0
    tuned = {k: v for k, v in svc.plan.configs.items() if v}
    sharded = ""
    if svc.plan.mesh is not None:
        m = svc.plan.mesh
        sharded = (f", mesh {dict(m.shape)} "
                   f"({args.batch // m.shape[svc.plan.batch_axis]} "
                   "rows/device)")
    ladder = f", buckets {list(svc.buckets)}"
    prec = ("" if args.precision == "f32"
            else f", precisions: {svc.plan.precisions}")
    nplans = sum(len(t.plans) for t in svc.tenants.values())
    multi = (f", {len(svc.tenants)} tenants" if len(svc.tenants) > 1
             else "")
    print(f"[dsp_serve] {args.pipeline}: {nplans} plan(s) compiled "
          f"in {t_compile:.2f}s (lowerings: {svc.plan.lowerings}"
          + (f", block configs: {tuned}" if tuned else "")
          + prec + sharded + ladder + multi + ")")

    # round-robin the request load across every tenant; --priority mix
    # alternates rt/batch so the priority classes are both exercised
    tenant_names = list(tenant_specs)
    reqs = []
    for i in range(args.requests):
        tn = tenant_names[i % len(tenant_names)]
        x = rng.standard_normal(tenant_lens[tn]).astype(np.float32)
        pr = ("rt" if args.priority == "rt"
              or (args.priority == "mix" and i % 2 == 0) else "batch")
        reqs.append((tn, pr, x))
    poison_idx: set = set()
    if args.poison:
        if args.poison > len(reqs):
            raise SystemExit(f"--poison {args.poison} > --requests "
                             f"{len(reqs)}")
        # spread the poison so it lands in different batches
        poison_idx = set(np.linspace(0, len(reqs) - 1,
                                     args.poison).astype(int).tolist())
        for i in poison_idx:
            x = reqs[i][2]
            x[x.shape[-1] // 3] = np.nan
    metrics_stop = (_start_metrics_thread(svc, args.metrics_interval)
                    if args.metrics_interval > 0 else None)
    profiling = False
    if args.jax_profiler:
        import jax
        jax.profiler.start_trace(args.jax_profiler)
        profiling = True
    t0 = time.perf_counter()
    try:
        if args.use_async:
            import asyncio

            async def _drive():
                async with svc:
                    # outcome-tolerant: gather keeps typed failures as
                    # values, exactly like the sync path below
                    return await asyncio.gather(
                        *(svc.submit_async(x, priority=pr, tenant=tn)
                          for tn, pr, x in reqs),
                        return_exceptions=True)

            outs = list(asyncio.run(_drive()))
        else:
            with svc:
                futs = []
                for tn, pr, x in reqs:
                    try:
                        futs.append(svc.submit(x, priority=pr, tenant=tn))
                    except Exception as e:  # noqa: BLE001 on_full="raise"
                        futs.append(e)
                # outcome-tolerant: every slot ends up a result array or
                # the typed exception its future resolved with
                outs = [f if isinstance(f, Exception) else
                        _result_or_exception(f) for f in futs]
    finally:
        elapsed = time.perf_counter() - t0
        if profiling:
            import jax
            jax.profiler.stop_trace()
            print(f"[dsp_serve] jax profiler trace in {args.jax_profiler}")
        if metrics_stop is not None:
            metrics_stop.set()
            # one final scrape so short runs still emit a snapshot
            print(json.dumps(_metrics_snapshot(svc)), file=sys.stderr,
                  flush=True)

    checked = 0
    min_sqnr = float("inf")
    for i, ((tn, _pr, x), o) in enumerate(zip(reqs, outs)):
        if isinstance(o, Exception) or i in poison_idx:
            continue                 # oracle-check served requests only
        tspec = tenant_specs[tn]
        if args.precision == "f32":
            np.testing.assert_allclose(o, tspec.oracle(x), rtol=2e-3,
                                       atol=2e-3)
        else:
            # reduced-precision responses are judged the way their
            # budgets are: SQNR against the oracle, floored well below
            # any OpDef budget so a quantization bug (not quantization
            # noise) fails the launch
            from repro.core.opdefs import sqnr_db
            q = sqnr_db(tspec.oracle(x), np.asarray(o))
            min_sqnr = min(min_sqnr, q)
            assert q > 20.0, (
                f"response {i}: SQNR {q:.1f} dB vs the numpy oracle at "
                f"precision={args.precision} — below the 20 dB sanity "
                "floor")
        checked += 1
        if checked >= args.check:
            break

    s = svc.stats()                  # one consistent locked snapshot
    served = sum(1 for o in outs if not isinstance(o, Exception))
    # padded_slots is measured against each batch's own bucket, so the
    # fill ratio is exact
    buckets = f", buckets {s['bucket_batches']}"
    traces = max(p.trace_count for p in svc.plans.values())
    print(f"[dsp_serve] {served}/{len(outs)} requests served in "
          f"{elapsed:.3f}s ({served / elapsed:.1f} req/s), "
          f"{s['batches']} batches, "
          f"fill {s['fill_ratio']:.0%}{buckets}, plan traces {traces} "
          f"(1 == every batch was a cache hit)")
    if len(svc.tenants) > 1:
        print("[dsp_serve] tenants: " + ", ".join(
            f"{tn} {c['requests']} req / {c['batches']} batch(es)"
            for tn, c in s["tenants"].items()))
    if args.priority != "batch":
        print(f"[dsp_serve] priorities: {s['priorities']}")
    from collections import Counter
    from repro.obs import faults
    failures = Counter(type(o).__name__ for o in outs
                       if isinstance(o, Exception))
    rob = {k: s[k] for k in ("shed", "expired", "retries", "quarantined",
                             "degraded", "invalid")}
    if any(rob.values()) or failures or faults.active():
        print(f"[dsp_serve] robustness: {rob}, failure types "
              f"{dict(failures)}, injected {faults.stats()}, runtime "
              f"downgrades {svc.downgrades}")
    if args.poison:
        leaked = [i for i in sorted(poison_idx)
                  if not isinstance(outs[i], Exception)]
        if leaked:
            raise SystemExit(
                f"[dsp_serve] --poison: corrupted request(s) {leaked} "
                "received results instead of typed failures — poison "
                "isolation is broken (is TINA_FAULTS=device_run:nan or "
                "--validate strict armed?)")
        harmed = sum(1 for i, o in enumerate(outs)
                     if i not in poison_idx and isinstance(o, Exception))
        print(f"[dsp_serve] poison isolation: {len(poison_idx)}/"
              f"{len(poison_idx)} corrupted request(s) failed typed "
              f"({sorted({type(outs[i]).__name__ for i in poison_idx})}), "
              f"{s['quarantined']} quarantined, {harmed} healthy "
              "request(s) caught in the blast radius")
    lat = s["latency_ms"]
    if lat["total"]["count"]:
        print("[dsp_serve] latency p50/p99 ms — "
              + ", ".join(f"{k} {lat[k]['p50']:.2f}/{lat[k]['p99']:.2f}"
                          for k in ("queued", "pad", "stage", "wait",
                                    "fetch", "total")))
    sq = (f" (min SQNR {min_sqnr:.1f} dB @ {args.precision})"
          if np.isfinite(min_sqnr) else "")
    print(f"[dsp_serve] {checked} response(s) verified against the "
          f"numpy oracle{sq}")
    if args.trace:
        n_events = obs.export_chrome_trace(args.trace)
        dropped = obs.REGISTRY.dropped_events
        print(f"[dsp_serve] wrote {n_events} trace events to {args.trace}"
              + (f" ({dropped} dropped: buffer full)" if dropped else "")
              + " — open in chrome://tracing or https://ui.perfetto.dev")
    _fail_unless_served(svc, outs, poison_idx)


def _fail_unless_served(svc, outs, poison_idx) -> None:
    """Exit non-zero when the run did not serve what was asked: a
    healthy request failed (shedding and deadline expiry are the
    admission policies the flags asked for, not failures), or a bucket
    degraded to the reference lowering."""
    from repro.graph.errors import DeadlineExceeded, Overloaded
    failed = sorted({type(o).__name__ for i, o in enumerate(outs)
                     if i not in poison_idx and isinstance(o, Exception)
                     and not isinstance(o, (Overloaded, DeadlineExceeded))})
    degraded = {name: dict(t.downgrades)
                for name, t in svc.tenants.items() if t.downgrades}
    problems = []
    if failed:
        problems.append(f"healthy request(s) failed with {failed}")
    if degraded:
        problems.append(f"bucket(s) degraded to the reference lowering "
                        f"{degraded}")
    if problems:
        raise SystemExit("[dsp_serve] FAILED: " + "; ".join(problems))


if __name__ == "__main__":
    main(persistent_cache=True)
