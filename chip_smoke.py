#!/usr/bin/env python3
"""Smoke run of the served DSP path with the Pallas kernels compiled
for the TPU.  Not a benchmark: the times it prints are one-off wall
times of a smoke run, compilation included.

    python chip_smoke.py              # one chip: phases A, B and C
    python chip_smoke.py --chips 4    # four chips: the mesh path only

One chip:
  A. every built-in pipeline at its registered parameters, at f32 and
     int8, through ``graph.compile`` with ``lowering="pallas"``, each
     checked against its numpy oracle (f32: allclose at rtol=atol=2e-3;
     int8: SQNR at or above the strictest int8 budget of its nodes);
  B. the LOFAR station channelizer — a 1024-point, 16-tap PFB power
     spectrum (van Haarlem et al. 2013, A&A 556, A2) — served by
     ``PipelineService``: 64 requests of 2^20 samples at batch 8, every
     response checked against the oracle;
  C. one ``dsp_serve`` launch (``--lowering pallas``), which checks its
     own responses.

Four chips: the channelizer at batch 8 on a 4-device batch mesh, as a
plan (bitwise per shard against the one-chip plan at the per-shard
shape, allclose to the oracle, output spread over 4 devices) and as a
``PipelineService`` with ``CompileOptions(mesh=4)``.

Any of these fails the run: no TPU (never a CPU fallback), a lowering
downgrade, a Pallas node without a ``tpu_custom_call`` in its compiled
plan, a retry / degradation / quarantine / failed batch in a service,
a failed request, or a response off its oracle.  On success the last
line of stdout is ``{"ok": true, "device": {...}}`` with the device as
JAX reports it.  JAX's compilation cache is placed by
:mod:`repro.compile_cache`; autotuning is off, so what compiles comes
from the checked-in code alone.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))
os.environ["TINA_AUTOTUNE"] = "off"

SEED = 0
BATCH = 8
PHASE_A_LEN = 1 << 16            # samples per row in phase A
CHAN_BRANCHES, CHAN_TAPS = 1024, 16
CHAN_LEN = 1 << 20               # samples per channelizer request
CHAN_REQUESTS = 64
F32_TOL = 2e-3                   # dsp_serve's oracle tolerance
KERNEL_CALL = 'custom_call_target="tpu_custom_call"'


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def fail(msg: str):
    raise SystemExit(f"[chip_smoke] FAILED: {msg}")


def require_tpu(chips: int):
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(f"no TPU: JAX's first device is {devices[0].platform!r} "
             f"({devices[0].device_kind}); this smoke run needs a TPU "
             "and never falls back to the CPU")
    if len(devices) < chips:
        fail(f"--chips {chips}: only {len(devices)} TPU device(s) visible")
    return devices


def check_plan(plan, label: str, kernels: bool = True) -> None:
    """No lowering downgrade; precision downgrades only on ops that
    declare no int8 tier (their f32 epilogue is the int8 plan's
    contract); and, with ``kernels``, at least one TPU kernel launch per
    Pallas node in the compiled plan."""
    from repro.core.opdefs import OPDEFS
    bad = {}
    for name, tags in plan.downgrades.items():
        op = OPDEFS[plan.graph.nodes[name].op]
        for tag in tags.split(","):
            dim, _, req = tag.partition(":")
            if dim != "precision" or req in op.precisions:
                bad[name] = tags
    if bad:
        fail(f"{label}: downgrades {bad}")
    pallas = [n for n, lw in plan.lowerings.items() if lw == "pallas"]
    if not pallas:
        fail(f"{label}: no node runs the pallas lowering {plan.lowerings}")
    if kernels:
        launches = plan.compiled_text().count(KERNEL_CALL)
        if launches < len(pallas):
            fail(f"{label}: {launches} tpu_custom_call(s) in the compiled "
                 f"plan for {len(pallas)} Pallas node(s) {pallas}")


def check_output(got, want, label: str, floor_db: float | None = None):
    """f32: allclose at the dsp_serve tolerance; int8 (``floor_db``):
    SQNR against the oracle at or above the floor."""
    from repro.core.opdefs import sqnr_db
    got = np.asarray(got)
    if got.shape != np.shape(want) or not np.isfinite(got).all():
        fail(f"{label}: output shape {got.shape} (want {np.shape(want)}) "
             f"or non-finite values")
    if floor_db is None:
        if not np.allclose(got, want, rtol=F32_TOL, atol=F32_TOL):
            fail(f"{label}: off the oracle by up to "
                 f"{float(np.abs(got - want).max()):.3g} "
                 f"(rtol=atol={F32_TOL})")
        return None
    q = sqnr_db(want, got)
    if not q >= floor_db:
        fail(f"{label}: SQNR {q:.2f} dB < budget {floor_db} dB")
    return q


def int8_floor(plan) -> float:
    """The strictest int8 budget among the plan's int8 nodes."""
    from repro.core.opdefs import OPDEFS
    floors = [OPDEFS[plan.graph.nodes[n].op].budget("int8").sqnr_db
              for n, p in plan.precisions.items() if p == "int8"
              and OPDEFS[plan.graph.nodes[n].op].budget("int8")]
    if not floors:
        fail(f"{plan.graph.name}: no node runs int8 {plan.precisions}")
    return max(floors)


def check_service(svc, label: str) -> None:
    s = svc.stats()
    bad = {k: s[k] for k in ("retries", "degraded", "quarantined",
                             "failed_batches") if s[k]}
    if bad:
        fail(f"{label}: service recovered from faults {bad}")


def results(futs, label: str, timeout: float = 600.0) -> list:
    outs = []
    for i, f in enumerate(futs):
        try:
            outs.append(f.result(timeout=timeout))
        except Exception as e:  # noqa: BLE001 — any failure fails the run
            fail(f"{label}: request {i} failed: {type(e).__name__}: {e}")
    return outs


def phase_a(batch: int = BATCH, n: int = PHASE_A_LEN,
            kernels: bool = True) -> None:
    """Every built-in pipeline at its registered parameters, f32 and
    int8, compiled with lowering="pallas"."""
    import jax.numpy as jnp
    from repro import graph
    from repro.core.registry import PIPELINES, pipelines
    pipelines()
    rng = np.random.default_rng(SEED)
    for name in graph.BUILTINS:
        spec = PIPELINES[name]
        g = spec.build()
        ln = spec.valid_len(n)
        x = rng.standard_normal((batch, ln), dtype=np.float32)
        want = spec.oracle(x)
        for prec in ("f32", "int8"):
            label = f"A {name}@{prec}"
            t0 = time.perf_counter()
            plan = graph.compile(g, {g.inputs[0]: (batch, ln)},
                                 options=graph.CompileOptions(
                                     lowering="pallas", precision=prec))
            got = np.asarray(plan(jnp.asarray(x)))
            t = time.perf_counter() - t0
            check_plan(plan, label, kernels)
            q = check_output(got, want, label,
                             int8_floor(plan) if prec == "int8" else None)
            log(f"{label}: ({batch}, {ln}) ok"
                + (f", SQNR {q:.2f} dB" if q is not None else "")
                + f"; smoke wall time {t:.2f}s incl. compile")


def channelizer():
    from repro import graph
    from repro.graph.pipelines import pfb_power_oracle
    return (graph.build_pfb_power(CHAN_BRANCHES, CHAN_TAPS),
            pfb_power_oracle(CHAN_BRANCHES, CHAN_TAPS))


def phase_b(n_requests: int = CHAN_REQUESTS, signal_len: int = CHAN_LEN,
            batch: int = BATCH, kernels: bool = True) -> None:
    """The station channelizer served by the batching service."""
    from repro import graph
    g, oracle = channelizer()
    rng = np.random.default_rng(SEED + 1)
    xs = rng.standard_normal((n_requests, signal_len), dtype=np.float32)
    t0 = time.perf_counter()
    svc = graph.PipelineService(
        g, signal_len, batch_size=batch,
        options=graph.CompileOptions(lowering="pallas"))
    t_compile = time.perf_counter() - t0
    for b, plan in svc.plans.items():
        check_plan(plan, f"B bucket {b}", kernels)
    t0 = time.perf_counter()
    with svc:
        outs = results([svc.submit(x) for x in xs], "B")
    t_serve = time.perf_counter() - t0
    check_service(svc, "B")
    for i, (x, o) in enumerate(zip(xs, outs)):
        check_output(o, oracle(x), f"B request {i}")
    s = svc.stats()
    log(f"B channelizer P={CHAN_BRANCHES} M={CHAN_TAPS}: {n_requests} "
        f"requests of {signal_len} samples served and oracle-checked, "
        f"{s['batches']} batches {s.get('bucket_batches')}; smoke wall "
        f"times: {t_compile:.2f}s bucket compile, {t_serve:.2f}s serve")


def phase_c(signal_len: int = PHASE_A_LEN, requests: int = 32,
            batch: int = BATCH) -> None:
    """One dsp_serve launch through its command-line entry point."""
    from repro.launch import dsp_serve
    t0 = time.perf_counter()
    dsp_serve.main(["--pipeline", "spectrogram", "--lowering", "pallas",
                    "--batch", str(batch), "--requests", str(requests),
                    "--signal-len", str(signal_len),
                    "--check", str(requests)])
    log(f"C dsp_serve ok; smoke wall time {time.perf_counter() - t0:.2f}s")


def check_all_plans(kernels: bool = True) -> None:
    """Every plan the phases compiled (dsp_serve's included)."""
    from repro.graph import plan as plan_lib
    plans = plan_lib.cached_plans()
    for plan in plans:
        check_plan(plan, f"plan {plan.graph.name} "
                         f"{[s.shape for s in plan.input_specs]}", kernels)
    log(f"{len(plans)} compiled plan(s): no downgrade, every Pallas node "
        "launches a compiled TPU kernel")


def phase_mesh(chips: int = 4, batch: int = BATCH,
               signal_len: int = CHAN_LEN, kernels: bool = True) -> None:
    """The channelizer batch-sharded over ``chips`` devices, against the
    one-chip plan at the per-shard shape and the oracle."""
    import jax.numpy as jnp
    from repro import graph
    g, oracle = channelizer()
    rng = np.random.default_rng(SEED + 2)
    x = rng.standard_normal((batch, signal_len), dtype=np.float32)
    per = batch // chips
    one = graph.compile(g, {g.inputs[0]: (per, signal_len)},
                        options=graph.CompileOptions(lowering="pallas"))
    sharded = graph.compile(g, {g.inputs[0]: (batch, signal_len)},
                            options=graph.CompileOptions(lowering="pallas",
                                                         mesh=chips))
    check_plan(one, "mesh one-chip plan", kernels)
    check_plan(sharded, f"mesh {chips}-chip plan", kernels)
    out = sharded(sharded.shard_inputs(x))
    devices = {d.id for d in out.sharding.device_set}
    if len(devices) != chips:
        fail(f"sharded output spans devices {sorted(devices)}, "
             f"not {chips}")
    got = np.asarray(out)
    for i in range(chips):
        rows = slice(i * per, (i + 1) * per)
        ref = np.asarray(one(jnp.asarray(x[rows])))
        if not np.array_equal(got[rows], ref):
            fail(f"shard {i}: sharded rows differ from the one-chip plan "
                 f"by up to {float(np.abs(got[rows] - ref).max()):.3g}")
    check_output(got, oracle(x), f"mesh {chips}-chip plan")
    log(f"mesh: ({batch}, {signal_len}) over {chips} devices "
        f"{sorted(devices)}, bitwise per shard vs the one-chip plan, "
        "oracle ok")

    n_requests = 2 * batch
    xs = rng.standard_normal((n_requests, signal_len), dtype=np.float32)
    t0 = time.perf_counter()
    with graph.PipelineService(
            g, signal_len, batch_size=batch,
            options=graph.CompileOptions(lowering="pallas",
                                         mesh=chips)) as svc:
        outs = results([svc.submit(x) for x in xs], "mesh service")
    check_service(svc, "mesh service")
    for b, plan in svc.plans.items():
        check_plan(plan, f"mesh service bucket {b}", kernels)
    for i, (xi, o) in enumerate(zip(xs, outs)):
        check_output(o, oracle(xi), f"mesh service request {i}")
    log(f"mesh service: {n_requests} requests over {chips} devices, "
        f"buckets {list(svc.buckets)}, oracle ok; smoke wall time "
        f"{time.perf_counter() - t0:.2f}s")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: phases A-C on one chip; 4: only the "
                         "mesh-sharded channelizer on four chips")
    args = ap.parse_args(argv)
    devices = require_tpu(args.chips)
    from repro import compile_cache
    log(f"compilation cache: {compile_cache.enable()}")
    log(f"device: {devices[0].device_kind} x{len(devices)}")
    t0 = time.perf_counter()
    if args.chips == 4:
        phase_mesh(chips=4)
    else:
        phase_a()
        phase_b()
        phase_c()
        check_all_plans()
    log(f"all phases ok; smoke wall time {time.perf_counter() - t0:.2f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
