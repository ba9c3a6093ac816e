"""PR-10 serving API suite: the consolidated ``CompileOptions`` front
door (with its warn-once deprecation shim), the double-buffered
scheduler's telemetry contract, multi-tenant priority serving, the
asyncio-native surface, and the ``python -m repro.tina`` umbrella CLI.

The fairness soak is the PR's acceptance check in miniature: two
tenants share one device pool under mixed rt/batch priorities, every
future resolves, the rt class's latency distribution sits below the
batch class's, and replay verification stays bit-for-bit per tenant.
"""
import asyncio
import time

import numpy as np
import pytest

from repro import graph, obs
from repro.core.registry import PIPELINES, pipelines
from repro.graph import plan as plan_lib
from repro.graph.plan import CompileOptions
from repro.graph.service import (PRIORITIES, PipelineService,
                                 replay_batches)
from repro.graph.stream import ChunkedRunner
from repro.obs.trace import validate_nesting

pipelines()
RNG = np.random.default_rng(31)

pytestmark = pytest.mark.timeout(120)


def _signals(n_req, n=256):
    return [RNG.standard_normal(n).astype(np.float32) for _ in range(n_req)]


# ---------------------------------------------------------------------------
# CompileOptions: one object, one cache key, one deprecation shim
# ---------------------------------------------------------------------------
def test_compile_options_and_legacy_kwargs_share_plans():
    g = PIPELINES["spectrogram"].build()
    shapes = {g.inputs[0]: (512,)}
    plan_lib._warned_legacy_compile = False      # re-arm the once-latch
    with pytest.warns(DeprecationWarning, match="CompileOptions"):
        p1 = graph.compile(g, shapes, lowering="native")
    # the shim folds into the same options object -> same cache entry
    p2 = graph.compile(g, shapes,
                       options=CompileOptions(lowering="native"))
    assert p1 is p2
    # ... and warns exactly once per process
    import warnings
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        assert graph.compile(g, shapes, lowering="native") is p1


def test_compile_options_replace_and_defaults():
    o = CompileOptions()
    assert o.dtype == "float32" and o.lowering == "native"
    assert not o.donate
    o2 = o.replace(precision="bf16", donate=True)
    assert (o2.precision, o2.donate) == ("bf16", True)
    assert o.precision == "f32"                  # frozen: replace copies


def test_compile_rejects_unknown_and_mixed_kwargs():
    g = PIPELINES["spectrogram"].build()
    shapes = {g.inputs[0]: (512,)}
    with pytest.raises(TypeError, match="unexpected keyword"):
        graph.compile(g, shapes, bogus=1)
    with pytest.raises(TypeError, match="options="):
        graph.compile(g, shapes, options=CompileOptions(),
                      lowering="native")
    with pytest.raises(TypeError, match="options="):
        ChunkedRunner(g, options=CompileOptions(), lowering="native")
    with pytest.raises(TypeError, match="options="):
        PipelineService(g, signal_len=512, options=CompileOptions(),
                        lowering="native")
    with pytest.raises(TypeError, match="dtype"):
        PipelineService(g, signal_len=512, dtype="float64",
                        options=CompileOptions(dtype="float32"))


def test_service_and_runner_build_on_compile_options():
    spec = PIPELINES["spectrogram"]
    opts = CompileOptions(lowering="native")
    svc = PipelineService(spec.build(), signal_len=256, batch_size=4,
                          batching="continuous", options=opts)
    r = ChunkedRunner(spec.build(), options=opts)
    x = _signals(1, 1024)[0]
    out = np.asarray(r.run(x, chunk_len=300))
    fut = svc.submit(x[:256])
    svc.flush()
    np.testing.assert_allclose(fut.result(timeout=30),
                               spec.oracle(x[:256]), rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(out, spec.oracle(x), rtol=2e-3, atol=2e-3)
    svc.close()


def test_stats_is_a_method_now():
    _ = PIPELINES["spectrogram"]
    svc = PipelineService(_.build(), signal_len=256, batch_size=2)
    s = svc.stats()
    assert isinstance(s, dict) and s["requests"] == 0
    with pytest.raises(TypeError):
        svc.stats["requests"]                    # the old attribute form
    svc.close()


# ---------------------------------------------------------------------------
# overlapped scheduler: per-batch phase spans, bitwise replay
# ---------------------------------------------------------------------------
def test_overlap_scheduler_device_spans_and_replay():
    was_on = obs.REGISTRY.enabled
    obs.REGISTRY.enable()
    ev0 = len(obs.REGISTRY.events())
    try:
        spec = PIPELINES["spectrogram"]
        svc = PipelineService(spec.build(), signal_len=256, batch_size=4,
                              batching="continuous", record_batches=True)
        assert svc.overlap                       # continuous -> auto-on
        xs = _signals(17)
        futs = [svc.submit(x) for x in xs]       # queued before start
        with svc:
            outs = [f.result(timeout=60) for f in futs]
        for x, o in zip(xs, outs):
            np.testing.assert_allclose(o, spec.oracle(x),
                                       rtol=2e-3, atol=2e-3)
        assert replay_batches(svc) == len(xs)    # bitwise per packing
        evs = obs.REGISTRY.events()[ev0:]
        validate_nesting(evs)
        phases = {}
        for e in evs:
            if e["name"].startswith("service.") and "batch" in e["args"]:
                phases.setdefault(e["args"]["batch"], {})[e["name"]] = e
        assert len(phases) == svc.stats()["batches"]
        for seq, ph in phases.items():
            for parent, kids in (
                    ("service.dispatch", ("service.pack", "service.stage",
                                          "service.enqueue")),
                    ("service.complete", ("service.wait", "service.fetch",
                                          "service.deliver"))):
                p = ph[parent]
                for k in kids:     # same thread, inside the parent
                    assert ph[k]["tid"] == p["tid"]
                    assert p["ts"] <= ph[k]["ts"] and \
                        ph[k]["ts"] + ph[k]["dur"] <= p["ts"] + p["dur"]
        # the double buffer: batch N+1 launches before N completes
        seqs = sorted(phases)
        assert any(phases[b]["service.dispatch"]["ts"]
                   < phases[a]["service.complete"]["ts"]
                   for a, b in zip(seqs, seqs[1:]))
        # no synthetic track: every span sits on a real thread
        assert not any(e.get("tid") == "device" for e in evs)
    finally:
        if not was_on:
            obs.REGISTRY.disable()


def test_overlap_off_is_the_blocking_scheduler():
    spec = PIPELINES["spectrogram"]
    svc = PipelineService(spec.build(), signal_len=256, batch_size=4,
                          batching="continuous", overlap=False,
                          record_batches=True)
    assert not svc.overlap
    xs = _signals(9)
    with svc:
        outs = [f.result(timeout=60) for f in [svc.submit(x) for x in xs]]
    for x, o in zip(xs, outs):
        np.testing.assert_allclose(o, spec.oracle(x), rtol=2e-3, atol=2e-3)
    assert replay_batches(svc) == len(xs)


# ---------------------------------------------------------------------------
# multi-tenant priorities: fairness / starvation soak
# ---------------------------------------------------------------------------
def test_multi_tenant_priority_fairness_soak():
    spec_a = PIPELINES["spectrogram"]
    spec_b = PIPELINES["pfb_power"]
    svc = PipelineService(spec_a.build(), signal_len=256, batch_size=4,
                          batching="continuous", record_batches=True)
    svc.add_tenant("b", spec_b.build(), 512, record_batches=True)
    lat = {}
    metas, futs = [], []
    # one interleaved burst BEFORE the batcher starts: a deep queue
    # forms, so the rt class demonstrably jumps the order while batch
    # requests still all get served (strict priority, no starvation —
    # the queue drains completely)
    for i in range(40):
        tn = None if i % 2 == 0 else "b"
        pr = "rt" if i % 4 < 2 else "batch"      # both tenants mix classes
        x = RNG.standard_normal(256 if tn is None else 512) \
               .astype(np.float32)
        t0 = time.perf_counter()
        fut = svc.submit(x, priority=pr, tenant=tn)
        fut.add_done_callback(
            lambda f, i=i, t0=t0: lat.__setitem__(
                i, time.perf_counter() - t0))
        metas.append((tn, pr, x))
        futs.append(fut)
    svc.start()
    for f in futs:
        f.result(timeout=120)                    # every future resolves
    svc.close()
    for (tn, pr, x), f in zip(metas, futs):
        spec = spec_a if tn is None else spec_b
        np.testing.assert_allclose(f.result(timeout=0), spec.oracle(x),
                                   rtol=2e-3, atol=2e-3)
    rt = [lat[i] for i, (_, pr, _x) in enumerate(metas) if pr == "rt"]
    bt = [lat[i] for i, (_, pr, _x) in enumerate(metas) if pr == "batch"]
    assert len(rt) == len(bt) == 20
    assert np.percentile(rt, 99) < np.percentile(bt, 99)
    # replay is per tenant and bit-for-bit for each
    assert replay_batches(svc, tenant="default") == 20
    assert replay_batches(svc, tenant="b") == 20
    s = svc.stats()
    assert s["priorities"] == {"rt": 20, "batch": 20}
    assert s["tenants"]["default"]["requests"] == 20
    assert s["tenants"]["b"]["requests"] == 20
    assert s["tenants"]["b"]["batches"] >= 1


def test_tenant_validation():
    spec = PIPELINES["spectrogram"]
    svc = PipelineService(spec.build(), signal_len=256, batch_size=2)
    with pytest.raises(ValueError, match="already exists"):
        svc.add_tenant("default", spec.build(), 256)
    svc.add_tenant("t2", PIPELINES["pfb_power"].build(), 512)
    with pytest.raises(KeyError):
        svc.submit(np.zeros(256, np.float32), tenant="nope")
    with pytest.raises(ValueError, match="priority="):
        svc.submit(np.zeros(256, np.float32), priority="urgent")
    # per-tenant shape check: tenant t2 serves 512-sample signals
    with pytest.raises(ValueError, match="512"):
        svc.submit(np.zeros(256, np.float32), tenant="t2")
    assert tuple(PRIORITIES) == ("rt", "batch")
    svc.close()
    with pytest.raises(RuntimeError, match="closed"):
        svc.add_tenant("late", spec.build(), 256)


# ---------------------------------------------------------------------------
# asyncio-native surface
# ---------------------------------------------------------------------------
def test_asyncio_soak_gather_100():
    spec = PIPELINES["spectrogram"]
    xs = _signals(100)

    async def soak():
        async with PipelineService(spec.build(), signal_len=256,
                                   batch_size=8,
                                   batching="continuous") as svc:
            outs = await asyncio.gather(
                *(svc.submit_async(x) for x in xs))
            return svc, outs

    svc, outs = asyncio.run(soak())
    assert len(outs) == 100
    for x, o in zip(xs[:8], outs[:8]):
        np.testing.assert_allclose(o, spec.oracle(x), rtol=2e-3, atol=2e-3)
    assert svc.stats()["requests"] == 100


def test_asyncio_close_mid_flight_raises_cleanly():
    spec = PIPELINES["spectrogram"]
    xs = _signals(24)

    async def run():
        svc = PipelineService(spec.build(), signal_len=256, batch_size=4,
                              batching="continuous")
        async with svc:
            tasks = [asyncio.ensure_future(svc.submit_async(x))
                     for x in xs]
            await asyncio.sleep(0)               # let submissions land
        # the block exit closed the service mid-flight: everything
        # already admitted still resolves (close drains the queue)...
        outs = await asyncio.gather(*tasks, return_exceptions=True)
        # ...and a post-close submit raises cleanly in the event loop
        with pytest.raises(RuntimeError, match="service closed"):
            await svc.submit_async(xs[0])
        return outs

    outs = asyncio.run(run())
    assert len(outs) == 24
    assert not any(isinstance(o, Exception) for o in outs)
    for x, o in zip(xs, outs):
        np.testing.assert_allclose(o, spec.oracle(x), rtol=2e-3, atol=2e-3)


def test_async_priorities_compose():
    spec = PIPELINES["spectrogram"]
    xs = _signals(12)

    async def run():
        async with PipelineService(spec.build(), signal_len=256,
                                   batch_size=4,
                                   batching="continuous") as svc:
            outs = await asyncio.gather(
                *(svc.submit_async(x, priority=("rt" if i % 2 else
                                                "batch"))
                  for i, x in enumerate(xs)))
            return svc.stats()["priorities"], outs

    prios, outs = asyncio.run(run())
    assert prios == {"rt": 6, "batch": 6}
    for x, o in zip(xs, outs):
        np.testing.assert_allclose(o, spec.oracle(x), rtol=2e-3, atol=2e-3)


# ---------------------------------------------------------------------------
# umbrella CLI
# ---------------------------------------------------------------------------
def test_umbrella_cli_routes(tmp_path, capsys):
    from repro import tina
    assert tina.main([]) == 0
    out = capsys.readouterr().out
    for cmd in ("serve", "tune", "trace"):
        assert cmd in out
    with pytest.raises(SystemExit, match="unknown command"):
        tina.main(["bogus"])
    # route a real subcommand end to end: write a trace, validate it
    was_on = obs.REGISTRY.enabled
    obs.REGISTRY.enable()
    try:
        with obs.span("cli.smoke", cat="test"):
            pass
        p = tmp_path / "t.json"
        obs.export_chrome_trace(str(p))
    finally:
        if not was_on:
            obs.REGISTRY.disable()
    assert tina.main(["trace", str(p), "--require", "cli.smoke"]) == 0
    assert "nested OK" in capsys.readouterr().out
