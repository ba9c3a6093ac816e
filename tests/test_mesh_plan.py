"""Mesh-sharded pipeline plans.

In-process tests pin an explicit 1-device mesh: the pytest process's
device count is whatever earlier-collected modules froze it to (plain
runs see 1 CPU device; importing the dry-run forces 512; the CI mesh
job forces 8), so nothing here may assume it.  The
real multi-device numerics run in a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=8``, asserting the
sharded plan is *bit-identical* to the single-device plan compiled at
the per-shard shape (that per-shard program is exactly what shard_map
runs on every device) for every builtin pipeline x lowering, and
tightly allclose to the global-batch unsharded plan (XLA's contraction
tiling depends on batch size, so global bitwise equality is not a
guarantee the hardware makes).
"""
import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

from repro import graph
from repro.core.registry import PIPELINES, pipelines
from repro.graph import plan as plan_lib
from repro.launch.mesh import make_batch_mesh

pipelines()
RNG = np.random.default_rng(11)
SRC = os.path.join(os.path.dirname(__file__), "..", "src")

# per-test ceiling (enforced when pytest-timeout is installed, as in
# CI): the subprocess numerics sweeps are the slow tail of this suite —
# a hang must fail in minutes, not eat the 45-minute job timeout
pytestmark = pytest.mark.timeout(900)


def run_subprocess(body: str, n_devices: int = 8, env_extra=None):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("TINA_AUTOTUNE", "cached")
    env.update(env_extra or {})
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(body)],
                       capture_output=True, text=True, env=env, timeout=900)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


# ---------------------------------------------------------------------------
# in-process: the sharded code path on a 1-device mesh
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name", sorted(PIPELINES))
def test_sharded_plan_matches_unsharded_one_device(name):
    spec = PIPELINES[name]
    (x,) = spec.make_args(RNG, 512)
    xb = np.stack([x, 2.0 * x, -x, 0.5 * x])
    g = spec.build()
    p0 = graph.compile(g, {g.inputs[0]: xb.shape})
    p1 = graph.compile(g, {g.inputs[0]: xb.shape},
                       options=graph.CompileOptions(mesh=1))
    assert p1 is not p0                  # mesh topology is in the cache key
    assert p1.mesh is not None and p1.batch_axis == "batch"
    assert len(p1.input_shardings) == 1
    np.testing.assert_array_equal(np.asarray(p1(jnp.asarray(xb))),
                                  np.asarray(p0(jnp.asarray(xb))))
    # identical mesh spec -> plan cache hit
    assert graph.compile(g, {g.inputs[0]: xb.shape},
                         options=graph.CompileOptions(mesh=1)) is p1


def test_sharded_plan_mesh_arg_forms():
    g = PIPELINES["spectrogram"].build()
    shapes = {"x": (4, 512)}
    p_int = graph.compile(g, shapes, options=graph.CompileOptions(mesh=1))
    p_mesh = graph.compile(g, shapes, options=graph.CompileOptions(
        mesh=make_batch_mesh(1)))
    assert p_int is p_mesh               # same topology, same cache entry
    with pytest.raises(ValueError, match="only 'batch'"):
        graph.compile(g, shapes, options=graph.CompileOptions(shard="time"))
    with pytest.raises(TypeError, match="mesh="):
        graph.compile(g, shapes,
                      options=graph.CompileOptions(mesh="everything"))


def test_sharded_plan_requires_batch_axis():
    g = PIPELINES["spectrogram"].build()
    with pytest.raises(ValueError, match="batch axis"):
        graph.compile(g, {"x": (512,)},
                      options=graph.CompileOptions(shard="batch"))


def test_sharded_service_one_device_mesh():
    spec = PIPELINES["fir_decimate"]
    g = spec.build()
    xs = [RNG.standard_normal(512).astype(np.float32) for _ in range(5)]
    with graph.PipelineService(g, signal_len=512, batch_size=2,
                               options=graph.CompileOptions(mesh=1)) as svc:
        outs = [f.result(timeout=60) for f in [svc.submit(x) for x in xs]]
    assert svc.plan.mesh is not None
    for x, o in zip(xs, outs):
        np.testing.assert_allclose(o, spec.oracle(x), rtol=2e-3, atol=2e-3)


def test_sharded_chunked_runner_one_device_mesh():
    spec = PIPELINES["spectrogram"]
    g = spec.build()
    (x,) = spec.make_args(RNG, 1024)
    xb = np.stack([x, -x])
    offline = np.asarray(graph.compile(g, {g.inputs[0]: xb.shape})(
        jnp.asarray(xb)))
    runner = graph.ChunkedRunner(g, options=graph.CompileOptions(mesh=1))
    got = np.asarray(runner.run(xb, 300))
    np.testing.assert_allclose(got, offline, rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# multi-device: forced 8-device host in a subprocess ("distributed" in the
# names keeps these out of CI's fast-signal job, like test_distributed.py)
# ---------------------------------------------------------------------------
def test_distributed_sharded_numerics_all_pipelines_all_lowerings():
    run_subprocess("""
        import numpy as np, jax, jax.numpy as jnp
        from repro import graph
        from repro.core.registry import PIPELINES, pipelines
        pipelines()
        assert len(jax.devices()) == 8
        g0 = PIPELINES['spectrogram'].build()
        # shard="batch" == mesh over all local devices: same cache entry
        O = graph.CompileOptions
        assert (graph.compile(g0, {'x': (8, 512)}, options=O(shard='batch'))
                is graph.compile(g0, {'x': (8, 512)}, options=O(mesh=8)))
        rng = np.random.default_rng(0)
        for name, spec in sorted(PIPELINES.items()):
            g = spec.build()
            (x,) = spec.make_args(rng, 512)
            xb = np.stack([x * (1.0 + 0.1 * i) for i in range(8)])
            per_shard = xb.shape[0] // 8
            for lw in spec.lowerings:
                p_global = graph.compile(g, {g.inputs[0]: xb.shape},
                                         options=O(lowering=lw))
                p_shard = graph.compile(g, {g.inputs[0]: xb.shape},
                                        options=O(lowering=lw, mesh=8))
                got = np.asarray(p_shard(p_shard.shard_inputs(
                    jnp.asarray(xb))))
                # bit-identical to the per-shard single-device program
                # (what shard_map actually runs on each device)
                p_row = graph.compile(
                    g, {g.inputs[0]: (per_shard,) + xb.shape[1:]},
                    options=O(lowering=lw))
                want = np.concatenate(
                    [np.asarray(p_row(jnp.asarray(
                        xb[i:i + per_shard])))
                     for i in range(0, 8, per_shard)])
                np.testing.assert_array_equal(
                    got, want, err_msg=f"{name}/{lw} not bit-identical")
                # and numerically the same answer as the global plan
                np.testing.assert_allclose(
                    got, np.asarray(p_global(jnp.asarray(xb))),
                    rtol=1e-4, atol=1e-5, err_msg=f"{name}/{lw}")
                np.testing.assert_allclose(
                    got[0], spec.oracle(xb[0]), rtol=2e-3, atol=2e-3)
        print("OK")
        """)


def test_distributed_sharded_service_and_stream():
    run_subprocess("""
        import numpy as np, jax, jax.numpy as jnp
        from repro import graph
        from repro.core.registry import PIPELINES, pipelines
        pipelines()
        spec = PIPELINES['spectrogram']
        g = spec.build()
        rng = np.random.default_rng(3)

        # batched sharded service: 16 requests, batch 8 over 8 devices
        xs = [rng.standard_normal(256).astype(np.float32)
              for _ in range(16)]
        O = graph.CompileOptions
        with graph.PipelineService(g, signal_len=256, batch_size=8,
                                   options=O(mesh=8)) as svc:
            outs = [f.result(timeout=120)
                    for f in [svc.submit(x) for x in xs]]
        for x, o in zip(xs, outs):
            np.testing.assert_allclose(o, spec.oracle(x),
                                       rtol=2e-3, atol=2e-3)
        assert svc.plan.trace_count == 1

        # batch_size not divisible by the mesh -> clear error
        try:
            graph.PipelineService(g, signal_len=256, batch_size=6,
                                  options=O(mesh=4))
        except ValueError as e:
            assert 'divisible' in str(e), e
        else:
            raise AssertionError('expected divisibility error')

        # non-dividing batch at compile time -> clear error
        try:
            graph.compile(g, {'x': (6, 256)}, options=O(mesh=4))
        except ValueError as e:
            assert 'batch divisibility' in str(e), e
        else:
            raise AssertionError('expected divisibility error')

        # sharded batched stream == offline
        (x,) = spec.make_args(rng, 2048)
        xb = np.stack([x * (1.0 + i) for i in range(8)])
        offline = np.asarray(graph.compile(
            g, {g.inputs[0]: xb.shape})(jnp.asarray(xb)))
        got = np.asarray(
            graph.ChunkedRunner(g, options=O(mesh=8)).run(xb, 600))
        np.testing.assert_allclose(got, offline, rtol=1e-6, atol=1e-6)

        # the batcher on the mesh: the bucket ladder starts at
        # the shard count (16 over 8 devices -> buckets 8/16), every
        # response replays bit-for-bit against its served packing
        from repro.graph.service import replay_batches
        xs2 = [rng.standard_normal(256).astype(np.float32)
               for _ in range(11)]
        with graph.PipelineService(g, signal_len=256, batch_size=16,
                                   options=O(mesh=8),
                                   record_batches=True) as svc2:
            outs2 = [f.result(timeout=120)
                     for f in [svc2.submit(x) for x in xs2]]
        assert svc2.buckets == (8, 16), svc2.buckets
        assert all(b % 8 == 0 for b, _ in svc2.batch_log)
        assert replay_batches(svc2) == len(xs2)
        for x, o in zip(xs2, outs2):
            np.testing.assert_allclose(o, spec.oracle(x),
                                       rtol=2e-3, atol=2e-3)
        print("OK")
        """)


def test_distributed_sharded_autotune_uses_per_shard_shapes(tmp_path):
    """The tuner must see the per-device problem: cache keys written
    while compiling a sharded plan carry per-shard (batch/8) shapes."""
    cache = tmp_path / "tune.json"
    run_subprocess(f"""
        import json, numpy as np, jax
        from repro import graph
        from repro.core.registry import PIPELINES, pipelines
        pipelines()
        g = PIPELINES['spectrogram'].build()
        p = graph.compile(g, {{'x': (8, 512)}}, options=graph.CompileOptions(
            mesh=8, lowering='auto', autotune_kwargs={{'repeats': 1}}))
        keys = list(json.load(open({str(cache)!r}))['entries'])
        assert keys, 'tuner wrote nothing'
        assert any('(1, ' in k for k in keys), keys   # per-shard batch dim
        assert not any('(8, ' in k for k in keys), keys
        print('OK')
        """, env_extra={"TINA_AUTOTUNE": "on",
                        "TINA_AUTOTUNE_CACHE": str(cache)})
