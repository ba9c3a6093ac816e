"""Pipeline-graph subsystem tests: registry-driven oracle sweeps across
lowerings, streaming == offline, plan-cache hits (no retrace), fusion,
autotune persistence, and batched serving."""
import os

import jax.numpy as jnp
import numpy as np
import pytest

from repro import graph
from repro.core.registry import PIPELINES, pipelines
from repro.graph import autotune, plan as plan_lib
from repro.graph.stream import stream_spec

pipelines()                       # register built-ins
RNG = np.random.default_rng(7)


def _args(name, n=512):
    spec = PIPELINES[name]
    (x,) = spec.make_args(RNG, n)
    return spec, x


# ---------------------------------------------------------------------------
# registry sweep: every built-in pipeline == numpy oracle, every lowering
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name", sorted(PIPELINES))
def test_pipeline_matches_oracle_all_lowerings(name):
    spec, x = _args(name)
    g = spec.build()
    want = spec.oracle(x)
    for lowering in spec.lowerings:
        p = graph.compile(g, {g.inputs[0]: x.shape},
                          options=graph.CompileOptions(lowering=lowering))
        got = np.asarray(p(jnp.asarray(x)))
        assert got.shape == want.shape, (name, lowering)
        np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3,
                                   err_msg=f"{name} lowering={lowering}")


@pytest.mark.parametrize("name", sorted(PIPELINES))
def test_pipeline_batched_input(name):
    """Pipelines accept leading batch dims (the serving layout)."""
    spec, x = _args(name)
    xb = np.stack([x, 2.0 * x])
    g = spec.build()
    p = graph.compile(g, {g.inputs[0]: xb.shape})
    got = np.asarray(p(jnp.asarray(xb)))
    np.testing.assert_allclose(got[0], spec.oracle(x), rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(got[1], spec.oracle(2.0 * x),
                               rtol=2e-3, atol=2e-3)


# ---------------------------------------------------------------------------
# plan cache: second identical compile is a hit, no retrace
# ---------------------------------------------------------------------------
def test_plan_cache_hit_no_retrace():
    spec, x = _args("spectrogram")
    g = spec.build()
    shapes = {g.inputs[0]: x.shape}
    before = plan_lib.cache_stats()
    p1 = graph.compile(g, shapes)
    p1(jnp.asarray(x))
    p2 = graph.compile(g, shapes)
    after = plan_lib.cache_stats()
    assert p2 is p1
    assert after["hits"] >= before["hits"] + 1
    p2(jnp.asarray(x))
    assert p1.trace_count == 1        # two executions, one trace

    # a different shape is a different plan (shape-specialized)
    p3 = graph.compile(g, {g.inputs[0]: (x.shape[0] + 64,)})
    assert p3 is not p1

    # structurally identical rebuilt graph shares the cache entry
    p4 = graph.compile(spec.build(), shapes)
    assert p4 is p1


def test_plan_cache_keyed_on_consts():
    """Same structure, different taps -> different plan."""
    g1 = graph.build_fir_decimate(taps1=31, taps2=15)
    g2 = graph.build_fir_decimate(taps1=31, taps2=15)
    g3 = graph.build_spectrogram(window=64, kind="hanning")
    g4 = graph.build_spectrogram(window=64, kind="rect")
    assert g1.signature == g2.signature
    assert g3.signature != g4.signature


# ---------------------------------------------------------------------------
# fusion: adjacent elementwise nodes collapse, output unchanged
# ---------------------------------------------------------------------------
def test_elementwise_fusion_collapses_and_matches():
    spec, x = _args("spectrogram")
    g = spec.build()
    fused = graph.compile(g, {g.inputs[0]: x.shape},
                          options=graph.CompileOptions(fuse=True))
    unfused = graph.compile(g, {g.inputs[0]: x.shape},
                            options=graph.CompileOptions(fuse=False))
    fused_ops = [n.op for n in fused.graph.topo()]
    assert "fused_ew" in fused_ops
    assert len(fused.graph.nodes) < len(unfused.graph.nodes)
    np.testing.assert_allclose(np.asarray(fused(jnp.asarray(x))),
                               np.asarray(unfused(jnp.asarray(x))),
                               rtol=1e-6, atol=1e-6)


def test_fused_pallas_kernel_matches_native():
    """The single-launch pallas chain == the composed jnp expression."""
    spec, x = _args("spectrogram", 256)
    g = spec.build()
    pn = graph.compile(g, {g.inputs[0]: x.shape},
                       options=graph.CompileOptions(lowering="native"))
    pp = graph.compile(g, {g.inputs[0]: x.shape},
                       options=graph.CompileOptions(lowering="pallas"))
    np.testing.assert_allclose(np.asarray(pp(jnp.asarray(x))),
                               np.asarray(pn(jnp.asarray(x))),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# streaming: chunked output == offline whole-signal output
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name", sorted(PIPELINES))
@pytest.mark.parametrize("chunk", [96, 256, 1000])
def test_streaming_equals_offline(name, chunk):
    spec, x = _args(name, 2048)
    g = spec.build()
    offline = np.asarray(
        graph.compile(g, {g.inputs[0]: x.shape})(jnp.asarray(x)))
    got = np.asarray(graph.stream_execute(g, x, chunk))
    assert got.shape == offline.shape, (name, chunk)
    np.testing.assert_allclose(got, offline, rtol=1e-6, atol=1e-6,
                               err_msg=f"{name} chunk={chunk}")


def test_streaming_conv_lowering():
    """Overlap-carry is lowering-agnostic: conv chunked == conv offline."""
    spec, x = _args("fir_decimate", 1024)
    g = spec.build()
    offline = np.asarray(graph.compile(
        g, {g.inputs[0]: x.shape},
        options=graph.CompileOptions(lowering="conv"))(jnp.asarray(x)))
    got = np.asarray(graph.stream_execute(
        g, x, 300, options=graph.CompileOptions(lowering="conv")))
    np.testing.assert_allclose(got, offline, rtol=1e-6, atol=1e-6)


def test_stream_spec_composition():
    """Receptive-field/stride arithmetic composes like conv shapes."""
    s = stream_spec(graph.build_fir_decimate(taps1=31, taps2=15))
    assert s.block == 4                       # two ↓2 stages
    assert s.receptive == 31 + (15 - 1) * 2   # K1 + (K2-1)·D1
    assert s.tail_dims == 0
    s = stream_spec(graph.build_pfb_power(n_branches=16, n_taps=8))
    assert (s.block, s.receptive, s.tail_dims) == (16, 128, 1)
    s = stream_spec(graph.build_spectrogram(window=64))
    assert (s.block, s.receptive, s.tail_dims) == (1, 64, 1)


def test_stream_step_buckets_bounded_plans_offline_identical():
    """step_buckets=True: irregular push sizes compile a bounded ladder
    of window shapes (not one plan per distinct length) and, with
    finalize(), the concatenated output still equals offline exactly."""
    spec, x = _args("spectrogram", 2048)
    g = spec.build()
    offline = np.asarray(
        graph.compile(g, {g.inputs[0]: x.shape})(jnp.asarray(x)))
    sizes = [97, 411, 64, 801, 333, 342]            # sums to 2048
    free = graph.ChunkedRunner(g)
    bucketed = graph.ChunkedRunner(g, step_buckets=True)
    for runner in (free, bucketed):
        outs, i = [], 0
        for s in sizes:
            o = runner.push(x[i:i + s])
            i += s
            if o is not None:
                outs.append(np.asarray(o))
        o = runner.finalize()
        if o is not None:
            outs.append(np.asarray(o))
        got = np.concatenate(outs, axis=runner.spec.concat_axis)
        np.testing.assert_allclose(got, offline, rtol=1e-6, atol=1e-6)
    # power-of-two step quantization: strictly fewer distinct plan
    # shapes than the free-running runner on this irregular schedule
    assert len(bucketed.window_lens) < len(free.window_lens)


def test_streaming_incremental_pushes():
    """Tiny pushes (smaller than the receptive field) buffer correctly."""
    spec, x = _args("spectrogram", 300)
    g = spec.build()
    offline = np.asarray(
        graph.compile(g, {g.inputs[0]: x.shape})(jnp.asarray(x)))
    runner = graph.ChunkedRunner(g)
    outs = [runner.push(x[i:i + 40]) for i in range(0, 300, 40)]
    got = np.concatenate([np.asarray(o) for o in outs if o is not None],
                         axis=runner.spec.concat_axis)
    np.testing.assert_allclose(got, offline, rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# autotune: measured once, persisted, reused
# ---------------------------------------------------------------------------
def test_autotune_persists_and_reuses(tmp_path, monkeypatch):
    cache = tmp_path / "autotune.json"
    monkeypatch.setenv("TINA_AUTOTUNE_CACHE", str(cache))
    monkeypatch.setenv("TINA_AUTOTUNE", "on")
    autotune._MEM.clear()
    spec, x = _args("fir_decimate", 256)
    g = spec.build()
    plan_lib.clear_cache()
    before = autotune.stats()
    p = graph.compile(g, {g.inputs[0]: x.shape}, options=graph.CompileOptions(
        lowering="auto", autotune_kwargs={"repeats": 1}))
    mid = autotune.stats()
    assert mid["measured"] > before["measured"]
    assert cache.exists()
    assert all(lw in ("native", "conv", "pallas")
               for lw in p.lowerings.values())
    np.testing.assert_allclose(np.asarray(p(jnp.asarray(x))),
                               spec.oracle(x), rtol=2e-3, atol=2e-3)
    # second compile of the same graph: disk/memory cache, no measuring
    plan_lib.clear_cache()
    graph.compile(g, {g.inputs[0]: x.shape}, options=graph.CompileOptions(
        lowering="auto", autotune_kwargs={"repeats": 1}))
    after = autotune.stats()
    assert after["measured"] == mid["measured"]
    assert after["cache_hits"] > mid["cache_hits"]


# ---------------------------------------------------------------------------
# serving: packed batches through one cached plan
# ---------------------------------------------------------------------------
def test_service_sync_flush_matches_oracle():
    spec = PIPELINES["spectrogram"]
    g = spec.build()
    svc = graph.PipelineService(g, signal_len=256, batch_size=4)
    xs = [RNG.standard_normal(256).astype(np.float32) for _ in range(6)]
    futs = [svc.submit(x) for x in xs]
    assert svc.flush() == 2               # 4, then 2 in bucket 2
    for x, f in zip(xs, futs):
        np.testing.assert_allclose(f.result(timeout=5), spec.oracle(x),
                                   rtol=2e-3, atol=2e-3)
    s = svc.stats()                       # fresh consistent snapshot
    assert {k: s[k] for k in ("requests", "batches", "padded_slots")} \
        == {"requests": 6, "batches": 2, "padded_slots": 0}
    assert s["latency_ms"]["total"]["count"] == 6
    # each batch ran its bucket's cached plan, traced once
    assert svc.plans[4].trace_count == svc.plans[2].trace_count == 1


def test_service_background_thread():
    spec = PIPELINES["fir_decimate"]
    g = spec.build()
    xs = [RNG.standard_normal(512).astype(np.float32) for _ in range(5)]
    with graph.PipelineService(g, signal_len=512, batch_size=2) as svc:
        futs = [svc.submit(x) for x in xs]
        outs = [f.result(timeout=60) for f in futs]
    for x, o in zip(xs, outs):
        np.testing.assert_allclose(o, spec.oracle(x), rtol=2e-3, atol=2e-3)


def test_service_rejects_wrong_shape():
    g = PIPELINES["spectrogram"].build()
    svc = graph.PipelineService(g, signal_len=256, batch_size=2)
    with pytest.raises(ValueError):
        svc.submit(np.zeros(300, np.float32))


def test_service_failed_batch_fails_futures_not_thread():
    g = PIPELINES["spectrogram"].build()
    svc = graph.PipelineService(g, signal_len=256, batch_size=2)
    svc.plans = {b: (lambda x: (_ for _ in ()).throw(RuntimeError("boom")))
                 for b in svc.plans}
    f = svc.submit(np.zeros(256, np.float32))
    svc.flush()
    with pytest.raises(RuntimeError, match="boom"):
        f.result(timeout=5)
    assert svc.stats()["failed_batches"] == 1


# ---------------------------------------------------------------------------
# edge cases surfaced by review
# ---------------------------------------------------------------------------
def test_per_node_lowering_dict_survives_fusion():
    """Requesting a lowering for nodes that the fusion pass folds must
    reach the fused node, not silently fall back to native."""
    g = graph.build_spectrogram(window=64)
    req = {n.name: "pallas" for n in g.topo()
           if n.op not in ("input", "const")}
    p = graph.compile(g, {"x": (300,)},
                      options=graph.CompileOptions(lowering=req))
    fused = [n for n in p.graph.topo() if n.op == "fused_ew"]
    assert fused and p.lowerings[fused[0].name] == "pallas"


def test_stream_signal_shorter_than_receptive_field():
    g = graph.build_spectrogram(window=64)
    with pytest.raises(ValueError, match="receptive field"):
        graph.stream_execute(g, np.zeros(50, np.float32), 20)


def test_fusion_with_interleaved_const_declarations():
    """Operands declared between run members must survive fusion (the
    fused node is emitted at the run tail, after all its inputs)."""
    g = graph.Graph("interleaved")
    x = g.input("x")
    c0 = g.const(np.full((8, 8), 2.0, np.float32))
    a = g.apply("ew_mul", x, c0)
    c1 = g.const(np.full((8, 8), 3.0, np.float32))   # declared mid-chain
    b = g.apply("ew_add", a, c1)
    g.output(b)
    xv = RNG.standard_normal((8, 8)).astype(np.float32)
    p = graph.compile(g, {"x": xv.shape})
    assert any(n.op == "fused_ew" for n in p.graph.topo())
    np.testing.assert_allclose(np.asarray(p(jnp.asarray(xv))),
                               xv * 2.0 + 3.0, rtol=1e-6, atol=1e-6)


def test_service_rejects_multi_output_graph():
    g = graph.Graph("two_out")
    x = g.input("x")
    a = g.apply("scale", x, factor=2.0)
    b = g.apply("scale", x, factor=3.0)
    g.output(a, b)
    with pytest.raises(ValueError, match="single-output"):
        graph.PipelineService(g, signal_len=16, batch_size=2)


def test_unknown_op_raises_cleanly():
    g = graph.Graph("bad")
    x = g.input("x")
    g.output(g.apply("fft_magic", x))
    with pytest.raises(ValueError, match="unknown op 'fft_magic'"):
        graph.compile(g, {"x": (8,)})


def test_service_submit_after_close_raises():
    """A closed service has no consumer left (thread joined, final flush
    ran): enqueuing would hang the caller in fut.result() forever."""
    g = PIPELINES["spectrogram"].build()
    svc = graph.PipelineService(g, signal_len=256, batch_size=2)
    with svc:
        f = svc.submit(np.zeros(256, np.float32))
        f.result(timeout=60)
    with pytest.raises(RuntimeError, match="service closed"):
        svc.submit(np.zeros(256, np.float32))
    with pytest.raises(RuntimeError, match="service closed"):
        svc.start()


def test_service_close_is_idempotent():
    g = PIPELINES["spectrogram"].build()
    svc = graph.PipelineService(g, signal_len=256, batch_size=2)
    f = svc.submit(np.zeros(256, np.float32))
    svc.close()                      # never started: close just drains
    assert f.result(timeout=5).shape
    svc.close()                      # second close: no-op, no error


def test_service_flush_while_started_raises():
    """flush() racing the batcher thread would split one logical batch
    between two consumers (each dispatching a padded partial)."""
    g = PIPELINES["spectrogram"].build()
    svc = graph.PipelineService(g, signal_len=256, batch_size=2)
    svc.start()
    try:
        with pytest.raises(RuntimeError, match="two consumers"):
            svc.flush()
    finally:
        svc.close()
    # after close the thread is gone: flush is legal again (and empty)
    assert svc.flush() == 0


def test_service_close_timeout_is_retryable():
    """A close() that times out on a slow (not hung) batch raises but
    leaves the service retryable: the next close() re-joins the thread
    and finishes the shutdown instead of silently no-opping."""
    import time as time_lib

    g = PIPELINES["spectrogram"].build()
    svc = graph.PipelineService(g, signal_len=256, batch_size=2,
                                close_timeout=0.05)
    svc.plans = {b: (lambda x, p=p: (time_lib.sleep(0.4), p(x))[1])
                 for b, p in svc.plans.items()}
    svc.start()
    f = svc.submit(np.zeros(256, np.float32))
    with pytest.raises(RuntimeError, match="retry"):
        svc.close()
    svc.close_timeout = 30
    svc.close()                       # retry joins the finishing thread
    assert f.result(timeout=5).shape  # the slow batch still completed
    with pytest.raises(RuntimeError, match="service closed"):
        svc.submit(np.zeros(256, np.float32))


def test_append_bench_json_atomic_on_crash(tmp_path, monkeypatch):
    """A crash mid-write must not destroy the accumulated trajectory:
    the dump goes to a temp file and lands via os.replace."""
    import json as json_lib

    from benchmarks import common
    path = tmp_path / "BENCH_z.json"
    common.append_bench_json(str(path), [{"t": 1.0}], figure="f")
    before = path.read_text()

    def boom(*a, **k):
        raise KeyboardInterrupt("simulated crash mid-dump")

    monkeypatch.setattr(common.json, "dump", boom)
    with pytest.raises(KeyboardInterrupt):
        common.append_bench_json(str(path), [{"t": 2.0}], figure="f")
    assert path.read_text() == before          # previous file intact
    assert not list(tmp_path.glob("*.tmp"))    # temp file cleaned up
    monkeypatch.undo()
    data = json_lib.loads(path.read_text())
    assert len(data["runs"]) == 1


def test_append_bench_json_corrupt_file_backed_up(tmp_path):
    """A corrupt/truncated accumulator must not crash the bench job: the
    damaged bytes move to .bak and the run list restarts."""
    import json as json_lib

    from benchmarks import common
    path = tmp_path / "BENCH_c.json"
    path.write_text('{"figure": "f", "runs": [{"resul')   # truncated dump
    with pytest.warns(UserWarning, match="corrupt"):
        common.append_bench_json(str(path), [{"t": 3.0}], figure="f")
    assert (tmp_path / "BENCH_c.json.bak").read_text().startswith(
        '{"figure"')                                      # forensics kept
    data = json_lib.loads(path.read_text())
    assert len(data["runs"]) == 1
    assert data["runs"][0]["results"] == [{"t": 3.0}]
    # and the repaired file accumulates normally again
    common.append_bench_json(str(path), [{"t": 4.0}], figure="f")
    assert len(json_lib.loads(path.read_text())["runs"]) == 2


def test_check_regression_gate(tmp_path, monkeypatch):
    """The CI bench gate: >threshold tuned-plan throughput loss fails,
    equal-or-better passes, and a commit-message waiver downgrades."""
    import json as json_lib

    from benchmarks import check_regression

    def bench(path, t, per_op=2.0e-3):
        # per_op is the same-run normalizer: the gate compares
        # t_pallas_tuned_s / t_per_op_s so machine speed cancels
        path.write_text(json_lib.dumps({"figure": "fig4_pipelines", "runs": [
            {"git_rev": "x", "timestamp": "t", "results": [
                {"pipeline": "spectrogram", "n": 4096,
                 "t_per_op_s": per_op, "t_pallas_tuned_s": t}]}]}))

    base, fresh = tmp_path / "base.json", tmp_path / "fresh.json"
    bench(base, 1.0e-3)
    monkeypatch.setenv("BENCH_COMMIT_MSG", "normal commit message")
    # hermetic: the waiver scan falls through to git history, and this
    # repo's actual commit messages must not decide the test
    monkeypatch.setattr(check_regression, "_git_msg", lambda *rev: "")

    bench(fresh, 1.1e-3)          # 9% slower: inside the 25% budget
    assert check_regression.main(["--baseline", str(base),
                                  "--fresh", str(fresh)]) == 0
    bench(fresh, 1.5e-3)          # 33% throughput loss: gate fires
    assert check_regression.main(["--baseline", str(base),
                                  "--fresh", str(fresh)]) == 1
    monkeypatch.setenv("BENCH_COMMIT_MSG",
                       "slow but correct\n\nbench-waiver: kernel fix")
    assert check_regression.main(["--baseline", str(base),
                                  "--fresh", str(fresh)]) == 0
    # a uniformly 2x slower CI runner is NOT a regression: the gate
    # compares tuned-plan time relative to the same run's per-op
    # baseline, so machine speed cancels
    monkeypatch.setenv("BENCH_COMMIT_MSG", "normal commit message")
    bench(fresh, 2.0e-3, per_op=4.0e-3)
    assert check_regression.main(["--baseline", str(base),
                                  "--fresh", str(fresh)]) == 0


def test_check_regression_multi_metric(tmp_path, monkeypatch):
    """Comma-separated --metric gates each metric independently: a
    regression in the second metric alone fails, a single --relative-to
    broadcasts to all metrics, and mismatched list lengths are a usage
    error."""
    import json as json_lib

    from benchmarks import check_regression

    def bench(path, t_plan, t_tuned):
        path.write_text(json_lib.dumps({"figure": "fig4_pipelines", "runs": [
            {"git_rev": "x", "timestamp": "t", "results": [
                {"pipeline": "pfb_power", "n": 4096,
                 "t_per_op_s": 10.0, "t_pallas_default_s": 20.0,
                 "t_plan_s": t_plan, "t_pallas_tuned_s": t_tuned}]}]}))

    base, fresh = tmp_path / "base.json", tmp_path / "fresh.json"
    bench(base, 2.0, 5.0)
    monkeypatch.setenv("BENCH_COMMIT_MSG", "normal commit message")
    monkeypatch.setattr(check_regression, "_git_msg", lambda *rev: "")
    args = ["--baseline", str(base), "--fresh", str(fresh),
            "--metric", "t_plan_s,t_pallas_tuned_s",
            "--relative-to", "t_per_op_s,t_pallas_default_s"]

    bench(fresh, 2.1, 5.2)            # both inside the 25% budget
    assert check_regression.main(args) == 0
    bench(fresh, 2.1, 9.0)            # first fine, second regressed 80%
    assert check_regression.main(args) == 1
    # the waiver mechanism covers every metric in the invocation
    monkeypatch.setenv("BENCH_COMMIT_MSG",
                       "tail hit\n\nbench-waiver: scheduler rework")
    assert check_regression.main(args) == 0
    monkeypatch.setenv("BENCH_COMMIT_MSG", "normal commit message")
    # one --relative-to broadcasts across all metrics
    bench(fresh, 2.1, 5.2)
    assert check_regression.main(
        ["--baseline", str(base), "--fresh", str(fresh),
         "--metric", "t_plan_s,t_pallas_tuned_s",
         "--relative-to", "t_per_op_s"]) == 0
    # 2 metrics x 3 relative-to entries is a usage error, not a pass
    with pytest.raises(SystemExit):
        check_regression.main(
            ["--baseline", str(base), "--fresh", str(fresh),
             "--metric", "t_plan_s,t_pallas_tuned_s",
             "--relative-to", "a,b,c"])


def test_check_regression_higher_is_better_floor(tmp_path, monkeypatch):
    """--higher-is-better turns the gate into a quality floor (the CI
    int8_sqnr_db invocation): a drop below threshold fails, a *rise*
    never does, and values <= 0 are gated instead of skipped."""
    import json as json_lib

    from benchmarks import check_regression

    def bench(path, q):
        path.write_text(json_lib.dumps({"figure": "fig4_pipelines", "runs": [
            {"git_rev": "x", "timestamp": "t", "results": [
                {"pipeline": "pfb_power", "n": 4096,
                 "t_pallas_tuned_s": 1e-3, "int8_sqnr_db": q}]}]}))

    base, fresh = tmp_path / "base.json", tmp_path / "fresh.json"
    bench(base, 30.0)
    monkeypatch.setenv("BENCH_COMMIT_MSG", "normal commit message")
    monkeypatch.setattr(check_regression, "_git_msg", lambda *rev: "")
    args = ["--baseline", str(base), "--fresh", str(fresh),
            "--threshold", "0.10", "--metric", "int8_sqnr_db",
            "--relative-to", "", "--higher-is-better"]

    bench(fresh, 28.0)                # -6.7%: inside the 10% budget
    assert check_regression.main(args) == 0
    bench(fresh, 45.0)                # better accuracy never fails
    assert check_regression.main(args) == 0
    bench(fresh, 24.0)                # -20%: floor fires
    assert check_regression.main(args) == 1
    bench(fresh, -3.0)                # catastrophic: gated, not skipped
    assert check_regression.main(args) == 1
    monkeypatch.setenv("BENCH_COMMIT_MSG",
                       "tradeoff\n\nbench-waiver: tile change")
    assert check_regression.main(args) == 0
    # without the flag the same drop would PASS (ceiling semantics
    # reads a smaller value as faster) — the flag is load-bearing
    monkeypatch.setenv("BENCH_COMMIT_MSG", "normal commit message")
    bench(fresh, 24.0)
    assert check_regression.main(args[:-1]) == 0


def test_autotune_save_merges_concurrent_entries(tmp_path, monkeypatch):
    """_save must not clobber entries another process persisted — and a
    v1-format file on disk must survive the merge (migrated to v2)."""
    import json
    cache_file = tmp_path / "tune.json"
    cache_file.write_text(json.dumps({"other_proc_key": {"lowering": "conv"}}))
    autotune._MEM.clear()
    autotune._save(str(cache_file), {"my_key": {"lowering": "native",
                                                "config": {"bn": 512}}})
    raw = json.loads(cache_file.read_text())
    assert raw["schema"] == autotune.SCHEMA_VERSION
    assert set(raw["entries"]) == {"other_proc_key", "my_key"}
    assert raw["entries"]["other_proc_key"]["lowering"] == "conv"
