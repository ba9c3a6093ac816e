"""Block-size autotuning v2 tests: per-kernel TuneSpace config sweeps
(every valid config is output-identical), kernel-boundary validation,
tuner candidate filtering, cache schema v2 + v1 migration, in-process
cache mtime invalidation, TINA_AUTOTUNE modes, config plumbing through
plans/streaming/serving, and per-PR benchmark accumulation."""
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from repro import graph
from repro.core.registry import PIPELINES, pipelines
from repro.graph import autotune, plan as plan_lib
from repro.kernels import ops
from repro.kernels import tune as ktune

pipelines()
RNG = np.random.default_rng(3)


@pytest.fixture()
def tune_env(tmp_path, monkeypatch):
    """Isolated autotune cache + explicit mode, clean in-process state."""
    cache = tmp_path / "autotune.json"
    monkeypatch.setenv("TINA_AUTOTUNE_CACHE", str(cache))
    monkeypatch.setenv("TINA_AUTOTUNE", "on")
    autotune._MEM.clear()
    plan_lib.clear_cache()
    return cache


# ---------------------------------------------------------------------------
# config sweeps: every valid block config produces the same output
# ---------------------------------------------------------------------------
_FIR_CTX = {"k": 31, "n": 300, "rows": 2}


@pytest.mark.parametrize(
    "cfg", ktune.space("fir").configs(_FIR_CTX),
    ids=lambda c: f"bb{c['bb']}bn{c['bn']}")
def test_fir_all_valid_configs_match(cfg):
    x = RNG.standard_normal((2, 300)).astype(np.float32)
    k = RNG.standard_normal(31).astype(np.float32)
    want = np.stack([np.correlate(r, k, mode="valid") for r in x])
    got = np.asarray(ops.fir(jnp.asarray(x), jnp.asarray(k), **cfg))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


_PFB_CTX = {"m": 8, "p": 16, "t": 64}


@pytest.mark.parametrize(
    "cfg", ktune.space("pfb").configs(_PFB_CTX),
    ids=lambda c: f"bt{c['bt']}bn{c['bn']}")
def test_pfb_all_valid_configs_match(cfg):
    from repro.core import pfb as pfb_lib
    taps = pfb_lib.pfb_window(16, 8).astype(np.float32)
    x = RNG.standard_normal(16 * 64).astype(np.float32)
    want = PIPELINES["pfb_power"].oracle(x)     # |pfb|² with same taps
    z = np.asarray(ops.pfb(jnp.asarray(x), jnp.asarray(taps), **cfg))
    np.testing.assert_allclose(np.abs(z) ** 2, want, rtol=2e-3, atol=2e-3)


_MM_CTX = {"m": 96, "n": 48, "k": 80}


@pytest.mark.parametrize(
    "cfg", ktune.space("matmul").configs(_MM_CTX),
    ids=lambda c: f"bm{c['bm']}bn{c['bn']}bk{c['bk']}")
def test_matmul_all_valid_configs_match(cfg):
    x = RNG.standard_normal((96, 80)).astype(np.float32)
    y = RNG.standard_normal((80, 48)).astype(np.float32)
    got = np.asarray(ops.matmul(jnp.asarray(x), jnp.asarray(y), **cfg))
    np.testing.assert_allclose(got, x @ y, rtol=1e-4, atol=1e-4)


_EW_CTX = {"rows": 33, "cols": 40, "n_in": 3}


@pytest.mark.parametrize(
    "cfg", ktune.space("elementwise").configs(_EW_CTX),
    ids=lambda c: f"bm{c['bm']}bn{c['bn']}")
def test_elementwise_chain_all_valid_configs_match(cfg):
    z = (RNG.standard_normal((33, 40))
         + 1j * RNG.standard_normal((33, 40))).astype(np.complex64)
    w = RNG.standard_normal((33, 40)).astype(np.float32)
    want = (np.abs(z) ** 2) * w * 0.5
    got = np.asarray(ops.fused_elementwise(
        jnp.asarray(z), (jnp.asarray(w),),
        (("abs2",), ("mul",), ("scale", 0.5)), **cfg))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# kernel-boundary validation: invalid configs raise, not assert mid-trace
# ---------------------------------------------------------------------------
def test_invalid_fir_config_rejected():
    x = jnp.zeros((2, 300), jnp.float32)
    k = jnp.zeros(31, jnp.float32)
    with pytest.raises(ValueError, match="invalid block config"):
        ops.fir(x, k, bn=16)            # taps 31 exceed the halo block
    with pytest.raises(ValueError, match="unknown block param"):
        ktune.space("fir").check({"bq": 4}, _FIR_CTX)


def test_invalid_pfb_config_rejected():
    from repro.core import pfb as pfb_lib
    taps = jnp.asarray(pfb_lib.pfb_window(16, 8).astype(np.float32))
    x = jnp.zeros(16 * 64, jnp.float32)
    with pytest.raises(ValueError, match="invalid block config"):
        ops.pfb(x, taps, bn=24)         # 24 does not divide P=16
    with pytest.raises(ValueError, match="invalid block config"):
        ops.pfb(x, taps, bt=4)          # taps 8 exceed the frame halo


def test_tuner_never_selects_invalid_config(tune_env, monkeypatch):
    """Candidates failing the validity predicate are filtered before
    measurement — even if the declared candidate list contains them."""
    import dataclasses
    from repro.kernels import fir as fir_kernel
    sp = dataclasses.replace(
        fir_kernel.TUNE_SPACE,
        candidates=lambda ctx: (
            {"bb": 8, "bn": 16},        # invalid: taps exceed halo
            {"bb": 8, "bn": 1024},      # valid
        ))
    monkeypatch.setitem(ktune.SPACES, "fir", sp)
    taps = np.hanning(31).astype(np.float32)
    g = graph.Graph("one_fir")
    g.output(g.apply("fir", g.input("x"), g.const(taps, "taps")))
    p = graph.compile(g, {"x": (600,)}, options=graph.CompileOptions(
        lowering="pallas", block_configs="auto",
        autotune_kwargs={"repeats": 1}))
    (cfg,) = [c for c in p.configs.values() if c]
    assert cfg["bn"] >= 30              # 31 taps: bn=16 must be filtered
    entries = json.load(open(tune_env))["entries"]
    assert entries                      # the fir node was measured
    for entry in entries.values():
        assert not any("bn=16" in label for label in entry["times_us"])
    x = RNG.standard_normal(600).astype(np.float32)
    np.testing.assert_allclose(
        np.asarray(p(jnp.asarray(x))),
        np.convolve(x, taps, mode="valid"), rtol=2e-3, atol=2e-3)


def test_default_config_trusted_even_when_predicate_rejects_it():
    """The kernel default must keep working for shapes the (TPU-minded)
    VMEM predicate is conservative about — only explicit overrides are
    gated.  window=511 makes every unfold candidate fail the VMEM bound
    (the (bb, bt, J) output tile alone is ~8 MB), yet the pre-tuning
    wrapper ran it."""
    ctx = {"j": 511, "n": 2048, "rows": 1}
    assert ktune.space("unfold").configs(ctx) == ()     # all filtered
    x = jnp.asarray(RNG.standard_normal(2048).astype(np.float32))
    got = np.asarray(ops.unfold(x, 511))                # defaults: runs
    want = np.lib.stride_tricks.sliding_window_view(
        np.asarray(x), 511, axis=-1)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


def test_tuner_falls_back_when_config_space_is_empty(tune_env):
    """A node whose TuneSpace yields zero valid candidates must compile
    with kernel defaults, not crash the tuner."""
    g = graph.Graph("big_unfold")
    g.output(g.apply("unfold", g.input("x"), window=511))
    p = graph.compile(g, {"x": (2048,)}, options=graph.CompileOptions(
        lowering="pallas", block_configs="auto",
        autotune_kwargs={"repeats": 1}))
    assert all(not c for c in p.configs.values())
    x = RNG.standard_normal(2048).astype(np.float32)
    want = np.lib.stride_tricks.sliding_window_view(x, 511, axis=-1)
    np.testing.assert_allclose(np.asarray(p(jnp.asarray(x))), want,
                               rtol=1e-6, atol=1e-6)


def test_pfb_default_bn_divides_awkward_branch_counts():
    """The default column block must divide P even for P that is not a
    power of two (> the old min(128, P) assumption)."""
    sp = ktune.space("pfb")
    for p in (8, 16, 24, 128, 136, 129):
        bn = sp.default({"m": 4, "p": p, "t": 32})["bn"]
        assert p % bn == 0, (p, bn)
    from repro.core import pfb as pfb_lib
    taps = pfb_lib.pfb_window(24, 4).astype(np.float32)
    x = RNG.standard_normal(24 * 32).astype(np.float32)
    z = np.asarray(ops.pfb(jnp.asarray(x), jnp.asarray(taps)))
    assert z.shape == (29, 24)


def test_full_auto_still_measures_pallas_when_space_is_empty(tune_env):
    """An empty config space must not silently drop the pallas lowering
    from the full-auto search — the trusted kernel default still runs
    (and v1 always measured pallas)."""
    g = graph.Graph("big_unfold_auto")
    g.output(g.apply("unfold", g.input("x"), window=511))
    graph.compile(g, {"x": (2048,)}, options=graph.CompileOptions(
        lowering="auto", autotune_kwargs={"repeats": 1}))
    entries = json.load(open(tune_env))["entries"]
    (entry,) = entries.values()
    assert "pallas" in entry["times_us"]    # measured with default blocks


def test_stale_cached_config_falls_back_not_crashes(tune_env, monkeypatch):
    """A persisted config the current TuneSpace rejects (e.g. after a
    predicate change) must be ignored, not fed into the kernel boundary
    where it would raise mid-compile."""
    import jax
    g = graph.Graph("one_fir_stale")
    g.output(g.apply("fir", g.input("x"),
                     g.const(np.hanning(31).astype(np.float32), "taps")))
    specs = plan_lib._norm_specs(g, {"x": (600,)}, "float32")
    avals = plan_lib.infer(g, specs)
    node = next(n for n in g.topo() if n.op == "fir")
    key = autotune.node_key(node, [avals[i] for i in node.inputs],
                            jax.default_backend()) + "|only=pallas"
    tune_env.write_text(json.dumps({"schema": 2, "entries": {key: {
        "lowering": "pallas", "config": {"bb": 8, "bn": 16},  # 31 taps!
        "backend": jax.default_backend()}}}))
    autotune._MEM.clear()
    monkeypatch.setenv("TINA_AUTOTUNE", "cached")
    p = graph.compile(g, {"x": (600,)}, options=graph.CompileOptions(
        lowering="pallas", block_configs="auto"))
    assert all(not c for c in p.configs.values())   # defaults, no crash
    x = RNG.standard_normal(600).astype(np.float32)
    p(jnp.asarray(x))


def test_restricted_candidates_honored_in_cached_mode(tune_env, monkeypatch):
    """With a cold cache in cached/off mode, pick must fall back inside
    the caller's candidate set, never to an excluded lowering."""
    import jax
    monkeypatch.setenv("TINA_AUTOTUNE", "cached")
    g = graph.build_fir_decimate()
    specs = plan_lib._norm_specs(g, {"x": (600,)}, "float32")
    avals = plan_lib.infer(g, specs)
    node = next(n for n in g.topo() if n.op == "fir")
    lw, cfg = autotune.pick(g, node, avals, backend=jax.default_backend(),
                            candidates=("conv", "pallas"))
    assert lw in ("conv", "pallas") and cfg == {}


# ---------------------------------------------------------------------------
# cache schema: v1 migration, mtime invalidation
# ---------------------------------------------------------------------------
def test_cache_v1_entries_migrate_and_are_honored(tune_env, monkeypatch):
    """A v1 (flat, lowering-only) cache file is readable, its winners
    are honored with default block configs, and a save rewrites it as
    schema v2 without losing entries."""
    import jax
    g = graph.build_fir_decimate(taps1=31, taps2=15)
    specs = plan_lib._norm_specs(g, {"x": (600,)}, "float32")
    avals = plan_lib.infer(g, specs)
    backend = jax.default_backend()
    v1 = {}
    for node in g.topo():
        if node.op == "fir":
            key = autotune.node_key(
                node, [avals[i] for i in node.inputs], backend)
            v1[key] = {"lowering": "conv", "backend": backend}
    tune_env.write_text(json.dumps(v1))
    autotune._MEM.clear()

    monkeypatch.setenv("TINA_AUTOTUNE", "cached")
    p = graph.compile(g, {"x": (600,)},
                      options=graph.CompileOptions(lowering="auto"))
    fir_lw = [p.lowerings[n.name] for n in p.graph.topo() if n.op == "fir"]
    assert fir_lw == ["conv", "conv"]   # the v1 winners, not defaults
    assert all(not c for c in p.configs.values())

    # a later save in "on" mode upgrades the file, keeping v1 entries
    monkeypatch.setenv("TINA_AUTOTUNE", "on")
    autotune._save(str(tune_env), {"new_key": {"lowering": "native",
                                               "config": {}}})
    raw = json.load(open(tune_env))
    assert raw["schema"] == autotune.SCHEMA_VERSION
    assert set(v1) | {"new_key"} == set(raw["entries"])


def test_mem_cache_invalidated_on_mtime_change(tune_env):
    autotune._save(str(tune_env), {"a": {"lowering": "native", "config": {}}})
    assert set(autotune._load(str(tune_env))) == {"a"}
    # another process rewrites the file: same path, new content + mtime
    tune_env.write_text(json.dumps(
        {"schema": 2, "entries": {"b": {"lowering": "conv", "config": {}}}}))
    os.utime(tune_env, ns=(1, int(os.stat(tune_env).st_mtime_ns) + 10 ** 9))
    assert set(autotune._load(str(tune_env))) == {"b"}


@pytest.mark.parametrize("garbage, why", [
    ("{not json", "unparseable JSON"),
    ("[1, 2, 3]", "not a JSON object"),
    (json.dumps({"schema": 2, "entries": [1]}), "not an object"),
], ids=["bad-json", "non-dict", "bad-entries"])
def test_corrupt_cache_quarantined_to_bak(tune_env, garbage, why):
    """A cache file that exists but can't be parsed is preserved as
    .bak (not silently shadowed), warned about, counted, and replaced
    by a fresh cache — the append_bench_json discipline."""
    tune_env.write_text(garbage)
    before = autotune.stats()["cache_corrupt"]
    with pytest.warns(UserWarning, match=why):
        entries = autotune._read_file(str(tune_env))
    assert entries == {}
    assert autotune.stats()["cache_corrupt"] == before + 1
    bak = tune_env.with_suffix(tune_env.suffix + ".bak")
    assert bak.read_text() == garbage       # evidence preserved
    assert not tune_env.exists()            # fresh start
    # and the tuner can immediately save a healthy v2 file again
    autotune._save(str(tune_env), {"k": {"lowering": "native",
                                         "config": {}}})
    assert json.load(open(tune_env))["schema"] == autotune.SCHEMA_VERSION


def test_missing_cache_is_not_corrupt(tune_env):
    before = autotune.stats()["cache_corrupt"]
    assert autotune._read_file(str(tune_env)) == {}     # no file: fresh
    assert autotune.stats()["cache_corrupt"] == before  # not an anomaly


def test_cache_io_fault_falls_back_to_memory(tune_env):
    """An injected cache_io fault behaves like a read-only FS: reads
    are a fresh start, saves keep tuning in-memory — never a crash, and
    a healthy file is never quarantined for an I/O failure."""
    from repro.obs import faults
    tune_env.write_text(json.dumps({"schema": 2, "entries": {
        "k": {"lowering": "conv", "config": {}}}}))
    faults.configure("cache_io:x2", seed=0)
    try:
        assert autotune._read_file(str(tune_env)) == {}     # injected read
        autotune._save(str(tune_env), {"j": {"lowering": "native",
                                             "config": {}}})  # injected write
        assert tune_env.exists()            # file untouched, not .bak'd
        assert set(autotune._read_file(str(tune_env))) == {"k"}  # healed
    finally:
        faults.reset()


# ---------------------------------------------------------------------------
# TINA_AUTOTUNE modes
# ---------------------------------------------------------------------------
def test_mode_off_uses_fixed_defaults(tune_env, monkeypatch):
    monkeypatch.setenv("TINA_AUTOTUNE", "off")
    before = autotune.stats()["measured"]
    p = graph.compile(PIPELINES["spectrogram"].build(), {"x": (300,)},
                      options=graph.CompileOptions(lowering="auto"))
    assert autotune.stats()["measured"] == before
    assert all(lw == "native" for lw in p.lowerings.values())
    assert all(not c for c in p.configs.values())
    assert not tune_env.exists()


def test_mode_cached_never_measures(tune_env, monkeypatch):
    monkeypatch.setenv("TINA_AUTOTUNE", "cached")
    before = autotune.stats()["measured"]
    p = graph.compile(PIPELINES["spectrogram"].build(), {"x": (300,)},
                      options=graph.CompileOptions(lowering="auto"))
    assert autotune.stats()["measured"] == before
    assert not tune_env.exists()        # nothing persisted either
    x = RNG.standard_normal(300).astype(np.float32)
    np.testing.assert_allclose(np.asarray(p(jnp.asarray(x))),
                               PIPELINES["spectrogram"].oracle(x),
                               rtol=2e-3, atol=2e-3)


def test_mode_on_measures_and_cached_then_reuses(tune_env, monkeypatch):
    g = PIPELINES["spectrogram"].build()
    p1 = graph.compile(g, {"x": (300,)}, options=graph.CompileOptions(
        lowering="auto", autotune_kwargs={"repeats": 1}))
    assert tune_env.exists()
    # flip to cached with the just-written cache: same selections, and
    # a fresh process (cleared _MEM/plan cache) must not re-measure
    monkeypatch.setenv("TINA_AUTOTUNE", "cached")
    autotune._MEM.clear()
    plan_lib.clear_cache()
    before = autotune.stats()["measured"]
    p2 = graph.compile(g, {"x": (300,)}, options=graph.CompileOptions(
        lowering="auto", autotune_kwargs={"repeats": 1}))
    assert autotune.stats()["measured"] == before
    assert p2.lowerings == p1.lowerings and p2.configs == p1.configs


def test_mode_invalid_raises(monkeypatch):
    monkeypatch.setenv("TINA_AUTOTUNE", "sometimes")
    with pytest.raises(ValueError, match="TINA_AUTOTUNE"):
        autotune.mode()


# ---------------------------------------------------------------------------
# plumbing: explicit + tuned configs reach the executed kernels
# ---------------------------------------------------------------------------
def test_explicit_block_configs_reach_plan(tune_env):
    g = graph.build_fir_decimate(taps1=31, taps2=15)
    names = [n.name for n in g.topo() if n.op == "fir"]
    cfgs = {names[0]: {"bb": 8, "bn": 1024}, names[1]: {"bb": 16, "bn": 256}}
    p = graph.compile(g, {"x": (600,)}, options=graph.CompileOptions(
        lowering="pallas", block_configs=cfgs))
    assert p.configs[names[0]] == {"bb": 8, "bn": 1024}
    x = RNG.standard_normal(600).astype(np.float32)
    np.testing.assert_allclose(np.asarray(p(jnp.asarray(x))),
                               PIPELINES["fir_decimate"].oracle(x),
                               rtol=2e-3, atol=2e-3)


def test_streaming_with_tuned_configs_equals_offline(tune_env):
    spec = PIPELINES["spectrogram"]
    x = spec.make_args(RNG, 1024)[0]
    g = spec.build()
    offline = np.asarray(graph.compile(g, {"x": x.shape})(jnp.asarray(x)))
    got = np.asarray(graph.stream_execute(
        g, x, 400, options=graph.CompileOptions(
            lowering="auto", autotune_kwargs={"repeats": 1})))
    np.testing.assert_allclose(got, offline, rtol=2e-3, atol=2e-3)


def test_service_with_tuned_configs_matches_oracle(tune_env):
    spec = PIPELINES["fir_decimate"]
    svc = graph.PipelineService(spec.build(), signal_len=256, batch_size=2,
                                options=graph.CompileOptions(
                                    lowering="pallas", block_configs="auto",
                                    autotune_kwargs={"repeats": 1}))
    xs = [RNG.standard_normal(256).astype(np.float32) for _ in range(3)]
    futs = [svc.submit(x) for x in xs]
    svc.flush()
    for x, f in zip(xs, futs):
        np.testing.assert_allclose(f.result(timeout=5), spec.oracle(x),
                                   rtol=2e-3, atol=2e-3)


def test_auto_plan_cache_hit_after_tuning_writes(tune_env):
    """The tuning pass bumps the cache file's mtime; the compiled plan
    must be memoized under the post-save key so the next identical
    compile is a pure cache hit (the streaming warm-up guarantee)."""
    g = PIPELINES["spectrogram"].build()
    p1 = graph.compile(g, {"x": (300,)}, options=graph.CompileOptions(
        lowering="auto", autotune_kwargs={"repeats": 1}))
    p2 = graph.compile(g, {"x": (300,)}, options=graph.CompileOptions(
        lowering="auto", autotune_kwargs={"repeats": 1}))
    assert p2 is p1


def test_auto_plan_not_stale_across_mode_switch(tune_env, monkeypatch):
    """compile(lowering='auto') under a new TINA_AUTOTUNE mode must not
    return the plan memoized under the old mode."""
    g = PIPELINES["spectrogram"].build()
    p_on = graph.compile(g, {"x": (300,)}, options=graph.CompileOptions(
        lowering="auto", autotune_kwargs={"repeats": 1}))
    monkeypatch.setenv("TINA_AUTOTUNE", "off")
    p_off = graph.compile(g, {"x": (300,)},
                          options=graph.CompileOptions(lowering="auto"))
    assert p_off is not p_on
    assert all(lw == "native" for lw in p_off.lowerings.values())


# ---------------------------------------------------------------------------
# fusion autotuning: fuse="auto" measures fused vs unfused per chain
# ---------------------------------------------------------------------------
def test_fusion_verdict_measured_persisted_and_replayed(tune_env,
                                                       monkeypatch):
    """TINA_AUTOTUNE=on measures the fused node against the sequential
    member chain, persists the verdict in the v2 cache, and cached mode
    replays it without re-measuring."""
    g = graph.build_spectrogram(window=64)       # abs2 -> scale chain
    # decisive unfused win: pick_fusion measures fused first, unfused
    # second — make the chain "win" by 2x so hysteresis can't keep it
    times = iter([1.0, 0.4])
    monkeypatch.setattr(autotune, "measure",
                        lambda fn, args, **k: next(times, 0.4))
    p = graph.compile(g, {"x": (300,)}, options=graph.CompileOptions(
        fuse="auto", autotune_kwargs={"repeats": 1}))
    assert not any(n.op == "fused_ew" for n in p.graph.topo())
    entries = json.load(open(tune_env))["entries"]
    fkeys = [k for k in entries if k.startswith("fusion|")]
    assert fkeys and entries[fkeys[0]]["fused"] is False
    assert entries[fkeys[0]]["times_us"]["unfused"] < \
        entries[fkeys[0]]["times_us"]["fused"]

    # cached mode: verdict replayed, nothing measured
    monkeypatch.setenv("TINA_AUTOTUNE", "cached")
    monkeypatch.setattr(autotune, "measure",
                        lambda *a, **k: pytest.fail("measured in cached"))
    autotune._MEM.clear()
    plan_lib.clear_cache()
    p2 = graph.compile(g, {"x": (300,)},
                       options=graph.CompileOptions(fuse="auto"))
    assert not any(n.op == "fused_ew" for n in p2.graph.topo())


def test_fusion_auto_keeps_fused_when_not_decisively_slower(tune_env,
                                                           monkeypatch):
    """A marginal unfused 'win' inside the hysteresis margin keeps the
    fused default (noise must not flap plans)."""
    g = graph.build_spectrogram(window=64)
    times = iter([1.0, 0.99])
    monkeypatch.setattr(autotune, "measure",
                        lambda fn, args, **k: next(times, 0.99))
    p = graph.compile(g, {"x": (300,)}, options=graph.CompileOptions(
        fuse="auto", autotune_kwargs={"repeats": 1}))
    assert any(n.op == "fused_ew" for n in p.graph.topo())
    entries = json.load(open(tune_env))["entries"]
    (fe,) = [v for k, v in entries.items() if k.startswith("fusion|")]
    assert fe["fused"] is True


def test_fusion_auto_off_and_cold_cached_keep_fused_default(tune_env,
                                                            monkeypatch):
    for mode in ("off", "cached"):
        monkeypatch.setenv("TINA_AUTOTUNE", mode)
        plan_lib.clear_cache()
        p = graph.compile(graph.build_spectrogram(window=64),
                          {"x": (300,)},
                          options=graph.CompileOptions(fuse="auto"))
        assert any(n.op == "fused_ew" for n in p.graph.topo()), mode
        assert not tune_env.exists()


def test_fusion_auto_real_measurement_roundtrip(tune_env):
    """No mocks: a real fuse='auto' compile measures, persists a
    fusion verdict, and produces oracle-correct output either way."""
    spec = PIPELINES["spectrogram"]
    (x,) = spec.make_args(RNG, 300)
    g = spec.build()
    p = graph.compile(g, {"x": x.shape}, options=graph.CompileOptions(
        fuse="auto", autotune_kwargs={"repeats": 1}))
    entries = json.load(open(tune_env))["entries"]
    assert any(k.startswith("fusion|") for k in entries)
    np.testing.assert_allclose(np.asarray(p(jnp.asarray(x))),
                               spec.oracle(x), rtol=2e-3, atol=2e-3)
    # identical compile: plan cache hit under the post-save tune key
    assert graph.compile(g, {"x": x.shape}, options=graph.CompileOptions(
        fuse="auto", autotune_kwargs={"repeats": 1})) is p


# ---------------------------------------------------------------------------
# benchmark accumulation
# ---------------------------------------------------------------------------
def test_append_bench_json_accumulates_runs(tmp_path):
    from benchmarks.common import append_bench_json
    path = tmp_path / "BENCH_x.json"
    append_bench_json(str(path), [{"pipeline": "a", "t": 1.0}], figure="f")
    append_bench_json(str(path), [{"pipeline": "a", "t": 0.5}], figure="f")
    data = json.load(open(path))
    assert len(data["runs"]) == 2
    assert all("git_rev" in r and "timestamp" in r for r in data["runs"])
    assert data["runs"][1]["results"][0]["t"] == 0.5


def test_append_bench_json_migrates_single_run_format(tmp_path):
    from benchmarks.common import append_bench_json, write_bench_json
    path = tmp_path / "BENCH_y.json"
    write_bench_json(str(path), [{"pipeline": "a", "t": 2.0}], figure="f")
    append_bench_json(str(path), [{"pipeline": "a", "t": 1.0}], figure="f")
    data = json.load(open(path))
    assert len(data["runs"]) == 2
    assert data["runs"][0]["results"][0]["t"] == 2.0


# ---------------------------------------------------------------------------
# int8 tune spaces: separate |prec= cache cells, grid-order candidates
# ---------------------------------------------------------------------------
def _one_matmul(n=64):
    g = graph.Graph("one_qmm")
    w = RNG.standard_normal((n, n)).astype(np.float32)
    g.output(g.apply("matmul", g.input("x"), g.const(w, "w")))
    return g


@pytest.mark.parametrize(
    "cfg", ktune.space("matmul_int8").configs(_MM_CTX),
    ids=lambda c: f"bm{c['bm']}bn{c['bn']}bk{c['bk']}{c['order']}")
def test_matmul_int8_all_valid_configs_bit_identical(cfg):
    """Every int8 matmul tile/order is exact int32 accumulation plus one
    f32 rescale — so every candidate must be *bitwise* equal to the
    native integer path, not merely close."""
    g = _one_matmul()
    node = next(n for n in g.topo() if n.op == "matmul")
    x = jnp.asarray(RNG.standard_normal((96, 80)).astype(np.float32))
    w = jnp.asarray(RNG.standard_normal((80, 48)).astype(np.float32))
    want = np.asarray(plan_lib.apply_node(node, (x, w), "native", None, "int8"))
    got = np.asarray(plan_lib.apply_node(node, (x, w), "pallas", cfg, "int8"))
    assert np.array_equal(got, want)


@pytest.mark.parametrize(
    "cfg", ktune.space("pfb_int8").configs(_PFB_CTX),
    ids=lambda c: f"bt{c['bt']}bn{c['bn']}{c['order']}")
def test_pfb_int8_all_valid_configs_bit_identical(cfg):
    from repro.core import pfb as pfb_lib
    taps = pfb_lib.pfb_window(16, 8).astype(np.float32)
    g = graph.Graph("one_qpfb")
    g.output(g.apply("pfb", g.input("x"), g.const(taps, "taps")))
    node = next(n for n in g.topo() if n.op == "pfb")
    x = jnp.asarray(RNG.standard_normal(16 * 64).astype(np.float32))
    tj = jnp.asarray(taps)
    want = np.asarray(plan_lib.apply_node(node, (x, tj), "native", None, "int8"))
    got = np.asarray(plan_lib.apply_node(node, (x, tj), "pallas", cfg, "int8"))
    assert np.array_equal(got, want)


def test_grid_order_candidates_gated_by_validity():
    """matmul and pfb spaces enumerate both grid-walk orders, and an
    order the kernel cannot walk is rejected by the validity predicate
    (pruned like any other illegal block config)."""
    for name, ctx, base in (
            ("matmul", _MM_CTX, {"bm": 128, "bn": 128, "bk": 128}),
            ("matmul_int8", _MM_CTX, {"bm": 128, "bn": 128, "bk": 128}),
            ("pfb", _PFB_CTX, {"bt": 64, "bn": 16}),
            ("pfb_int8", _PFB_CTX, {"bt": 64, "bn": 16})):
        sp = ktune.space(name)
        orders = {c["order"] for c in sp.configs(ctx)}
        assert len(orders) == 2, name
        with pytest.raises(ValueError, match="invalid block config"):
            sp.check({**base, "order": "zz"}, ctx)


def test_int8_winners_cached_under_distinct_prec_keys(tune_env, monkeypatch):
    """precision="int8" tuning races the *integer* candidates and writes
    them to their own `|prec=int8` cache cell — the f32 winners for the
    same node live under the unsuffixed key — and cached mode replays
    the int8 cell without measuring."""
    g = _one_matmul()
    shapes = {"x": (32, 64)}
    p32 = graph.compile(g, shapes, options=graph.CompileOptions(
        lowering="auto", autotune_kwargs={"repeats": 1}))
    p8 = graph.compile(g, shapes, options=graph.CompileOptions(
        lowering="auto", precision="int8", autotune_kwargs={"repeats": 1}))
    entries = json.load(open(tune_env))["entries"]
    int8_keys = [k for k in entries if k.endswith("|prec=int8")]
    f32_keys = [k for k in entries if "|prec=" not in k]
    assert len(int8_keys) == 1 and len(f32_keys) == 1
    assert int8_keys[0] == f32_keys[0] + "|prec=int8"
    # the int8 cell raced real integer-kernel candidates, incl. pallas
    labels = entries[int8_keys[0]]["times_us"]
    assert any(lbl.startswith("pallas[") for lbl in labels)
    assert set(p8.node_precisions.values()) == {"int8"}
    # replay: a fresh process in cached mode re-reads the int8 winner
    # without any measurement and lands on the same plan
    monkeypatch.setenv("TINA_AUTOTUNE", "cached")
    autotune._MEM.clear()
    plan_lib.clear_cache()
    before = autotune.stats()["measured"]
    p8b = graph.compile(g, shapes, options=graph.CompileOptions(
        lowering="auto", precision="int8", autotune_kwargs={"repeats": 1}))
    assert autotune.stats()["measured"] == before
    assert p8b.lowerings == p8.lowerings and p8b.configs == p8.configs
    assert p32.lowerings is not None    # f32 plan unaffected by int8 cell
