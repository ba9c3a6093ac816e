"""Unified OpDef layer tests: every op is declared exactly once in
repro.core.opdefs and every consumer derives from it.

  * round-trip consistency — each Table-1 OpDef produces identical
    numerics through the eager path and a single-node graph plan, per
    supported lowering, and both match the numpy oracle
  * catalog-drift guard — graph/plan.py must not grow its own op
    catalog again (no OpSpec, OPS is the OpDef registry), and every
    OpDef is internally consistent (native lowering, resolvable
    TuneSpace, streamable elementwise trait)
  * the three OpDef-layer workloads (stft_overlap_add, correlate,
    cascaded_channelizer) run end-to-end: compile -> autotune(cached)
    -> stream -> serve, with a mesh-sharded case for the channelizer
  * requested-but-unsupported lowerings are recorded on
    Plan.downgrades / Plan.node_lowerings and warned once
"""
import inspect
import os
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import graph
from repro.core import opdefs
from repro.core.registry import PIPELINES, REGISTRY, pipelines
from repro.graph import plan as plan_lib

pipelines()
RNG = np.random.default_rng(17)

NEW_PIPELINES = ("stft_overlap_add", "correlate", "cascaded_channelizer")


# ---------------------------------------------------------------------------
# round-trip: eager path == graph path == oracle, per lowering
# ---------------------------------------------------------------------------
def _single_node_graph(d: opdefs.OpDef, args):
    """Build a one-node graph for an OpDef from its make_args tuple:
    the first array is the graph input, later arrays are consts, and
    non-array entries bind to the attrs named by ``arg_attrs``."""
    g = graph.Graph(f"one_{d.name}")
    refs, attrs = [], {}
    attr_names = list(d.arg_attrs)
    for i, a in enumerate(args):
        if isinstance(a, np.ndarray):
            refs.append(g.input("x") if not refs else g.const(a, f"c{i}"))
        else:
            attrs[attr_names.pop(0)] = a
    assert not attr_names, f"{d.name}: arg_attrs left unbound"
    g.output(g.apply(d.name, *refs, **attrs))
    specs = {"x": jax.ShapeDtypeStruct(args[0].shape, args[0].dtype)}
    return g, specs


@pytest.mark.parametrize(
    "name", sorted(d.name for d in opdefs.table_ops()))
def test_opdef_round_trips_eager_and_graph(name):
    d = opdefs.OPDEFS[name]
    args = d.make_args(RNG, 16)
    want = np.asarray(d.oracle(*[np.asarray(a) if isinstance(a, np.ndarray)
                                 else a for a in args]))
    g, specs = _single_node_graph(d, args)
    jargs = [jnp.asarray(a) if isinstance(a, np.ndarray) else a
             for a in args]
    for lowering in d.lowerings:
        eager = np.asarray(d.eager(*jargs, lowering=lowering))
        p = graph.compile(g, specs,
                          options=graph.CompileOptions(lowering=lowering))
        planned = np.asarray(p(jargs[0]))
        # graph and eager paths run the same OpDef implementation: the
        # numerics must agree to roundoff, not just oracle tolerance
        np.testing.assert_allclose(planned, eager, rtol=1e-5, atol=1e-5,
                                   err_msg=f"{name}/{lowering} eager!=graph")
        np.testing.assert_allclose(planned, want, rtol=2e-3, atol=2e-3,
                                   err_msg=f"{name}/{lowering} !=oracle")


def test_registry_is_generated_from_opdefs():
    table = {d.table_name for d in opdefs.table_ops()}
    assert set(REGISTRY) == table
    for d in opdefs.table_ops():
        op = REGISTRY[d.table_name]
        assert op.fn is d.eager and op.oracle is d.oracle
        assert op.lowerings == d.lowerings


# ---------------------------------------------------------------------------
# catalog-drift guard: plan.py must stay derived
# ---------------------------------------------------------------------------
def test_plan_catalog_is_the_opdef_registry():
    src = inspect.getsource(plan_lib)
    assert "OpSpec" not in src, \
        "graph/plan.py grew its own op catalog again — declare ops in " \
        "repro.core.opdefs instead"
    assert plan_lib.OPS is opdefs.OPDEFS


def test_every_pipeline_op_is_an_opdef():
    for name, spec in PIPELINES.items():
        for node in spec.build().topo():
            if node.op in ("input", "const"):
                continue
            assert node.op in opdefs.OPDEFS, (name, node.op)


def test_opdefs_internally_consistent():
    from repro.kernels import tune as ktune
    for name, d in opdefs.OPDEFS.items():
        assert d.name == name
        assert "native" in d.lowerings, name
        if d.tune_space is not None:
            assert ktune.space(d.tune_space) is not None, \
                f"{name}: tune_space {d.tune_space!r} not registered"
            assert d.tune_ctx is not None, name
        if d.elementwise:
            assert d.stream is not None and d.stream.kind == "pointwise", \
                f"{name}: elementwise ops must stream pointwise"
            assert d.fuse_step is not None, \
                f"{name}: elementwise ops must declare their fused-chain " \
                "step (fuse_step) — the trait alone cannot be honored"
        if d.lowering_agnostic:
            assert d.lowerings == ("native",), \
                f"{name}: lowering_agnostic means one code path"
        if d.table_name is not None:
            assert d.eager and d.oracle and d.make_args, name


def test_elementwise_without_fuse_step_stays_unfused(monkeypatch):
    """An elementwise OpDef that declares no fused-chain step must be
    left out of fusion runs (correct output, no fused_ew), never fed
    into run_to_steps where it would crash."""
    neg = opdefs.OpDef("neg", lambda a, at, lw, b=None: -a[0],
                       ("native",), elementwise=True,
                       stream=opdefs.StreamRule("pointwise"))
    monkeypatch.setitem(opdefs.OPDEFS, "neg", neg)
    g = graph.Graph("neg_chain")
    x = g.input("x")
    c = g.const(np.full((8, 8), 2.0, np.float32))
    a = g.apply("ew_mul", x, c)
    b = g.apply("neg", a)
    g.output(g.apply("scale", b, factor=0.5))
    xv = RNG.standard_normal((8, 8)).astype(np.float32)
    p = graph.compile(g, {"x": xv.shape})
    assert not any(n.op == "fused_ew" for n in p.graph.topo())
    np.testing.assert_allclose(np.asarray(p(jnp.asarray(xv))),
                               -(xv * 2.0) * 0.5, rtol=1e-6, atol=1e-6)


def test_unknown_attr_rejected_at_compile():
    g = graph.Graph("bad_attr")
    g.output(g.apply("unfold", g.input("x"), window=8, stride=2))
    with pytest.raises(ValueError, match="unknown attr"):
        graph.compile(g, {"x": (32,)})
    g2 = graph.Graph("missing_attr")
    g2.output(g2.apply("unfold", g2.input("x")))
    with pytest.raises(ValueError, match="missing required attr"):
        graph.compile(g2, {"x": (32,)})


# ---------------------------------------------------------------------------
# the three OpDef-layer workloads, end-to-end
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name", NEW_PIPELINES)
def test_new_pipeline_oracle_all_lowerings(name):
    spec = PIPELINES[name]
    (x,) = spec.make_args(RNG, 512)
    g = spec.build()
    want = spec.oracle(x)
    for lowering in spec.lowerings:
        p = graph.compile(g, {g.inputs[0]: x.shape},
                          options=graph.CompileOptions(lowering=lowering))
        got = np.asarray(p(jnp.asarray(x)))
        assert got.shape == want.shape, (name, lowering)
        np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3,
                                   err_msg=f"{name} lowering={lowering}")


@pytest.mark.parametrize("name", NEW_PIPELINES)
def test_new_pipeline_end_to_end(name, monkeypatch, tmp_path):
    """compile -> autotune(cached mode) -> stream -> serve, one flow."""
    monkeypatch.setenv("TINA_AUTOTUNE", "cached")
    monkeypatch.setenv("TINA_AUTOTUNE_CACHE", str(tmp_path / "tune.json"))
    spec = PIPELINES[name]
    n = spec.valid_len(1024)
    (x,) = spec.make_args(RNG, 1024)
    g = spec.build()

    # autotuned compile (cached mode: deterministic defaults)
    p = graph.compile(g, {g.inputs[0]: x.shape},
                      options=graph.CompileOptions(lowering="auto"))
    np.testing.assert_allclose(np.asarray(p(jnp.asarray(x))),
                               spec.oracle(x), rtol=2e-3, atol=2e-3)

    # chunked streaming == offline
    offline = np.asarray(graph.compile(g, {g.inputs[0]: x.shape})(
        jnp.asarray(x)))
    got = np.asarray(graph.stream_execute(g, x, 300))
    np.testing.assert_allclose(got, offline, rtol=1e-6, atol=1e-6)

    # batched serving matches the oracle, one cached plan
    xs = [spec.make_args(RNG, 1024)[0] for _ in range(3)]
    svc = graph.PipelineService(g, signal_len=n, batch_size=2)
    futs = [svc.submit(s) for s in xs]
    svc.flush()
    for s, f in zip(xs, futs):
        np.testing.assert_allclose(f.result(timeout=5), spec.oracle(s),
                                   rtol=2e-3, atol=2e-3)
    assert svc.plan.trace_count == 1


def test_stft_overlap_add_reconstructs_signal():
    """Physics check: sqrt-Hann analysis+synthesis at 50% overlap is a
    COLA pair — the steady-state output reproduces the (delayed) input."""
    spec = PIPELINES["stft_overlap_add"]
    (x,) = spec.make_args(RNG, 1024)
    g = spec.build()
    y = np.asarray(graph.compile(g, {"x": x.shape})(jnp.asarray(x)))
    # output sample s corresponds to input sample s + (J - H) = s + 32
    np.testing.assert_allclose(y, x[32:32 + y.shape[-1]],
                               rtol=1e-3, atol=1e-3)


def test_new_stream_specs_compose():
    s = graph.stream_spec(graph.build_stft_overlap_add(window=64, hop=32))
    assert (s.block, s.receptive, s.tail_dims) == (32, 96, 0)  # 2J - H
    s = graph.stream_spec(graph.build_correlate(taps=63))
    assert (s.block, s.receptive, s.tail_dims) == (1, 63, 0)
    s = graph.stream_spec(graph.build_cascaded_channelizer(31, 16, 4))
    # fir k=31 then ↓2 then pfb (P=16, M=4): R = 31 + (64-1)*2, B = 32
    assert (s.block, s.receptive, s.tail_dims) == (32, 157, 1)


def test_cascaded_channelizer_mesh_sharded():
    """The mesh-sharded case for the channelizer: batch axis across a
    1-device mesh in-process (the 8-device subprocess sweep in
    test_mesh_plan covers all pipelines including this one)."""
    spec = PIPELINES["cascaded_channelizer"]
    (x,) = spec.make_args(RNG, 512)
    xb = np.stack([x, 2.0 * x, -x, 0.5 * x])
    g = spec.build()
    p0 = graph.compile(g, {g.inputs[0]: xb.shape})
    p1 = graph.compile(g, {g.inputs[0]: xb.shape},
                       options=graph.CompileOptions(mesh=1))
    assert p1.mesh is not None
    np.testing.assert_array_equal(np.asarray(p1(jnp.asarray(xb))),
                                  np.asarray(p0(jnp.asarray(xb))))
    np.testing.assert_allclose(np.asarray(p1(jnp.asarray(xb)))[0],
                               spec.oracle(x), rtol=2e-3, atol=2e-3)


# ---------------------------------------------------------------------------
# effective lowerings are recorded, downgrades warned once
# ---------------------------------------------------------------------------
def test_plan_records_downgrades_and_warns_once():
    """overlap_add gained a real Pallas kernel, so a pallas STFT->OLA
    plan now has NO lowering downgrades at all (it was the last
    always-downgraded op); the downgrade machinery is exercised on the
    precision dimension instead — overlap_add declares no int8 tier,
    so requesting int8 records + warns exactly once."""
    plan_lib._WARNED_DOWNGRADES.clear()
    g = graph.build_stft_overlap_add(window=64, hop=32)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        p = graph.compile(g, {"x": (300,)},
                          options=graph.CompileOptions(lowering="pallas"))
    assert p.downgrades == {}
    ola = [n.name for n in p.graph.topo() if n.op == "overlap_add"]
    assert ola and all(p.node_lowerings[n] == "pallas" for n in ola)
    dft_nodes = [n.name for n in p.graph.topo() if n.op == "dft"]
    assert all(p.node_lowerings[n] == "pallas" for n in dft_nodes)
    # the pallas plan agrees with the native one end to end
    x = jnp.asarray(RNG.standard_normal(300).astype(np.float32))
    p_nat = graph.compile(g, {"x": (300,)},
                          options=graph.CompileOptions(lowering="native"))
    np.testing.assert_allclose(np.asarray(p(x)), np.asarray(p_nat(x)),
                               rtol=1e-5, atol=1e-5)
    # precision downgrades: overlap_add has no int8 tier and is not
    # lowering-agnostic -> recorded dimension-tagged + warned
    with pytest.warns(UserWarning, match="fell back to precision='f32'"):
        p8 = graph.compile(g, {"x": (300,)},
                           options=graph.CompileOptions(precision="int8"))
    down_ops = {p8.graph.nodes[n].op for n in p8.downgrades}
    assert "overlap_add" in down_ops
    assert all("precision:int8" in req for req in p8.downgrades.values())
    assert all(p8.node_precisions[n] == "f32" for n in p8.downgrades)
    # the same downgrade set warns only once, even for a new shape
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        graph.compile(g, {"x": (364,)},
                      options=graph.CompileOptions(precision="int8"))


def test_agnostic_data_movement_ops_do_not_warn():
    """Requesting pallas on a plan whose only native-only nodes are
    pure data movement (downsample) is fully satisfied — no downgrade
    record, no warning."""
    plan_lib._WARNED_DOWNGRADES.clear()
    g = graph.build_fir_decimate(taps1=31, taps2=15)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        p = graph.compile(g, {"x": (777,)},
                          options=graph.CompileOptions(lowering="pallas"))
    assert p.downgrades == {}
    fir_nodes = [n.name for n in p.graph.topo() if n.op == "fir"]
    assert all(p.node_lowerings[n] == "pallas" for n in fir_nodes)


def test_no_downgrades_no_warning():
    plan_lib._WARNED_DOWNGRADES.clear()
    g = graph.build_spectrogram(window=32)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        p = graph.compile(g, {"x": (200,)},
                          options=graph.CompileOptions(lowering="native"))
    assert p.downgrades == {}


# ---------------------------------------------------------------------------
# deploy-time cache pre-warm
# ---------------------------------------------------------------------------
def test_prewarm_measures_despite_cached_mode(tmp_path, monkeypatch):
    from repro.graph import autotune
    from repro.launch import dsp_serve

    cache = tmp_path / "tune.json"
    monkeypatch.setenv("TINA_AUTOTUNE_CACHE", str(cache))
    monkeypatch.setenv("TINA_AUTOTUNE", "cached")   # production serving mode
    autotune._MEM.clear()
    plan_lib.clear_cache()

    g = graph.Graph("one_fir")
    taps = np.hanning(31).astype(np.float32)
    g.output(g.apply("fir", g.input("x"), g.const(taps, "taps")))
    delta = dsp_serve.prewarm(g, 2, 300, lowering="pallas", repeats=1)
    assert delta["measured"] >= 1          # measured despite cached mode
    assert cache.exists()
    assert os.environ["TINA_AUTOTUNE"] == "cached"   # mode restored

    # the (cached-mode) serving compile now picks the tuned config
    # without measuring anything
    before = autotune.stats()["measured"]
    p = graph.compile(g, {"x": (2, 300)}, options=graph.CompileOptions(
        lowering="pallas", block_configs="auto"))
    assert autotune.stats()["measured"] == before
    x = RNG.standard_normal((2, 300)).astype(np.float32)
    np.testing.assert_allclose(
        np.asarray(p(jnp.asarray(x))),
        np.stack([np.convolve(r, taps, mode="valid") for r in x]),
        rtol=2e-3, atol=2e-3)


def test_dsp_serve_cli_new_pipeline(tmp_path, monkeypatch):
    """The serving launcher end-to-end on an OpDef-layer workload."""
    from repro.launch import dsp_serve
    monkeypatch.setenv("TINA_AUTOTUNE", "cached")
    monkeypatch.setenv("TINA_AUTOTUNE_CACHE", str(tmp_path / "t.json"))
    dsp_serve.main(["--pipeline", "correlate", "--requests", "6",
                    "--batch", "2", "--signal-len", "128", "--check", "2"])
