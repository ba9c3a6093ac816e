"""Precision as a compile dimension: int8/bf16/auto plans vs the numpy
oracle under each OpDef's declared accuracy Budget, cache-key
separation, dimension-tagged downgrades, precision-boundary fusion,
budget-gated joint autotuning, and streamed == offline / served ==
offline at every tier."""
import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import graph, obs
from repro.core.opdefs import OPDEFS, Budget, sqnr_db
from repro.core.registry import PIPELINES, pipelines
from repro.graph import autotune, plan as plan_lib
from repro.graph.service import PipelineService
from repro.graph.stream import stream_execute

pipelines()                       # register built-ins
RNG = np.random.default_rng(11)

# pipelines whose compute is dominated by quantizable (matmul-shaped)
# ops — the acceptance bar for the int8 tier
QUANT_PIPELINES = ("pfb_power", "spectrogram")


def _compile_quiet(g, shapes, *, options):
    """Compile suppressing the (expected, tested separately) downgrade
    warning for elementwise ops that don't declare int8."""
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", UserWarning)
        return graph.compile(g, shapes, options=options)


def _unique(g, tag):
    """Unique graph name per test: the warn-once downgrade dedup and the
    plan cache are both keyed on it."""
    g.name = f"{g.name}+{tag}"
    return g


# ---------------------------------------------------------------------------
# accuracy: reduced-precision plans vs the numpy oracle
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name", QUANT_PIPELINES)
def test_int8_pipeline_meets_budget_vs_oracle(name):
    spec = PIPELINES[name]
    g = spec.build()
    (x,) = spec.make_args(RNG, 2048)
    p = _compile_quiet(g, {g.inputs[0]: x.shape},
                       options=graph.CompileOptions(precision="int8"))
    # the matmul-shaped nodes actually run quantized...
    assert "int8" in p.precisions.values(), p.precisions
    # ...and the pipeline output clears the strictest per-op budget (the
    # weakest link bounds the chain; budgets are 26-30 dB, achieved is
    # comfortably above)
    floors = [d.budget("int8").sqnr_db for d in OPDEFS.values()
              if d.budget("int8") is not None]
    q = sqnr_db(spec.oracle(x), np.asarray(p(jnp.asarray(x))))
    assert q >= min(floors), (name, q)


@pytest.mark.parametrize("name", QUANT_PIPELINES)
def test_bf16_pipeline_meets_budget_vs_oracle(name):
    spec = PIPELINES[name]
    g = spec.build()
    (x,) = spec.make_args(RNG, 2048)
    p = graph.compile(g, {g.inputs[0]: x.shape},
                      options=graph.CompileOptions(precision="bf16"))
    assert set(p.precisions.values()) == {"bf16"}    # every node honors it
    assert p.downgrades == {}
    q = sqnr_db(spec.oracle(x), np.asarray(p(jnp.asarray(x))))
    assert q >= 30.0, (name, q)          # the default bf16 Budget floor


# ---------------------------------------------------------------------------
# planner contract: cache key, downgrades, fusion boundaries
# ---------------------------------------------------------------------------
def test_precision_joins_plan_cache_key():
    spec = PIPELINES["pfb_power"]
    g = _unique(spec.build(), "cachekey")
    (x,) = spec.make_args(RNG, 1024)
    shapes = {g.inputs[0]: x.shape}
    p32 = graph.compile(g, shapes)
    p8 = _compile_quiet(g, shapes,
                        options=graph.CompileOptions(precision="int8"))
    pb = graph.compile(g, shapes,
                       options=graph.CompileOptions(precision="bf16"))
    assert len({id(p32), id(p8), id(pb)}) == 3     # distinct cache slots
    hits0 = plan_lib.cache_stats()["hits"]
    assert _compile_quiet(g, shapes,
                          options=graph.CompileOptions(precision="int8")) is p8
    assert plan_lib.cache_stats()["hits"] == hits0 + 1
    # and the tiers really diverge numerically (int8 is quantized)
    assert not np.array_equal(np.asarray(p32(jnp.asarray(x))),
                              np.asarray(p8(jnp.asarray(x))))


def test_precision_downgrades_recorded_and_warned_once():
    # a graph built here (not a shared builtin) so the compile is never
    # a plan-cache hit from another test — the warning must fire
    g = graph.Graph("dft_power+prec_downgrade")
    x = g.input("x")
    z = g.apply("dft", x)
    a = g.apply("abs2", z)
    g.output(a)
    with pytest.warns(UserWarning, match="fell back to precision='f32'"):
        p = graph.compile(g, {"x": (4, 64)},
                          options=graph.CompileOptions(precision="int8"))
    # dimension-tagged: which axis fell back — only abs2 (no declared
    # int8 path) appears; the dft runs quantized
    assert p.downgrades == {a: "precision:int8"}
    assert p.precisions[a] == "f32"
    assert p.node_precisions[a] == "f32"
    assert p.precisions[z] == "int8"
    # warn-once: a recompile at new shapes (same graph, same downgrade
    # set) stays silent
    with warnings.catch_warnings():
        warnings.simplefilter("error", UserWarning)
        graph.compile(g, {"x": (8, 64)},
                      options=graph.CompileOptions(precision="int8"))


def test_unknown_precision_rejected():
    g = PIPELINES["spectrogram"].build()
    with pytest.raises(ValueError, match="unknown tier"):
        graph.compile(g, {g.inputs[0]: (512,)},
                      options=graph.CompileOptions(precision="fp4"))
    with pytest.raises(ValueError, match="unknown tier"):
        graph.compile(g, {g.inputs[0]: (512,)},
                      options=graph.CompileOptions(precision={"dft2": "int4"}))


def _window_scale_graph(tag):
    """Two adjacent fusable elementwise nodes: window mult -> scale."""
    g = graph.Graph(f"winscale+{tag}")
    x = g.input("x")
    w = g.const(np.hanning(64).astype(np.float32), "win")
    a = g.apply("window", x, w)
    b = g.apply("scale", a, factor=0.5)
    g.output(b)
    return g, a, b


def test_precision_dict_is_a_fusion_boundary():
    shapes = {"x": (8, 64)}
    x = RNG.standard_normal((8, 64)).astype(np.float32)

    g, a, b = _window_scale_graph("fused")
    p_same = graph.compile(g, shapes, options=graph.CompileOptions(
        precision={a: "bf16", b: "bf16"}))
    assert any(n.op == "fused_ew" for n in p_same.graph.topo())
    fused = next(n for n in p_same.graph.topo() if n.op == "fused_ew")
    assert p_same.precisions[fused.name] == "bf16"   # members' agreed tier

    g2, a2, b2 = _window_scale_graph("split")
    p_mixed = graph.compile(g2, shapes, options=graph.CompileOptions(
        precision={a2: "bf16", b2: "f32"}))
    assert not any(n.op == "fused_ew" for n in p_mixed.graph.topo())
    assert p_mixed.precisions[a2] == "bf16"
    assert p_mixed.precisions[b2] == "f32"
    # both still compute the same function (bf16 rounding aside)
    np.testing.assert_allclose(np.asarray(p_same(jnp.asarray(x))),
                               np.asarray(p_mixed(jnp.asarray(x))),
                               rtol=2e-2, atol=2e-2)


# ---------------------------------------------------------------------------
# fuse=None default: "auto" for lowering="auto" plans, True otherwise
# ---------------------------------------------------------------------------
def test_fuse_default_resolves_to_auto_for_auto_plans(monkeypatch, tmp_path):
    monkeypatch.setenv("TINA_AUTOTUNE", "cached")
    monkeypatch.setenv("TINA_AUTOTUNE_CACHE", str(tmp_path / "tune.json"))
    spec = PIPELINES["spectrogram"]
    g = _unique(spec.build(), "fusedefault")
    (x,) = spec.make_args(RNG, 1024)
    shapes = {g.inputs[0]: x.shape}

    def verdicts():
        return (obs.counter("plan.fusion.fused").value,
                obs.counter("plan.fusion.unfused").value)

    f0, u0 = verdicts()
    # fuse unspecified
    p = graph.compile(g, shapes, options=graph.CompileOptions(lowering="auto"))
    f1, u1 = verdicts()
    # the verdict machinery ran (fuse=None resolved to "auto"), and a
    # cold cache in cached mode keeps the fused default for every chain
    assert f1 > f0 and u1 == u0
    assert any(n.op == "fused_ew" for n in p.graph.topo())
    # verdict stability: a forced recompile re-consults and lands on the
    # identical fused/unfused split (the PR-6 counters make this
    # checkable per run)
    plan_lib.clear_cache()
    p2 = graph.compile(g, shapes,
                       options=graph.CompileOptions(lowering="auto"))
    f2, u2 = verdicts()
    assert (f2 - f1, u2 - u1) == (f1 - f0, u1 - u0)
    assert [n.op for n in p2.graph.topo()] == [n.op for n in p.graph.topo()]
    # non-auto plans keep the unconditional-fuse default: no verdicts
    p3 = graph.compile(g, {g.inputs[0]: (512,)})
    assert verdicts() == (f2, u2)
    assert any(n.op == "fused_ew" for n in p3.graph.topo())


# ---------------------------------------------------------------------------
# precision="auto": budget-gated joint search
# ---------------------------------------------------------------------------
def _matmul_graph(tag, n=64):
    g = graph.Graph(f"mm+{tag}")
    x = g.input("x")
    w = g.const(RNG.standard_normal((n, n)).astype(np.float32), "w")
    g.output(g.apply("matmul", x, w))
    return g


def test_pick_joint_rejects_budget_violations(monkeypatch, tmp_path):
    """An impossible budget must force the f32 answer — precision="auto"
    can never return a budget-violating winner — and the measured
    verdict (ok=False) must be persisted in the v2 cache."""
    monkeypatch.setenv("TINA_AUTOTUNE", "on")
    path = str(tmp_path / "tune.json")
    monkeypatch.setitem(
        OPDEFS, "matmul",
        dataclasses.replace(OPDEFS["matmul"],
                            budgets=(("bf16", Budget(sqnr_db=1000.0)),
                                     ("int8", Budget(sqnr_db=1000.0)))))
    g = _matmul_graph("strict")
    avals = plan_lib.infer(
        g, {"x": jax.ShapeDtypeStruct((8, 64), jnp.float32)})
    node = next(n for n in g.topo() if n.op == "matmul")
    lw, cfg, prec = autotune.pick_joint(g, node, avals, path=path, repeats=1)
    assert prec == "f32"
    entries = autotune._load(path)
    (key,) = [k for k in entries if k.endswith("|prec=auto")]
    acc = entries[key]["accuracy"]
    assert acc["int8"]["ok"] is False     # probed, measured, rejected
    assert entries[key]["precision"] == "f32"


def test_precision_auto_plan_honors_budgets(monkeypatch, tmp_path):
    """compile(..., precision="auto") end to end: whatever tier wins per
    node, every probed reduced tier recorded in the cache carries a
    budget verdict, and a winner is never one that failed it."""
    monkeypatch.setenv("TINA_AUTOTUNE", "on")
    path = str(tmp_path / "tune.json")
    spec = PIPELINES["pfb_power"]
    g = _unique(spec.build(), "autoprec")
    (x,) = spec.make_args(RNG, 1024)
    p = _compile_quiet(g, {g.inputs[0]: x.shape}, options=graph.CompileOptions(
        lowering="auto", precision="auto",
        autotune_kwargs={"repeats": 1, "path": path}))
    # the plan runs, and at whatever tiers won the budget held
    q = sqnr_db(spec.oracle(x), np.asarray(p(jnp.asarray(x))))
    assert q >= 26.0, (p.precisions, q)
    entries = autotune._load(path)
    joint = {k: v for k, v in entries.items() if k.endswith("|prec=auto")}
    assert joint, "precision=auto persisted no joint entries"
    for k, v in joint.items():
        for tier, verdict in v.get("accuracy", {}).items():
            if v["precision"] == tier:
                assert verdict["ok"] is True, (k, tier, verdict)
    # cached replay resolves identically without re-measuring
    m0 = autotune.stats()["measured"]
    monkeypatch.setenv("TINA_AUTOTUNE", "cached")
    plan_lib.clear_cache()
    p2 = _compile_quiet(g, {g.inputs[0]: x.shape},
                        options=graph.CompileOptions(
                            lowering="auto", precision="auto",
                            autotune_kwargs={"repeats": 1, "path": path}))
    assert autotune.stats()["measured"] == m0
    assert p2.precisions == p.precisions
    assert p2.lowerings == p.lowerings


# ---------------------------------------------------------------------------
# streamed == offline and served == offline at every tier
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name", QUANT_PIPELINES)
@pytest.mark.parametrize("prec", ["f32", "bf16", "int8"])
def test_streamed_equals_offline_at_every_precision(name, prec):
    spec = PIPELINES[name]
    g = spec.build()
    (x,) = spec.make_args(RNG, 4096)
    offline = _compile_quiet(g, {g.inputs[0]: x.shape},
                             options=graph.CompileOptions(
                                 precision=prec))(jnp.asarray(x))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", UserWarning)
        chunked = stream_execute(g, x, 1024,
                                 options=graph.CompileOptions(precision=prec))
    # equality up to float associativity (the repo-wide streaming bar):
    # bf16 rounding is pointwise and int8 activation scales are per-row,
    # so each emitted window quantizes exactly as offline — only XLA's
    # shape-dependent reduction tiling can differ
    np.testing.assert_allclose(np.asarray(offline), np.asarray(chunked),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# true integer kernels: the int8 tier really computes in int8
# ---------------------------------------------------------------------------
QUANT_OPS = sorted(n for n, d in OPDEFS.items() if d.qimpl is not None)


def _qnode(d):
    """A single node + jnp args for a quantized OpDef, from make_args."""
    g = graph.Graph(f"q_{d.name}")
    refs, attrs = [], {}
    attr_names = list(d.arg_attrs)
    args = d.make_args(RNG, 256)
    for i, a in enumerate(args):
        if isinstance(a, np.ndarray):
            refs.append(g.input("x") if not refs else g.const(a, f"c{i}"))
        else:
            attrs[attr_names.pop(0)] = a
    node = g.nodes[g.apply(d.name, *refs, **attrs)]
    jargs = [jnp.asarray(a) for a in args if isinstance(a, np.ndarray)]
    return node, jargs


def _has_int8_dot(jaxpr) -> bool:
    """Walk a jaxpr (into pallas_call bodies and other sub-jaxprs) for a
    dot_general whose operands are int8 and whose result is int32 — the
    MXU-native integer MAC the tentpole promises."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in ("dot_general", "dot"):
            if (all(str(v.aval.dtype) == "int8" for v in eqn.invars)
                    and str(eqn.outvars[0].aval.dtype) == "int32"):
                return True
        for v in eqn.params.values():
            sub = getattr(v, "jaxpr", v)
            if hasattr(sub, "eqns") and _has_int8_dot(sub):
                return True
    return False


@pytest.mark.parametrize("name", QUANT_OPS)
def test_int8_tier_emits_integer_dot_general(name):
    """At the int8 tier every quantized op's jaxpr contains an
    int8 x int8 -> int32 dot — the tier computes in integers, it does
    not dequantize back to f32 first."""
    d = OPDEFS[name]
    node, jargs = _qnode(d)
    for lw in d.q_lowerings:
        if (name, lw) == ("fir", "pallas"):
            # the fir kernel quantizes each sliding window in-registers
            # and MACs int32 scalars over the taps loop — integer
            # compute, but there is no dot_general to find (its
            # bit-identity to the integer reference is asserted in
            # test_integer_paths_bit_identical_to_dequantized_reference)
            continue
        jx = jax.make_jaxpr(
            lambda *a, _lw=lw: plan_lib.apply_node(node, a, _lw, None,
                                                   "int8"))(*jargs)
        assert _has_int8_dot(jx.jaxpr), (name, lw)
    # and the f32 tier does NOT (the quantized path is tier-gated)
    jx32 = jax.make_jaxpr(
        lambda *a: plan_lib.apply_node(node, a, "native"))(*jargs)
    assert not _has_int8_dot(jx32.jaxpr), name


@pytest.mark.parametrize("name", QUANT_OPS)
def test_integer_paths_bit_identical_to_dequantized_reference(name):
    """The integer engine (jnp int8 dot_general) and every int8 Pallas
    kernel are BIT-identical to the dequantize-then-f32 reference at
    the int8 tier: same int32 accumulation, same one-multiply epilogue
    — so streamed == offline == serving holds unchanged.

    One carve-out: a complex-input (I)DFT recombines its four real
    matmuls with a cross-term subtract/add, and XLA FMA-contracts the
    jnp terms' rescale into that combine (the unrounded product is one
    ulp away); the Pallas route materializes each term first.  Both jnp
    engines contract identically — int == ref stays bitwise — but
    pallas-vs-jnp there is exact only to one ulp."""
    from repro.core import quantize
    d = OPDEFS[name]
    node, jargs = _qnode(d)
    complex_in = any(jnp.issubdtype(a.dtype, jnp.complexfloating)
                     for a in jargs)
    with quantize.engine_override("ref"):
        want = np.asarray(jax.jit(
            lambda *a: plan_lib.apply_node(node, a, "native", None,
                                           "int8"))(*jargs))
    for lw in d.q_lowerings:
        got = np.asarray(jax.jit(
            lambda *a, _lw=lw: plan_lib.apply_node(node, a, _lw, None,
                                                   "int8"))(*jargs))
        if lw != "native" and complex_in:
            # one ulp of the pre-cancellation term magnitude
            ulp = np.float32(np.finfo(np.float32).eps) * np.abs(want).max()
            np.testing.assert_allclose(got, want, rtol=0, atol=2 * ulp,
                                       err_msg=f"{name}/{lw}")
        else:
            assert np.array_equal(got, want), (name, lw)


def test_int8_pallas_plan_keeps_pallas_lowering():
    """precision="int8" + lowering="pallas" no longer collapses to
    native: the quantized ops run their int8 Pallas kernels (recorded
    on the plan), matching the native integer path to the ulp."""
    spec = PIPELINES["pfb_power"]
    g = _unique(spec.build(), "q_pallas")
    (x,) = spec.make_args(RNG, 2048)
    shapes = {g.inputs[0]: x.shape}
    p_pl = _compile_quiet(g, shapes, options=graph.CompileOptions(
        lowering="pallas", precision="int8"))
    p_nat = _compile_quiet(g, shapes, options=graph.CompileOptions(
        lowering="native", precision="int8"))
    q_nodes = [n for n, pr in p_pl.precisions.items() if pr == "int8"
               and OPDEFS[p_pl.graph.nodes[n].op].qimpl is not None]
    assert q_nodes
    assert all(p_pl.node_lowerings[n] == "pallas" for n in q_nodes), \
        p_pl.node_lowerings
    # 2-ulp bound, not array_equal: full-plan jits give XLA:CPU more
    # fusion context than the per-node jits above, and under some
    # process configs (e.g. a forced multi-device host platform, set
    # by an earlier test module) it FMA-contracts the f32 rescale into
    # the jnp route's complex recombination — the documented one-ulp
    # divergence from the Pallas route (see quantize.qdft).
    got = np.asarray(p_pl(jnp.asarray(x)))
    want = np.asarray(p_nat(jnp.asarray(x)))
    np.testing.assert_allclose(
        got, want, rtol=2 * np.float32(np.finfo(np.float32).eps), atol=0)


def test_quantize_engine_joins_plan_cache_key():
    """engine_override("ref") compiles must get their own plan-cache
    slot — a ref-engine benchmark must never poison the int plans."""
    from repro.core import quantize
    spec = PIPELINES["pfb_power"]
    g = _unique(spec.build(), "engine_key")
    (x,) = spec.make_args(RNG, 1024)
    shapes = {g.inputs[0]: x.shape}
    p_int = _compile_quiet(g, shapes,
                           options=graph.CompileOptions(precision="int8"))
    with quantize.engine_override("ref"):
        p_ref = _compile_quiet(g, shapes,
                               options=graph.CompileOptions(precision="int8"))
    assert p_ref is not p_int
    # both engines compute the int8 tier bit-identically
    np.testing.assert_array_equal(np.asarray(p_int(jnp.asarray(x))),
                                  np.asarray(p_ref(jnp.asarray(x))))


def test_service_serves_int8_plans_matching_offline():
    spec = PIPELINES["pfb_power"]
    g = spec.build()
    xs = [spec.make_args(RNG, 1024)[0] for _ in range(5)]
    n = xs[0].shape[-1]
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", UserWarning)
        svc = PipelineService(g, signal_len=n, batch_size=4,
                              options=graph.CompileOptions(precision="int8"))
        futs = [svc.submit(x) for x in xs]
        svc.flush()
        offline = _compile_quiet(
            g, {g.inputs[0]: (1, n)},
            options=graph.CompileOptions(precision="int8"))
    assert "int8" in svc.plan.precisions.values()
    for x, f in zip(xs, futs):
        want = np.asarray(offline(jnp.asarray(x[None, :])))[0]
        np.testing.assert_allclose(np.asarray(f.result(timeout=30)), want,
                                   rtol=1e-5, atol=1e-6)
