"""Beyond-paper Fig. 4: compiled pipeline plans vs naive per-op dispatch,
and block-tuned plans vs fixed-default plans.

The paper composes TINA layers one framework call at a time; the graph
subsystem compiles the whole pipeline into one cached jitted plan, and
the v2 autotuner tunes each Pallas kernel's block sizes on the node's
actual shapes.  This benchmark quantifies both for every built-in
pipeline:

  * per-op       — each graph node executed through its own jitted
                   callable, synchronizing (block_until_ready) between
                   nodes: the dispatch pattern of calling
                   repro.core.functions by hand
  * plan         — ``graph.compile(...)`` product: one jit region, fused
                   elementwise chains, no host round-trips
  * pallas-def   — the all-Pallas plan with every kernel's fixed default
                   block sizes (the pre-tuning behavior)
  * pallas-tuned — the same plan with ``block_configs="auto"``: the
                   autotuner searches each kernel's TuneSpace on the
                   pipeline's actual shapes
  * plan+auto    — (--autotune) full joint tuning: fastest lowering AND
                   fastest tiling per node

When the tuner's winners equal the defaults the two plans are the same
computation, so the default timing is reused (speedup exactly 1.0)
instead of re-measuring noise.

Appends a run record (git rev + timestamp) to ``BENCH_pipelines.json``
via benchmarks/common.py, so the perf trajectory accumulates across PRs.
Each record carries a ``telemetry`` sub-dict with the plan-cache and
autotuner counter deltas for that (pipeline, n) cell — how many compiles
were cache hits and how many candidate measurements the tuner actually
ran — so a trajectory regression can be cross-read against compile/tune
churn.
"""
from __future__ import annotations

import argparse
import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import (append_bench_json, fmt_table, speedup,
                               timeit_group, us)
from repro.core.registry import PIPELINES, pipelines as _load_pipelines
from repro.graph import autotune
from repro.graph import plan as plan_lib
from repro.graph import CompileOptions, compile as graph_compile


def make_per_op_dispatch(graph):
    """Execute the (unfused) graph node-by-node, one jitted callable and
    one device synchronization per node — the naive dispatch baseline."""
    fns = {}
    for node in graph.topo():
        if node.op in ("input", "const"):
            continue
        fns[node.name] = jax.jit(functools.partial(
            lambda node, *args: plan_lib.apply_node(node, args, "native"),
            node))

    consts = {k: jnp.asarray(v) for k, v in graph.consts.items()}

    def run(x):
        env = dict(consts)
        env[graph.inputs[0]] = x
        out = None
        for node in graph.topo():
            if node.op in ("input", "const"):
                continue
            out = fns[node.name](*[env[i] for i in node.inputs])
            out.block_until_ready()       # per-op host round-trip
            env[node.name] = out
        return env[graph.outputs[0]]

    return run


def tuned_equals_default(plan, shapes) -> bool:
    """True when every tuned block config equals its kernel's default —
    the tuned plan is then the same computation as the default plan."""
    specs = {k: jax.ShapeDtypeStruct(tuple(v), jnp.float32)
             for k, v in shapes.items()}
    avals = plan_lib.infer(plan.graph, specs)
    for node in plan.graph.topo():
        if node.op in ("input", "const"):
            continue
        cfg = plan.configs.get(node.name) or {}
        if not cfg or plan.lowerings.get(node.name) != "pallas":
            continue
        ctx = autotune.tune_ctx(node, [avals[i] for i in node.inputs])
        space = autotune.space_for(node.op)
        if ctx is None or space is None:
            continue
        if space.check({}, ctx) != space.check(cfg, ctx):
            return False
    return True


def run(sizes=(2 ** 13, 2 ** 15), repeats=10, autotune_col=False,
        tune_repeats=3, tuned=True, sharded="auto"):
    """``tuned=False`` skips the pallas def/tuned columns (they compile,
    block-tune, and time all-Pallas plans — minutes of interpret-mode
    work on CPU, and writes to the autotune cache).

    ``sharded``: "auto" adds a sharded-vs-single-device throughput
    comparison when this process sees more than one device (run under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` on CPU);
    "on"/"off" force it.  The comparison runs a batch of one signal per
    device through the same plan compiled single-device and mesh-sharded
    (batch axis split across all devices)."""
    _load_pipelines()
    n_dev = len(jax.devices())
    do_sharded = sharded == "on" or (sharded == "auto" and n_dev > 1)
    if tuned and autotune.mode() != "on":
        print(f"[fig4] warning: TINA_AUTOTUNE={autotune.mode()} — the "
              "tuned-plan column will reuse cached/default configs")
    rng = np.random.default_rng(0)
    rows, records = [], []
    def _meters():
        c, a = plan_lib.cache_stats(), autotune.stats()
        return {"plan_cache_hits": c["hits"], "plan_cache_misses":
                c["misses"], "autotune_measured": a["measured"],
                "autotune_cache_hits": a["cache_hits"]}

    for name, spec in sorted(PIPELINES.items()):
        g = spec.build()
        for n in sizes:
            m0 = _meters()
            (x_np,) = spec.make_args(rng, n)
            x = jnp.asarray(x_np)
            shapes = {g.inputs[0]: x.shape}
            naive = make_per_op_dispatch(g)
            p = graph_compile(g, shapes)
            # interleaved timing groups: drift/contention hits both
            # sides of a comparison equally (common.timeit_group); the
            # def-vs-tuned pair gets extra repeats — its gaps can be a
            # few percent, which back-to-back timing can't resolve
            t_naive, t_plan = timeit_group([naive, p], x, repeats=repeats)
            row = [name, x_np.shape[-1], us(t_naive), us(t_plan),
                   speedup(t_naive, t_plan)]
            rec = {"pipeline": name, "n": int(x_np.shape[-1]),
                   "t_per_op_s": t_naive, "t_plan_s": t_plan,
                   "speedup_plan": t_naive / t_plan}

            if tuned:
                # the tentpole comparison: fixed-default vs block-tuned
                # tiling of the same all-Pallas plan
                p_def = graph_compile(
                    g, shapes, options=CompileOptions(lowering="pallas"))
                p_tuned = graph_compile(
                    g, shapes, options=CompileOptions(
                        lowering="pallas", block_configs="auto",
                        autotune_kwargs={"repeats": tune_repeats}))
                same = tuned_equals_default(p_tuned, shapes)
                if same:
                    (t_def,) = timeit_group([p_def], x, repeats=repeats)
                    t_tuned = t_def
                else:
                    t_def, t_tuned = timeit_group([p_def, p_tuned], x,
                                                  repeats=max(repeats, 16))
                row += [us(t_def), us(t_tuned), speedup(t_def, t_tuned)]
                rec.update(t_pallas_default_s=t_def, t_pallas_tuned_s=t_tuned,
                           speedup_tuned_vs_default=t_def / t_tuned,
                           tuned_configs={k: v for k, v in
                                          p_tuned.configs.items() if v})
            if autotune_col:
                pa = graph_compile(g, shapes, options=CompileOptions(
                    lowering="auto",
                    autotune_kwargs={"repeats": tune_repeats}))
                (t_auto,) = timeit_group([pa], x, repeats=repeats)
                row += [us(t_auto), speedup(t_naive, t_auto)]
                rec.update(t_plan_auto_s=t_auto,
                           speedup_auto=t_naive / t_auto,
                           auto_lowerings=pa.lowerings,
                           auto_configs={k: v for k, v in
                                         pa.configs.items() if v})

            # int8-vs-f32 plan: the paper's §1 "quantization inherited
            # from the NN stack" claim, quantified — throughput side by
            # side with the achieved accuracy (SQNR vs the f32 plan's
            # output), so the trajectory records what the speed cost in
            # bits actually bought
            from repro.core.opdefs import sqnr_db
            p_int8 = graph_compile(
                g, shapes, options=CompileOptions(precision="int8"))
            if "int8" in p_int8.precisions.values():
                t32b, t_int8 = timeit_group([p, p_int8], x,
                                            repeats=repeats)
                q = sqnr_db(np.asarray(p(x)), np.asarray(p_int8(x)))
                row += [us(t_int8), speedup(t32b, t_int8),
                        f"{q:.1f}"]
                rec.update(
                    t_plan_int8_s=t_int8,
                    speedup_int8_vs_f32=t32b / t_int8,
                    int8_sqnr_db=round(q, 2),
                    int8_precisions=p_int8.precisions,
                    int8_downgrades=p_int8.downgrades)
                # true integer kernels vs the dequantize-then-f32-dot
                # reference engine: what int8 *compute* buys over int8
                # *storage*.  The engine joins the plan-cache key, and
                # tracing is lazy — compile AND warm/time inside the
                # override so the ref path is what gets jitted.
                from repro.core import quantize
                with quantize.engine_override("ref"):
                    p_ref = graph_compile(
                        g, shapes,
                        options=CompileOptions(precision="int8"))
                    (t_ref,) = timeit_group([p_ref], x, repeats=repeats)
                rec.update(t_plan_int8_dequant_s=t_ref,
                           speedup_int8_true_vs_dequant=t_ref / t_int8)
            else:
                # no node quantizes (e.g. an overlap_add-only tail):
                # keep the table rectangular
                row += ["-", "-", "-"]

            if do_sharded:
                # one signal per device: the same batch through the plan
                # compiled single-device vs batch-sharded over the mesh
                xb = jnp.asarray(np.stack(
                    [spec.make_args(rng, n)[0] for _ in range(n_dev)]))
                bshapes = {g.inputs[0]: xb.shape}
                p_single = graph_compile(g, bshapes)
                p_shard = graph_compile(g, bshapes,
                        options=CompileOptions(shard="batch"))
                xb_sharded = p_shard.shard_inputs(xb)
                t_single, t_shard = timeit_group(
                    [lambda: p_single(xb), lambda: p_shard(xb_sharded)],
                    repeats=repeats)
                row += [n_dev, us(t_single), us(t_shard),
                        speedup(t_single, t_shard)]
                rec.update(
                    batch=n_dev, n_devices=n_dev,
                    mesh={a: int(s) for a, s in p_shard.mesh.shape.items()},
                    t_batch_single_s=t_single, t_batch_sharded_s=t_shard,
                    speedup_sharded_vs_single=t_single / t_shard)
            m1 = _meters()
            rec["telemetry"] = {k: m1[k] - m0[k] for k in m0}
            rows.append(row)
            records.append(rec)

    header = ["pipeline", "n", "per_op_us", "plan_us", "plan_vs_per_op"]
    if tuned:
        header += ["pallas_def_us", "pallas_tuned_us", "tuned_vs_def"]
    if autotune_col:
        header += ["auto_us", "auto_vs_per_op"]
    header += ["int8_us", "int8_vs_plan", "int8_sqnr_db"]
    if do_sharded:
        header += ["batch", "batch_single_us", "sharded_us",
                   "sharded_vs_single"]
    return fmt_table("Fig.4: compiled plans vs per-op dispatch; "
                     "block-tuned vs fixed-default plans",
                     header, rows), records


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--sizes", type=int, nargs="+",
                    default=[2 ** 13, 2 ** 15])
    ap.add_argument("--repeats", type=int, default=10)
    ap.add_argument("--tune-repeats", type=int, default=3,
                    help="per-candidate repeats inside the autotuner")
    ap.add_argument("--autotune", action="store_true",
                    help="add a jointly-autotuned (lowering+config) column")
    ap.add_argument("--sharded", choices=["auto", "on", "off"],
                    default="auto",
                    help="sharded-vs-single-device throughput columns "
                         "(auto: when >1 device is visible)")
    ap.add_argument("--out", default="BENCH_pipelines.json")
    args = ap.parse_args(argv)
    table, records = run(tuple(args.sizes), args.repeats, args.autotune,
                         args.tune_repeats, sharded=args.sharded)
    print(table)
    path = append_bench_json(args.out, records, figure="fig4_pipelines",
                             sizes=list(args.sizes), repeats=args.repeats,
                             n_devices=len(jax.devices()))
    print(f"\n[fig4] appended run to {path}")


if __name__ == "__main__":
    from repro import compile_cache
    compile_cache.enable()
    main()
