"""Pipeline-graph quickstart: build, compile, stream, and serve a DSP
pipeline through the graph subsystem.

    PYTHONPATH=src python examples/pipeline_quickstart.py

Walks the four layers: (1) declare a graph of TINA ops, (2) compile it
into a cached shape-specialized plan, (3) stream a long signal through
in chunks with overlap carry, (4) serve batched requests through one
cached plan.
"""
import os
import tempfile

import numpy as np
import jax.numpy as jnp

from repro import graph
from repro.core.registry import PIPELINES, pipelines

# keep the example self-contained: tune into a temp cache, not the
# user's global ~/.cache/tina/autotune.json (respects an explicit env)
os.environ.setdefault(
    "TINA_AUTOTUNE_CACHE",
    os.path.join(tempfile.gettempdir(), "tina-quickstart-autotune.json"))

rng = np.random.default_rng(0)

# -- 1. declare a pipeline as a graph of TINA ops ---------------------------
J = 64
win = np.hanning(J).astype(np.float32)
g = graph.Graph("my_spectrogram")
x = g.input("x")
w = g.const(win, "win")
frames = g.apply("unfold", x, window=J)          # §4.4 standard conv
windowed = g.apply("window", frames, w)          # §3.1 depthwise conv
spec = g.apply("dft", windowed)                  # §4.1 pointwise conv
power = g.apply("abs2", spec)
out = g.apply("scale", power, factor=1.0 / J)
g.output(out)
print("graph:", g)

# -- 2. compile: shape-specialized, fused, memoized -------------------------
sig = rng.standard_normal(4096).astype(np.float32)
plan = graph.compile(g, {"x": sig.shape})        # lowering="conv"/"pallas"/
offline = np.asarray(plan(jnp.asarray(sig)))     # "auto" also work
plan2 = graph.compile(g, {"x": sig.shape})
assert plan2 is plan, "second compile must be a cache hit"
print(f"plan: out {offline.shape}, traces {plan.trace_count}, "
      f"fused graph {plan.graph}")

# -- 2b. autotune the Pallas tiling for these exact shapes ------------------
# block_configs="auto" searches each kernel's TuneSpace (valid block
# sizes only) on the pipeline's real shapes; winners persist to the
# on-disk cache, so a second run compiles instantly.  lowering="auto"
# would tune lowering AND tiling jointly.
tuned = graph.compile(g, {"x": sig.shape}, options=graph.CompileOptions(
    lowering="pallas", block_configs="auto", autotune_kwargs={"repeats": 1}))
np.testing.assert_allclose(np.asarray(tuned(jnp.asarray(sig))), offline,
                           rtol=2e-3, atol=2e-3)
print("tuned:", {k: v for k, v in tuned.configs.items() if v})

# -- 3. stream it chunk-by-chunk: identical to offline ----------------------
chunked = np.asarray(graph.stream_execute(g, sig, chunk_len=1000))
np.testing.assert_allclose(chunked, offline, rtol=1e-6, atol=1e-6)
print(f"stream: {sig.shape[-1]} samples in chunks of 1000 -> "
      f"{chunked.shape}, equals offline")

# -- 4. serve batched requests through cached plans --------------------------
builtin = PIPELINES["pfb_power"]                 # pipelines() registers these
pg = builtin.build()
with graph.PipelineService(pg, signal_len=1024, batch_size=4) as svc:
    futs = [svc.submit(rng.standard_normal(1024).astype(np.float32))
            for _ in range(10)]
    outs = [f.result(timeout=60) for f in futs]
print(f"service: {svc.stats()}, buckets {list(svc.buckets)}, "
      f"plan traces {svc.plan.trace_count}")

# the built-ins come with numpy oracles — verify one response
xs = np.asarray(outs[0])

# -- 5. every op above is ONE OpDef declaration ------------------------------
# core/opdefs.py is the single registry the planner, fuser, autotuner,
# streaming executor, and Table-1 sweep all derive from.
from repro.core.opdefs import OPDEFS
used = sorted({n.op for n in g.topo() if n.op not in ("input", "const")})
print("ops used:", {op: (f"§{OPDEFS[op].section}" if OPDEFS[op].section
                         else "glue") for op in used})

print("pipeline quickstart: all stages verified" if xs.shape else "")
