"""Planner: shape-specialize a pipeline graph, fuse adjacent elementwise
nodes, pick each node's lowering, and memoize the compiled jitted plan.

``compile(graph, shapes)`` returns a :class:`Plan`; the cache key is
``(graph.signature, input shapes+dtypes, backend, lowering spec)`` so a
second identical call is a pure dict lookup — no retrace (asserted in
tests via ``Plan.trace_count``).

Op catalog: the planner declares NO ops of its own — every node's
implementation, supported lowerings, attr schema, and fusion trait come
from the unified :mod:`repro.core.opdefs` registry (:data:`OPS` below
*is* ``opdefs.OPDEFS``).  Adding an op means declaring one OpDef there;
the planner, fuser, autotuner, and streaming executor all derive from
it.

Lowering selection: ``lowering=`` may be a single name applied to every
node, a per-node dict, or ``"auto"`` — the measurement-based autotuner
of :mod:`repro.graph.autotune`, which times each candidate on the
node's actual shapes and persists the winner to an on-disk cache.
Nodes that don't support the requested lowering run ``native`` — the
substitution is **recorded** on ``Plan.node_lowerings`` /
``Plan.downgrades`` and warned once per graph, so a
requested-pallas-got-native plan is visible instead of silently slow.

Block-config selection: ``block_configs=`` picks the Pallas block sizes
each node's kernel runs with — ``None`` (kernel defaults), ``"auto"``
(the autotuner searches each kernel's declared
:class:`repro.kernels.tune.TuneSpace` on the node's actual shapes), or
a ``{node: {param: int}}`` dict.  With ``lowering="auto"`` the tuner
searches lowerings and configs *jointly*, so the plan is not just "the
fastest lowering" but "the fastest tiling of the fastest lowering".

Fusion: maximal runs of adjacent single-consumer elementwise nodes
(the OpDefs carrying the ``elementwise`` trait) collapse into one
``fused_ew`` node — executed as a single jnp expression (native), a
sequential paper-faithful chain (conv), or ONE Pallas kernel launch via
:func:`repro.kernels.ops.fused_elementwise` (pallas).  ``fuse=True``
fuses unconditionally (the historical default); ``fuse="auto"`` lets
the autotuner measure fused vs unfused per chain and persist the
verdict (``TINA_AUTOTUNE=on``; ``cached`` reads prior verdicts,
``off``/cold-cache keeps the fused default).

Mesh sharding: ``compile(..., mesh=...)`` (or ``shard="batch"``) places
the plan's batch axis — the leading dim of every graph input — across a
device mesh built via :mod:`repro.launch.mesh`.  The plan body runs
under ``shard_map``, so each device executes the *per-shard* problem:
shape inference, fusion, and the autotuner all see per-shard shapes
(tuned block configs fit the per-device workload, not the global one).
Outputs are batch-sharded on the same axis.  Every batch row is
computed independently, so a sharded plan is bit-identical to the
single-device plan compiled at the per-shard shape (and allclose to the
global-batch plan — XLA's contraction tiling can vary with batch size,
so *global* bitwise equality is not something the hardware guarantees).
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro import obs
from repro.core import quantize
from repro.core.opdefs import OPDEFS, bf16_round
from repro.graph.graph import Graph, Node

# The op catalog IS the unified OpDef registry — kept under the name the
# rest of the codebase historically imported from here.
OPS = OPDEFS


def apply_node(node: Node, args: Sequence[jax.Array], lowering: str,
               block: dict | None = None, precision: str = "f32",
               qpack=None):
    """Execute one graph node through its OpDef.

    An unsupported ``lowering`` (or ``precision``) falls back to
    native/f32 *here* for the eager callers (shape inference, per-op
    benchmarks, the tuner's candidate probes); the planner resolves
    effective lowerings and precisions ahead of time and records the
    substitutions on the plan instead of relying on this fallback.

    ``precision``: ``"int8"`` dispatches to the op's quantized impl
    (``qpack`` is the plan-built weight pack, or None to quantize per
    call) — the lowering routes within the op's ``q_lowerings``
    (``"pallas"`` runs the int8 Pallas kernel, anything unsupported
    falls back to the jnp integer dot_general); ``"bf16"`` rounds
    inputs and output through bfloat16 around the f32 impl (MXU
    numerics — composes with every lowering).  An op declaring a tier
    but no qimpl is precision-transparent: the f32 impl IS its
    behavior at that tier.
    """
    d = OPS[node.op]
    at = d.bind(node.attr)
    if lowering not in d.lowerings:
        lowering = "native"
    if precision not in (None, "f32") \
            and not d.supports_precision(precision, at):
        precision = "f32"
    if precision == "int8" and d.qimpl is not None:
        if lowering not in d.q_lowerings:
            lowering = "native"
        return d.qimpl(list(args), at, qpack, lowering, block)
    if precision == "bf16":
        args = [bf16_round(a) for a in args]
        return bf16_round(d.impl(list(args), at, lowering, block))
    return d.impl(list(args), at, lowering, block)


# ---------------------------------------------------------------------------
# Execution + shape inference
# ---------------------------------------------------------------------------
def _execute(graph: Graph, inputs: dict[str, jax.Array],
             lowerings: dict[str, str],
             configs: dict[str, dict] | None = None,
             precisions: dict[str, str] | None = None,
             qconsts: dict[str, tuple] | None = None):
    configs = configs or {}
    precisions = precisions or {}
    qconsts = qconsts or {}
    env: dict[str, jax.Array] = {}
    for node in graph.topo():
        if node.op == "input":
            env[node.name] = inputs[node.name]
        elif node.op == "const":
            env[node.name] = jnp.asarray(graph.consts[node.name])
        else:
            args = [env[i] for i in node.inputs]
            env[node.name] = apply_node(node, args,
                                        lowerings.get(node.name, "native"),
                                        configs.get(node.name),
                                        precisions.get(node.name, "f32"),
                                        qconsts.get(node.name))
    outs = tuple(env[o] for o in graph.outputs)
    return outs[0] if len(outs) == 1 else outs


def infer(graph: Graph, input_specs: dict[str, jax.ShapeDtypeStruct]
          ) -> dict[str, jax.ShapeDtypeStruct]:
    """Abstract-eval every node (native lowering) -> name -> aval."""
    avals: dict[str, jax.ShapeDtypeStruct] = {}

    def run(inputs):
        env = {}
        for node in graph.topo():
            if node.op == "input":
                env[node.name] = inputs[node.name]
            elif node.op == "const":
                env[node.name] = jnp.asarray(graph.consts[node.name])
            else:
                env[node.name] = apply_node(
                    node, [env[i] for i in node.inputs], "native")
        return env

    env = jax.eval_shape(run, input_specs)
    for k, v in env.items():
        avals[k] = jax.ShapeDtypeStruct(v.shape, v.dtype)
    return avals


# ---------------------------------------------------------------------------
# Elementwise fusion pass
# ---------------------------------------------------------------------------
def _step_of(node: Node) -> tuple | None:
    """The node's fused-chain step, from its OpDef's ``fuse_step``
    (None: the op cannot be expressed as a chain step)."""
    d = OPS.get(node.op)
    if d is None or not d.elementwise or d.fuse_step is None:
        return None
    return d.fuse_step(d.bind(node.attr))


def run_to_steps(run: Sequence[Node]) -> tuple[tuple, tuple[str, ...]]:
    """A run of elementwise nodes -> (fused steps, operand node names).

    Steps come from each OpDef's declared ``fuse_step``; tags
    ``"mul"``/``"add"`` consume the node's second input as a chain
    operand.  Shared by the fuser below and the fusion autotuner
    (:func:`repro.graph.autotune.pick_fusion`), so both describe a
    chain the same way.
    """
    steps: list[tuple] = []
    operands: list[str] = []
    for n in run:
        step = _step_of(n)
        if step is None:
            raise ValueError(f"unfusable op {n.op!r} in run")
        steps.append(step)
        if step[0] in ("mul", "add"):
            operands.append(n.inputs[1])
    return tuple(steps), tuple(operands)


def fuse_elementwise(graph: Graph,
                     avals: dict[str, jax.ShapeDtypeStruct],
                     keep: Callable[[list[Node]], bool] | None = None
                     ) -> Graph:
    """Collapse maximal runs of adjacent single-consumer elementwise
    nodes (OpDefs with the ``elementwise`` trait) into ``fused_ew``
    nodes.  A complex-input elementwise node only joins as an ``abs2``
    run head (the Pallas chain kernel is real).  ``keep`` filters the
    candidate runs (the fusion autotuner's hook): a run it rejects
    stays unfused."""
    consumers = graph.consumers()

    def _is_abs2(node: Node) -> bool:
        step = _step_of(node)
        return step is not None and step[0] == "abs2"

    def fusable(node: Node) -> bool:
        # the trait alone is not enough: the op must also express
        # itself as a chain step the fused kernel understands
        if _step_of(node) is None:
            return False
        if not _is_abs2(node) and any(
                np.issubdtype(avals[i].dtype, np.complexfloating)
                for i in node.inputs if graph.nodes[i].op != "const"):
            return False
        return True

    # group nodes into runs along the data edge (first input)
    runs: list[list[Node]] = []
    run_of: dict[str, int] = {}
    for node in graph.topo():
        if not fusable(node):
            continue
        prev = node.inputs[0] if node.inputs else None
        if (prev in run_of and not _is_abs2(node)
                and len(consumers[prev]) == 1
                and prev not in graph.outputs):
            idx = run_of[prev]
            runs[idx].append(node)
            run_of[node.name] = idx
        else:
            run_of[node.name] = len(runs)
            runs.append([node])
    runs = [r for r in runs if len(r) >= 2]
    if keep is not None:
        runs = [r for r in runs if keep(r)]
    if not runs:
        return graph

    # emit each fused node at its run TAIL's topo position: operands of
    # later members may be declared after the run head, and by the tail
    # every input of every member exists in the rebuilt graph
    tail_of = {r[-1].name: r for r in runs}
    merged = {n.name for r in runs for n in r}

    out = Graph(graph.name + "+fused")
    out.consts = dict(graph.consts)
    renamed: dict[str, str] = {}   # old producer name -> new name

    def resolve(name: str) -> str:
        return renamed.get(name, name)

    for node in graph.topo():
        if node.name in merged and node.name not in tail_of:
            continue                       # non-tail member: folded away
        if node.name in tail_of:
            run = tail_of[node.name]
            steps, operand_refs = run_to_steps(run)
            data_in = resolve(run[0].inputs[0])
            operands = [resolve(o) for o in operand_refs]
            fname = f"fused_{run[0].name}"
            members = tuple(n.name for n in run)
            out._add(Node(fname, "fused_ew", (data_in, *operands),
                          (("members", members), ("steps", steps))))
            renamed[node.name] = fname     # run tail -> fused node
        elif node.op == "input":
            out.inputs.append(node.name)
            out._add(node)
        else:
            out._add(Node(node.name, node.op,
                          tuple(resolve(i) for i in node.inputs),
                          node.attrs))
    out.outputs = [resolve(o) for o in graph.outputs]
    return out


# ---------------------------------------------------------------------------
# Plan cache
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Plan:
    graph: Graph                  # post-fusion graph the plan executes
    input_names: tuple[str, ...]
    lowerings: dict[str, str]     # node name -> effective lowering
    key: tuple
    configs: dict[str, dict] = dataclasses.field(default_factory=dict)
    # node name -> chosen Pallas block config ({} = kernel defaults)
    downgrades: dict[str, str] = dataclasses.field(default_factory=dict)
    # node name -> dimension-tagged request(s) the node couldn't honor:
    # "lowering:pallas", "precision:int8", or both comma-joined (the
    # effective entries in ``lowerings``/``precisions`` are what runs)
    precisions: dict[str, str] = dataclasses.field(default_factory=dict)
    # node name -> effective execution precision (absent == "f32")
    qconsts: dict[str, tuple] = dataclasses.field(default_factory=dict)
    # node name -> int8 (q, scale) weight pack, quantized ONCE at plan
    # build by the OpDef's qprep (activations quantize per dispatch)
    mesh: Mesh | None = None      # device mesh of a sharded plan
    batch_axis: str | None = None  # mesh axis carrying the batch dim
    input_shardings: tuple = ()   # NamedSharding per input (sharded plans)
    input_specs: tuple = ()       # ShapeDtypeStruct per input (global)
    _fn: Callable = None
    _traces: list = dataclasses.field(default_factory=list)

    @property
    def node_lowerings(self) -> dict[str, str]:
        """Effective per-node lowerings (what each node actually runs —
        requested lowerings a node doesn't support appear as ``native``
        here and in :attr:`downgrades`).  The same mapping as
        :attr:`lowerings`; treat it as read-only."""
        return self.lowerings

    @property
    def node_precisions(self) -> dict[str, str]:
        """Effective per-node precisions (what each node actually runs —
        requested tiers a node doesn't support appear as ``f32`` here
        and dimension-tagged in :attr:`downgrades`).  The same mapping
        as :attr:`precisions`; treat it as read-only."""
        return self.precisions

    @property
    def trace_count(self) -> int:
        """Times jax actually retraced the plan body (1 == fully cached)."""
        return len(self._traces)

    def shard_inputs(self, *arrays):
        """Place inputs onto the plan's mesh (batch-sharded) ahead of the
        call, so execution doesn't pay the reshard; no-op when unsharded."""
        if not self.input_shardings:
            return arrays if len(arrays) > 1 else arrays[0]
        out = tuple(jax.device_put(a, s)
                    for a, s in zip(arrays, self.input_shardings))
        return out if len(out) > 1 else out[0]

    def shard_rows(self, n: int) -> tuple[tuple[int, int, int], ...]:
        """``(device id, start, stop)`` of the rows of an ``n``-row batch
        that :meth:`shard_inputs` puts on each device; () when
        unsharded."""
        if not self.input_shardings:
            return ()
        index = self.input_shardings[0].addressable_devices_indices_map(
            (n,))
        return tuple((d.id, *idx[0].indices(n)[:2])
                     for d, idx in index.items())

    def compiled_text(self) -> str:
        """Optimized HLO of the plan at its input shapes (compiled here
        if it has not run yet).  Each TPU kernel launch in it is a
        ``tpu_custom_call``; interpreted kernels leave none."""
        return self._fn.lower(*self.input_specs).compile().as_text()

    def __call__(self, *args, **kwargs):
        arrays = list(args)
        for name in self.input_names[len(arrays):]:
            arrays.append(kwargs[name])
        return self._fn(*arrays)


_CACHE: dict[tuple, Plan] = {}
_WARNED_DOWNGRADES: set[tuple] = set()

# the ONE set of books for the plan cache — cache_stats() reads these
# same counters ``compile``/``clear_cache`` bump (no parallel dict),
# and they show up in obs.snapshot() / dsp_serve --metrics-interval
_HITS = obs.counter("plan.cache.hits")
_MISSES = obs.counter("plan.cache.misses")
_EVICTIONS = obs.counter("plan.cache.evictions")
_DOWNGRADES = obs.counter("plan.downgrades")


def cache_stats() -> dict:
    """Plan-cache telemetry: size + hit/miss/eviction counts (read off
    the :mod:`repro.obs` counters ``compile`` maintains)."""
    return {"size": len(_CACHE), "hits": _HITS.value,
            "misses": _MISSES.value, "evictions": _EVICTIONS.value}


def cached_plans() -> list[Plan]:
    """Every plan this process has compiled and still caches."""
    return list(_CACHE.values())


def clear_cache() -> None:
    _EVICTIONS.add(len(_CACHE))
    _CACHE.clear()
    _HITS.reset()
    _MISSES.reset()


def _warn_downgrades(graph: Graph, downgrades: dict[str, str]) -> None:
    """Surface requested-but-unsupported lowerings/precisions, once per
    (graph, downgrade set) — a requested-pallas-got-native (or
    requested-int8-got-f32) plan must be visible instead of silently
    slow/full-precision.  Downgrade values are dimension-tagged
    (``"lowering:pallas"`` / ``"precision:int8"``, comma-joined when a
    node downgraded on both), and the warning says which dimension fell
    back."""
    key = (graph.name, tuple(sorted(downgrades.items())))
    if key in _WARNED_DOWNGRADES:
        return
    _WARNED_DOWNGRADES.add(key)
    by_dim: dict[str, dict[str, str]] = {"lowering": {}, "precision": {}}
    for name, tags in downgrades.items():
        for tag in tags.split(","):
            dim, _, req = tag.partition(":")
            by_dim.setdefault(dim, {})[name] = req
    parts = []
    if by_dim["lowering"]:
        detail = ", ".join(
            f"{name} ({OPS[graph.nodes[name].op].name}: requested {req!r}, "
            f"supports {'/'.join(OPS[graph.nodes[name].op].lowerings)})"
            for name, req in sorted(by_dim["lowering"].items()))
        parts.append(f"{len(by_dim['lowering'])} node(s) fell back to "
                     f"lowering='native': {detail}")
    if by_dim["precision"]:
        detail = ", ".join(
            f"{name} ({OPS[graph.nodes[name].op].name}: requested {req!r}, "
            f"supports {'/'.join(OPS[graph.nodes[name].op].precisions)})"
            for name, req in sorted(by_dim["precision"].items()))
        parts.append(f"{len(by_dim['precision'])} node(s) fell back to "
                     f"precision='f32': {detail}")
    warnings.warn(
        f"plan for {graph.name!r}: " + "; ".join(parts)
        + "; see Plan.downgrades / Plan.node_lowerings", stacklevel=3)


def _norm_mesh(mesh, shard) -> tuple[Mesh | None, str | None]:
    """Normalize ``(mesh=, shard=)`` into (Mesh, batch-axis name).

    ``mesh`` may be a Mesh, a device count (a 1-D batch mesh over that
    many local devices via :func:`repro.launch.mesh.make_batch_mesh`),
    or None; ``shard="batch"`` alone shards over every local device.
    The batch axis is ``"batch"`` when the mesh has one, else ``"data"``,
    else the mesh's first axis (other axes replicate the computation).
    """
    if mesh is None and shard is None:
        return None, None
    if shard not in (None, "batch"):
        raise ValueError(
            f"shard={shard!r}: only 'batch' (data-parallel over the "
            "leading input dim) is supported")
    from repro.launch.mesh import make_batch_mesh
    if mesh is None:
        mesh = make_batch_mesh()
    elif isinstance(mesh, int):
        mesh = make_batch_mesh(mesh)
    elif not isinstance(mesh, Mesh):
        raise TypeError(f"mesh= expects a jax Mesh, an int device count, "
                        f"or None; got {type(mesh).__name__}")
    for axis in ("batch", "data"):
        if axis in mesh.axis_names:
            return mesh, axis
    return mesh, mesh.axis_names[0]


def _norm_specs(graph: Graph, shapes, dtype) -> dict[str, jax.ShapeDtypeStruct]:
    """shapes: {input: shape | (shape, dtype) | ShapeDtypeStruct}."""
    if not isinstance(shapes, dict):
        shapes = {name: s for name, s in zip(graph.inputs, [shapes])} \
            if len(graph.inputs) == 1 else dict(zip(graph.inputs, shapes))
    specs = {}
    for name in graph.inputs:
        s = shapes[name]
        if isinstance(s, jax.ShapeDtypeStruct):
            specs[name] = s
        elif (isinstance(s, tuple) and len(s) == 2 and isinstance(s[0], tuple)):
            specs[name] = jax.ShapeDtypeStruct(s[0], jnp.dtype(s[1]))
        else:
            specs[name] = jax.ShapeDtypeStruct(tuple(s), jnp.dtype(dtype))
    return specs


@dataclasses.dataclass(frozen=True)
class CompileOptions:
    """Every compile-time knob in one value object.

    Nine PRs accreted nine ``compile()`` keyword arguments; this is the
    consolidation: one dataclass that :func:`compile`,
    :class:`repro.graph.service.PipelineService`,
    :class:`repro.graph.stream.ChunkedRunner`, and ``dsp_serve`` all
    build on, instead of re-plumbing each knob through every layer.
    Immutable (hashable construction aside — dict-valued fields are
    allowed), so one instance can be shared across tenants and plan
    compiles; derive variants with :meth:`replace`::

        opts = CompileOptions(lowering="auto", precision="int8")
        plan = graph.compile(g, shapes, options=opts)
        svc = PipelineService(g, n, options=opts.replace(donate=True))

    Field semantics match the historical keyword arguments (documented
    on :func:`compile`); the one new field is ``donate`` — donate input
    buffers to the computation (``jax.jit(donate_argnums=...)``), which
    the overlapped scheduler uses so batch N's input buffer is recycled
    into batch N's output instead of holding host memory while batch
    N+1 is formed.  Only inputs that match an output's shape and dtype
    are donated: no other buffer can be reused for an output (a PFB
    power spectrum's input, for one, cannot).  Donation makes the
    *caller's* input array unusable after the call on backends that
    honor it; leave it off unless every input is a fresh throwaway (the
    service's packed batches are).
    """

    dtype: str = "float32"
    backend: str | None = None
    lowering: object = "native"       # str | {node: str}
    precision: object = "f32"         # str | {node: str}
    block_configs: object = None      # None | "auto" | {node: {param: int}}
    fuse: object = None               # None | bool | "auto"
    mesh: object = None               # Mesh | int device count | None
    shard: str | None = None
    donate: bool = False
    autotune_kwargs: dict | None = None

    def replace(self, **changes) -> "CompileOptions":
        """A copy with the given fields changed (``dataclasses.replace``)."""
        return dataclasses.replace(self, **changes)


_LEGACY_COMPILE_KWARGS = tuple(
    f.name for f in dataclasses.fields(CompileOptions) if f.name != "donate")
_warned_legacy_compile = False


def compile(graph: Graph, shapes, *, options: CompileOptions | None = None,
            **legacy) -> Plan:
    """Compile ``graph`` for the given input shapes; memoized.

    Knobs ride a :class:`CompileOptions`::

        compile(g, shapes, options=CompileOptions(lowering="auto"))

    The historical keyword arguments (``lowering=``, ``precision=``,
    ``mesh=``, ...) still work — they are folded into a
    :class:`CompileOptions` behind a once-per-process
    ``DeprecationWarning`` — but can't be mixed with ``options=`` in
    one call (that raises ``TypeError``: two sources of truth for the
    same knob).

    ``lowering``: a lowering name for every node (unsupported nodes fall
    back to native — recorded on ``Plan.downgrades`` and warned once), a
    {node: lowering} dict, or ``"auto"`` to let the measurement-based
    autotuner choose per node.  ``"reference"`` is an alias for the
    native (pure jax.numpy) path — the degradation target the serving
    layer recompiles a persistently failing bucket with (its runtime
    downgrades live on ``PipelineService.downgrades``, extending the
    compile-time ``Plan.downgrades`` contract).

    ``precision``: the execution tier, mirroring the ``lowering``
    contract — ``"f32"`` (default), ``"bf16"`` (inputs/outputs rounded
    through bfloat16 around f32 accumulate, MXU numerics, any lowering),
    ``"int8"`` (quantized impls for the matmul-shaped ops; const
    weights quantized ONCE here and carried on ``Plan.qconsts``,
    activations per dispatch), a per-node dict, or ``"auto"`` (the
    autotuner searches precision jointly with lowering × block config,
    rejecting candidates that violate the OpDef's accuracy Budget).
    Nodes that don't support the requested tier run f32 — recorded
    dimension-tagged on ``Plan.downgrades`` (``"precision:int8"``) and
    warned once, like lowering downgrades.  int8 nodes with a quantized
    impl route the lowering through the OpDef's ``q_lowerings``:
    ``pallas`` runs the op's int8 Pallas kernel (tuned over its
    ``qtune_space``), any other request quietly runs the jnp integer
    dot_general (not a downgrade — the integer path is the tier's
    contract either way, bit-identically).

    ``block_configs``: Pallas block sizes per node — ``None`` (kernel
    defaults; with ``lowering="auto"`` the autotuner picks them jointly
    with the lowering), ``"auto"`` (tune configs for whatever lowering
    each node ends up with), or a ``{node: {param: int}}`` dict
    (post-fusion node names; explicit entries win over tuned ones).

    ``fuse``: ``True`` fuses elementwise chains unconditionally,
    ``False`` never fuses, ``"auto"`` asks the autotuner to measure
    fused vs unfused per chain (``TINA_AUTOTUNE=on`` measures and
    persists the verdict; ``cached`` replays it; ``off`` keeps the
    fused default).  The default (``None``) resolves to ``"auto"`` for
    ``lowering="auto"`` plans — tuned plans get tuned fusion — and
    ``True`` otherwise.  Chains whose members request different
    precisions (dict form) refuse to fuse: a fused node runs at ONE
    tier, so precision boundaries are fusion boundaries.

    ``mesh`` / ``shard``: ``mesh=`` (a Mesh or a device count) shards
    the batch axis — the leading dim of every input — across the mesh's
    batch axis via ``shard_map``; ``shard="batch"`` alone shards over
    all local devices.  Every input needs ``ndim >= 2`` with a batch dim
    divisible by the shard count.  Shape inference, fusion, and the
    autotuner run on the *per-shard* shapes, so tuned block configs fit
    the per-device problem; the plan cache is keyed on the mesh topology
    (axes, sizes, device ids).
    """
    if legacy:
        unknown = sorted(set(legacy) - set(_LEGACY_COMPILE_KWARGS))
        if unknown:
            raise TypeError(
                f"compile() got unexpected keyword argument(s) {unknown}; "
                f"known options: {sorted(_LEGACY_COMPILE_KWARGS)} "
                f"(preferably via options=CompileOptions(...))")
        if options is not None:
            raise TypeError(
                "compile() got both options= and legacy keyword "
                f"argument(s) {sorted(legacy)}: fold everything into the "
                "CompileOptions")
        global _warned_legacy_compile
        if not _warned_legacy_compile:
            _warned_legacy_compile = True
            warnings.warn(
                "compile(..., lowering=, precision=, mesh=, ...) keyword "
                "arguments are deprecated; pass "
                "compile(graph, shapes, options=CompileOptions(...))",
                DeprecationWarning, stacklevel=2)
        options = CompileOptions(**legacy)
    return _compile_impl(graph, shapes, options or CompileOptions())


def _compile_impl(graph: Graph, shapes, o: CompileOptions) -> Plan:
    dtype, lowering, precision = o.dtype, o.lowering, o.precision
    block_configs, fuse, mesh, shard = o.block_configs, o.fuse, o.mesh, o.shard
    autotune_kwargs, donate = o.autotune_kwargs, o.donate
    backend = o.backend or jax.default_backend()
    if lowering == "reference":
        lowering = "native"      # alias: "run the trusted slow path" —
        # shares native's cache key so degraded buckets reuse any
        # already-compiled native plan
    if fuse is None:
        fuse = "auto" if lowering == "auto" else True
    if precision is None:
        precision = "f32"
    _tiers = ("f32", "bf16", "int8", "auto")
    bad = ({p for p in precision.values() if p not in _tiers}
           if isinstance(precision, dict)
           else (set() if precision in _tiers else {precision}))
    if bad:
        raise ValueError(f"precision: unknown tier(s) {sorted(bad)}; "
                         f"expected one of {_tiers} or a per-node dict")
    prec_auto = (precision == "auto"
                 or (isinstance(precision, dict)
                     and "auto" in precision.values()))
    specs = _norm_specs(graph, shapes, dtype)
    mesh, batch_axis = _norm_mesh(mesh, shard)
    mesh_key = None
    if mesh is not None:
        n_shards = int(mesh.shape[batch_axis])
        for name in graph.inputs:
            s = specs[name]
            if len(s.shape) < 2:
                raise ValueError(
                    f"sharded plans need a batch axis: input {name!r} has "
                    f"shape {s.shape}; provide (batch, ...) inputs")
            if s.shape[0] % n_shards != 0:
                raise ValueError(
                    f"batch divisibility: input {name!r} batch dim "
                    f"{s.shape[0]} is not divisible by the mesh's "
                    f"{batch_axis!r} axis ({n_shards} shards)")
        mesh_key = (batch_axis,
                    tuple((a, int(mesh.shape[a])) for a in mesh.axis_names),
                    tuple(int(d.id) for d in mesh.devices.flat))
    spec_key = tuple((n, specs[n].shape, str(specs[n].dtype))
                     for n in graph.inputs)
    low_key = (tuple(sorted(lowering.items()))
               if isinstance(lowering, dict) else lowering)
    prec_key = (tuple(sorted(precision.items()))
                if isinstance(precision, dict) else precision)
    cfg_key = (tuple(sorted((n, tuple(sorted(c.items())))
                            for n, c in block_configs.items()))
               if isinstance(block_configs, dict) else block_configs)
    tune_key = None
    if (lowering == "auto" or block_configs == "auto" or fuse == "auto"
            or prec_auto):
        # tuned selections depend on the autotune mode, the cache file
        # (path AND content — another process tuning entries must reach
        # plans compiled after its write, hence the mtime), and the
        # tuner kwargs (path/lowerings/repeats all change the answer);
        # none of these may return a stale memoized plan
        from repro.graph import autotune
        path = (autotune_kwargs or {}).get("path") or autotune.cache_path()
        tune_key = (autotune.mode(), path, autotune._mtime(path),
                    repr(sorted((autotune_kwargs or {}).items())))
    # quantize.engine() is part of the key: an engine_override("ref")
    # compile must not collide with (or poison) the default "int" plans
    # — Graph.signature carries no engine information.
    key = (graph.signature, spec_key, backend, low_key, prec_key,
           quantize.engine(), cfg_key, fuse, mesh_key, bool(donate),
           tune_key)
    plan = _CACHE.get(key)
    if plan is not None:
        _HITS.add()
        return plan
    _MISSES.add()
    with obs.span("plan.compile", cat="compile", graph=graph.name,
                  backend=backend, lowering=str(low_key),
                  precision=str(prec_key),
                  shapes=",".join(f"{n}:{specs[n].shape}"
                                  for n in graph.inputs)):
        for node in graph.topo():
            if node.op in ("input", "const"):
                continue
            if node.op not in OPS:
                raise ValueError(f"{node.name}: unknown op {node.op!r}; "
                                 f"known ops: {sorted(OPS)}")
            try:
                OPS[node.op].bind(node.attr)
            except ValueError as e:
                raise ValueError(f"{node.name}: {e}") from None
        # sharded plans trace/fuse/tune on the per-shard problem: the
        # body runs under shard_map, so that's what each device
        # actually executes
        body_specs = specs
        if mesh is not None:
            body_specs = {
                n: jax.ShapeDtypeStruct((s.shape[0] // n_shards,)
                                        + tuple(s.shape[1:]), s.dtype)
                for n, s in specs.items()}
        avals = infer(graph, body_specs)

        def req_prec(name: str) -> str:
            """The precision requested for a (pre-fusion) node name."""
            if not isinstance(precision, dict):
                return precision
            return precision.get(name, "f32")

        with obs.span("plan.fuse", cat="compile", graph=graph.name,
                      mode=str(fuse)):
            keeps: list[Callable] = []
            if isinstance(precision, dict):
                # precision boundaries are fusion boundaries: a fused
                # node executes at ONE tier, so a run whose members
                # request different tiers stays unfused
                keeps.append(lambda run: len(
                    {req_prec(n.name) for n in run}) == 1)
            if fuse == "auto":
                from repro.graph import autotune
                if isinstance(lowering, str) and lowering in (
                        "native", "conv", "pallas"):
                    probe_lw = lowering
                else:
                    # auto / per-node requests: measure the verdict where
                    # it is consequential — the pallas chain kernel (one
                    # launch) vs per-member kernels.  Fused-vs-unfused
                    # native is the same XLA fusion either way, so a
                    # native probe would answer a question the autotuned
                    # plan never asks.
                    probe_lw = "pallas"
                keeps.append(lambda run: autotune.pick_fusion(
                    graph, run, avals, backend=backend,
                    lowering=probe_lw, **(autotune_kwargs or {})))
            if fuse:
                keep = (None if not keeps else
                        lambda run: all(k(run) for k in keeps))
                g = fuse_elementwise(graph, avals, keep=keep)
            else:
                g = graph
        if g is not graph:
            avals = infer(g, body_specs)

        lowerings: dict[str, str] = {}
        configs: dict[str, dict] = {}
        downgrades: dict[str, str] = {}
        precisions_map: dict[str, str] = {}
        qconsts: dict[str, tuple] = {}
        compute = [n for n in g.topo() if n.op not in ("input", "const")]

        def _tag_downgrade(name: str, dim: str, req: str) -> None:
            tag = f"{dim}:{req}"
            downgrades[name] = (f"{downgrades[name]},{tag}"
                                if name in downgrades else tag)

        def resolve(node: Node, requested: str | None) -> None:
            """Record the node's effective lowering (+ the downgrade when
            the request can't be honored).  Lowering-agnostic ops (pure
            data movement — one code path whatever the lowering) satisfy
            any request with native and are not downgrades."""
            if requested is None:
                lowerings[node.name] = "native"
            elif requested in OPS[node.op].lowerings:
                lowerings[node.name] = requested
            else:
                lowerings[node.name] = "native"
                if requested != "native" \
                        and not OPS[node.op].lowering_agnostic:
                    _tag_downgrade(node.name, "lowering", requested)

        def req_prec_node(node: Node) -> str:
            """The precision requested for a post-fusion node (fused_ew
            honors the members' request when they agree — the fusion
            keep-filter guarantees they do for dict requests)."""
            if not isinstance(precision, dict):
                return precision
            if node.name in precision:
                return precision[node.name]
            if node.op == "fused_ew":
                req = {precision[m] for m in node.attr.get("members", ())
                       if m in precision}
                if len(req) == 1:
                    return req.pop()
            return "f32"

        def resolve_prec(node: Node, rp: str) -> None:
            """Record the node's effective precision.  int8 with a
            quantized impl keeps the resolved lowering when the qimpl
            supports it (``q_lowerings`` — the int8 Pallas kernels);
            otherwise the lowering quietly collapses to native (the jnp
            integer dot_general — not a downgrade: the quantized path
            IS the int8 contract).  Unsupported tiers fall back to f32
            — recorded dimension-tagged + warned, unless the op is
            lowering-agnostic (pure data movement runs identically at
            any tier, so the request is satisfied, not downgraded)."""
            d = OPS[node.op]
            if rp in (None, "f32"):
                precisions_map[node.name] = "f32"
            elif d.supports_precision(rp, d.bind(node.attr)):
                precisions_map[node.name] = rp
                if rp == "int8" and d.qimpl is not None \
                        and lowerings.get(node.name) not in d.q_lowerings:
                    lowerings[node.name] = "native"
                    configs.pop(node.name, None)
            else:
                precisions_map[node.name] = "f32"
                if not d.lowering_agnostic:
                    _tag_downgrade(node.name, "precision", rp)

        # one lowering-selection span whatever the mode: the phase that
        # consults (or bypasses) the autotuner, so every compile's trace
        # attributes its selection time — auto plans additionally get a
        # per-node span around each tuner query
        with obs.span("plan.autotune", cat="autotune", graph=g.name,
                      mode=(lowering if isinstance(lowering, str)
                            else "per-node")):
            def tune_prec(node: Node, only=None) -> None:
                """precision="auto" for one node: joint (precision ×
                lowering × block) search, budget-gated vs the numpy
                oracle (``only`` restricts the lowering candidates when
                the lowering was fixed by the caller)."""
                from repro.graph import autotune
                kw = dict(autotune_kwargs or {})
                if only is not None:
                    kw["lowerings"] = only
                with obs.span("plan.lower", cat="autotune",
                              node=node.name, op=node.op):
                    lw, cfg, prec = autotune.pick_joint(
                        g, node, avals, backend=backend, **kw)
                lowerings[node.name] = lw
                configs[node.name] = cfg
                precisions_map[node.name] = prec

            if lowering == "auto":
                from repro.graph import autotune
                for node in compute:
                    rp = req_prec_node(node)
                    d = OPS[node.op]
                    if rp == "auto":
                        tune_prec(node)
                    elif (rp == "int8" and d.qimpl is not None
                          and d.supports_precision(rp, d.bind(node.attr))):
                        # the integer path has its own lowering × block
                        # search (q_lowerings / qtune_space): time the
                        # jnp int8 dot_general against the int8 Pallas
                        # kernel on the node's actual shapes
                        with obs.span("plan.lower", cat="autotune",
                                      node=node.name, op=node.op):
                            lw, cfg = autotune.pick(
                                g, node, avals, backend=backend,
                                precision="int8",
                                **(autotune_kwargs or {}))
                        lowerings[node.name] = lw
                        configs[node.name] = cfg
                        precisions_map[node.name] = "int8"
                    else:
                        with obs.span("plan.lower", cat="autotune",
                                      node=node.name, op=node.op):
                            lw, cfg = autotune.pick(
                                g, node, avals, backend=backend,
                                **(autotune_kwargs or {}))
                        lowerings[node.name] = lw
                        configs[node.name] = cfg
                        resolve_prec(node, rp)
            elif isinstance(lowering, dict):
                for node in compute:
                    if node.name in lowering:
                        resolve(node, lowering[node.name])
                    elif node.op == "fused_ew":
                        # fusion renamed the member nodes: honor their
                        # requested lowering when the members agree,
                        # else fall back
                        req = {lowering[m]
                               for m in node.attr.get("members", ())
                               if m in lowering}
                        resolve(node, req.pop() if len(req) == 1 else None)
                    else:
                        resolve(node, None)
                for node in compute:
                    rp = req_prec_node(node)
                    if rp == "auto":
                        tune_prec(node, only=(lowerings[node.name],))
                    else:
                        resolve_prec(node, rp)
            else:
                for node in compute:
                    resolve(node, lowering)
                for node in compute:
                    rp = req_prec_node(node)
                    if rp == "auto":
                        tune_prec(node, only=(lowerings[node.name],))
                    else:
                        resolve_prec(node, rp)
            if downgrades:
                _DOWNGRADES.add(len(downgrades))
                _warn_downgrades(g, downgrades)

            if block_configs == "auto" and lowering != "auto":
                # tune block configs for the already-chosen lowerings
                from repro.graph import autotune
                for node in compute:
                    with obs.span("plan.lower", cat="autotune",
                                  node=node.name, op=node.op):
                        _, cfg = autotune.pick(
                            g, node, avals, backend=backend,
                            lowerings=(lowerings[node.name],),
                            precision=precisions_map.get(node.name, "f32"),
                            **(autotune_kwargs or {}))
                    configs[node.name] = cfg
            elif isinstance(block_configs, dict):
                configs.update({n: dict(c)
                                for n, c in block_configs.items()})

        if tune_key is not None:
            # tuning above may have written the cache file (bumping its
            # mtime); store the plan under the post-save key so the next
            # identical compile is the cache hit stream.py promises
            from repro.graph import autotune
            path = tune_key[1]
            key = key[:-1] + ((tune_key[0], path, autotune._mtime(path),
                               tune_key[3]),)

        # quantize const weights ONCE, here at plan build: the (q, scale)
        # packs ride the Plan and are closed over by the jitted body, so
        # dispatches only quantize activations
        for node in compute:
            if precisions_map.get(node.name) != "int8":
                continue
            d = OPS[node.op]
            if d.qprep is None:
                continue
            consts = {i: jnp.asarray(g.consts[ref])
                      for i, ref in enumerate(node.inputs)
                      if g.nodes[ref].op == "const"}
            qp = d.qprep(d.bind(node.attr), consts)
            if qp is not None:
                qconsts[node.name] = qp

        plan = Plan(graph=g, input_names=tuple(g.inputs),
                    lowerings=lowerings, key=key, configs=configs,
                    downgrades=downgrades, precisions=precisions_map,
                    qconsts=qconsts, mesh=mesh,
                    batch_axis=batch_axis,
                    input_specs=tuple(specs[n] for n in g.inputs))

        def raw(*arrays):
            plan._traces.append(1)  # side effect fires only while tracing
            return _execute(g, dict(zip(g.inputs, arrays)), lowerings,
                            configs, precisions_map, qconsts)

        # donate only inputs that an output can alias (same per-shard
        # shape and dtype): XLA reuses a donated buffer for a matching
        # output and nothing else, and warns about every other donation
        out_avals = [(avals[o].shape, avals[o].dtype) for o in g.outputs]
        donate_argnums = tuple(
            i for i, name in enumerate(g.inputs)
            if donate and (avals[name].shape, avals[name].dtype) in out_avals)
        if mesh is None:
            plan._fn = jax.jit(raw, donate_argnums=donate_argnums)
        else:
            batch_sharding = NamedSharding(mesh, P(batch_axis))
            plan.input_shardings = tuple(batch_sharding for _ in g.inputs)
            fn = jax.shard_map(raw, mesh=mesh,
                               in_specs=tuple(P(batch_axis)
                                              for _ in g.inputs),
                               out_specs=(P(batch_axis)
                                          if len(g.outputs) == 1
                                          else tuple(P(batch_axis)
                                                     for _ in g.outputs)),
                               check_vma=False)
            plan._fn = jax.jit(fn, in_shardings=plan.input_shardings,
                               donate_argnums=donate_argnums)
        _CACHE[key] = plan
    return plan


__all__ = ["OPS", "Plan", "CompileOptions", "apply_node", "compile",
           "infer", "fuse_elementwise", "run_to_steps", "cache_stats",
           "cached_plans", "clear_cache"]
