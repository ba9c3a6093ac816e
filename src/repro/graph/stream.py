"""Streaming executor: run chunked 1-D signals through a pipeline in
bounded memory, with chunked output identical to offline whole-signal
execution.

Overlap-carry scheme: every streamable op advertises how it maps the
streamed (time) axis via the :class:`~repro.core.opdefs.StreamRule` on
its OpDef —

  * ``block``      input samples consumed per output step (stride)
  * ``receptive``  input samples contributing to one output step
  * ``tail``       trailing axes the op appends after the time axis
                   (unfold/pfb emit (time, J|P) frames)

"time" rules spend these on the raw sample axis; "framed" rules
(frame_decimate's hop, overlap_add's K-frame reach) spend them on the
frame axis after an unfold/pfb — in both cases they compose down the
chain exactly like conv stride/kernel arithmetic (``R += (r-1)·B;
B *= b``), giving the whole pipeline's receptive field R and stride B
in *input* samples.  An overlap_add re-synthesizes the time axis
(tail -= 1), emitting ``hop`` samples per step.  The runner keeps the last < R
unconsumed samples as carry; each push runs the compiled plan on the
longest prefix that yields whole output steps.  Every emitted step is
computed from exactly the same input window the offline run uses, so
concatenated chunked output equals offline output (valid-mode, no
padding anywhere in the chain).

Plans are compiled through :func:`repro.graph.plan.compile`, so pushes
of equal size after warm-up are pure plan-cache hits.  ``compile_opts``
pass through verbatim — ``lowering="auto"`` / ``block_configs="auto"``
make every chunk run the autotuner's tuned kernels (tuned once per push
shape, then cached), and ``precision="bf16"|"int8"`` streams at a
reduced execution tier.  Streamed output equals offline output at
EVERY precision: bf16 rounding is pointwise, and int8 activation
quantization uses per-row (last-axis) scales, so each emitted window's
quantized values depend only on that window — exactly the samples the
offline run feeds the same op (int32 accumulation is batch-invariant).

Bucketed pushes: ``ChunkedRunner(..., step_buckets=True)`` quantizes
every push to a power-of-two number of output steps (the remainder
stays in the carry; ``finalize()``/``run()`` drains it).  Irregular
push sizes — the arrival pattern a continuous-batching front door
produces — then compile a bounded ladder of plan shapes instead of one
plan per distinct chunk length, while the emitted windows (and thus the
concatenated output) stay exactly the offline ones.

Sharded batched streams: a runner built with
``options=CompileOptions(mesh=...)`` accepts chunks with a leading
batch dim (``(batch, chunk_len)``) and compiles every push's plan with
the batch axis sharded across the mesh — the carry
arithmetic is identical (overlap lives on the *time* axis; the batch
axis just rides along), so chunked sharded output still equals offline
output.  The batch dim must divide by the mesh's shard count.
"""
from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.opdefs import OPDEFS
from repro.graph import plan as plan_lib
from repro.graph.graph import Graph, Node

# Op streaming behavior comes from each OpDef's StreamRule
# (repro.core.opdefs): "pointwise" ops pass through, "frame" ops need a
# framed axis, "time"/"framed" ops declare (block, receptive,
# tail_delta) on the sample/frame axis respectively — declared once per
# op, composed here.


def _taps_shape(graph: Graph, node: Node) -> tuple:
    ref = node.inputs[1]
    if graph.nodes[ref].op != "const":
        raise ValueError(
            f"streaming requires const taps for {node.name} ({node.op})")
    return graph.consts[ref].shape


@dataclasses.dataclass(frozen=True)
class PipeStreamSpec:
    block: int         # pipeline stride, in input samples per output step
    receptive: int     # input samples contributing to one output step
    tail_dims: int     # axes after the time axis in the final output

    @property
    def concat_axis(self) -> int:
        return -(1 + self.tail_dims)


def stream_spec(graph: Graph) -> PipeStreamSpec:
    """Compose per-op specs along the (unique) path from the stream input
    to the output.  Raises if the graph isn't streamable."""
    if len(graph.inputs) != 1:
        raise ValueError("streaming supports single-input graphs "
                         "(bake taps/windows as consts)")
    if len(graph.outputs) != 1:
        raise ValueError("streaming supports single-output graphs")
    streamed = {graph.inputs[0]}
    b_total, r_total, tail = 1, 1, 0
    deframed = False      # an overlap_add ran: steps are now multi-sample
    for node in graph.topo():
        hot = [i for i in node.inputs if i in streamed]
        if not hot:
            continue
        d = OPDEFS.get(node.op)
        rule = d.stream if d is not None else None
        if rule is None:
            raise ValueError(f"{node.name} ({node.op}) is not streamable")
        if len(hot) > 1 and rule.kind != "pointwise":
            raise ValueError(f"{node.name}: multiple streamed inputs")
        if rule.kind in ("time", "framed"):
            if rule.kind == "time" and tail:
                raise ValueError(
                    f"{node.name} ({node.op}) reads the time axis, but an "
                    "upstream op already framed it")
            if rule.kind == "time" and deframed:
                raise ValueError(
                    f"{node.name} ({node.op}) reads the time axis after an "
                    "overlap-add re-synthesized it (multi-sample steps); "
                    "not streamable")
            if rule.kind == "framed" and not tail:
                raise ValueError(
                    f"{node.name} ({node.op}) consumes the frame axis; "
                    "insert an unfold/pfb first")
            taps = (_taps_shape(graph, node) if rule.needs_taps else None)
            b, r, dt = rule.spec(d.bind(node.attr), taps)
            r_total += (r - 1) * b_total
            b_total *= b
            tail += dt
            if dt < 0:
                deframed = True
        elif rule.kind == "frame":
            if not tail:
                raise ValueError(
                    f"{node.name} ({node.op}) mixes the streamed axis; "
                    "insert an unfold/pfb first")
        streamed.add(node.name)
    if graph.outputs[0] not in streamed:
        raise ValueError("output does not depend on the stream input")
    return PipeStreamSpec(b_total, r_total, tail)


class ChunkedRunner:
    """Push chunks in, get output steps out; carries FIR/PFB/unfold
    overlap state so the concatenated output equals offline execution."""

    def __init__(self, graph: Graph, *,
                 options: plan_lib.CompileOptions | None = None,
                 step_buckets: bool = False):
        self.graph = graph
        self.spec = stream_spec(graph)
        options = options or plan_lib.CompileOptions()
        if options.mesh is not None or options.shard is not None:
            # normalize (int -> Mesh) once: every push re-enters
            # plan.compile, and steady-state pushes must stay pure
            # cache hits, not rebuild a Mesh per chunk
            m, _ = plan_lib._norm_mesh(options.mesh, options.shard)
            options = options.replace(mesh=m, shard=None)
        self.options = options
        # step_buckets: quantize each push to a power-of-two number of
        # output steps (carrying the remainder) so irregular push sizes
        # — the continuous-serving arrival pattern — compile a bounded
        # LADDER of plan shapes instead of one plan per distinct length.
        # finalize() (called by run()) drains the deferred remainder, so
        # concatenated output still equals offline exactly.
        self.step_buckets = bool(step_buckets)
        self.window_lens: set[int] = set()   # distinct compiled windows
        self._carry: np.ndarray | None = None

    @property
    def carry_len(self) -> int:
        return 0 if self._carry is None else self._carry.shape[-1]

    def push(self, chunk, *, final: bool = False) -> jax.Array | None:
        chunk = np.asarray(chunk)
        buf = (chunk if self._carry is None
               else np.concatenate([self._carry, chunk], axis=-1))
        r, b = self.spec.receptive, self.spec.block
        if buf.shape[-1] < r:
            self._carry = buf
            obs.gauge("stream.deferred_samples").set(self.carry_len)
            return None
        n_steps = (buf.shape[-1] - r) // b + 1
        if self.step_buckets and not final:
            n_steps = 1 << (n_steps.bit_length() - 1)  # largest 2^k <= n
        use = r + (n_steps - 1) * b
        window = buf[..., :use]
        self.window_lens.add(int(use))
        with obs.span("stream.push", cat="stream", graph=self.graph.name,
                      steps=int(n_steps), window=int(use)):
            p = plan_lib.compile(
                self.graph, {self.graph.inputs[0]: window.shape},
                options=self.options.replace(dtype=str(window.dtype)))
            out = p(jnp.asarray(window))
        self._carry = buf[..., n_steps * b:]
        # the deferred remainder a bucketed push left behind (plus the
        # ordinary sub-receptive-field overlap) — a streaming front door
        # watches this to see how far behind the quantizer is running
        obs.gauge("stream.deferred_samples").set(self.carry_len)
        return out

    def finalize(self) -> jax.Array | None:
        """Emit every whole output step still held in the carry.  Only a
        ``step_buckets`` runner ever defers whole steps (sub-bucket
        remainders); for others this is a no-op returning None."""
        if self._carry is None:
            return None
        return self.push(self._carry[..., :0], final=True)

    def run(self, x, chunk_len: int) -> jax.Array:
        """Stream ``x`` through in ``chunk_len`` pieces; concatenate
        (finalizing any bucket-deferred remainder)."""
        x = np.asarray(x)
        outs = []
        for i in range(0, x.shape[-1], chunk_len):
            o = self.push(x[..., i:i + chunk_len])
            if o is not None:
                outs.append(o)
        o = self.finalize()
        if o is not None:
            outs.append(o)
        if not outs:
            raise ValueError(
                f"signal length {x.shape[-1]} is shorter than the "
                f"pipeline's receptive field ({self.spec.receptive}): "
                "no output steps were produced")
        return jnp.concatenate(outs, axis=self.spec.concat_axis)


def stream_execute(graph: Graph, x, chunk_len: int, *,
                   options: plan_lib.CompileOptions | None = None):
    """One-shot helper: chunked execution of ``x`` (tests/benchmarks)."""
    return ChunkedRunner(graph, options=options).run(x, chunk_len)


__all__ = ["ChunkedRunner", "PipeStreamSpec", "stream_spec",
           "stream_execute"]
