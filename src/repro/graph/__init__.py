"""TINA pipeline-graph subsystem: composable op graphs compiled into
cached, autotuned, streamable plans.

  core/opdefs.py  (in core) the unified op registry every layer below
                  derives from — one OpDef per op
  graph.py      declarative graph IR (nodes = OpDef invocations)
  plan.py       planner: shape specialization, elementwise fusion,
                lowering selection, memoized jitted plans
  autotune.py   measurement-based lowering/config/fusion autotuner,
                on-disk cache
  stream.py     chunked streaming executor (offline-identical output)
  service.py    batched pipeline serving: continuous, overlapped
                batching over a ladder of pre-compiled bucket plans,
                with admission control, deadlines, and batch-failure
                recovery (retry / bisect / degrade)
  errors.py     typed serving failures (Overloaded, DeadlineExceeded,
                InvalidRequest)
  pipelines.py  built-in workloads (spectrogram, pfb_power,
                fir_decimate, stft_overlap_add, correlate,
                cascaded_channelizer)

Quick use::

    from repro import graph
    g = graph.build_spectrogram(window=128)
    plan = graph.compile(g, {"x": (16384,)})      # cached on 2nd call
    power = plan(x)
    chunked = graph.stream_execute(g, x, chunk_len=4096)  # == power
    sharded = graph.compile(g, {"x": (64, 16384)},
                            options=graph.CompileOptions(shard="batch"))
    # batch axis split across local devices; == unsharded numerics
"""
from repro.core.opdefs import OPDEFS, OpDef
from repro.graph import autotune, errors, pipelines, plan, service, stream
from repro.graph.errors import (DeadlineExceeded, InvalidRequest,
                                Overloaded, ServiceError)
from repro.graph.graph import Graph, Node
from repro.graph.pipelines import (BUILTINS, build_cascaded_channelizer,
                                   build_correlate, build_fir_decimate,
                                   build_pfb_power, build_spectrogram,
                                   build_stft_overlap_add)
from repro.graph.plan import (CompileOptions, Plan, cache_stats,
                              clear_cache, compile)
from repro.graph.service import (PipelineService, bucket_ladder,
                                 replay_batches)
from repro.graph.stream import ChunkedRunner, stream_execute, stream_spec

__all__ = [
    "Graph", "Node", "OpDef", "OPDEFS", "Plan", "CompileOptions",
    "compile", "cache_stats",
    "clear_cache", "ChunkedRunner", "stream_execute", "stream_spec",
    "PipelineService", "bucket_ladder", "replay_batches",
    "ServiceError", "Overloaded", "DeadlineExceeded", "InvalidRequest",
    "BUILTINS", "build_spectrogram", "build_pfb_power",
    "build_fir_decimate", "build_stft_overlap_add", "build_correlate",
    "build_cascaded_channelizer", "autotune", "pipelines", "plan",
    "service", "stream",
]
