"""Batched pipeline serving: queue requests, pack them into fixed-shape
batches, run one cached plan per batch.

Fixed shapes are the whole point: every batch is padded to a
pre-compiled shape, so after warm-up every execution is a plan-cache
hit (no retrace, no recompile) — the serving front door the ROADMAP's
production-scale north star needs.  Two batching policies:

``batching="fixed"`` (the historical default) — every batch pads to
exactly ``(batch_size, signal_len)`` through ONE plan.  The batcher
waits up to ``max_wait_ms`` per request to fill a batch before
dispatching a partial (padded) one, so light traffic pays the wait
deadline on every batch and pads most of the slots.

``batching="continuous"`` — a continuous batcher: the scheduler forms
the **largest admissible batch the moment the executor goes idle**
(bounded by the tenant's ``batch_size``; an idle device never waits for
a full batch), and executes it against a small ladder of pre-compiled
bucket plans (1/2/4/…/batch_size — each a cached ``graph.compile``,
reusing the plan cache and per-shape autotuned configs), padding only
up to the next bucket.  Requests that arrive while the device is busy
coalesce in the queue for at most one batch's execution time.  Futures
complete per-request, so one slow producer can't stall unrelated
submitters.

**Overlapped (double-buffered) scheduling** — ``overlap=True`` (the
default under ``batching="continuous"``): while batch N runs on the
device, the batcher thread forms, pads, and (under mesh) shards batch
N+1 on the host and *dispatches it* — jax's async dispatch returns as
soon as the work is enqueued — before blocking on batch N's result,
so the device's queue is not empty while the host packs.  Input buffers
are donated to the computation (``CompileOptions.donate``) on backends
that honor donation (not CPU, where it is a silent no-op), so batch
N's input storage is recycled instead of held across the overlap.
Failures fall back to the synchronous recovery path (retry → degrade →
bisect) exactly as in blocking mode.

**Multi-tenant serving** — one service hosts multiple pipelines on a
shared device pool.  The constructor's graph becomes the ``"default"``
tenant; :meth:`PipelineService.add_tenant` compiles further pipelines
(each with its own signal length, bucket ladder, and
:class:`~repro.graph.plan.CompileOptions` — identical graphs/shapes
share compiled plans through the process-wide plan cache).  ``submit``
routes by ``tenant=`` name, and every request carries a **priority
class**: ``submit(x, priority="rt")`` jumps the queue ahead of
``priority="batch"`` work (strict priority: higher classes preempt
*queue order*, never a running batch; deadlines are the starvation
backstop for ``"batch"`` traffic under sustained ``"rt"`` load).  A
batch is always single-tenant — the head-of-queue request picks the
tenant, then same-tenant requests (highest priority first) fill the
bucket.  Replay verification stays bit-for-bit **per tenant**
(per-tenant batch logs; :func:`replay_batches` checks every tenant or
one by name).

Three drive modes (orthogonal to the batching policy):
  * synchronous — ``submit()`` then ``flush()`` (deterministic, tests)
  * background  — ``start()`` spawns a batcher thread that drains the
    queue with the configured policy.
  * asyncio     — ``await svc.submit_async(x)`` awaits the request's
    result on the running event loop (the same futures, bridged via
    ``asyncio.wrap_future``); ``async with PipelineService(...)``
    starts/closes the service without blocking the loop.

``submit`` returns a ``concurrent.futures.Future`` resolving to that
request's output slice (a numpy array) **or a typed exception** — the
fault-tolerance contract is that every admitted future resolves, with
a result or with an error that names what went wrong
(:mod:`repro.graph.errors`).

Fault tolerance (every behavior testable via :mod:`repro.obs.faults` —
no monkeypatching):

  * **Admission** — ``queue_limit=`` bounds the queue; ``on_full``
    picks the policy when it's at the limit: ``"block"`` (submit waits
    for space, honoring the request's deadline), ``"shed"`` (the
    returned future fails immediately with :class:`Overloaded` — the
    load-shedding a saturated replica needs), or ``"raise"``
    (``submit`` raises :class:`Overloaded`).
  * **Deadlines** — ``submit(x, deadline_ms=...)`` (or the service-wide
    ``deadline_ms=``) stamps an expiry; requests still queued past it
    fail with :class:`DeadlineExceeded` *before* consuming a device
    slot (swept at dispatch time and while blocked at admission).
    Requests dispatched in time always get their result.
  * **Validation** — ``validate="strict"`` rejects non-finite payloads
    at submit: the returned future fails with :class:`InvalidRequest`
    and the poison never reaches a batch.
  * **Retry / poison isolation** — a failed batch retries with capped
    exponential backoff (``max_retries``, ``retry_backoff_ms``);
    injected faults marked persistent skip the pointless retries.  A
    batch that still fails is **bisected**: halves re-run through their
    own bucket plans, recursively, so healthy requests get their
    results and only the poisoned row's future receives the error
    (quarantine counter + ``service.quarantine`` instant per
    isolation).
  * **Degradation** — a bucket whose plan keeps failing
    (``degrade_after`` consecutive post-retry failures) is recompiled
    once with ``lowering="reference"`` and the downgrade is recorded on
    the tenant's ``downgrades`` (the runtime extension of the
    compile-time ``Plan.downgrades`` contract) — predictable slow beats
    unpredictable dead.

Telemetry: ``service.stats()`` returns one consistent locked snapshot
(a plain dict — the deprecated ``service.stats`` attribute access was
removed; call it) — request/batch/padding counters, per-priority
admission counts, per-tenant breakdowns, the fault-tolerance counters
(``shed`` / ``expired`` / ``retries`` / ``quarantined`` / ``degraded``
/ ``invalid``), queue depth, fill ratio, and per-phase request-latency
histograms (``queued`` and ``total`` per request; ``pad``, ``stage``,
``wait`` and ``fetch`` per batch; ``fetch_shard`` per shard of a batch
on a mesh); ``shards`` holds, per device id of a mesh, the ``batches``
staged there and their request ``rows`` and ``pad_rows``; ``gather``
counts the mesh batches delivered as ``views`` of their shards' host
blocks and those ``assembled`` by ``np.asarray``.  Every batch,
in every scheduler path, runs the same phases as :mod:`repro.obs`
spans, each carrying the batch's sequence number (``batch=<n>``, so an
overlapped launch of N+1 and the completion of N can be paired):

  * ``service.dispatch`` ⊃ ``service.pack`` (pad to the bucket),
    ``service.stage`` (host to device), ``service.enqueue`` (the
    asynchronous plan call);
  * ``service.complete`` ⊃ ``service.wait`` (blocked on the device),
    ``service.fetch`` (device to host and host layout),
    ``service.deliver`` (books and futures);
  * on a mesh, ``service.fetch`` ⊃ one ``service.fetch_shard`` per
    device (``device=<id>``: the wait for that shard's copy to the
    host, timed in the ``fetch_shard`` histogram); the rows delivered
    are views of those host blocks, so nothing is copied after the
    waits;
  * ``service.idle``: the batcher blocked on an empty queue with
    nothing in flight;
  * ``python.gc``: every garbage collection (:func:`repro.obs.trace_gc`,
    installed by :meth:`PipelineService.start`).

The spans reach the Chrome trace with ``TINA_TELEMETRY=on`` and the
``jax.profiler`` trace, on the device's clock, while a profiler session
records.  The recovery machinery adds ``service.retry`` /
``service.bisect`` spans plus ``service.quarantine`` /
``service.degrade`` instants.

Sharded mode: ``CompileOptions(mesh=...)`` (a Mesh or device count)
compiles the serving plan(s) with the batch axis placed across the
mesh.  Every bucket in the continuous ladder is restricted to
shard-divisible sizes — the ladder starts at the shard count instead
of 1, so each bucket splits evenly over the devices.  The overlapped
scheduler shards batch N+1's input onto the mesh while N runs.

Lifecycle (defined order: ``start`` -> ``submit``/... -> ``close``):
``flush()`` on a *started* service raises — the batcher thread is the
queue's only consumer while it runs, and a second drain would split one
logical batch across two consumers.  ``close()`` stops the thread
(verifying it actually exited before draining the remainder — the
in-flight overlapped batch is completed first, never abandoned), wakes
any submitter blocked at admission (they raise ``RuntimeError``), and
marks the service closed: ``submit()``/``start()`` afterwards raise
RuntimeError instead of enqueuing requests no consumer will ever serve.
These invariants hold under both batching policies, with and without
overlap, and under fault injection — the batcher thread survives every
failure mode above.
"""
from __future__ import annotations

import asyncio
import bisect
import itertools
import threading
import time
import warnings
from collections import deque
from concurrent.futures import Future

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.graph import plan as plan_lib
from repro.graph.errors import (DeadlineExceeded, InvalidRequest,
                                Overloaded)
from repro.graph.graph import Graph
from repro.obs import faults

#: Priority classes, highest first: ``"rt"`` requests preempt queue
#: order over ``"batch"`` requests (never a running batch).
PRIORITIES = ("rt", "batch")

# _get() outcomes that aren't requests: nothing arrived within the
# timeout / the service is stopping and the queue is fully drained
_EMPTY = object()
_STOPPED = object()
# reused host staging buffers kept per (tenant, bucket): one whose batch
# is in flight on the device while the overlapped loop packs the next
_KEEP_BUFFERS = 2


def bucket_ladder(max_batch: int, shards: int = 1) -> tuple[int, ...]:
    """The pre-compiled batch sizes of a continuous batcher: shard-count,
    doubling up to ``max_batch`` (which is always the top rung).  With
    ``shards=1`` this is the classic 1/2/4/…/max ladder; sharded
    services start at ``shards`` so every bucket splits evenly over the
    mesh (``max_batch % shards == 0`` is validated by plan compilation).
    """
    if max_batch < 1:
        raise ValueError(f"max_batch must be >= 1, got {max_batch}")
    if shards < 1 or shards > max_batch:
        raise ValueError(
            f"shard count {shards} not in [1, max_batch={max_batch}]")
    sizes = []
    b = shards
    while b < max_batch:
        sizes.append(b)
        b *= 2
    sizes.append(max_batch)
    return tuple(sizes)


def _stage(plan, batch: np.ndarray):
    """Host batch -> device.  A sharded plan's batch goes straight into
    its batch sharding in one ``device_put``, which the runtime splits
    into one block of rows per device; staging it whole on the default
    device first would make the jitted call reshard it."""
    if getattr(plan, "input_shardings", None):
        return plan.shard_inputs(batch)
    return jnp.asarray(batch)


class Tenant:
    """One hosted pipeline: its graph, signal length, bucket ladder,
    compiled plans, packing dtype, replay log, and runtime-degradation
    books.  Built by :class:`PipelineService` (the constructor graph
    becomes the ``"default"`` tenant; :meth:`PipelineService.add_tenant`
    adds more) — identical (graph, shape, options) tenants share
    compiled plans through the process-wide plan cache."""

    def __init__(self, name: str, graph: Graph, signal_len: int, *,
                 batch_size: int, batching: str,
                 options: plan_lib.CompileOptions,
                 record_batches: bool):
        if len(graph.inputs) != 1:
            raise ValueError("serving supports single-input graphs")
        if len(graph.outputs) != 1:
            # a tuple-returning plan would make out[i] index outputs,
            # not batch rows — reject instead of corrupting responses
            raise ValueError("serving supports single-output graphs")
        self.name = name
        self.graph = graph
        self.signal_len = int(signal_len)
        self.batch_size = int(batch_size)
        self.dtype = np.dtype(options.dtype)
        # normalize the mesh ONCE: every bucket plan must share the same
        # Mesh object, and the ladder needs the shard count before any
        # plan compiles
        mesh, batch_axis = plan_lib._norm_mesh(options.mesh, options.shard)
        self.options = options.replace(mesh=mesh, shard=None)
        self.mesh = mesh
        shards = 1 if mesh is None else int(mesh.shape[batch_axis])
        if batching == "continuous":
            self.buckets = bucket_ladder(self.batch_size, shards)
        else:
            self.buckets = (self.batch_size,)
        # compile every bucket's serving plan up front: requests never
        # pay trace cost — and with lowering="auto" (or
        # block_configs="auto") each bucket runs the autotuner's tuned
        # kernels for ITS shape.  compile validates mesh divisibility on
        # the (bucket, signal_len) spec, so an indivisible batch_size
        # fails here, not at runtime
        self.plans = {
            b: plan_lib.compile(
                graph, {graph.inputs[0]: (b, self.signal_len)},
                options=self.options)
            for b in self.buckets}
        self.plan = self.plans[self.batch_size]
        # optional packing trace for tests/benchmarks: every batch that
        # DELIVERED results appends (bucket, [(request, future)]) so a
        # replay can verify delivered responses bit-for-bit against the
        # exact packing that was served (failed dispatches deliver
        # exceptions, not rows, and are not packings to replay)
        self.batch_log: list[tuple[int, list[tuple[np.ndarray, Future]]]] \
            | None = [] if record_batches else None
        # runtime degradation books (consumer-thread-only mutation):
        # consecutive post-retry failures per bucket, the recorded
        # runtime downgrades (bucket -> requested lowering), and the
        # fault-point tag each bucket's device_run checks carry (its
        # current lowering request; "reference" once degraded)
        self._bucket_fails: dict[int, int] = {}
        self.downgrades: dict[int, str] = {}
        tag = (options.lowering if isinstance(options.lowering, str)
               else "per-node")
        self._tags: dict[int, str] = {b: tag for b in self.buckets}
        # per-tenant counters, mutated under the service's stats lock
        # and surfaced as stats()["tenants"][name]
        self.counts: dict = {"requests": 0, "batches": 0,
                             "padded_slots": 0}
        # free host staging buffers per bucket (consumer-thread-only):
        # (array, rows its last packing wrote; the rows below are zero)
        self._staging: dict[int, list[tuple[np.ndarray, int]]] = {}
        if batching == "continuous":
            self.counts["bucket_batches"] = {b: 0 for b in self.buckets}

    def bucket_for(self, n: int) -> int:
        """Smallest pre-compiled bucket admitting ``n`` requests."""
        return self.buckets[bisect.bisect_left(self.buckets, n)]


class _Inflight:
    """One dispatched-but-not-retired batch: the device is (or will be)
    computing ``out`` while the batcher forms the next batch;
    :meth:`PipelineService._complete` blocks on it and delivers.  ``seq``
    is the batch's sequence number; ``t_dispatch`` is its host-clock
    stamp, ``pad_ms`` and ``stage_ms`` the times of its pack and stage
    phases; ``batch`` is the host staging buffer it was packed into."""

    __slots__ = ("tenant", "bucket", "items", "out", "batch", "seq",
                 "t_dispatch", "pad_ms", "stage_ms")

    def __init__(self, tenant, bucket, items, out, batch, seq, t_dispatch,
                 pad_ms, stage_ms):
        self.tenant = tenant
        self.bucket = bucket
        self.items = items
        self.out = out
        self.batch = batch
        self.seq = seq
        self.t_dispatch = t_dispatch
        self.pad_ms = pad_ms
        self.stage_ms = stage_ms

    def ready(self) -> bool:
        try:
            return bool(self.out.is_ready())
        except AttributeError:   # non-jax out (monkeypatched plan)
            return True


class PipelineService:
    def __init__(self, graph: Graph, signal_len: int, *,
                 batch_size: int = 8, batching: str = "fixed",
                 dtype=None, options: plan_lib.CompileOptions | None = None,
                 overlap: bool | None = None,
                 max_wait_ms: float = 2.0,
                 close_timeout: float = 30.0, record_batches: bool = False,
                 queue_limit: int | None = None, on_full: str = "block",
                 deadline_ms: float | None = None, validate: str = "off",
                 max_retries: int = 2, retry_backoff_ms: float = 1.0,
                 retry_backoff_max_ms: float = 100.0,
                 degrade_after: int = 3, **compile_kwargs):
        if batching not in ("fixed", "continuous"):
            raise ValueError(
                f"batching={batching!r}: expected 'fixed' or 'continuous'")
        if on_full not in ("block", "shed", "raise"):
            raise ValueError(
                f"on_full={on_full!r}: expected 'block', 'shed', or "
                "'raise'")
        if validate not in ("strict", "off"):
            raise ValueError(
                f"validate={validate!r}: expected 'strict' or 'off'")
        if queue_limit is not None and queue_limit < 1:
            raise ValueError(
                f"queue_limit={queue_limit}: expected None (unbounded) "
                "or a positive depth")
        if deadline_ms is not None and deadline_ms < 0:
            raise ValueError(f"deadline_ms={deadline_ms}: must be >= 0")
        if max_retries < 0:
            raise ValueError(f"max_retries={max_retries}: must be >= 0")
        faults.load()   # strict TINA_FAULTS validation: fail the launch,
        # not the Nth request, on a typo'd chaos spec
        self.batching = batching
        # overlap defaults on for the continuous batcher (where the
        # device-idle gap is the cost being removed); fixed mode keeps
        # the historical blocking loop unless asked
        self.overlap = (batching == "continuous") if overlap is None \
            else bool(overlap)
        self.max_wait_ms = max_wait_ms
        self.close_timeout = close_timeout
        self.queue_limit = queue_limit
        self.on_full = on_full
        self.deadline_ms = deadline_ms
        self.validate = validate
        self.max_retries = int(max_retries)
        self.retry_backoff_ms = float(retry_backoff_ms)
        self.retry_backoff_max_ms = float(retry_backoff_max_ms)
        self.degrade_after = int(degrade_after)
        self._record_batches = bool(record_batches)
        # the priority queue: one FIFO per class, popped highest-first;
        # single-tenant batches are gathered by scanning for the head
        # request's tenant
        self._pending: dict[str, deque] = {p: deque() for p in PRIORITIES}
        self._thread: threading.Thread | None = None
        self._closed = False
        self._stopping = False
        self._drain_lock = threading.Lock()  # the single-consumer claim
        # makes check-closed + enqueue atomic against close(): without
        # it a submit racing close can enqueue after the final drain,
        # recreating the hung-future bug the flag exists to prevent
        self._lifecycle = threading.Lock()
        # two Conditions on the one lifecycle lock: admission waits
        # (on_full="block") ride _space (the consumer notifies per
        # dequeue), the batcher's wait-for-work rides _avail (submit
        # notifies per enqueue); close() wakes both sides so nothing
        # outlives the service
        self._space = threading.Condition(self._lifecycle)
        self._avail = threading.Condition(self._lifecycle)
        self._depth = 0              # admitted-but-undequeued requests
        # stats live behind their own lock and are only read through
        # consistent snapshots (``stats()``): the scheduler thread
        # mutates them while callers read
        self._stats_lock = threading.Lock()
        self._stats = {"requests": 0, "batches": 0, "padded_slots": 0,
                       "failed_batches": 0, "shed": 0, "expired": 0,
                       "retries": 0, "quarantined": 0, "degraded": 0,
                       "invalid": 0,
                       "priorities": {p: 0 for p in PRIORITIES},
                       "pack_buffers": {"reused": 0, "allocated": 0},
                       "gather": {"views": 0, "assembled": 0},
                       "shards": {}}
        # request-latency attribution (milliseconds): total is
        # submit -> result and queued is submit -> dispatch (per
        # request); per batch, pad is packing, stage the host-to-device
        # transfer, wait the host blocked on the device, fetch the pull
        # back to the host; fetch_shard is, on a mesh, the wait for one
        # shard's copy to the host within a fetch.  Service-private histograms: two services must not
        # mix their latency distributions in a shared registry.
        self._lat = {k: obs.Histogram(f"service.latency.{k}", unit="ms")
                     for k in ("total", "queued", "pad", "stage", "wait",
                               "fetch", "fetch_shard")}
        self._seq = itertools.count()    # batch sequence numbers
        self.tenants: dict[str, Tenant] = {}
        self._default = self._add_tenant(
            "default", graph, signal_len, batch_size=int(batch_size),
            options=self._resolve_options(options, dtype, compile_kwargs),
            record_batches=self._record_batches)
        if batching == "continuous":
            self._stats["bucket_batches"] = {b: 0
                                             for b in self._default.buckets}

    # -- options / tenants --------------------------------------------------
    @staticmethod
    def _resolve_options(options, dtype, compile_kwargs
                         ) -> plan_lib.CompileOptions:
        """One CompileOptions from whichever spelling the caller used:
        ``options=`` (preferred), or the historical loose kwargs
        (``lowering=``, ``precision=``, ``mesh=``, ... plus ``dtype=``)
        folded into one — but not both, which would give the same knob
        two sources of truth."""
        if compile_kwargs:
            if options is not None:
                raise TypeError(
                    "PipelineService got both options= and legacy compile "
                    f"keyword argument(s) {sorted(compile_kwargs)}: fold "
                    "everything into the CompileOptions")
            return plan_lib.CompileOptions(
                dtype=str(dtype) if dtype is not None else "float32",
                **compile_kwargs)
        if options is None:
            return plan_lib.CompileOptions(
                dtype=str(dtype) if dtype is not None else "float32")
        if dtype is not None and str(dtype) != options.dtype:
            raise TypeError(
                f"dtype={dtype!r} conflicts with options.dtype="
                f"{options.dtype!r}: set it on the CompileOptions")
        return options

    def _finalize_options(self, options: plan_lib.CompileOptions
                          ) -> plan_lib.CompileOptions:
        """Overlap-mode donation: staged batches are throwaway device
        arrays, so donate them to the computation — but only on
        backends that honor donation (CPU ignores it with a warning,
        which would fire once per compiled bucket)."""
        if self.overlap and not options.donate \
                and jax.default_backend() != "cpu":
            options = options.replace(donate=True)
        return options

    def add_tenant(self, name: str, graph: Graph, signal_len: int, *,
                   batch_size: int | None = None, dtype=None,
                   options: plan_lib.CompileOptions | None = None,
                   record_batches: bool | None = None,
                   **compile_kwargs) -> Tenant:
        """Host another pipeline on this service's device pool and
        scheduler.  The tenant gets its own signal length, bucket
        ladder, compiled plans, replay log, and (optionally) its own
        :class:`~repro.graph.plan.CompileOptions` — defaults inherit
        the service's.  Returns the :class:`Tenant`; route requests to
        it with ``submit(x, tenant=name)``."""
        with self._lifecycle:
            if self._closed:
                raise RuntimeError("service closed")
        if options is None and not compile_kwargs and dtype is None:
            options = self._default.options
        return self._add_tenant(
            name, graph, signal_len,
            batch_size=(self._default.batch_size if batch_size is None
                        else int(batch_size)),
            options=self._resolve_options(options, dtype, compile_kwargs),
            record_batches=(self._record_batches if record_batches is None
                            else bool(record_batches)))

    def _add_tenant(self, name, graph, signal_len, *, batch_size,
                    options, record_batches) -> Tenant:
        if name in self.tenants:
            raise ValueError(f"tenant {name!r} already exists")
        t = Tenant(name, graph, signal_len, batch_size=batch_size,
                   batching=self.batching,
                   options=self._finalize_options(options),
                   record_batches=record_batches)
        self.tenants[name] = t
        if "bucket_batches" in self._stats:
            with self._stats_lock:
                for b in t.buckets:
                    self._stats["bucket_batches"].setdefault(b, 0)
        return t

    def _tenant(self, tenant) -> Tenant:
        if tenant is None:
            return self._default
        if isinstance(tenant, Tenant):
            return tenant
        try:
            return self.tenants[tenant]
        except KeyError:
            raise KeyError(f"unknown tenant {tenant!r}; have "
                           f"{sorted(self.tenants)}") from None

    # -- default-tenant delegation (the historical single-pipeline API) -----
    @property
    def graph(self) -> Graph:
        return self._default.graph

    @property
    def signal_len(self) -> int:
        return self._default.signal_len

    @property
    def dtype(self) -> np.dtype:
        return self._default.dtype

    @property
    def batch_size(self) -> int:
        return self._default.batch_size

    @property
    def buckets(self) -> tuple[int, ...]:
        return self._default.buckets

    @property
    def plans(self) -> dict:
        return self._default.plans

    @plans.setter
    def plans(self, value: dict) -> None:
        self._default.plans = value

    @property
    def plan(self):
        return self._default.plan

    @plan.setter
    def plan(self, value) -> None:
        self._default.plan = value

    @property
    def batch_log(self):
        return self._default.batch_log

    @property
    def downgrades(self) -> dict:
        return self._default.downgrades

    # -- request side -------------------------------------------------------
    def submit(self, x, *, deadline_ms: float | None = None,
               priority: str = "batch", tenant=None) -> Future:
        """Enqueue one request; returns a Future resolving to its output
        row or to a typed exception (:mod:`repro.graph.errors`).

        ``priority`` (``"rt"`` or ``"batch"``, default ``"batch"``)
        picks the queue class: ``"rt"`` requests are dequeued before any
        ``"batch"`` request whenever the scheduler forms a batch —
        strict priority over queue order, never preemption of a running
        batch.  ``tenant=`` routes to a hosted pipeline by name (or
        :class:`Tenant`); default is the constructor's pipeline.

        ``deadline_ms`` (default: the service-wide ``deadline_ms``)
        bounds how long the request may wait *before dispatch*: expired
        requests fail with :class:`DeadlineExceeded` without consuming a
        device slot.  With ``validate="strict"`` a non-finite payload
        fails the returned future with :class:`InvalidRequest` instead
        of entering a batch.  A full bounded queue blocks, sheds (the
        future fails with :class:`Overloaded` immediately), or raises
        per ``on_full``.
        """
        if priority not in PRIORITIES:
            raise ValueError(f"priority={priority!r}: expected one of "
                             f"{PRIORITIES}")
        t = self._tenant(tenant)
        x = np.asarray(x, t.dtype)
        if x.shape != (t.signal_len,):
            raise ValueError(
                f"request shape {x.shape} != ({t.signal_len},) — "
                "fixed-shape serving; open one service (or tenant) per "
                "signal length")
        fut: Future = Future()
        fut._tina_submit_t = time.perf_counter()   # queued-phase stamp
        if self.validate == "strict" and not np.isfinite(x).all():
            with self._stats_lock:
                self._stats["invalid"] += 1
            fut.set_exception(InvalidRequest(
                "payload contains non-finite sample(s) "
                "(validate='strict'): rejected at submit, never batched"))
            return fut
        dl = self.deadline_ms if deadline_ms is None else deadline_ms
        fut._tina_deadline = (fut._tina_submit_t + dl / 1e3
                              if dl is not None else None)
        with self._space:   # the lifecycle lock, as a Condition
            if self._closed:
                # the consumer is gone (thread joined, final flush ran):
                # enqueuing would leave the caller hanging in fut.result()
                raise RuntimeError("service closed")
            if self.queue_limit is not None \
                    and self._depth >= self.queue_limit:
                if self.on_full == "block":
                    # wait for space, honoring the deadline; close()
                    # notifies so no submitter outlives the service
                    while not self._closed \
                            and self._depth >= self.queue_limit:
                        wait = 0.05
                        if fut._tina_deadline is not None:
                            left = fut._tina_deadline - time.perf_counter()
                            if left <= 0:
                                self._expire(fut)
                                return fut
                            wait = min(wait, left)
                        self._space.wait(wait)
                    if self._closed:
                        raise RuntimeError("service closed")
                else:
                    with self._stats_lock:
                        self._stats["shed"] += 1
                    err = Overloaded(
                        f"queue full ({self.queue_limit} deep, "
                        f"on_full={self.on_full!r}): request shed")
                    if self.on_full == "raise":
                        raise err
                    fut.set_exception(err)       # on_full="shed"
                    return fut
            with self._stats_lock:
                self._stats["requests"] += 1
                self._stats["priorities"][priority] += 1
                t.counts["requests"] += 1
            self._depth += 1
            self._pending[priority].append((x, fut, t))
            self._avail.notify()
        return fut

    async def submit_async(self, x, *, deadline_ms: float | None = None,
                           priority: str = "batch", tenant=None):
        """``await`` one request's result on the running event loop —
        the asyncio-native front of the same machinery: the request
        rides the identical priority queue and resolves the identical
        future (bridged via ``asyncio.wrap_future``), so sync and async
        clients share one scheduler and one set of guarantees.  Typed
        failures (:mod:`repro.graph.errors`) raise out of the await.
        When admission can block (``queue_limit`` + ``on_full="block"``)
        the enqueue itself runs in the default executor so a full queue
        never stalls the event loop."""
        if self.queue_limit is not None and self.on_full == "block":
            fut = await asyncio.get_running_loop().run_in_executor(
                None, lambda: self.submit(x, deadline_ms=deadline_ms,
                                          priority=priority, tenant=tenant))
        else:
            fut = self.submit(x, deadline_ms=deadline_ms,
                              priority=priority, tenant=tenant)
        return await asyncio.wrap_future(fut)

    # -- stats --------------------------------------------------------------
    def stats(self) -> dict:
        """One consistent snapshot of every stat (all keys copied under
        the stats lock) plus the derived observability surface: queue
        depth, fill ratio, per-tenant breakdowns, and the
        phase-attributed latency summaries.  (This is a plain method —
        the PR-6-deprecated ``service.stats`` attribute access is gone.)
        """
        with self._stats_lock:
            d = {k: (dict(v) if isinstance(v, dict) else v)
                 for k, v in self._stats.items()}
            d["shards"] = {dev: dict(c)
                           for dev, c in self._stats["shards"].items()}
            d["tenants"] = {
                name: {k: (dict(v) if isinstance(v, dict) else v)
                       for k, v in t.counts.items()}
                for name, t in self.tenants.items()}
        d["queue_depth"] = self._depth
        d["fill_ratio"] = d["requests"] / max(
            1, d["requests"] + d["padded_slots"])
        d["latency_ms"] = {k: h.summary() for k, h in self._lat.items()}
        return d

    # -- deadlines ----------------------------------------------------------
    def _expire(self, fut: Future) -> None:
        with self._stats_lock:
            self._stats["expired"] += 1
        fut.set_exception(DeadlineExceeded(
            "deadline expired before a device dispatch picked the "
            "request up"))

    def _sweep_expired(self, items: list) -> list:
        """Fail every expired request and return the live remainder —
        called at dispatch time, *before* packing, so an expired request
        never wastes a device slot."""
        now = time.perf_counter()
        live = []
        for it in items:
            dl = getattr(it[1], "_tina_deadline", None)
            if dl is not None and now > dl:
                self._expire(it[1])
            else:
                live.append(it)
        return live

    # -- queue --------------------------------------------------------------
    def _pop_locked(self, tenant: Tenant | None = None):
        """Pop the highest-priority pending request (optionally only
        ``tenant``'s), or None.  Caller holds the lifecycle lock."""
        req = None
        for p in PRIORITIES:
            dq = self._pending[p]
            if tenant is None:
                if dq:
                    req = dq.popleft()
                    break
            else:
                # index-based removal: tuple == would compare the numpy
                # payloads elementwise
                for i, r in enumerate(dq):
                    if r[2] is tenant:
                        del dq[i]
                        req = r
                        break
                if req is not None:
                    break
        if req is None:
            return None
        self._depth -= 1
        if self.queue_limit is not None:
            self._space.notify()
        return req

    def _get(self, timeout: float | None, tenant: Tenant | None = None):
        """Dequeue one request, blocking up to ``timeout`` seconds
        (None = forever).  Returns the request, ``_EMPTY`` on timeout,
        or ``_STOPPED`` once the service is stopping and nothing is
        pending (everything admitted before close() is drained first —
        the close contract)."""
        deadline = (None if timeout is None
                    else time.perf_counter() + timeout)
        with self._avail:
            while True:
                req = self._pop_locked(tenant)
                if req is not None:
                    return req
                if self._stopping:
                    return _STOPPED
                if deadline is None:
                    self._avail.wait()
                else:
                    left = deadline - time.perf_counter()
                    if left <= 0:
                        return _EMPTY
                    self._avail.wait(left)

    def _gather(self, first, fill_wait: float | None) -> tuple[Tenant, list]:
        """Form one single-tenant batch seeded by ``first``: same-tenant
        requests (highest priority first) fill the bucket.  ``fill_wait``
        is fixed mode's per-request linger; continuous mode takes
        exactly what has queued."""
        tenant = first[2]
        items = [first]
        while len(items) < tenant.batch_size:
            nxt = self._get(fill_wait if fill_wait is not None else 0,
                            tenant)
            if nxt is _EMPTY or nxt is _STOPPED:
                break
            items.append(nxt)
        return tenant, items

    # -- batch execution ----------------------------------------------------
    def _plan_for(self, tenant: Tenant, n: int):
        """(bucket, plan) serving an ``n``-request batch under the
        current policy (fixed mode always pads to the one batch shape;
        ``tenant.plan`` stays monkeypatchable there)."""
        if self.batching == "continuous":
            b = tenant.bucket_for(n)
            return b, tenant.plans[b]
        return tenant.batch_size, tenant.plan

    def _pack(self, tenant: Tenant, bucket: int, items: list,
              out: np.ndarray | None = None, stale: int = 0) -> np.ndarray:
        """The one definition of batch packing: requests fill the first
        rows, zero padding fills the rest.  ``out`` is a staging buffer
        to pack into, whose last packing wrote its first ``stale`` rows
        (the rows below are zero); without one a zeroed batch is
        allocated.  ``replay_batches`` packs through this too, so the
        replay checks the packing actually served."""
        if out is None:
            out = np.zeros((bucket, tenant.signal_len), tenant.dtype)
        else:
            out[len(items):stale] = 0
        for i, it in enumerate(items):
            out[i] = it[0]
        return out

    def _take_buffer(self, tenant: Tenant, bucket: int):
        """A free staging buffer of ``bucket`` and its written rows, or
        (None, 0) for ``_pack`` to allocate one; counted either way."""
        free = tenant._staging.get(bucket)
        took = free.pop() if free else (None, 0)
        with self._stats_lock:
            self._stats["pack_buffers"][
                "allocated" if took[0] is None else "reused"] += 1
        return took

    def _release_buffer(self, inf: _Inflight, out) -> None:
        """Return a completed batch's staging buffer to its free list.
        Its output is ready, so the device has consumed it whatever the
        backend's host-buffer semantics; a buffer that the delivered
        rows still view is left to them instead.  A mesh result's rows
        are a list of views of its shards' blocks: each row is tested,
        never the list stacked into one array."""
        free = inf.tenant._staging.setdefault(inf.bucket, [])
        rows = out if isinstance(out, list) else (out,)
        if len(free) < _KEEP_BUFFERS and not any(
                np.may_share_memory(r, inf.batch) for r in rows):
            free.append((inf.batch, len(inf.items)))

    def _deliver(self, tenant: Tenant, bucket: int, items: list,
                 out, t_dispatch: float) -> None:
        """Post-device bookkeeping of one successful batch: log the
        packing, bump the books, record request latencies, resolve
        futures."""
        n = len(items)
        if tenant.batch_log is not None:
            tenant.batch_log.append((bucket,
                                     [(it[0], it[1]) for it in items]))
        with self._stats_lock:
            self._stats["batches"] += 1
            self._stats["padded_slots"] += bucket - n
            tenant.counts["batches"] += 1
            tenant.counts["padded_slots"] += bucket - n
            if "bucket_batches" in self._stats:
                self._stats["bucket_batches"][bucket] = \
                    self._stats["bucket_batches"].get(bucket, 0) + 1
            if "bucket_batches" in tenant.counts:
                tenant.counts["bucket_batches"][bucket] += 1
        for i, it in enumerate(items):
            fut = it[1]
            t_sub = getattr(fut, "_tina_submit_t", None)
            if t_sub is not None:
                self._lat["queued"].record((t_dispatch - t_sub) * 1e3)
                self._lat["total"].record(
                    (time.perf_counter() - t_sub) * 1e3)
            fut.set_result(out[i])

    def _execute_once(self, tenant: Tenant, bucket: int, plan,
                      items: list) -> None:
        """One synchronous dispatch attempt: launch, then complete.
        Raises on failure (the recovery machinery in ``_dispatch``
        decides what happens next); on success the packing is logged and
        every future resolves.  Used by flush/fixed/retry/bisection
        paths; the overlapped loop runs the same two halves with the
        next batch's launch between them."""
        self._complete(self._launch(tenant, bucket, plan, items))

    def _launch(self, tenant: Tenant, bucket: int, plan,
                items: list) -> _Inflight:
        """Front half of a batch: pack, stage (sharded on a mesh) and
        *dispatch* without blocking on the result — jax's async dispatch
        returns once the work is enqueued, so the overlapped loop moves
        on to forming the next batch while the device computes this
        one."""
        seq = next(self._seq)
        t_dispatch = time.perf_counter()
        with obs.span("service.dispatch", cat="serve", batch=seq,
                      bucket=bucket, n=len(items), tenant=tenant.name):
            with obs.span("service.pack", cat="serve", batch=seq):
                batch = self._pack(tenant, bucket, items,
                                   *self._take_buffer(tenant, bucket))
            t_packed = time.perf_counter()
            faults.check("device_run", payload=batch,
                         tag=tenant._tags.get(bucket))
            t_stage = time.perf_counter()
            with obs.span("service.stage", cat="serve", batch=seq):
                x = _stage(plan, batch)
            t_staged = time.perf_counter()
            if tenant.mesh is not None:
                self._count_shards(plan.shard_rows(len(batch)), len(items))
            with obs.span("service.enqueue", cat="serve", batch=seq):
                out = plan(x)            # async: enqueued, not computed
        return _Inflight(tenant, bucket, items, out, batch, seq, t_dispatch,
                         (t_packed - t_dispatch) * 1e3,
                         (t_staged - t_stage) * 1e3)

    def _complete(self, inf: _Inflight) -> None:
        """Back half of a batch: block until the device is done, pull
        the result back to the host, record the batch's phase times,
        deliver, and free the batch's staging buffer for reuse.  Device
        errors surface in the wait or the pull-back; a batch that raises
        drops its buffer."""
        seq = inf.seq
        with obs.span("service.complete", cat="serve", batch=seq,
                      bucket=inf.bucket, tenant=inf.tenant.name):
            t_wait = time.perf_counter()
            with obs.span("service.wait", cat="serve", batch=seq):
                jax.block_until_ready(inf.out)
            t_ready = time.perf_counter()
            with obs.span("service.fetch", cat="serve", batch=seq):
                out = self._fetch(inf.out, seq)
            t_fetched = time.perf_counter()
            self._lat["pad"].record(inf.pad_ms)
            self._lat["stage"].record(inf.stage_ms)
            self._lat["wait"].record((t_ready - t_wait) * 1e3)
            self._lat["fetch"].record((t_fetched - t_ready) * 1e3)
            with obs.span("service.deliver", cat="serve", batch=seq):
                self._deliver(inf.tenant, inf.bucket, inf.items, out,
                              inf.t_dispatch)
        self._release_buffer(inf, out)

    def _count_shards(self, rows, n: int) -> None:
        """Books of a batch of ``n`` requests staged over a mesh, ``rows``
        its plan's ``(device id, start, stop)`` blocks: per device, the
        batch and its request and padding rows (padding fills the last
        rows, so it falls on the last shards)."""
        with self._stats_lock:
            books = self._stats["shards"]
            for dev, start, stop in rows:
                real = min(max(n - start, 0), stop - start)
                c = books.setdefault(dev, {"batches": 0, "rows": 0,
                                           "pad_rows": 0})
                c["batches"] += 1
                c["rows"] += real
                c["pad_rows"] += stop - start - real

    def _fetch(self, out, seq: int):
        """Device -> host: the batch's result rows, ``out[i]`` row ``i``,
        each read-only.  A result on one device, or not a ``jax.Array``,
        is ``np.asarray``.  On a mesh, a result whose shards split its
        leading (batch) axis alone is delivered from the shards' own
        host blocks, with no bucket-sized host array: every shard's
        copy to the host starts first, so the copies overlap as in
        ``np.asarray``; then, in a ``service.fetch_shard`` span per
        device, the host waits for that shard's block (timed in the
        ``fetch_shard`` histogram), and each row is a view of the first
        block that holds it.  Any other layout is ``np.asarray``, the
        runtime's gather.  ``stats()["gather"]`` counts the two."""
        if not isinstance(out, jax.Array) \
                or len(out.sharding.device_set) < 2:
            return np.asarray(out)
        n = out.shape[0]
        shard = out.sharding.shard_shape(out.shape)
        views = shard[0] < n and shard[1:] == out.shape[1:]
        with self._stats_lock:
            self._stats["gather"]["views" if views else "assembled"] += 1
        if not views:
            return np.asarray(out)
        out.copy_to_host_async()
        rows = [None] * n
        for s in out.addressable_shards:
            with obs.span("service.fetch_shard", cat="serve", batch=seq,
                          device=s.device.id):
                t0 = time.perf_counter()
                block = np.asarray(s.data)
                self._lat["fetch_shard"].record(
                    (time.perf_counter() - t0) * 1e3)
            for i, row in enumerate(block, s.index[0].indices(n)[0]):
                if rows[i] is None:         # replicas: the first holds it
                    rows[i] = row
        return rows

    def _finish(self, inf: _Inflight) -> None:
        """Retire one inflight batch; failures route into the same
        recovery machinery as blocking mode (the first attempt — the
        overlapped dispatch — counts as attempt zero)."""
        try:
            self._complete(inf)
            inf.tenant._bucket_fails[inf.bucket] = 0
        except Exception as e:   # noqa: BLE001 — recovery boundary
            self._dispatch(inf.tenant, inf.items, first_err=e)
        return None

    def _run_batch(self, tenant: Tenant, items: list) -> bool:
        """Sweep deadlines, then dispatch with full failure recovery;
        returns whether anything was actually dispatched."""
        items = self._sweep_expired(items)
        if not items:
            return False
        self._dispatch(tenant, items)
        return True

    def _dispatch(self, tenant: Tenant, items: list, *,
                  first_err: BaseException | None = None) -> None:
        """Dispatch with recovery: retry transient failures with capped
        exponential backoff; on persistent failure optionally degrade
        the bucket's lowering, then bisect to isolate poison rows so
        healthy requests still resolve.  The batcher thread survives
        every path — clients see results or typed exceptions, never a
        dead consumer.  ``first_err`` feeds an already-failed overlapped
        attempt into the same retry accounting."""
        bucket, plan = self._plan_for(tenant, len(items))
        attempt = 0
        err = first_err
        while True:
            if err is None:
                try:
                    self._execute_once(tenant, bucket, plan, items)
                    tenant._bucket_fails[bucket] = 0
                    return
                except Exception as e:   # noqa: BLE001
                    err = e
            # persistent faults (poison payloads) can't be retried
            # away: skip straight to isolation
            if getattr(err, "persistent", False) \
                    or attempt >= self.max_retries:
                break
            attempt += 1
            with self._stats_lock:
                self._stats["retries"] += 1
            delay = min(
                self.retry_backoff_ms * (2 ** (attempt - 1)),
                self.retry_backoff_max_ms) / 1e3
            with obs.span("service.retry", cat="serve", bucket=bucket,
                          attempt=attempt, error=type(err).__name__):
                if delay > 0:
                    time.sleep(delay)
            err = None
        # post-retry failure: the batch (not the thread) is the casualty
        with self._stats_lock:
            self._stats["failed_batches"] += 1
        fails = tenant._bucket_fails.get(bucket, 0) + 1
        tenant._bucket_fails[bucket] = fails
        if fails >= self.degrade_after \
                and bucket not in tenant.downgrades:
            degraded = self._degrade(tenant, bucket, err)
            if degraded is not None:
                try:
                    self._execute_once(tenant, bucket, degraded, items)
                    tenant._bucket_fails[bucket] = 0
                    return
                except Exception as e:   # noqa: BLE001
                    err = e              # degraded plan failed too
        if len(items) == 1:
            self._quarantine(items[0][1], err)
            return
        with obs.span("service.bisect", cat="serve", bucket=bucket,
                      n=len(items), error=type(err).__name__):
            mid = len(items) // 2
            self._isolate(tenant, items[:mid])
            self._isolate(tenant, items[mid:])

    def _isolate(self, tenant: Tenant, items: list) -> None:
        """Bisection step: run ``items`` once through their own bucket
        plan; on failure split again, down to the single poisoned row —
        healthy sub-batches deliver results (and are logged for replay),
        poison rows get the error."""
        bucket, plan = self._plan_for(tenant, len(items))
        try:
            self._execute_once(tenant, bucket, plan, items)
        except Exception as e:   # noqa: BLE001
            if len(items) == 1:
                self._quarantine(items[0][1], e)
                return
            mid = len(items) // 2
            self._isolate(tenant, items[:mid])
            self._isolate(tenant, items[mid:])

    def _quarantine(self, fut: Future, err: BaseException) -> None:
        """Deliver the isolating error to exactly one future."""
        with self._stats_lock:
            self._stats["quarantined"] += 1
        obs.instant("service.quarantine", cat="serve",
                    error=type(err).__name__)
        fut.set_exception(err)

    def _degrade(self, tenant: Tenant, bucket: int, err: BaseException):
        """Recompile a persistently failing bucket with the reference
        lowering at f32, once — runtime graceful degradation, extending
        the compile-time ``Plan.downgrades`` contract to runtime.
        Returns the degraded plan, or None when there is nothing to
        shed (the bucket already runs the reference path at full
        precision) or the recompile itself fails (the batcher must
        survive that too)."""
        requested = tenant.options.lowering
        prec = tenant.options.precision
        lowering_trivial = (isinstance(requested, str)
                            and requested in ("native", "reference"))
        precision_trivial = prec in (None, "f32")
        if lowering_trivial and precision_trivial:
            return None
        try:
            plan = plan_lib.compile(
                tenant.graph,
                {tenant.graph.inputs[0]: (bucket, tenant.signal_len)},
                options=plan_lib.CompileOptions(
                    dtype=str(tenant.dtype), lowering="reference",
                    mesh=tenant.mesh))
        except Exception:   # noqa: BLE001 — degradation must never kill
            return None     # the batcher; bisection still runs
        tenant.plans[bucket] = plan
        if bucket == tenant.batch_size:
            tenant.plan = plan
        # record what the bucket gave up: the lowering request when one
        # was non-trivial (the historical record shape), else the
        # dimension-tagged precision request
        if not lowering_trivial:
            tenant.downgrades[bucket] = (requested
                                         if isinstance(requested, str)
                                         else "per-node")
        else:
            tenant.downgrades[bucket] = "precision:" + (
                prec if isinstance(prec, str) else "per-node")
        tenant._tags[bucket] = "reference"
        with self._stats_lock:
            self._stats["degraded"] += 1
        obs.instant("service.degrade", cat="serve", bucket=bucket,
                    tenant=tenant.name, requested=str(requested),
                    error=type(err).__name__)
        warnings.warn(
            f"service bucket {bucket} (tenant {tenant.name!r}): plan "
            f"failed {self.degrade_after} consecutive dispatch(es) "
            f"(last: {type(err).__name__}); recompiled with the "
            f"reference lowering (was {requested!r}) — see the tenant's "
            "downgrades",
            stacklevel=2)
        return plan

    def flush(self) -> int:
        """Drain the queue synchronously; returns batches executed.

        Only legal while no other consumer exists: a background batcher
        or a second concurrent ``flush()`` would split one logical batch
        between two consumers (each dispatching a padded partial).  The
        single-consumer claim is registered under the lifecycle lock but
        the drain itself runs outside it, so batch execution never
        blocks ``submit()`` and a Future done-callback that re-enters
        the service cannot deadlock.
        """
        with self._lifecycle:    # claim + thread check atomic vs start()
            t = self._thread
            if t is not None and t.is_alive():
                raise RuntimeError(
                    "flush() while the background batcher is running "
                    "would split batches across two consumers; close() "
                    "the service to drain it")
            if not self._drain_lock.acquire(blocking=False):
                raise RuntimeError(
                    "flush() while another flush() is draining would "
                    "split batches across two consumers")
        try:
            return self._drain_queue()
        finally:
            self._drain_lock.release()

    def _drain_queue(self) -> int:
        ran = 0
        while True:
            with self._avail:
                first = self._pop_locked()
            if first is None:
                return ran
            tenant, items = self._gather(first, None)
            if self._run_batch(tenant, items):
                ran += 1

    # -- background batcher -------------------------------------------------
    def start(self) -> "PipelineService":
        with self._lifecycle:
            if self._closed:
                raise RuntimeError("service closed")
            if self._drain_lock.locked():
                raise RuntimeError(
                    "start() while flush() is draining would spawn a "
                    "second consumer mid-batch")
            if self._thread is None:
                obs.trace_gc()
                self._thread = threading.Thread(target=self._loop,
                                                daemon=True)
                self._thread.start()
        return self

    def _loop(self) -> None:
        """The batcher.  Blocking mode: block for the first request,
        gather up to the tenant's batch size, dispatch+wait, repeat —
        the two batching policies differ only in the fill wait (fixed
        lingers up to ``max_wait_ms`` per request; continuous takes
        exactly what has queued).  Overlapped mode (the double buffer):
        at most ONE batch is in flight on the device; the loop launches
        batch N+1 (pack/shard/dispatch, no wait) *before* blocking on
        batch N's completion, so the device's queue is never empty while
        requests are waiting.  An idle queue with a batch in flight
        degrades to a short poll — new arrivals and batch completion
        both end it promptly."""
        fill_wait = (self.max_wait_ms / 1e3
                     if self.batching == "fixed" else None)
        inflight: _Inflight | None = None
        while True:
            if inflight is None:
                with obs.span("service.idle", cat="serve"):
                    first = self._get(None)   # block for a request
                if first is _STOPPED:
                    return
            else:
                first = self._get(0.001)  # overlap: poll between checks
                if first is _EMPTY or first is _STOPPED:
                    if first is _STOPPED or inflight.ready():
                        inflight = self._finish(inflight)
                    continue
            tenant, items = self._gather(first, fill_wait)
            items = self._sweep_expired(items)
            if not items:
                continue
            if not self.overlap:
                self._dispatch(tenant, items)
                continue
            bucket, plan = self._plan_for(tenant, len(items))
            try:
                launched = self._launch(tenant, bucket, plan, items)
            except Exception as e:   # noqa: BLE001 — recovery boundary
                if inflight is not None:
                    inflight = self._finish(inflight)
                self._dispatch(tenant, items, first_err=e)
                continue
            if inflight is not None:
                self._finish(inflight)
            inflight = launched

    def close(self) -> None:
        """Stop the batcher (if started), drain the queue, and reject all
        future ``submit``/``start`` calls.  Submitters blocked at a full
        queue are woken and raise.  An in-flight overlapped batch is
        completed, never abandoned.  Idempotent on success; if the
        batcher doesn't stop within ``close_timeout`` (e.g. a slow
        interpret-mode batch) it raises but stays retryable — a second
        ``close()`` re-joins the thread rather than no-opping."""
        with self._space:
            self._closed = True      # new submits now raise, not enqueue
            self._stopping = True    # the batcher drains, then exits
            self._space.notify_all()  # wake admission-blocked submitters
            self._avail.notify_all()  # wake the batcher's work wait
            t = self._thread
        if t is not None:
            t.join(timeout=self.close_timeout)
            if t.is_alive():
                # the thread may still be draining the queue: flushing
                # now would make two concurrent consumers — refuse, but
                # leave _thread set so a retry can finish the shutdown
                raise RuntimeError(
                    f"batcher thread did not stop within "
                    f"{self.close_timeout}s (slow batch in flight?); "
                    "call close() again to retry the shutdown")
            with self._lifecycle:
                self._thread = None
        self._drain_lock.acquire()   # waits out a legal in-flight flush
        try:
            self._drain_queue()
        finally:
            self._drain_lock.release()

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        # the with-form has no retry path: wait out slow (not hung)
        # batches rather than replacing the body's exception with the
        # retryable close-timeout error and stranding pending futures.
        # Bounded (20 x close_timeout, 10 min at defaults) so a batch
        # that is genuinely hung — not slow — still surfaces the error.
        for _ in range(20):
            try:
                self.close()
                return
            except RuntimeError:
                if self._thread is None:
                    raise            # not a batcher timeout: genuine error
                time.sleep(0.01)     # slow batch in flight: keep waiting
        self.close()                 # final attempt: let the timeout raise

    async def __aenter__(self):
        return self.start()

    async def __aexit__(self, *exc):
        # close() joins the batcher thread and may drain batches — off
        # the event loop, so in-flight awaits can still resolve while
        # the service shuts down
        await asyncio.get_running_loop().run_in_executor(
            None, self.__exit__)


def replay_batches(svc: PipelineService, tenant=None) -> int:
    """Verify a ``record_batches=True`` service bit-for-bit: re-run every
    logged (bucket, requests) packing through the same bucket plan and
    compare each delivered response against its replayed row with
    ``assert_array_equal``.  Returns the number of requests checked.
    This is the strong numerics claim continuous batching must honor —
    a response is exactly the bucket plan's row for the packing that was
    served, whatever that packing turned out to be: no padding bleed, no
    row misindexing, no bucket-dependent corruption.  (Row-level results
    across *different* batch sizes are an XLA tiling decision, so
    cross-bucket bitwise equality is not the contract — per-packing
    determinism is.)  Only packings that delivered results are logged,
    so a fault-injected run replays exactly its healthy dispatches —
    including the healthy halves bisection salvaged from poisoned
    batches.

    Replay is **per tenant**: each tenant's log replays through its own
    bucket plans.  ``tenant=`` (a name or :class:`Tenant`) restricts the
    check to one tenant; the default verifies every recording tenant.
    """
    tenants = ([svc._tenant(tenant)] if tenant is not None
               else list(svc.tenants.values()))
    if all(t.batch_log is None for t in tenants):
        raise ValueError("service was not built with record_batches=True")
    checked = 0
    for t in tenants:
        if t.batch_log is None:
            continue
        for bucket, items in t.batch_log:
            if any(f.exception(timeout=0) is not None for _, f in items):
                # a failed batch delivered exceptions, not rows — skip it
                # so the healthy batches of an anomalous run still verify
                continue
            batch = svc._pack(t, bucket, items)
            plan = t.plans.get(bucket, t.plan)
            want = np.asarray(plan(_stage(plan, batch)))
            for i, (_, fut) in enumerate(items):
                np.testing.assert_array_equal(
                    np.asarray(fut.result(timeout=0)), want[i],
                    err_msg=f"tenant {t.name!r} bucket {bucket} row {i} "
                            "!= replayed plan row")
                checked += 1
    return checked


__all__ = ["PipelineService", "Tenant", "PRIORITIES", "bucket_ladder",
           "replay_batches"]
