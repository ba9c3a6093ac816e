"""Continuous-batching service suite: arrival-pattern soaks (Poisson /
bursty / adversarial), bucket-ladder numerics (every delivered response
bit-for-bit equal to a replay of the exact packing served), and the PR-3
lifecycle invariants under the continuous scheduler.

CI runs this file as the `service` job under 8 forced virtual devices
with pytest-timeout enforcing the per-test ceiling below — a deadlocked
batcher thread fails in minutes instead of eating the job timeout.
"""
import json
import os
import subprocess
import sys
import textwrap
import threading
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import graph, obs
from repro.core.registry import PIPELINES, pipelines
from repro.graph.errors import (DeadlineExceeded, InvalidRequest,
                                Overloaded)
from repro.graph.service import (PipelineService, bucket_ladder,
                                 replay_batches)
from repro.obs import faults
from repro.obs.faults import InjectedFault

pipelines()
RNG = np.random.default_rng(23)
SRC = os.path.join(os.path.dirname(__file__), "..", "src")

# per-test wall-clock ceiling (enforced when pytest-timeout is
# installed, as in CI): a wedged batcher must fail fast, not hang
pytestmark = pytest.mark.timeout(120)


def _signals(n_req, n=256):
    return [RNG.standard_normal(n).astype(np.float32) for _ in range(n_req)]


def _service(name="spectrogram", n=256, batch=8, **kw):
    kw.setdefault("batching", "continuous")
    kw.setdefault("record_batches", True)
    return PIPELINES[name], PipelineService(
        PIPELINES[name].build(), signal_len=n, batch_size=batch, **kw)


# ---------------------------------------------------------------------------
# bucket ladder
# ---------------------------------------------------------------------------
def test_bucket_ladder_shapes():
    assert bucket_ladder(8) == (1, 2, 4, 8)
    assert bucket_ladder(1) == (1,)
    assert bucket_ladder(12) == (1, 2, 4, 8, 12)   # max is always a rung
    assert bucket_ladder(8, 2) == (2, 4, 8)        # shard-divisible only
    assert bucket_ladder(16, 4) == (4, 8, 16)
    with pytest.raises(ValueError, match="max_batch"):
        bucket_ladder(0)
    with pytest.raises(ValueError, match="shard count"):
        bucket_ladder(4, 8)


def test_continuous_service_precompiles_ladder():
    _, svc = _service(batch=8)
    assert svc.buckets == (1, 2, 4, 8)
    assert set(svc.plans) == {1, 2, 4, 8}
    assert svc.plan is svc.plans[8]
    # bucket plans are ordinary cached plans: a direct compile of the
    # same shape is the same object (plan-cache reuse, no duplicates)
    g = svc.graph
    p = graph.compile(g, {g.inputs[0]: (4, 256)}, dtype="float32")
    assert p is svc.plans[4]
    svc.close()


def test_invalid_batching_mode_rejected():
    g = PIPELINES["spectrogram"].build()
    with pytest.raises(ValueError, match="batching="):
        PipelineService(g, signal_len=256, batch_size=2, batching="adaptive")


# ---------------------------------------------------------------------------
# numerics: responses == replayed packing, bit for bit
# ---------------------------------------------------------------------------
def test_continuous_sync_flush_buckets_and_oracle():
    spec, svc = _service(batch=8)
    xs = _signals(13)
    futs = [svc.submit(x) for x in xs]
    assert svc.flush() == 2                     # 8 + 5->bucket(8)
    for x, f in zip(xs, futs):
        np.testing.assert_allclose(f.result(timeout=5), spec.oracle(x),
                                   rtol=2e-3, atol=2e-3)
    s = svc.stats()
    assert s["requests"] == 13 and s["batches"] == 2
    assert s["padded_slots"] == 3               # 5 rode an 8-bucket
    assert replay_batches(svc) == 13            # bitwise, exact packing
    svc.close()


@pytest.mark.parametrize("name", ["spectrogram", "pfb_power"])
def test_continuous_poisson_soak(name):
    """Poisson arrivals at partial load: every future resolves, every
    response is bit-for-bit the bucket plan's row for the packing that
    served it (pfb_power included deliberately: its rows are NOT
    bit-stable across batch sizes, so this pins per-packing determinism,
    not a tiling accident)."""
    spec, svc = _service(name, batch=8)
    xs = _signals(48)
    gaps = np.random.default_rng(5).exponential(0.002, size=len(xs))
    with svc:
        futs = []
        for x, gap in zip(xs, gaps):
            time.sleep(gap)
            futs.append(svc.submit(x))
        outs = [f.result(timeout=60) for f in futs]       # all resolve
    for x, o in zip(xs, outs):
        np.testing.assert_allclose(o, spec.oracle(x), rtol=2e-3, atol=2e-3)
    assert replay_batches(svc) == len(xs)
    assert svc.stats()["batches"] >= 1
    # the scheduler actually used the ladder: padding never exceeds what
    # the next bucket requires (fixed packing would pad to 8 every time)
    total_slots = svc.stats()["requests"] + svc.stats()["padded_slots"]
    assert total_slots == sum(b for b, _ in svc.batch_log)


def test_continuous_bursty_arrivals():
    """Bursts larger than max_batch split into full batches; quiet gaps
    between bursts produce small buckets, not stalls."""
    spec, svc = _service(batch=4)
    xs = _signals(30)
    it = iter(xs)
    futs = []
    with svc:
        for burst in (9, 1, 12, 2, 6):          # > max, singleton, ...
            for _ in range(burst):
                futs.append(svc.submit(next(it)))
            time.sleep(0.05)                    # device drains the burst
        outs = [f.result(timeout=60) for f in futs]
    for x, o in zip(xs, outs):
        np.testing.assert_allclose(o, spec.oracle(x), rtol=2e-3, atol=2e-3)
    assert replay_batches(svc) == len(xs)
    assert all(b <= 4 for b, _ in svc.batch_log)
    assert any(len(items) == 4 for _, items in svc.batch_log)  # full loads


def test_continuous_adversarial_trickle_no_fill_wait():
    """The continuous claim itself: an idle device dispatches a lone
    request immediately.  With a fill deadline of 30s a fixed batcher
    would sit on it; continuous must resolve well inside the timeout."""
    spec, svc = _service(batch=8, max_wait_ms=30_000.0)
    with svc:
        for x in _signals(3):
            t0 = time.perf_counter()
            out = svc.submit(x).result(timeout=10)
            assert time.perf_counter() - t0 < 10
            np.testing.assert_allclose(out, spec.oracle(x),
                                       rtol=2e-3, atol=2e-3)
    assert all(b == 1 for b, _ in svc.batch_log)   # served as singletons
    assert replay_batches(svc) == 3


def test_continuous_concurrent_submitters():
    """Many producer threads racing submit(): per-request futures mean
    no submitter waits on another's result, and nothing is lost."""
    spec, svc = _service(batch=8)
    xs = _signals(40)
    results = [None] * len(xs)
    errs = []

    def producer(lo, hi):
        try:
            futs = [(i, svc.submit(xs[i])) for i in range(lo, hi)]
            for i, f in futs:
                results[i] = f.result(timeout=60)
        except Exception as e:                   # noqa: BLE001
            errs.append(e)

    with svc:
        threads = [threading.Thread(target=producer, args=(k, k + 8))
                   for k in range(0, 40, 8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=90)
    assert not errs
    for x, o in zip(xs, results):
        np.testing.assert_allclose(o, spec.oracle(x), rtol=2e-3, atol=2e-3)
    assert replay_batches(svc) == len(xs)


# ---------------------------------------------------------------------------
# lifecycle invariants survive the continuous scheduler
# ---------------------------------------------------------------------------
def test_continuous_close_while_loaded_resolves_everything():
    spec, svc = _service(batch=4)
    xs = _signals(21)
    svc.start()
    futs = [svc.submit(x) for x in xs]
    svc.close()                                  # queue may still be deep
    for x, f in zip(xs, futs):
        np.testing.assert_allclose(f.result(timeout=60), spec.oracle(x),
                                   rtol=2e-3, atol=2e-3)
    assert replay_batches(svc) == len(xs)


def test_continuous_submit_and_start_after_close_raise():
    _, svc = _service(batch=2)
    with svc:
        svc.submit(np.zeros(256, np.float32)).result(timeout=60)
    with pytest.raises(RuntimeError, match="service closed"):
        svc.submit(np.zeros(256, np.float32))
    with pytest.raises(RuntimeError, match="service closed"):
        svc.start()
    svc.close()                                  # idempotent on success


def test_continuous_flush_while_started_raises():
    _, svc = _service(batch=2)
    svc.start()
    try:
        with pytest.raises(RuntimeError, match="two consumers"):
            svc.flush()
    finally:
        svc.close()
    assert svc.flush() == 0                      # legal again, and empty


def test_continuous_failed_batch_fails_futures_not_thread():
    spec, svc = _service(batch=4)
    boom = RuntimeError("bucket boom")
    svc.plans = {b: (lambda x, e=boom: (_ for _ in ()).throw(e))
                 for b in svc.buckets}
    with svc:
        f = svc.submit(np.zeros(256, np.float32))
        with pytest.raises(RuntimeError, match="bucket boom"):
            f.result(timeout=30)
        # the batcher thread survived the failed bucket: prove it by
        # serving a healthy batch afterwards (plan-cache lookups)
        svc.plans = {
            b: graph.compile(svc.graph, {svc.graph.inputs[0]: (b, 256)},
                             dtype="float32") for b in svc.buckets}
        x = _signals(1)[0]
        out = svc.submit(x).result(timeout=60)
    np.testing.assert_allclose(out, spec.oracle(x), rtol=2e-3, atol=2e-3)
    assert svc.stats()["failed_batches"] == 1
    # replay skips the failed packing and still verifies the healthy one
    assert replay_batches(svc) == 1


def test_fixed_mode_unchanged_stats_contract():
    """batching="fixed" keeps the historical single-plan behavior: one
    batch shape, max_wait fill deadline, the legacy counter values —
    and no continuous-only keys (bucket_batches)."""
    spec = PIPELINES["spectrogram"]
    svc = PipelineService(spec.build(), signal_len=256, batch_size=4,
                          batching="fixed")
    assert svc.buckets == (4,)
    xs = _signals(6)
    futs = [svc.submit(x) for x in xs]
    assert svc.flush() == 2
    for x, f in zip(xs, futs):
        np.testing.assert_allclose(f.result(timeout=5), spec.oracle(x),
                                   rtol=2e-3, atol=2e-3)
    s = svc.stats()
    assert {k: s[k] for k in ("requests", "batches", "padded_slots")} \
        == {"requests": 6, "batches": 2, "padded_slots": 2}
    assert "bucket_batches" not in s
    assert s["fill_ratio"] == 6 / 8
    svc.close()


# ---------------------------------------------------------------------------
# mesh: bucket ladder restricted to shard-divisible sizes
# ---------------------------------------------------------------------------
def test_continuous_sharded_buckets_divisible():
    """Sharded continuous serving: every rung splits over the mesh.
    Runs on however many devices this process sees (1 locally, 8 in the
    CI service job)."""
    n_dev = len(jax.devices())
    shards = min(n_dev, 4)
    spec, svc = _service("fir_decimate", n=512, batch=4 * shards,
                         mesh=shards)
    assert svc.buckets == bucket_ladder(4 * shards, shards)
    assert all(b % shards == 0 for b in svc.buckets)
    for p in svc.plans.values():
        assert p.mesh is not None
    xs = _signals(2 * shards + 1, n=512)
    with svc:
        outs = [f.result(timeout=120) for f in [svc.submit(x) for x in xs]]
    for x, o in zip(xs, outs):
        np.testing.assert_allclose(o, spec.oracle(x), rtol=2e-3, atol=2e-3)
    assert replay_batches(svc) == len(xs)


def test_continuous_sharded_indivisible_batch_raises():
    g = PIPELINES["spectrogram"].build()
    n_dev = len(jax.devices())
    if n_dev < 2:
        pytest.skip("needs >= 2 devices (CI service job forces 8)")
    with pytest.raises(ValueError, match="divis"):
        PipelineService(g, signal_len=256, batch_size=n_dev + 1,
                        batching="continuous", mesh=n_dev)


# ---------------------------------------------------------------------------
# host staging buffers: reused per bucket once their batch's output is ready
# ---------------------------------------------------------------------------
class _Seen:
    """A bucket plan that keeps a copy of every host batch it is given,
    so a test can read the padding rows that were staged."""

    def __init__(self, plan, seen):
        self._plan, self._seen = plan, seen

    def __getattr__(self, name):
        return getattr(self._plan, name)

    def __call__(self, x):
        self._seen.append(np.array(x))
        return self._plan(x)


def test_pack_buffers_reused_after_warm_up():
    spec, svc = _service(batch=8)
    xs = _signals(8)
    for k in range(4):             # blocking: one buffer serves them all
        futs = [svc.submit(x) for x in xs]
        assert svc.flush() == 1
        assert svc.stats()["pack_buffers"] == {"reused": k, "allocated": 1}
    # the overlapped loop packs batch N+1 while N is in flight: a second
    # buffer, and no more, however long the backlog
    ys = _signals(40)
    futs = [svc.submit(y) for y in ys]
    with svc:
        outs = [f.result(timeout=60) for f in futs]
    assert svc.stats()["pack_buffers"] == {"reused": 3 + 5 - 1,
                                           "allocated": 2}
    assert len(svc.tenants["default"]._staging[8]) == 2
    for y, o in zip(ys, outs):
        np.testing.assert_allclose(o, spec.oracle(y), rtol=2e-3, atol=2e-3)
    assert replay_batches(svc) == 32 + 40


@pytest.mark.parametrize("batching", ["continuous", "fixed"])
def test_reused_buffer_pads_with_zeros_after_a_fuller_batch(batching):
    spec, svc = _service(batch=8, batching=batching)
    seen = []
    svc.plan = _Seen(svc.plan, seen)
    svc.plans = {b: (svc.plan if b == 8 else p) for b, p in svc.plans.items()}
    xs = _signals(8 + 6 + 5)
    parts = (xs[:8], xs[8:14], xs[14:])         # 8, then 6 and 5 in 8
    futs = []
    for part in parts:
        futs += [svc.submit(x) for x in part]
        assert svc.flush() == 1
    assert svc.stats()["pack_buffers"] == {"reused": 2, "allocated": 1}
    assert [len(b) for b in seen] == [8, 8, 8]
    for staged, part in zip(seen, parts):
        np.testing.assert_array_equal(staged[:len(part)], np.stack(part))
        assert not staged[len(part):].any()     # a fuller batch's rows
    for x, f in zip(xs, futs):
        np.testing.assert_allclose(f.result(timeout=0), spec.oracle(x),
                                   rtol=2e-3, atol=2e-3)
    assert replay_batches(svc) == len(xs)
    svc.close()


@pytest.mark.parametrize("overlap", [True, False])
def test_delivered_rows_survive_reuse_of_their_buffer(overlap):
    """Every result is held to the end: a later batch packed into the
    same buffer must not show through an earlier response."""
    spec, svc = _service(batch=4, overlap=overlap)
    xs = _signals(4 * 12 + 3)
    with svc:
        futs = []
        for k in range(0, len(xs), 8):           # backlogs of two batches
            futs += [svc.submit(x) for x in xs[k:k + 8]]
            time.sleep(0.01)
        outs = [f.result(timeout=60) for f in futs]
    assert svc.stats()["pack_buffers"]["reused"] > 0
    for x, o in zip(xs, outs):
        np.testing.assert_allclose(o, spec.oracle(x), rtol=2e-3, atol=2e-3)
    assert replay_batches(svc) == len(xs)


class _ZeroCopy:
    """A plan on a backend that stages the host batch without a copy
    and returns its input: the delivered rows view the staging buffer."""
    input_shardings = (None,)

    def shard_inputs(self, batch):
        return batch

    def __call__(self, x):
        return x


def test_buffer_viewed_by_delivered_rows_is_not_reused():
    _, svc = _service(batch=4)
    svc.plans = {b: _ZeroCopy() for b in svc.buckets}
    xs = _signals(4 * 3)
    futs = []
    for k in range(0, len(xs), 4):
        futs += [svc.submit(x) for x in xs[k:k + 4]]
        assert svc.flush() == 1
    for x, f in zip(xs, futs):
        np.testing.assert_array_equal(f.result(timeout=0), x)
    assert svc.stats()["pack_buffers"] == {"reused": 0, "allocated": 3}
    svc.close()


class _NoStack(list):
    """A mesh result's rows; stacking them into one array fails."""

    def __array__(self, *args, **kwargs):
        raise AssertionError("the row list was stacked into an array")


@pytest.mark.parametrize("views_buffer", [False, True])
def test_release_buffer_tests_each_row_without_stacking(views_buffer):
    _, svc = _service(batch=4)
    tenant = svc.tenants["default"]
    batch = np.zeros((4, 256), np.float32)
    rows = _NoStack(r for b in (np.ones((2, 3)), np.ones((2, 3)))
                    for r in b)
    if views_buffer:
        rows[3] = batch[1]
    inf = SimpleNamespace(tenant=tenant, bucket=4, batch=batch,
                          items=[None] * 3)
    svc._release_buffer(inf, rows)
    kept = [b for b, _ in tenant._staging[4]]
    assert any(b is batch for b in kept) is not views_buffer
    svc.close()


# ---------------------------------------------------------------------------
# robustness: admission, deadlines, validation, retry/bisect/degrade
# ---------------------------------------------------------------------------
@pytest.fixture
def chaos():
    """Deterministic fault config for one test; teardown disarms and
    forgets, so later tests re-read the ambient env (the CI chaos job
    exports TINA_FAULTS for the legacy suites above)."""
    yield faults.configure
    faults.reset()


def _poison(n=256):
    x = RNG.standard_normal(n).astype(np.float32)
    x[n // 3] = np.nan
    return x


def _outcome(f):
    e = f.exception(timeout=0)
    return ("err", e) if e is not None else ("ok", f.result(timeout=0))


def test_validate_strict_fails_poison_future_at_submit(chaos):
    spec, svc = _service(batch=2, validate="strict")
    bad = svc.submit(_poison())
    with pytest.raises(InvalidRequest, match="non-finite"):
        bad.result(timeout=0)                  # failed without any batch
    x = _signals(1)[0]
    good = svc.submit(x)
    assert svc.flush() == 1
    np.testing.assert_allclose(good.result(timeout=5), spec.oracle(x),
                               rtol=2e-3, atol=2e-3)
    s = svc.stats()
    assert s["invalid"] == 1 and s["requests"] == 1    # never admitted
    svc.close()


def test_invalid_robustness_knobs_rejected():
    g = PIPELINES["spectrogram"].build()
    for kw in ({"on_full": "drop"}, {"validate": "maybe"},
               {"queue_limit": 0}, {"deadline_ms": -1},
               {"max_retries": -1}):
        with pytest.raises(ValueError):
            PipelineService(g, signal_len=256, batch_size=2, **kw)


def test_queue_limit_shed_delivers_overloaded(chaos):
    spec, svc = _service(batch=4, queue_limit=2, on_full="shed")
    xs = _signals(5)
    futs = [svc.submit(x) for x in xs]         # no consumer yet: 2 admit,
    for f in futs[2:]:                         # 3 shed instantly
        with pytest.raises(Overloaded, match="queue full"):
            f.result(timeout=0)
    assert svc.flush() == 1
    for x, f in zip(xs[:2], futs[:2]):
        np.testing.assert_allclose(f.result(timeout=5), spec.oracle(x),
                                   rtol=2e-3, atol=2e-3)
    s = svc.stats()
    assert s["shed"] == 3 and s["requests"] == 2       # shed != admitted
    svc.close()


def test_queue_limit_raise_policy(chaos):
    _, svc = _service(batch=4, queue_limit=1, on_full="raise")
    svc.submit(_signals(1)[0])
    with pytest.raises(Overloaded):
        svc.submit(_signals(1)[0])
    assert svc.stats()["shed"] == 1
    svc.flush()
    svc.close()


def test_queue_limit_block_admits_when_space_frees(chaos):
    spec, svc = _service(batch=1, queue_limit=1, on_full="block")
    x0, x1 = _signals(2)
    f0 = svc.submit(x0)
    box = {}

    def blocked_submit():
        box["fut"] = svc.submit(x1)            # blocks until f0 drains

    t = threading.Thread(target=blocked_submit)
    t.start()
    time.sleep(0.05)
    assert t.is_alive()                        # genuinely blocked, not shed
    deadline = time.perf_counter() + 30
    while t.is_alive() and time.perf_counter() < deadline:
        svc.flush()                            # drain -> space -> admit
        time.sleep(0.005)
    t.join(timeout=30)
    assert not t.is_alive()
    svc.flush()
    np.testing.assert_allclose(f0.result(timeout=5), spec.oracle(x0),
                               rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(box["fut"].result(timeout=5),
                               spec.oracle(x1), rtol=2e-3, atol=2e-3)
    assert svc.stats()["shed"] == 0
    svc.close()


def test_blocked_submit_honors_deadline(chaos):
    _, svc = _service(batch=1, queue_limit=1, on_full="block")
    svc.submit(_signals(1)[0])                 # fills the queue; no consumer
    t0 = time.perf_counter()
    f = svc.submit(_signals(1)[0], deadline_ms=50)
    assert time.perf_counter() - t0 < 10       # gave up at the deadline,
    with pytest.raises(DeadlineExceeded):      # didn't block forever
        f.result(timeout=0)
    assert svc.stats()["expired"] == 1
    svc.flush()
    svc.close()


def test_close_wakes_blocked_submitter(chaos):
    _, svc = _service(batch=1, queue_limit=1, on_full="block")
    f0 = svc.submit(_signals(1)[0])
    errs = []

    def blocked_submit():
        try:
            svc.submit(_signals(1)[0])
        except RuntimeError as e:
            errs.append(e)

    t = threading.Thread(target=blocked_submit)
    t.start()
    time.sleep(0.05)
    svc.close()                                # wakes + rejects the waiter,
    t.join(timeout=30)                         # drains the admitted request
    assert not t.is_alive()
    assert len(errs) == 1 and "service closed" in str(errs[0])
    assert f0.result(timeout=5) is not None


def test_deadline_expiry_soak_no_device_slots(chaos):
    """Satellite (c) deadline soak: every expired future raises
    DeadlineExceeded and none of them consumed a device slot."""
    _, svc = _service(batch=8)
    futs = [svc.submit(x, deadline_ms=0) for x in _signals(50)]
    time.sleep(0.001)
    assert svc.flush() == 0                    # swept, nothing dispatched
    for f in futs:
        with pytest.raises(DeadlineExceeded):
            f.result(timeout=0)
    s = svc.stats()
    assert s["expired"] == 50 and s["batches"] == 0
    assert svc.batch_log == []                 # zero device dispatches
    svc.close()


def test_mixed_deadlines_only_expired_fail(chaos):
    spec, svc = _service(batch=8, deadline_ms=0)   # service-wide default
    x_live = _signals(1)[0]
    doomed = [svc.submit(x) for x in _signals(3)]
    live = svc.submit(x_live, deadline_ms=10_000)  # per-request override
    time.sleep(0.001)
    assert svc.flush() == 1
    for f in doomed:
        with pytest.raises(DeadlineExceeded):
            f.result(timeout=0)
    np.testing.assert_allclose(live.result(timeout=5), spec.oracle(x_live),
                               rtol=2e-3, atol=2e-3)
    assert svc.stats()["expired"] == 3
    svc.close()


def test_transient_fault_retried_to_success(chaos):
    chaos("device_run:once", seed=0)
    spec, svc = _service(batch=2, retry_backoff_ms=0.1)
    x = _signals(1)[0]
    f = svc.submit(x)
    assert svc.flush() == 1
    np.testing.assert_allclose(f.result(timeout=5), spec.oracle(x),
                               rtol=2e-3, atol=2e-3)
    s = svc.stats()
    assert s["retries"] == 1 and s["failed_batches"] == 0
    assert s["quarantined"] == 0
    assert replay_batches(svc) == 1
    svc.close()


def test_persistent_fault_skips_retries_and_quarantines(chaos):
    chaos("device_run:nan", seed=0)
    _, svc = _service(batch=2)
    f = svc.submit(_poison())
    assert svc.flush() == 1
    with pytest.raises(InjectedFault):
        f.result(timeout=0)
    s = svc.stats()
    assert s["retries"] == 0                   # pointless retries skipped
    assert s["failed_batches"] == 1 and s["quarantined"] == 1
    svc.close()


def test_bisect_isolates_poison_rows_healthy_rows_served(chaos):
    """The poison-isolation contract: one batch, two poison rows — the
    six healthy futures get bit-correct results (replay-verified), only
    the poisoned futures get the error."""
    chaos("device_run:nan", seed=0)
    spec, svc = _service(batch=8)
    xs = _signals(8)
    poison_idx = {2, 5}
    for i in poison_idx:
        xs[i] = _poison()
    futs = [svc.submit(x) for x in xs]
    svc.flush()
    for i, (x, f) in enumerate(zip(xs, futs)):
        if i in poison_idx:
            with pytest.raises(InjectedFault):
                f.result(timeout=0)
        else:
            np.testing.assert_allclose(f.result(timeout=0), spec.oracle(x),
                                       rtol=2e-3, atol=2e-3)
    s = svc.stats()
    assert s["quarantined"] == 2 and s["failed_batches"] == 1
    # healthy sub-batches were logged and replay bit-exactly; poisoned
    # dispatches never enter the log
    assert replay_batches(svc) == 6
    assert all(not any(np.isnan(x).any() for x, _ in items)
               for _, items in svc.batch_log)
    svc.close()


def test_failed_launch_drops_its_staging_buffer(chaos):
    """A batch that raises drops its buffer (the retry and the bisection
    halves take buffers like any launch), and every later batch still
    serves correct rows out of clean buffers."""
    chaos("device_run:once,device_run:nan", seed=0)
    spec, svc = _service(batch=8, retry_backoff_ms=0.1)
    staging = svc.tenants["default"]._staging
    xs = _signals(8)
    futs = [svc.submit(x) for x in xs]
    assert svc.flush() == 1                    # fails once, then retried
    assert svc.stats()["retries"] == 1
    assert svc.stats()["pack_buffers"] == {"reused": 0, "allocated": 2}
    assert len(staging[8]) == 1
    poisoned = _signals(8)
    poisoned[6] = _poison()
    futs += [svc.submit(x) for x in poisoned]
    assert svc.flush() == 1                    # bisected down to row 6
    assert staging[8] == []                    # its buffer held the NaN
    with pytest.raises(InjectedFault):
        futs[8 + 6].result(timeout=0)
    later = _signals(8 + 5)
    for part in (later[:8], later[8:]):        # a NaN left in a padding
        futs += [svc.submit(x) for x in part]  # row would fail these
        assert svc.flush() == 1
    want = xs + poisoned + later
    for i, (x, f) in enumerate(zip(want, futs)):
        if i != 8 + 6:
            np.testing.assert_allclose(f.result(timeout=0), spec.oracle(x),
                                       rtol=2e-3, atol=2e-3)
    s = svc.stats()
    assert s["quarantined"] == 1 and s["failed_batches"] == 1
    assert replay_batches(svc) == len(want) - 1
    svc.close()


def test_staging_buffers_on_a_four_device_mesh():
    """The mesh path stages slices of the reused buffer on each device."""
    body = textwrap.dedent("""
        import numpy as np
        from repro.core.registry import PIPELINES, pipelines
        from repro.graph.service import PipelineService, replay_batches
        pipelines()
        spec = PIPELINES['fir_decimate']
        rng = np.random.default_rng(7)
        svc = PipelineService(spec.build(), signal_len=512, batch_size=8,
                              batching='continuous', mesh=4,
                              record_batches=True)
        assert svc.buckets == (4, 8), svc.buckets
        xs = [rng.standard_normal(512).astype(np.float32)
              for _ in range(8 * 4 + 4 + 8 + 3)]
        futs = []
        for part in (xs[:8], xs[8:11]):           # 8 rows, then 3 in 4
            futs += [svc.submit(x) for x in part]
            assert svc.flush() == 1
        futs += [svc.submit(x) for x in xs[11:]]
        with svc:                                 # overlapped backlog
            outs = [f.result(timeout=120) for f in futs]
        for x, o in zip(xs, outs):
            np.testing.assert_allclose(o, spec.oracle(x),
                                       rtol=2e-3, atol=2e-3)
        assert replay_batches(svc) == len(xs)
        # 8, 3 in 4; then 8, 8 (in flight beside the first), 8, 8, 4
        s = svc.stats()['pack_buffers']
        assert s == {'reused': 4, 'allocated': 3}, s
        print('OK')
        """)
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=SRC + os.pathsep + os.environ.get("PYTHONPATH",
                                                            ""))
    env.setdefault("TINA_AUTOTUNE", "cached")
    r = subprocess.run([sys.executable, "-c", body], capture_output=True,
                       text=True, env=env, timeout=110)
    assert r.returncode == 0, r.stderr[-3000:]
    assert r.stdout.strip().endswith("OK")


# ---------------------------------------------------------------------------
# mesh: the sharded stage, the per-shard gather, its spans, histogram, books
# ---------------------------------------------------------------------------
MESH_SCRIPT = textwrap.dedent("""
    import json
    import jax
    import numpy as np
    from jax.sharding import PartitionSpec as P
    from repro import obs
    from repro.core.registry import PIPELINES, pipelines
    from repro.graph.service import PipelineService
    pipelines()
    spec = PIPELINES['fir_decimate']
    rng = np.random.default_rng(11)
    svc = PipelineService(spec.build(), signal_len=512, batch_size=8,
                          batching='continuous', mesh=4,
                          record_batches=True)
    xs = [rng.standard_normal(512).astype(np.float32) for _ in range(37)]
    futs = [svc.submit(x) for x in xs[:5]]     # 5 requests in bucket 8
    assert svc.flush() == 1
    partial = svc.stats()['shards']
    first = [f.result(timeout=0) for f in futs]
    futs += [svc.submit(x) for x in xs[5:]]
    with svc:                                  # overlapped backlog
        outs = [f.result(timeout=120) for f in futs]
    s = svc.stats()
    events = [e for e in obs.events()
              if e['name'] in ('service.stage', 'service.fetch',
                               'service.fetch_shard')]
    # the whole-array gather of the same packings
    same, read_only, blocks = True, True, set()
    for bucket, items in svc.batch_log:
        plan = svc.plans[bucket]
        y = plan(plan.shard_inputs(svc._pack(svc._default, bucket, items)))
        want = np.asarray(y)
        rows = svc._fetch(y, -1)
        same &= len(rows) == bucket and all(
            np.array_equal(r, w) for r, w in zip(rows, want))
        for i, (_, f) in enumerate(items):
            got = f.result(timeout=0)
            same &= np.array_equal(got, want[i])
            read_only &= not got.flags.writeable
            blocks.add((bucket, got.base.shape[0],
                        got.base.shape[1:] == got.shape))
    # results sharded other than on the batch axis: the runtime's gather
    mesh = jax.sharding.Mesh(np.array(jax.devices()), ('d',))
    grid = np.arange(8 * 16, dtype=np.float32).reshape(8, 16)
    before = svc.stats()['gather']
    fallback = {}
    for name, layout in (('replicated', P()), ('columns', P(None, 'd'))):
        y = jax.device_put(grid, jax.sharding.NamedSharding(mesh, layout))
        got = svc._fetch(y, -1)
        fallback[name] = (isinstance(got, np.ndarray)
                          and np.array_equal(got, np.asarray(y)))
    after = svc.stats()['gather']
    close = [np.allclose(o, spec.oracle(x), rtol=2e-3, atol=2e-3)
             for x, o in zip(xs, outs)]
    print(json.dumps({
        'same': bool(same), 'close': all(close),
        'read_only': bool(read_only), 'blocks': sorted(blocks),
        'first': [len(first), len({id(r.base) for r in first})],
        'batches': s['batches'], 'bucket_batches': s['bucket_batches'],
        'fetch_shard': s['latency_ms']['fetch_shard']['count'],
        'waits_ms': s['latency_ms']['fetch_shard']['mean'] * 4,
        'fetch_ms': s['latency_ms']['fetch']['mean'],
        'pack_buffers': s['pack_buffers'], 'gather': s['gather'],
        'fallback': fallback,
        'fallback_gather': {k: after[k] - before[k] for k in after},
        'partial': partial, 'shards': s['shards'], 'events': events}))
    """)


@pytest.fixture(scope="module")
def mesh4():
    env = dict(os.environ, TINA_TELEMETRY="on",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=SRC + os.pathsep + os.environ.get("PYTHONPATH",
                                                            ""))
    env.setdefault("TINA_AUTOTUNE", "cached")
    r = subprocess.run([sys.executable, "-c", MESH_SCRIPT],
                       capture_output=True, text=True, env=env, timeout=110)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_mesh_gather_is_bit_identical_to_the_whole_array_copy(mesh4):
    assert mesh4["same"] and mesh4["close"]


def test_mesh_rows_are_read_only_views_of_one_shard_block(mesh4):
    assert mesh4["read_only"]
    # each row's base is its shard's block of 8 / 4 rows, never a
    # bucket-sized array
    assert mesh4["blocks"] == [[8, 2, True]]


def test_mesh_partial_bucket_delivers_its_rows_and_no_padding(mesh4):
    # 5 requests in bucket 8: rows 0-4 from the blocks of devices 0-2;
    # device 3's block holds padding only and delivers nothing
    assert mesh4["first"] == [5, 3]


def test_mesh_staging_buffers_reused_beside_shard_views(mesh4):
    assert mesh4["pack_buffers"]["reused"] > 0


def test_mesh_gather_books_every_batch_as_views(mesh4):
    assert mesh4["gather"] == {"views": mesh4["batches"], "assembled": 0}


@pytest.mark.parametrize("layout", ["replicated", "columns"])
def test_mesh_gather_off_the_batch_axis_is_assembled(mesh4, layout):
    assert mesh4["fallback"][layout]
    assert mesh4["fallback_gather"] == {"views": 0, "assembled": 2}


def test_mesh_fetch_shard_spans_nest_in_their_fetch(mesh4):
    per = {}
    for e in mesh4["events"]:
        per.setdefault(e["args"]["batch"], {}).setdefault(
            e["name"], []).append(e)
    assert len(per) == mesh4["batches"] >= 5
    for seq, ph in per.items():
        assert len(ph["service.stage"]) == 1  # one sharded transfer
        (p,) = ph["service.fetch"]
        kids = ph["service.fetch_shard"]
        assert sorted(k["args"]["device"] for k in kids) == [0, 1, 2, 3]
        for k in kids:
            assert k["tid"] == p["tid"]
            assert p["ts"] <= k["ts"] and \
                k["ts"] + k["dur"] <= p["ts"] + p["dur"]


def test_mesh_fetch_shard_histogram_counts_every_shard(mesh4):
    assert mesh4["fetch_shard"] == 4 * mesh4["batches"]
    # it times the waits for the shards' copies, a part of each fetch
    assert 0 < mesh4["waits_ms"] <= mesh4["fetch_ms"]


def test_mesh_partial_bucket_pads_the_last_shards(mesh4):
    # rows 0-4 hold requests, 5-7 padding: two rows per device
    assert mesh4["partial"] == {
        "0": {"batches": 1, "rows": 2, "pad_rows": 0},
        "1": {"batches": 1, "rows": 2, "pad_rows": 0},
        "2": {"batches": 1, "rows": 1, "pad_rows": 1},
        "3": {"batches": 1, "rows": 0, "pad_rows": 2}}
    books = mesh4["shards"]
    assert sorted(books) == ["0", "1", "2", "3"]
    assert all(c["batches"] == mesh4["batches"] for c in books.values())
    assert sum(c["rows"] for c in books.values()) == 37
    staged = sum(int(b) * n for b, n in mesh4["bucket_batches"].items())
    assert sum(c["rows"] + c["pad_rows"] for c in books.values()) == staged


def test_unsharded_service_has_no_shard_spans_or_books():
    was_on = obs.enabled()
    obs.enable()
    try:
        ev0 = len(obs.events())
        spec, svc = _service(batch=4)
        with svc:
            for f in [svc.submit(x) for x in _signals(9)]:
                f.result(timeout=60)
        names = {e["name"] for e in obs.events()[ev0:]}
    finally:
        if not was_on:
            obs.disable()
    assert {"service.stage", "service.fetch"} <= names
    assert "service.fetch_shard" not in names
    s = svc.stats()
    assert s["latency_ms"]["fetch_shard"]["count"] == 0
    assert s["shards"] == {}
    assert s["gather"] == {"views": 0, "assembled": 0}


def test_runtime_degradation_to_reference_lowering(chaos):
    """A bucket whose pallas plan keeps failing is recompiled once with
    the reference lowering (the @tag spec stops matching after the
    retag), recorded on service.downgrades, and then serves requests."""
    chaos("device_run@pallas:always", seed=0)
    spec, svc = _service(batch=1, lowering="pallas", max_retries=0,
                         degrade_after=2)
    x1, x2, x3 = _signals(3)
    f1 = svc.submit(x1)
    svc.flush()
    with pytest.raises(InjectedFault):         # first strike: quarantined
        f1.result(timeout=0)
    assert svc.downgrades == {}
    f2 = svc.submit(x2)
    with pytest.warns(UserWarning, match="reference lowering"):
        svc.flush()                            # second strike: degrade,
    np.testing.assert_allclose(f2.result(timeout=0), spec.oracle(x2),
                               rtol=2e-3, atol=2e-3)   # same batch served
    assert svc.downgrades == {1: "pallas"}
    f3 = svc.submit(x3)                        # steady state: degraded plan
    svc.flush()
    np.testing.assert_allclose(f3.result(timeout=0), spec.oracle(x3),
                               rtol=2e-3, atol=2e-3)
    s = svc.stats()
    assert s["degraded"] == 1 and s["quarantined"] == 1
    assert replay_batches(svc) == 2            # the two healthy dispatches
    svc.close()


def test_dsp_serve_exits_nonzero_when_not_served(chaos, tmp_path,
                                                 monkeypatch):
    """A launch whose healthy requests failed, or whose bucket degraded
    to the reference lowering, is a failed launch — not an exit 0."""
    from repro.launch import dsp_serve
    monkeypatch.setenv("TINA_AUTOTUNE", "cached")
    monkeypatch.setenv("TINA_AUTOTUNE_CACHE", str(tmp_path / "t.json"))
    chaos("device_run@pallas:always", seed=0)
    with pytest.warns(UserWarning, match="reference lowering"), \
            pytest.raises(SystemExit, match="FAILED: healthy request.*"
                                            "degraded to the reference"):
        dsp_serve.main(["--pipeline", "correlate", "--lowering", "pallas",
                        "--requests", "6", "--batch", "1",
                        "--signal-len", "128", "--max-retries", "0"])


def test_close_under_failure_resolves_everything(chaos):
    """Satellite (c) shutdown-under-failure: close() while batches are
    retrying/bisecting resolves every pending future, leaves no live
    thread, and stays retryable."""
    chaos("device_run:0.5,device_run:nan", seed=3)
    _, svc = _service(batch=4, retry_backoff_ms=0.1)
    xs = _signals(30)
    for i in range(0, 30, 6):
        xs[i] = _poison()
    svc.start()
    futs = [svc.submit(x) for x in xs]
    svc.close()                                # mid-chaos shutdown
    assert svc._thread is None                 # batcher actually exited
    for i, f in enumerate(futs):
        kind, val = _outcome(f)                # every future resolved
        if kind == "err":
            assert isinstance(val, InjectedFault)
        if i % 6 == 0:
            assert kind == "err"               # poison never yields a row
    svc.close()                                # retryable/idempotent
    with pytest.raises(RuntimeError, match="service closed"):
        svc.submit(xs[1])


def test_acceptance_soak_faults_poison_overload(chaos):
    """The ISSUE's acceptance soak: >=5% device_run failure rate, mixed
    poison payloads, offered load > capacity with shedding.  Every
    future resolves with a result or a typed exception, healthy rows in
    poisoned batches replay bit-correct, and the batcher survives."""
    chaos("device_run:0.05,device_run:nan", seed=7)
    spec, svc = _service(batch=8, queue_limit=8, on_full="shed",
                         retry_backoff_ms=0.1)
    xs = _signals(40)
    poison_idx = {i for i in range(0, 40, 10)}
    for i in poison_idx:
        xs[i] = _poison()
    # phase 1: a burst into the bounded queue with no consumer —
    # deterministic overload, everything past the limit sheds
    futs = [svc.submit(x) for x in xs]
    assert svc.stats()["shed"] == 32
    svc.start()                                # phase 2: sustained load
    xs2 = _signals(80)
    for i in range(0, 80, 10):
        xs2[i] = _poison()
    futs2 = [svc.submit(x, deadline_ms=30_000) for x in xs2]
    expired = [svc.submit(x, deadline_ms=0) for x in _signals(5)]
    svc.close()
    assert svc._thread is None                 # the batcher never died
    for f in futs + futs2 + expired:
        kind, val = _outcome(f)                # EVERY future resolved
        if kind == "err":
            assert isinstance(val, (InjectedFault, Overloaded,
                                    DeadlineExceeded))
    for f in expired:
        assert isinstance(f.exception(timeout=0),
                          (DeadlineExceeded, Overloaded))
    for (i, f), x in zip(enumerate(futs2), xs2):
        kind, val = _outcome(f)
        if i % 10 == 0:
            assert kind == "err"               # poison never yields a row
        elif kind == "ok":
            np.testing.assert_allclose(val, spec.oracle(x),
                                       rtol=2e-3, atol=2e-3)
    s = svc.stats()
    assert s["quarantined"] >= 1 and s["shed"] >= 32
    assert replay_batches(svc) >= 1            # healthy packings bit-exact
    assert faults.stats()["device_run"] >= 1
