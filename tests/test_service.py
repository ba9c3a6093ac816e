"""Continuous-batching service suite: arrival-pattern soaks (Poisson /
bursty / adversarial), bucket-ladder numerics (every delivered response
bit-for-bit equal to a replay of the exact packing served), and the PR-3
lifecycle invariants under the continuous scheduler.

CI runs this file as the `service` job under 8 forced virtual devices
with pytest-timeout enforcing the per-test ceiling below — a deadlocked
batcher thread fails in minutes instead of eating the job timeout.
"""
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import graph
from repro.core.registry import PIPELINES, pipelines
from repro.graph.errors import (DeadlineExceeded, InvalidRequest,
                                Overloaded)
from repro.graph.service import (PipelineService, bucket_ladder,
                                 replay_batches)
from repro.obs import faults
from repro.obs.faults import InjectedFault

pipelines()
RNG = np.random.default_rng(23)

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
