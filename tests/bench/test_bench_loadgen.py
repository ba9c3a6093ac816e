"""The load generator: deterministic in the seed, open loops timed from
the due time, closed loops holding their requests outstanding."""
import itertools
import sys
import threading
import time
from concurrent.futures import Future
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import loadgen  # noqa: E402

BIG_SEED = 2**31 + 12345


def test_bench_loadgen_pool_order_is_seeded():
    a = list(itertools.islice(loadgen.pool_order(BIG_SEED, 64), 5000))
    b = list(itertools.islice(loadgen.pool_order(BIG_SEED, 64), 5000))
    c = list(itertools.islice(loadgen.pool_order(BIG_SEED + 1, 64), 5000))
    assert a == b and a != c
    assert min(a) == 0 and max(a) == 63


def test_bench_loadgen_periodic_bursts():
    due = loadgen.due_offsets({"bursts_per_s": 40.0, "burst": 12}, 1.0)
    assert len(due) == 40 * 12
    assert np.all(due[:12] == 0.0) and due[12] == pytest.approx(0.025)
    assert np.all(np.diff(due) >= 0) and due[-1] < 1.0


@pytest.mark.parametrize("rate", [0.0, -1.0, None])
def test_bench_loadgen_open_loop_needs_a_rate(rate):
    with pytest.raises(ValueError, match="bursts_per_s"):
        loadgen.due_offsets({"bursts_per_s": rate, "burst": 1}, 1.0)


def test_bench_loadgen_open_loop_times_from_due():
    """A server that resolves each request 30 ms after submit, and stalls
    its submitter for 50 ms once: requests due during the stall are late,
    and their latency counts the lateness."""
    stalled = []

    def submit(x):
        f = Future()
        if not stalled:
            stalled.append(1)
            time.sleep(0.05)
        threading.Timer(0.03, f.set_result, (x,)).start()
        return f

    pool = np.arange(8, dtype=np.float32)[:, None]
    tally = loadgen.Tally(time.perf_counter(), 5, 4)
    loadgen.drive(submit, {"loop": "open", "bursts_per_s": 100.0,
                           "burst": 2}, pool, seed=5, seconds=0.2,
                  batch_size=8, tally=tally)
    assert tally.wait_all(5)
    r = tally.arrays()
    assert len(r["due"]) == 40 and r["ok"].all()
    lat = r["done"] - r["due"]
    late = r["sub"] - r["due"]
    assert late[2] >= 0.04          # due at 10 ms, submitted after 50
    assert lat[2] >= 0.07           # 40+ ms late plus 30 ms of service
    assert np.all(lat >= 0.03 - 1e-3)
    assert len(tally.sample) == 4
    idx, row = tally.sample[0]
    assert row[0] == pool[idx][0]


def test_bench_loadgen_closed_loop_keeps_outstanding():
    inflight, peak, lock = [0], [0], threading.Lock()

    def submit(x):
        f = Future()
        with lock:
            inflight[0] += 1
            peak[0] = max(peak[0], inflight[0])

        def done():
            with lock:
                inflight[0] -= 1
            f.set_result(x)
        threading.Timer(0.01, done).start()
        return f

    pool = np.zeros((4, 1), np.float32)
    tally = loadgen.Tally(time.perf_counter(), 1, 2)
    loadgen.drive(submit, {"loop": "closed", "outstanding_batches": 2},
                  pool, seed=1, seconds=0.3, batch_size=3, tally=tally)
    assert tally.wait_all(5)
    assert peak[0] == 6
    assert len(tally.pool_idx) > 6 * 10   # ~30 rounds of 10 ms
    assert tally.arrays()["ok"].all()


def test_bench_loadgen_failed_request_recorded():
    def submit(x):
        f = Future()
        f.set_exception(ValueError("shed"))
        return f

    tally = loadgen.Tally(time.perf_counter(), 1, 2)
    tally.submit(submit, np.zeros((1, 1)), 0, 0.0)
    assert tally.wait_all(1)
    assert not tally.arrays()["ok"][0] and tally.sample == []
    assert "ValueError: shed" in tally.failed[0]
