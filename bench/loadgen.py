"""The one load generator.  A traffic mix is a data file,
``bench/traffic/<mix>.json``, that this module reads:

* closed loop: ``{"loop": "closed", "outstanding_batches": k}``: k times
  the service's batch size requests are kept outstanding; a new one is
  submitted the moment one completes, until the window closes.
* open loop: ``{"loop": "open", "bursts_per_s": r, "burst": b}``.
  ``b`` requests are due at once every ``1/r`` seconds.  Each request is
  timed from its due time, so a stall counts against every request
  queued behind it, and how late each submit ran behind its due time is
  recorded.

The seed picks which pool entry each request carries; every seed offers
the same work.
"""
from __future__ import annotations

import functools
import math
import random
import threading
import time

import numpy as np


def due_offsets(traffic: dict, seconds: float) -> np.ndarray:
    """Due time of every request of an open loop, in seconds after the
    window opens, in order."""
    rate = traffic.get("bursts_per_s")
    if not rate or rate <= 0:
        raise ValueError(f"open loop needs bursts_per_s > 0, got {rate!r}")
    starts = np.arange(int(math.ceil(seconds * rate)) + 1) / rate
    starts = starts[starts < seconds]
    return np.repeat(starts, int(traffic.get("burst", 1)))


def pool_order(seed: int, pool_size: int, chunk: int = 4096):
    """Endless pool indices, drawn from the seed."""
    rng = np.random.default_rng([seed, 1])
    while True:
        yield from rng.integers(0, pool_size, size=chunk).tolist()


class Tally:
    """Per-request books of one window, on the host clock: due, submit and
    done times (seconds after the window opens), success, and a seeded
    reservoir sample of delivered responses for the correctness check."""

    def __init__(self, t_open: float, seed: int, sample: int):
        self.t_open = t_open
        self.pool_idx: list[int] = []
        self.due: list[float] = []
        self.sub: list[float] = []
        self.done: dict[int, float] = {}
        self.failed: dict[int, str] = {}
        self.sample: list[tuple[int, object]] = []
        self._seen = 0
        self._cap = int(sample)
        self._rng = random.Random(seed)
        self._lock = threading.Lock()
        self._all_done = threading.Condition(self._lock)

    def on_done(self, k: int, fut, release=None) -> None:
        t = time.perf_counter() - self.t_open
        err = fut.exception()
        with self._lock:
            self.done[k] = t
            if err is not None:
                self.failed[k] = f"{type(err).__name__}: {err}"
            else:
                # reservoir sampling (Algorithm R): keeps a reference to
                # the row, no copy on the batcher thread
                self._seen += 1
                if len(self.sample) < self._cap:
                    self.sample.append((self.pool_idx[k], fut.result()))
                else:
                    j = self._rng.randrange(self._seen)
                    if j < self._cap:
                        self.sample[j] = (self.pool_idx[k], fut.result())
            self._all_done.notify_all()
        if release is not None:
            release()

    def submit(self, submit, pool, idx: int, due: float, release=None):
        k = len(self.pool_idx)
        self.pool_idx.append(idx)
        self.due.append(due)
        self.sub.append(time.perf_counter() - self.t_open)
        fut = submit(pool[idx])
        fut.add_done_callback(functools.partial(self.on_done, k,
                                                release=release))

    def wait_all(self, timeout: float) -> bool:
        """Wait until every submitted request has resolved."""
        end = time.perf_counter() + timeout
        with self._lock:
            while len(self.done) < len(self.pool_idx):
                left = end - time.perf_counter()
                if left <= 0:
                    return False
                self._all_done.wait(left)
        return True

    def arrays(self) -> dict:
        n = len(self.pool_idx)
        done = np.full(n, np.nan)
        ok = np.zeros(n, bool)
        with self._lock:
            for k, t in self.done.items():
                done[k] = t
                ok[k] = k not in self.failed
        return {"due": np.asarray(self.due), "sub": np.asarray(self.sub),
                "done": done, "ok": ok}


def drive(submit, traffic: dict, pool: np.ndarray, *, seed: int,
          seconds: float, batch_size: int, tally: Tally) -> None:
    """Offer the mix to ``submit`` from ``tally.t_open`` until the window
    closes ``seconds`` later.  Returns when the last request is submitted;
    requests still in flight are the caller's to wait for."""
    order = pool_order(seed, len(pool))
    t_open = tally.t_open
    if traffic["loop"] == "open":
        for off in due_offsets(traffic, seconds):
            wait = t_open + off - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            tally.submit(submit, pool, next(order), float(off))
        left = t_open + seconds - time.perf_counter()
        if left > 0:
            time.sleep(left)
    elif traffic["loop"] == "closed":
        slots = threading.Semaphore(int(traffic["outstanding_batches"])
                                    * int(batch_size))
        t_close = t_open + seconds
        while True:
            left = t_close - time.perf_counter()
            if left <= 0 or not slots.acquire(timeout=left):
                return
            now = time.perf_counter()
            if now >= t_close:
                return
            tally.submit(submit, pool, next(order), now - t_open,
                         release=slots.release)
    else:
        raise ValueError(f"loop={traffic['loop']!r}: expected open or closed")
