"""The reader of the service's staging-buffer counters
(``bench/metrics/pack_reuse_pct.py``) on hand-made snapshots."""
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402


def _run(open_, close):
    return SimpleNamespace(stats_open=open_, stats_close=close)


def _counts(reused, allocated):
    return {"pack_buffers": {"reused": reused, "allocated": allocated},
            "latency_ms": {}}


@pytest.mark.parametrize("open_,close,want", [
    # warm-up reused nothing; the window allocated 2 and reused 398
    (_counts(0, 0), _counts(398, 2), 99.5),
    # counts before the opening are left out
    (_counts(10, 4), _counts(110, 4), 100.0),
    (_counts(5, 1), _counts(5, 3), 0.0),
])
def test_bench_pack_reuse_reads_the_window(open_, close, want):
    read = harness.module("metrics", "pack_reuse_pct").read
    assert read(_run(open_, close)) == pytest.approx(want)


@pytest.mark.parametrize("open_,close", [
    # no batch packed in the window
    (_counts(7, 2), _counts(7, 2)),
    # a service that keeps no such counters
    ({"latency_ms": {}}, {"latency_ms": {}}),
    ({"latency_ms": {}}, _counts(3, 1)),
])
def test_bench_pack_reuse_reads_none_without_counts(open_, close):
    read = harness.module("metrics", "pack_reuse_pct").read
    assert read(_run(open_, close)) is None
