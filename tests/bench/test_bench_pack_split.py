"""The reader of the service's split-pack counters
(``bench/metrics/pack_split_pct.py``) on hand-made snapshots, and in the
traced result line of the toy x4 cell and of a one-chip cell."""
import json
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness, trace_reduce  # noqa: E402
from tests.bench.test_bench_gather_view import GATHER_SCRIPT  # noqa: E402
from tests.bench.test_bench_harness import ENV, run, toy  # noqa: E402
from tests.bench.test_bench_x4 import stand_in_chips  # noqa: E402


def _run(open_, close):
    return SimpleNamespace(stats_open=open_, stats_close=close)


def _counts(sharded, whole):
    return {"pack_split": {"sharded": sharded, "whole": whole},
            "latency_ms": {}}


@pytest.mark.parametrize("open_,close,want", [
    # every batch of the window split over the mesh's shards
    (_counts(0, 0), _counts(760, 0), 100.0),
    # counts before the opening are left out
    (_counts(12, 5), _counts(112, 5), 100.0),
    (_counts(4, 2), _counts(7, 11), 25.0),
    # a one-device service packs every batch whole
    (_counts(0, 9), _counts(0, 1389), 0.0),
])
def test_bench_pack_split_reads_the_window(open_, close, want):
    read = harness.module("metrics", "pack_split_pct").read
    assert read(_run(open_, close)) == pytest.approx(want)


@pytest.mark.parametrize("open_,close", [
    # no batch packed in the window
    (_counts(7, 2), _counts(7, 2)),
    # a service that keeps no such counters
    ({"latency_ms": {}}, {"latency_ms": {}}),
    ({"latency_ms": {}}, _counts(3, 1)),
])
def test_bench_pack_split_reads_none_without_counts(open_, close):
    read = harness.module("metrics", "pack_split_pct").read
    assert read(_run(open_, close)) is None


def test_bench_pack_split_reads_100_on_the_x4_toy_cell():
    """The x4 cell at toy size on four virtual devices, traced: every
    batch of the window is packed one shard's block per thread."""
    env = dict(ENV, XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", GATHER_SCRIPT.format(
        root=str(ROOT))], env=env, cwd=ROOT, capture_output=True, text=True,
        timeout=400)
    assert p.returncode == 0, p.stderr[-3000:]
    r = json.loads(p.stdout.strip().splitlines()[-1])
    assert r["correct"]
    assert r["metrics"]["pack_split_pct"] == {"value": 100.0, "unit": "%"}


def test_bench_pack_split_is_left_out_on_one_chip(monkeypatch):
    monkeypatch.setattr(trace_reduce, "reduce", stand_in_chips(1))
    cell = toy(harness.load_cell("lofar_station_pfb.saturate"))
    _, res = run(cell, trace=True)
    assert res["correct"]
    assert "pack_split_pct" not in res["metrics"]
