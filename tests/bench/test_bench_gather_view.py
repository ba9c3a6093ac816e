"""The reader of the service's mesh gather counters
(``bench/metrics/gather_view_pct.py``) on hand-made snapshots, and in
the traced result line of the toy x4 cell and of a one-chip cell."""
import json
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness, trace_reduce  # noqa: E402
from tests.bench.test_bench_harness import ENV, run, toy  # noqa: E402
from tests.bench.test_bench_x4 import stand_in_chips  # noqa: E402


def _run(open_, close):
    return SimpleNamespace(stats_open=open_, stats_close=close)


def _counts(views, assembled):
    return {"gather": {"views": views, "assembled": assembled},
            "latency_ms": {}}


@pytest.mark.parametrize("open_,close,want", [
    # every batch of the window delivered from shard views
    (_counts(0, 0), _counts(111, 0), 100.0),
    # counts before the opening are left out
    (_counts(12, 3), _counts(112, 3), 100.0),
    (_counts(4, 1), _counts(7, 4), 50.0),
    (_counts(9, 0), _counts(9, 5), 0.0),
])
def test_bench_gather_view_reads_the_window(open_, close, want):
    read = harness.module("metrics", "gather_view_pct").read
    assert read(_run(open_, close)) == pytest.approx(want)


@pytest.mark.parametrize("open_,close", [
    # no batch served on a mesh in the window (a one-chip service)
    (_counts(0, 0), _counts(0, 0)),
    (_counts(6, 2), _counts(6, 2)),
    # a service that keeps no such counters
    ({"latency_ms": {}}, {"latency_ms": {}}),
    ({"latency_ms": {}}, _counts(3, 0)),
])
def test_bench_gather_view_reads_none_without_mesh_batches(open_, close):
    read = harness.module("metrics", "gather_view_pct").read
    assert read(_run(open_, close)) is None


GATHER_SCRIPT = r"""
import json, sys
sys.path.insert(0, {root!r})
from bench import harness, trace_reduce
from tests.bench.test_bench_harness import toy, run
from tests.bench.test_bench_x4 import CELL, stand_in_chips
cell = toy(harness.load_cell(CELL))
trace_reduce.reduce = stand_in_chips(cell.chips)
load, r = run(cell, trace=True)
print(json.dumps({{"correct": r["correct"], "metrics": r["metrics"]}}))
"""


def test_bench_gather_view_reads_100_on_the_x4_toy_cell():
    """The x4 cell at toy size on four virtual devices, traced: every
    batch of the window is delivered from shard views."""
    env = dict(ENV, XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", GATHER_SCRIPT.format(
        root=str(ROOT))], env=env, cwd=ROOT, capture_output=True, text=True,
        timeout=400)
    assert p.returncode == 0, p.stderr[-3000:]
    r = json.loads(p.stdout.strip().splitlines()[-1])
    assert r["correct"]
    assert r["metrics"]["gather_view_pct"] == {"value": 100.0, "unit": "%"}


def test_bench_gather_view_is_left_out_on_one_chip(monkeypatch):
    monkeypatch.setattr(trace_reduce, "reduce", stand_in_chips(1))
    cell = toy(harness.load_cell("lofar_station_pfb.saturate"))
    _, res = run(cell, trace=True)
    assert res["correct"]
    assert "gather_view_pct" not in res["metrics"]
