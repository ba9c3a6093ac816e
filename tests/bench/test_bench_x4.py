"""The four-chip station channelizer cell (``lofar_station_pfb_x4``) on
the CPU at toy size: the cell as ``BENCHMARK.json`` gives it, run on four
virtual devices through the harness, a planted fault in the gather, and
the two readers of the mesh path (``fetch_shard_ms``,
``chip_busy_spread_pct``).

A CPU profiler trace has no ``/device:TPU:<n>`` planes, so the traced
runs here add stand-in planes, one per chip of the cell, busy inside the
trace's real window; everything else in the trace is the run's own."""
import json
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness, trace_reduce  # noqa: E402
from tests.bench.test_bench_harness import ENV, LIMIT, run, toy  # noqa: E402
from tests.bench.test_bench_trace import hand_trace  # noqa: E402

CELL = "lofar_station_pfb_x4.saturate"
NEW = ("fetch_shard_ms", "chip_busy_spread_pct")


def stand_in_chips(n: int):
    """``trace_reduce.reduce`` over the run's trace plus ``n`` chip
    planes, chip i busy for (50 + i)% of the window."""
    real = trace_reduce.reduce

    def reduce(planes, top=10):
        t0, t1 = trace_reduce.window(planes)
        chips = [{"name": f"/device:TPU:{i}", "lines": {"XLA Ops": [
            ["pfb_fused.1", t0, (t1 - t0) * (0.5 + 0.01 * i)]]}}
            for i in range(n)]
        return real(planes + chips, top)
    return reduce


X4_SCRIPT = r"""
import json, sys
sys.path.insert(0, {root!r})
import jax
import numpy as np
from bench import harness, trace_reduce
from tests.bench.test_bench_harness import toy, run, breaking
from tests.bench.test_bench_x4 import CELL, stand_in_chips
assert jax.device_count() == 4

def shards_1_and_2_swapped(out):
    out = np.array(out)
    q = len(out) // 4
    out[q:2 * q], out[2 * q:3 * q] = out[2 * q:3 * q].copy(), out[q:2 * q].copy()
    return out

res = {{}}
for name, hook, trace in (("sound", None, False),
                          ("fault", breaking(shards_1_and_2_swapped), False),
                          ("traced", None, True)):
    cell = toy(harness.load_cell(CELL))
    if trace:
        trace_reduce.reduce = stand_in_chips(cell.chips)
    load, r = run(cell, trace=trace, hook=hook)
    res[name] = {{"correct": r["correct"], "attempted": r["attempted"],
                  "failed": r["failed"],
                  "rel_err": r["check"]["rel_err"]["value"],
                  "bucket_batches": load["bucket_batches"],
                  "metrics": r["metrics"]}}
print(json.dumps(res))
"""


@pytest.fixture(scope="module")
def x4():
    env = dict(ENV, XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", X4_SCRIPT.format(
        root=str(ROOT))], env=env, cwd=ROOT, capture_output=True, text=True,
        timeout=400)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_bench_x4_config_is_the_one_chip_file_on_a_mesh():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    (w,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (w["config"], w["traffic"], w["chips"]) == (
        "lofar_station_pfb_x4", "saturate", 4)
    one = json.loads((ROOT / "bench/configs/lofar_station_pfb.json")
                     .read_text())
    x4 = harness.load_cell(CELL).config
    assert x4["compile_options"] == dict(one["compile_options"], mesh=4)
    assert x4["batch_size"] == 32 and x4["reduced"] == []
    # the one-chip lines, the batch's read per chip, then the host's own
    inherited = [a + " per chip" if a == "service batch of 8 requests"
                 else a for a in one["assumed"]]
    assert x4["assumed"][:len(inherited)] == inherited
    assert inherited != one["assumed"]
    # its own source: the same paper, at the station's count of paths
    assert x4["source"] != one["source"]
    assert x4["source"].startswith("van Haarlem et al. 2013, A&A 556, A2")
    assert "96 receiver paths" in x4["source"]
    assert x4["source"].endswith("station polyphase filter bank, "
                                 "1024-point FFT with 16 taps per branch")
    rest = {"source", "compile_options", "batch_size", "assumed"}
    assert {k: v for k, v in x4.items() if k not in rest} == \
        {k: v for k, v in one.items() if k not in rest}


def test_bench_x4_toy_runs_correct(x4):
    r = x4["sound"]
    assert r["correct"], r
    assert r["failed"] == 0 and r["attempted"] >= 128
    assert r["rel_err"] <= LIMIT
    assert r["bucket_batches"]["32"] > 0
    assert set(r["metrics"]) == {"samples_per_s", "setup_s"}


def test_bench_x4_swapped_shards_are_incorrect(x4):
    r = x4["fault"]
    assert r["failed"] == 0
    assert not r["correct"]
    assert r["rel_err"] >= 10 * LIMIT


def test_bench_x4_traced_reads_the_mesh_metrics(x4):
    r = x4["traced"]
    assert r["correct"], r
    m = {k: v["value"] for k, v in r["metrics"].items()}
    assert set(NEW) <= set(m)
    assert 0 < m["fetch_shard_ms"] <= m["fetch_ms"]
    # stand-in chips busy 50, 51, 52 and 53% of the window
    assert m["chip_busy_spread_pct"] == pytest.approx(300 / 51.5, rel=1e-6)


@pytest.mark.parametrize("trace", [False, True])
def test_bench_x4_readers_read_none_on_one_chip(monkeypatch, trace):
    runs = []

    class Kept(harness.Run):
        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            runs.append(self)

    monkeypatch.setattr(harness, "Run", Kept)
    monkeypatch.setattr(trace_reduce, "reduce", stand_in_chips(1))
    cell = toy(harness.load_cell("lofar_station_pfb.saturate"))
    _, res = run(cell, trace=trace)
    assert res["correct"]
    (r,) = runs
    assert r.stats_close["latency_ms"]["fetch_shard"]["count"] == 0
    assert r.stats_close["shards"] == {}
    for name in NEW:
        assert harness.module("metrics", name).read(r) is None
        assert name not in res["metrics"]


def _snap(count, mean):
    return {"latency_ms": {"fetch_shard": {"count": count, "mean": mean}}}


@pytest.mark.parametrize("open_,close,want", [
    (_snap(0, None), _snap(400, 3.0), 3.0),
    # records before the opening are left out
    (_snap(100, 1.0), _snap(300, 2.5), 3.25),
    (_snap(8, 2.0), _snap(8, 2.0), None),
    ({"latency_ms": {}}, {"latency_ms": {}}, None),
])
def test_bench_x4_fetch_shard_reads_the_window(open_, close, want):
    read = harness.module("metrics", "fetch_shard_ms").read
    got = read(SimpleNamespace(stats_open=open_, stats_close=close))
    assert got == (None if want is None else pytest.approx(want))


def test_bench_x4_chip_busy_spread_on_recorded_traces():
    read = harness.module("metrics", "chip_busy_spread_pct").read
    # two chips busy 40 and 10 ms of 100: (40 - 10) / 25
    two = trace_reduce.reduce(hand_trace())
    assert read(SimpleNamespace(trace=two)) == pytest.approx(120.0)
    # the recorded v5e excerpt has one chip
    one = trace_reduce.reduce(json.loads(
        (Path(__file__).parent / "data" / "trace_v5e_saturate_30ms.json")
        .read_text()))
    assert read(SimpleNamespace(trace=one)) is None
    assert read(SimpleNamespace(trace=None)) is None
    idle = {"busy_s": [0.0, 0.0], "window_s": 1.0}
    assert read(SimpleNamespace(trace=idle)) is None
