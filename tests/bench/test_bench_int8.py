"""The station channelizer in the int8 tier (``lofar_station_pfb_int8``)
on the CPU: the cell as ``BENCHMARK.json`` gives it, run through the
harness at toy size, its check and int4 control, a plain numpy model of
the tier that the served path agrees with, and the two readers this
configuration brought (``int8_batch_pct``, ``pfb_kernel_roofline_pct``).

A CPU profiler trace has no ``/device:TPU:<n>`` planes, so the traced
runs here add a stand-in chip plane (``test_bench_x4.stand_in_chips``)
whose one op is named as the f32 kernel is on the chip."""
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import control, harness, trace_reduce  # noqa: E402
from bench.reference import pfb_power, pfb_power_int8  # noqa: E402
from tests.bench.test_bench_harness import (breaking, half_batch,  # noqa: E402
                                            run, toy)
from tests.bench.test_bench_trace import MS, hand_trace  # noqa: E402
from tests.bench.test_bench_x4 import stand_in_chips  # noqa: E402

CELL = "lofar_station_pfb_int8.saturate"
CONFIG = json.loads((ROOT / "bench/configs/lofar_station_pfb_int8.json")
                    .read_text())
LIMIT = CONFIG["check"]["max_rel_err"]
NEW = ("int8_batch_pct", "pfb_kernel_roofline_pct")


def tier_model(x, taps, qmax=127):
    """The int8 tier's power spectrum of one request ``x``, in float64
    numpy, from the tier's description: every operand a symmetric word
    ``clip(round(a / s), -qmax, qmax)`` with ``s = max|a| / qmax`` over
    its group (the taps per branch, each M-frame window per (frame,
    branch), each filtered frame per row, the real and imaginary DFT
    matrices per column), exact integer sums, one rescale per product."""
    m, p = taps.shape

    def q(a, axis):
        s = np.maximum(np.abs(a).max(axis=axis, keepdims=True),
                       1e-12) / qmax
        return np.clip(np.round(a / s), -qmax, qmax), s

    tq, ts = q(np.asarray(taps, np.float64), 0)
    frames = np.asarray(x, np.float64).reshape(-1, p)
    nf = len(frames) - m + 1
    win = np.stack([frames[i:i + nf] for i in range(m)])   # (M, t, P)
    wq, ws = q(win, 0)
    # frame t + i meets tap M - 1 - i
    y = np.einsum("itp,ip->tp", wq, tq[::-1]) * ws[0] * ts[0]
    yq, ys = q(y, -1)
    k = np.arange(p)
    ang = -2.0 * np.pi * np.outer(k, k) / p
    (cq, cs), (sq, ss) = q(np.cos(ang), 0), q(np.sin(ang), 0)
    zr, zi = (yq @ cq) * ys * cs, (yq @ sq) * ys * ss
    return zr ** 2 + zi ** 2


def rel(got, want):
    return float(np.linalg.norm(np.asarray(got, np.float64) - want)
                 / np.linalg.norm(want))


def f32_in_place(svc):
    """Every bucket plan rebuilt at f32, under the int8 service."""
    from repro import graph
    t = svc.tenants["default"]
    opts = t.options.replace(precision="f32")
    svc.plans = {b: graph.compile(t.graph, {t.graph.inputs[0]:
                                            (b, t.signal_len)}, options=opts)
                 for b in t.buckets}


def test_bench_int8_config_is_the_one_chip_file_in_the_int8_tier():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    (w,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (w["config"], w["traffic"], w["chips"]) == (
        "lofar_station_pfb_int8", "saturate", 1)
    sources = [c["source"] for c in bench["configs"]]
    assert len(set(sources)) == len(sources)
    one = json.loads((ROOT / "bench/configs/lofar_station_pfb.json")
                     .read_text())
    assert CONFIG["pipeline"] == "pfb_power_int8"
    assert CONFIG["compile_options"] == dict(one["compile_options"],
                                             precision="int8")
    assert 1e-3 < LIMIT < 0.1 and CONFIG["reduced"] == []
    assert CONFIG["assumed"][:len(one["assumed"])] == one["assumed"]
    assert len(CONFIG["assumed"]) > len(one["assumed"])
    assert CONFIG["source"] != one["source"]
    assert CONFIG["guarantee"] != one["guarantee"]
    rest = {"pipeline", "compile_options", "source", "guarantee", "check",
            "assumed"}
    assert {k: v for k, v in CONFIG.items() if k not in rest} == \
        {k: v for k, v in one.items() if k not in rest}
    assert CONFIG["check"] == dict(one["check"], max_rel_err=LIMIT)
    # the reference is pfb_power's float64 PFB, unchanged
    assert pfb_power_int8.reference is pfb_power.reference
    cell = harness.load_cell(CELL)
    assert {m["name"] for m in cell.end_to_end} == {"samples_per_s",
                                                    "setup_s"}
    assert set(NEW) <= {m["name"] for m in cell.per_layer}


@pytest.mark.parametrize("lowering", ["native", "pallas"])
def test_bench_int8_toy_runs_correct_in_the_int8_tier(monkeypatch,
                                                      lowering):
    monkeypatch.setattr(trace_reduce, "reduce", stand_in_chips(1))
    cell = toy(harness.load_cell(CELL), lowering)
    load, res = run(cell, trace=True)
    assert res["correct"], (load, res)
    assert res["failed"] == 0 and load["compared"] == 32
    assert res["check"]["rel_err"]["value"] <= LIMIT
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert m["int8_batch_pct"] == 100.0
    assert 0 < m["pfb_kernel_roofline_pct"] <= 100


def test_bench_int8_f32_in_place_passes_the_check_but_not_the_counter(
        monkeypatch):
    """The limitation and the guard: an f32 result is more accurate than
    the tier's, so the check cannot see a fallback; the counter can."""
    monkeypatch.setattr(trace_reduce, "reduce", stand_in_chips(1))
    cell = toy(harness.load_cell(CELL))
    _, res = run(cell, trace=True, hook=f32_in_place)
    assert res["correct"], res
    assert res["check"]["rel_err"]["value"] < 1e-5
    assert res["metrics"]["int8_batch_pct"]["value"] == 0.0


def test_bench_int8_half_batch_is_incorrect():
    cell = toy(harness.load_cell(CELL))
    load, res = run(cell, hook=breaking(half_batch))
    assert res["failed"] == 0 and load["compared"] == 32
    assert not res["correct"]
    assert res["check"]["rel_err"]["value"] > 10 * LIMIT


def test_bench_int8_control_above_limit_and_program_below():
    """At the cell's channel and tap count on short requests: the int4
    control reads above the limit on every seed, the program below it."""
    from repro import graph
    short = dict(CONFIG, signal_len=1 << 15, pool_requests=8,
                 check=dict(CONFIG["check"], sample=8))
    for r in control.readings(short, [2**31 + 5, 6, 7], batch=8):
        assert r["compared"] == 8
        assert r["rel_err"] > 5 * LIMIT, r
    pool = harness.make_pool(short, 2**31 + 5)
    plan = graph.compile(graph.build_pfb_power(**short["args"]),
                         {"x": pool.shape},
                         options=graph.CompileOptions(**short[
                             "compile_options"]))
    out = np.asarray(plan(jnp.asarray(pool)))
    err = harness.compare(list(enumerate(out)), pool, short)
    assert err < LIMIT


def test_bench_int8_control_is_the_model_at_four_bits():
    args = {"n_branches": 64, "n_taps": 16}
    xs = np.random.default_rng(3).standard_normal((2, 1 << 14),
                                                  dtype=np.float32)
    taps = pfb_power.prototype_taps(64, 16)
    got = np.asarray(pfb_power_int8.control(args)(xs))
    for x, g in zip(xs, got):
        # float32 scales against the model's float64 ones
        assert rel(g, tier_model(x, taps, qmax=7)) < 1e-5
        assert rel(g, pfb_power.reference(x, args)) > 5 * LIMIT


@pytest.mark.parametrize("lowering", ["native", "pallas"])
def test_bench_int8_served_path_agrees_with_the_numpy_model(lowering):
    """The served path against the model, over a batch of 8 requests of
    2^16 samples at P=64, M=16.  Tolerance 2e-4, relative L2 over the
    batch: a word whose value lies on a rounding boundary rounds one way
    under the served path's float32 scales and the other way under the
    model's float64 ones.  Each such flip moves its request's power by
    ~1.3e-4 here (at the cell's P=1024, 4.5e-5 to 5.6e-5); about half the
    requests hold one, so the batch reads ~1e-4 at most."""
    from repro import graph
    p, m, n = 64, 16, 1 << 16
    args = {"n_branches": p, "n_taps": m}
    xs = np.random.default_rng(2**31 + 9).standard_normal((8, n),
                                                          dtype=np.float32)
    svc = graph.PipelineService(
        graph.build_pfb_power(p, m), n, batch_size=8,
        options=graph.CompileOptions(lowering=lowering, precision="int8"))
    try:
        futs = [svc.submit(x) for x in xs]
        assert svc.flush() == 1
        got = np.stack([f.result(timeout=0) for f in futs])
        assert svc.stats()["tier"] == {"int8": 1, "fallback": 0}
    finally:
        svc.close()
    taps = pfb_power.prototype_taps(p, m)
    want = np.stack([tier_model(x, taps) for x in xs])
    assert rel(got, want) < 2e-4
    for x, g in zip(xs, got):
        assert rel(g, pfb_power.reference(x, args)) < LIMIT


# ---------------------------------------------------------------------------
# the readers, on hand-built inputs
# ---------------------------------------------------------------------------
def _tiers(int8, fallback):
    return {"tier": {"int8": int8, "fallback": fallback}}


@pytest.mark.parametrize("open_,close,want", [
    (_tiers(0, 0), _tiers(40, 0), 100.0),
    # batches before the opening are left out
    (_tiers(6, 2), _tiers(36, 12), 75.0),
    (_tiers(0, 0), _tiers(0, 9), 0.0),
    (_tiers(3, 1), _tiers(3, 1), None),
    ({}, {}, None),
])
def test_bench_int8_batch_pct_reads_the_window(open_, close, want):
    read = harness.module("metrics", "int8_batch_pct").read
    got = read(SimpleNamespace(stats_open=open_, stats_close=close))
    assert got == (None if want is None else pytest.approx(want))


KERNEL_HLO = ("%pfb_fused_int8.1 = (f32[8,1024,1024]{2,1,0}, "
              "f32[8,1024,1024]{2,1,0}) custom-call(f32[8,1536,1024] %pad)")


def _run(trace, done=4, window_s=0.1):
    return SimpleNamespace(
        trace=trace, config=CONFIG, window_s=window_s,
        peaks=harness.peaks_for("TPU v5 lite"),
        requests={"ok": np.ones(done + 1, bool),
                  "done": np.r_[np.full(done, window_s / 2), 2 * window_s]})


def test_bench_pfb_kernel_roofline_on_a_hand_built_trace():
    planes = hand_trace()
    for plane in planes[:2]:
        ops = plane["lines"]["XLA Ops"]
        plane["lines"]["XLA Ops"] = [
            [KERNEL_HLO if name == "pfb" else name, s, d]
            for name, s, d in ops]
    trace = trace_reduce.reduce(planes)
    assert trace["device_ops"][0][0] == "pfb_fused_int8.1 f32[8,1024,1024]"
    read = harness.module("metrics", "pfb_kernel_roofline_pct").read
    # P=1024, M=16, 2^20 samples: 1009 frames; bytes 4 * 2^20 + 8 * 1009 *
    # 1024 bound the request (15.21 us); the kernel ran 30 + 10 ms on two
    # chips, and 4 requests completed in the window (the fifth after it)
    least = (4 * 2**20 + 8 * 1009 * 1024) / 819e9
    assert least > 1009 * (2 * 16 * 1024 + 2.5 * 1024 * 10) / 197e12
    assert read(_run(trace)) == pytest.approx(100 * 4 * least / (40 * MS
                                                                   / 1e9))
    # the f32 kernel's name reads alike; other ops are not counted
    f32 = [["pfb_fused.1 f32[8,1024,1024]", 0.02], ["abs2", 0.5]]
    assert read(_run(dict(trace, device_ops=f32))) == pytest.approx(
        100 * 4 * least / 0.04)
    assert read(_run(dict(trace, device_ops=[["abs2", 0.5]]))) is None
    assert read(_run(None)) is None


def test_bench_pfb_kernel_roofline_on_the_recorded_v5e_excerpt():
    """One batch of 8 through the f32 kernel in 30 ms of a v5e trace: the
    share is the kernel's, not the window's."""
    planes = json.loads((Path(__file__).parent / "data"
                         / "trace_v5e_saturate_30ms.json").read_text())
    trace = trace_reduce.reduce(planes)
    kernel_s = trace["device_ops"][0][1]
    read = harness.module("metrics", "pfb_kernel_roofline_pct").read
    got = read(_run(trace, done=8, window_s=trace["window_s"]))
    least = (4 * 2**20 + 8 * 1009 * 1024) / 819e9
    assert got == pytest.approx(100 * 8 * least / kernel_s)
    assert 0.1 < got < 100
