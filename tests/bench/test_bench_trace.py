"""The reduction from a profiler trace to busy time, idle share, top ops
and labelled idle gaps, on a hand-built trace."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import trace_reduce  # noqa: E402

MS = 1_000_000  # ns


def hand_trace():
    """Window 0-100 ms.  Chip 0: ops at 10-30 and 20-40 (overlapping) and
    90-120 (crosses the close); chip 1: one op at 50-60.  The host packs
    from 40 to 85 ms inside a dispatch span that covers the window."""
    return [
        {"name": "/device:TPU:0", "lines": {
            "XLA Ops": [["pfb", 10 * MS, 20 * MS], ["abs2", 20 * MS, 20 * MS],
                        ["pfb", 90 * MS, 30 * MS]],
            "XLA Modules": [["jit_raw", 0, 200 * MS]]}},
        {"name": "/device:TPU:1", "lines": {
            "XLA Ops": [["pfb", 50 * MS, 10 * MS]]}},
        {"name": "/host:CPU", "lines": {
            "batcher": [["dispatch", 0, 100 * MS], ["pack", 40 * MS, 45 * MS]],
            "main": [[trace_reduce.WINDOW, 0, 100 * MS]]}},
    ]


def test_bench_trace_busy_is_union_clipped_to_window():
    r = trace_reduce.reduce(hand_trace())
    assert r["window_s"] == pytest.approx(0.1)
    # chip 0: [10, 40) U [90, 100) = 40 ms; chip 1: 10 ms
    assert r["busy_s"] == pytest.approx([0.040, 0.010])
    idle = 100 * (1 - np.mean(r["busy_s"]) / r["window_s"])
    assert idle == pytest.approx(75.0)


def test_bench_trace_top_ops_are_mean_per_chip():
    ops = dict(trace_reduce.reduce(hand_trace())["device_ops"])
    # pfb: (20 + 10 clipped) on chip 0, 10 on chip 1 -> 20 ms per chip
    assert ops["pfb"] == pytest.approx(0.020)
    assert ops["abs2"] == pytest.approx(0.010)
    assert list(ops) == ["pfb", "abs2"]


def test_bench_trace_idle_gaps_longest_first_with_host_label():
    gaps = trace_reduce.reduce(hand_trace())["idle_gaps"]
    # chip 1 idle 60-100 (40 ms) and 0-50 (50 ms); chip 0 40-90 (50 ms)
    assert [g[1] for g in gaps[:3]] == pytest.approx([0.05, 0.05, 0.04])
    labels = {g[0] for g in gaps}
    # 40-90 on chip 0 is mostly packing: the shortest span covering half
    assert "batcher: pack" in labels
    assert all(g[1] > 0 for g in gaps)


def test_bench_trace_merge():
    iv = np.array([[5, 6], [0, 2], [1, 3], [3, 4]], float)
    assert trace_reduce.merge(iv).tolist() == [[0, 4], [5, 6]]
    assert trace_reduce.merge(np.zeros((0, 2))).shape == (0, 2)


def test_bench_trace_without_window_or_device_raises():
    planes = hand_trace()
    with pytest.raises(RuntimeError, match="no /device:TPU"):
        trace_reduce.reduce(planes[2:])
    planes[2]["lines"]["main"] = []
    with pytest.raises(RuntimeError, match=trace_reduce.WINDOW):
        trace_reduce.reduce(planes)


def test_bench_trace_recorded_v5e_excerpt():
    """30 ms of a traced saturate window on a TPU v5e (PR 12's probe):
    one batch of 8 through the fused PFB kernel, then the host packing
    the next batch while the chip idles."""
    planes = json.loads((Path(__file__).parent / "data"
                         / "trace_v5e_saturate_30ms.json").read_text())
    r = trace_reduce.reduce(planes)
    assert r["window_s"] == pytest.approx(0.03)
    assert 0 < r["busy_s"][0] < r["window_s"]
    name, t = r["device_ops"][0]
    assert name == "pfb_fused.1 f32[8,1024,1024]"
    assert t > 0.9 * r["busy_s"][0]
    label, gap = r["idle_gaps"][0]
    assert label.endswith("_pack") and gap > 0.01
    assert all(g[1] > 1e-6 for g in r["idle_gaps"])


def test_bench_trace_op_names():
    assert trace_reduce.op_name(
        "%pfb_fused.1 = (f32[8,1024,1024]{2,1,0:T(8,128)S(1)}, f32[8]{0}) "
        "custom-call(f32[8,1536,1024]{2,1,0} %pad.2)") == \
        "pfb_fused.1 f32[8,1024,1024]"
    assert trace_reduce.op_name("%copy.1 = f32[8,1009,1024]{2,0,1} copy("
                                "f32[8,1009,1024] %r)") == \
        "copy.1 f32[8,1009,1024]"
    assert trace_reduce.op_name("jit_raw(12)") == "jit_raw(12)"
