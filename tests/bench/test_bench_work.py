"""The work count behind ``plan_roofline_pct`` and the reader's arithmetic."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402


def station():
    return json.loads((ROOT / "bench/configs/lofar_station_pfb.json")
                      .read_text())


def test_bench_work_matches_hand_count():
    w = harness.module("work", "pfb_power").work(station())
    # 2^20 samples in, 1009 frames x 1024 powers out, float32
    assert w["bytes"] == 8_327_168
    # FIR 1009*1024*16*2 + FFT 1009*2.5*1024*10 + |z|^2 1009*1024*3
    assert w["flops"] == 61_992_960


def test_bench_work_is_memory_bound_on_v5e():
    w = harness.module("work", "pfb_power").work(station())
    peaks = harness.peaks_for("TPU v5 lite")
    t_flops = w["flops"] / peaks["bf16_flops_per_s"]
    t_bytes = w["bytes"] / peaks["hbm_bytes_per_s"]
    assert t_bytes > t_flops
    assert t_bytes == pytest.approx(10.17e-6, rel=1e-3)


def test_bench_peaks_table_names_its_source():
    peaks = json.loads((ROOT / "bench/peaks.json").read_text())
    v5e = peaks["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["int8_ops_per_s"] == 393e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["hbm_bytes"] == 16e9
    assert "TPU v5e" in v5e["source"]


def test_bench_unknown_device_kind_raises():
    with pytest.raises(harness.NoChip, match="peaks"):
        harness.peaks_for("TPU v9 imaginary")


def test_bench_roofline_reader():
    """100 requests of 10.17 us least time each over 2 chips busy 1 ms
    each: 50.8%.  A request completed after the window does not count."""
    run = harness.Run(
        config={}, traffic={}, signal_len=1 << 20, window_s=1.0,
        setup_s=0.0,
        requests={"done": np.r_[np.full(100, 0.5), 1.5],
                  "ok": np.ones(101, bool)},
        stats_open={}, stats_close={},
        work=harness.module("work", "pfb_power").work(station()),
        peaks=harness.peaks_for("TPU v5 lite"),
        trace={"busy_s": [1e-3, 1e-3], "window_s": 1.0})
    pct = harness.module("metrics", "plan_roofline_pct").read(run)
    assert pct == pytest.approx(100 * 100 * 8_327_168 / 819e9 / 2e-3)
    idle = harness.module("metrics", "device_idle_pct").read(run)
    assert idle == pytest.approx(99.9)
    run.trace = None
    assert harness.module("metrics", "plan_roofline_pct").read(run) is None
