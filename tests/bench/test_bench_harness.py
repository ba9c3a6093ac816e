"""The benchmark's harness on the CPU at toy size: a whole run but for the
look for a chip, the refusals of the command, cells found by name from
files a later change adds, and ``correct`` coming out false when the
timed path is broken underneath."""
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402

SEED = 2**31 + 77     # larger than 32 signed bits hold
ENV = {**os.environ, "JAX_PLATFORMS": "cpu",
       "PYTHONPATH": os.pathsep.join([str(ROOT / "src"), str(ROOT)])}
LIMIT = json.loads((ROOT / "bench/configs/lofar_station_pfb.json")
                   .read_text())["check"]["max_rel_err"]


def toy(cell: harness.Cell, lowering: str = "native", **config):
    """The cell at P=16, M=8 on 4096-sample requests: the same harness,
    service and check, sized for the CPU."""
    cell.config.update(args={"n_branches": 16, "n_taps": 8},
                       signal_len=4096, pool_requests=8, **config)
    cell.config["compile_options"] = dict(
        cell.config["compile_options"], lowering=lowering)
    if cell.traffic["loop"] == "open":
        cell.traffic["bursts_per_s"] = 50.0
    return cell


def run(cell, trace=False, hook=None, root=ROOT, seconds=1.0):
    return harness.run_cell(cell, seed=SEED, seconds=seconds, trace=trace,
                            t_start=time.perf_counter(), root=root,
                            require_chip=False,
                            peaks=harness.peaks_for("TPU v5 lite"),
                            hook=hook)


class Broken:
    """A bucket plan whose output is altered by ``fault``."""

    def __init__(self, plan, fault):
        self._plan, self._fault = plan, fault

    def __getattr__(self, name):
        return getattr(self._plan, name)

    def __call__(self, x):
        return self._fault(self._plan(x))


def breaking(fault):
    def hook(svc):
        svc.plans = {b: Broken(p, fault) for b, p in svc.plans.items()}
    return hook


def half_batch(out):
    """Only the first half of the batch computed; its rows stand in for
    the rest."""
    out = np.array(out)
    h = max(1, len(out) // 2)
    return np.concatenate([out[:h], out[:h]])[:len(out)]


def altered(out):
    """Every response off by 0.1% in one frame."""
    out = np.array(out)
    out[:, out.shape[1] // 2] *= 1.001
    return out


def test_bench_harness_limit_is_set():
    assert LIMIT is not None and 0 < LIMIT < 1e-3


def test_bench_harness_toy_saturate_runs_correct():
    cell = toy(harness.load_cell("lofar_station_pfb.saturate"), "pallas")
    load, res = run(cell)
    assert res["correct"], (load, res)
    assert res["attempted"] > 32 and res["failed"] == 0
    assert set(res["metrics"]) == {"samples_per_s", "setup_s"}
    assert load["compiles_in_window"] == 0
    assert load["compared"] == 32
    assert list(res)[-1] == "check"
    assert res["check"]["rel_err"]["value"] <= LIMIT
    assert res["device"]["platform"] == "cpu"


def test_bench_harness_toy_bursts_runs_correct():
    cell = toy(harness.load_cell("lofar_station_pfb.bursts"))
    load, res = run(cell)
    assert res["correct"], (load, res)
    assert set(res["metrics"]) == {"latency_p50_ms", "latency_p95_ms",
                                   "setup_s"}
    m = res["metrics"]
    assert 0 < m["latency_p50_ms"]["value"] <= m["latency_p95_ms"]["value"]
    # 50 bursts of 12 in the window, each one full and one partial bucket
    assert res["attempted"] == 600
    assert load["bucket_batches"][8] >= 50


@pytest.mark.parametrize("fault", [half_batch, altered])
def test_bench_harness_broken_path_is_incorrect(fault):
    cell = toy(harness.load_cell("lofar_station_pfb.saturate"))
    load, res = run(cell, hook=breaking(fault))
    assert res["failed"] == 0 and load["compared"] == 32
    assert not res["correct"]
    assert res["check"]["rel_err"]["value"] > 10 * LIMIT


def test_bench_harness_failed_requests_are_incorrect():
    def fail(out):
        raise RuntimeError("device lost")
    cell = toy(harness.load_cell("lofar_station_pfb.bursts"))
    _, res = run(cell, hook=breaking(fail), seconds=0.2)
    assert not res["correct"]
    assert res["failed"] == res["attempted"] > 0


X4_SCRIPT = r"""
import json, sys, time
sys.path.insert(0, {root!r})
import jax
import numpy as np
from tests.bench.test_bench_harness import toy, run, breaking, harness
assert jax.device_count() == 4
def exchange_left_out(out):
    # every shard's rows replaced by shard 0's, as if nothing was gathered
    out = np.asarray(out)
    return np.concatenate([out[:len(out) // 4]] * 4)
res = {{}}
for name, hook in (("sound", None), ("fault", breaking(exchange_left_out))):
    # the one-chip cell's configuration on a four-device batch mesh, 8
    # rows per device, under the saturate mix
    cell = harness.load_cell("lofar_station_pfb.saturate")
    cell.chips = 4
    cell.config["compile_options"] = dict(cell.config["compile_options"],
                                          mesh=4)
    cell = toy(cell, batch_size=32)
    load, r = run(cell, hook=hook)
    res[name] = (r["correct"], r["check"]["rel_err"]["value"],
                 load["bucket_batches"], r["attempted"])
print(json.dumps(res))
"""


def test_bench_harness_x4_on_four_virtual_devices():
    env = dict(ENV, XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", X4_SCRIPT.format(
        root=str(ROOT))], env=env, cwd=ROOT, capture_output=True, text=True,
        timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    ok, err, buckets, attempted = res["sound"]
    assert ok and err <= LIMIT and attempted >= 128
    assert buckets["32"] > 0
    ok, err, _, _ = res["fault"]
    assert not ok and err > 10 * LIMIT


def test_bench_harness_command_refuses_cpu():
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "lofar_station_pfb.saturate", "--seed", str(SEED),
                        "--seconds", "1", "--trace", "0"],
                       env=ENV, cwd=ROOT, capture_output=True, text=True,
                       timeout=120)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "not a TPU" in p.stderr


def test_bench_harness_command_refuses_bare_checkout(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's paths has
    no program to run."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in bench["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(ENV, PYTHONPATH="")
    p = subprocess.run([sys.executable, *bench["command"][1:], "--workload",
                        "lofar_station_pfb.saturate", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       env=env, cwd=tmp_path, capture_output=True, text=True,
                       timeout=120)
    assert p.returncode != 0 and p.stdout == ""


def test_bench_harness_finds_cells_added_as_files(tmp_path):
    """A configuration, a traffic mix and a metric added as new files and
    new BENCHMARK.json entries run without an edit to any existing file."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    conf = json.loads((ROOT / "bench/configs/lofar_station_pfb.json")
                      .read_text())
    conf.update(args={"n_branches": 32, "n_taps": 4}, signal_len=8192,
                pool_requests=4, batch_size=4)
    (tmp_path / "bench/configs/tiny_pfb.json").write_text(json.dumps(conf))
    (tmp_path / "bench/traffic/trickle.json").write_text(json.dumps(
        {"loop": "open", "bursts_per_s": 40, "burst": 3}))
    (tmp_path / "bench/metrics/served_requests.py").write_text(
        "import numpy as np\n\n\ndef read(run):\n"
        "    return float(np.sum(run.requests['ok']))\n")
    bench["configs"].append({"name": "tiny_pfb", "source": "test",
                             "file": "bench/configs/tiny_pfb.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny_pfb.trickle",
                               "config": "tiny_pfb", "traffic": "trickle",
                               "chips": 1, "why": "test"})
    bench["end_to_end"].append({"name": "served_requests", "unit": "1",
                                "better": "higher", "bound": 0.01,
                                "source": "host_clock",
                                "workloads": ["tiny_pfb.trickle"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = harness.load_cell("tiny_pfb.trickle", root=tmp_path)
    assert cell.config["args"] == {"n_branches": 32, "n_taps": 4}
    assert cell.traffic["burst"] == 3
    assert {m["name"] for m in cell.end_to_end} == {"served_requests",
                                                    "setup_s"}
    assert cell.per_layer == []
    _, res = run(cell, root=tmp_path)
    assert res["correct"]
    assert res["metrics"]["served_requests"]["value"] == res["attempted"]
    assert res["metrics"]["served_requests"]["unit"] == "1"
