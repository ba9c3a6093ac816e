"""``bench/gather_probe.py`` times every way it pulls a sharded batch
back, and its reused host array holds each shard's rows in their place:
run on four virtual CPU devices at a toy shape.  ``main`` refuses the
CPU, as every chip tool of the benchmark does."""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import gather_probe, harness  # noqa: E402

SCRIPT = textwrap.dedent("""
    import json, sys
    import jax
    import numpy as np
    sys.path.insert(0, sys.argv[1])
    from bench import gather_probe
    times, keep = gather_probe.probe((8, 3, 5), jax.devices()[:4], 3)
    want = np.full((8, 3, 5), 2.5, np.float32)
    print(json.dumps({"counts": {k: len(v) for k, v in times.items()},
                      "finite": all(t >= 0 for v in times.values()
                                    for t in v),
                      "placed": bool(np.array_equal(keep, want))}))
    """)


def test_bench_gather_probe_times_each_way_on_four_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", SCRIPT, str(ROOT)],
                       capture_output=True, text=True, env=env, timeout=120)
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["counts"] == {"asarray": 3, "wait_first": 3, "wait_rest": 9,
                             "fill_fresh": 3, "fill_reused": 3}
    assert out["finite"] and out["placed"]


def test_bench_gather_probe_refuses_the_cpu():
    with pytest.raises(harness.NoChip):
        gather_probe.main(["--config", "lofar_station_pfb_x4",
                           "--repeats", "1"])
