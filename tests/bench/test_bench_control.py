"""The correctness check's control comes out incorrect, and the program
correct, at the cell's channel and tap count on short requests: the
control is the reference put in the program's place with its DFT products
in three bfloat16 passes (``Precision.HIGH``), one step below the full
float32 products (``HIGHEST``) that the configuration states.  On the chip
``bench/control.py`` reads the same at the cell's own size."""
import json
import sys
from pathlib import Path

import jax.numpy as jnp
import numpy as np

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import control, harness  # noqa: E402

CONFIG = json.loads((ROOT / "bench/configs/lofar_station_pfb.json")
                    .read_text())
SHORT = dict(CONFIG, signal_len=1 << 15, pool_requests=8,
             check=dict(CONFIG["check"], sample=8))
LIMIT = CONFIG["check"]["max_rel_err"]


def test_bench_control_reads_above_limit():
    for r in control.readings(SHORT, [2**31 + 5, 6, 7], batch=8):
        assert r["compared"] == 8
        assert r["rel_err"] > LIMIT, r


def test_bench_control_program_reads_below_limit():
    from repro import graph
    pool = harness.make_pool(SHORT, 2**31 + 5)
    plan = graph.compile(graph.build_pfb_power(**SHORT["args"]),
                         {"x": pool.shape},
                         options=graph.CompileOptions(lowering="pallas"))
    out = np.asarray(plan(jnp.asarray(pool)))
    err = harness.compare(list(enumerate(out)), pool, SHORT)
    assert err < LIMIT
