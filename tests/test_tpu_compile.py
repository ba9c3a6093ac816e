"""Every Pallas kernel compiles for a TPU v5e at the sizes chip_smoke.py
runs, and the compiled program launches it as a ``tpu_custom_call``.

Nothing runs: the TPU compiler builds each program for a described,
unattached v5e, and refuses what the chip would refuse (an op Mosaic
cannot lower, a block that overflows VMEM) — what interpret mode on the
CPU cannot show.  The kernels are compiled through the ``ops`` wrappers
(the planner's entry points, which pick the block configs a plan uses),
with the wrappers steered off interpret mode inside each test.

The topology is described in a module fixture, never while a module is
imported: only one process may load the TPU library, and a test file
that decided at import whether its tests exist would give parallel test
workers different tests to collect.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

F32, I8 = jnp.float32, jnp.int8
B, N = 8, 1 << 16                     # a phase-A batch of rows
P, M, T = 1024, 16, 1024              # the station channelizer, per request
J, HOP = 64, 32                       # spectrogram / STFT window and hop
FRAMES = B * (N - J + 1)              # spectrogram frames in one batch
KERNEL_CALL = 'custom_call_target="tpu_custom_call"'

# name -> (wrapper over the ops entry point, argument shapes and dtypes)
CASES = {
    "matmul": (ops.matmul, [((8192, P), F32), ((P, P), F32)]),
    "matmul_int8": (ops.qmatmul,
                    [((8192, P), F32), ((P, P), I8), ((P,), F32)]),
    "dft": (lambda xr, xi, fr, fi: ops.dft(xr, xi, fr, fi),
            [((FRAMES, J), F32)] * 2 + [((J, J), F32)] * 2),
    "dft_int8": (ops.qdft, [((FRAMES, J), F32)]),
    "fir": (ops.fir, [((B, N), F32), ((31,), F32)]),
    "fir_int8": (ops.qfir, [((B, N), F32), ((31, 1), I8), ((1, 1), F32)]),
    "unfold": (lambda x: ops.unfold(x, J), [((B, N), F32)]),
    "overlap_add": (lambda f: ops.overlap_add(f, HOP),
                    [((B, (N - J) // HOP + 1, J), F32)]),
    "pfb": (ops.pfb, [((B, P * T), F32), ((M, P), F32)]),
    "pfb_p16": (ops.pfb, [((B, N), F32), ((8, 16), F32)]),
    "pfb_int8": (ops.qpfb, [((B, P * T), F32), ((M, P), I8), ((1, P), F32)]),
    "elementwise_chain": (
        lambda z: ops.fused_elementwise(z, (), (("abs2",), ("scale", 0.5))),
        [((B, T - M + 1, P), jnp.complex64)]),
}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled(monkeypatch):
    """Kernels compile for the TPU (no interpreter) and JAX's persistent
    cache stays out of it: an entry compiled for a described chip cannot
    be read back without one."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip, compiled):
    fn, args = CASES[name]
    specs = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
             for shape, dtype in args]
    text = jax.jit(fn).lower(*specs).compile().as_text()
    assert KERNEL_CALL in text, f"{name}: no compiled TPU kernel launch"
