"""Per-kernel shape/dtype sweeps: Pallas (interpret) vs pure-jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

RNG = np.random.default_rng(42)


def rnd(shape, dtype=np.float32):
    x = RNG.standard_normal(shape).astype(np.float32)
    return jnp.asarray(x, dtype=dtype)


def tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("m,l,n", [(128, 128, 128), (8, 64, 32),
                                   (300, 100, 50), (1, 1, 1), (257, 129, 255)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matmul(m, l, n, dtype):
    x, y = rnd((m, l), dtype), rnd((l, n), dtype)
    got = ops.matmul(x, y)
    want = ref.ref_matmul(x, y)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol(dtype))


def test_matmul_batched():
    x, y = rnd((3, 5, 40, 24)), rnd((24, 17))
    got = ops.matmul(x, y)
    want = jnp.einsum("...ij,jk->...ik", x, y)
    assert got.shape == (3, 5, 40, 17)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("shape", [(4, 4), (128, 128), (3, 300), (2, 5, 64)])
@pytest.mark.parametrize("op,oracle", [
    (ops.elementwise_mult, ref.ref_elementwise_mult),
    (ops.elementwise_add, ref.ref_elementwise_add),
])
def test_elementwise(shape, op, oracle):
    x, y = rnd(shape), rnd(shape)
    np.testing.assert_allclose(op(x, y), oracle(x, y), rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("b,n", [(4, 64), (128, 128), (3, 200), (1, 1024)])
@pytest.mark.parametrize("variant", ["3mult", "4mult"])
def test_dft_kernel(b, n, variant):
    xr, xi = rnd((b, n)), rnd((b, n))
    lk = np.outer(np.arange(n), np.arange(n))
    f = np.exp(-2j * np.pi * lk / n)
    fr, fi = jnp.asarray(f.real, jnp.float32), jnp.asarray(f.imag, jnp.float32)
    zr, zi = ops.dft(xr, xi, fr, fi, variant=variant)
    wr, wi = ref.ref_dft(xr, xi, fr, fi)
    np.testing.assert_allclose(zr, wr, rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(zi, wi, rtol=1e-3, atol=1e-3)


def test_dft_vs_fft():
    """End-to-end: TINA pallas DFT == numpy FFT."""
    from repro.core import functions
    x = rnd((4, 256))
    got = functions.dft(x, lowering="pallas")
    np.testing.assert_allclose(got, np.fft.fft(np.asarray(x)),
                               rtol=1e-3, atol=1e-3)
    back = functions.idft(got, lowering="native")
    np.testing.assert_allclose(np.asarray(back).real, np.asarray(x),
                               rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("b,n,k", [(2, 1024, 8), (8, 600, 31), (1, 2048, 129),
                                   (3, 64, 64)])
def test_fir_kernel(b, n, k):
    x, kern = rnd((b, n)), rnd((k,))
    got = ops.fir(x, kern)
    want = ref.ref_fir_valid(x, kern)
    assert got.shape == (b, n - k + 1)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("mode", ["valid", "same", "full"])
def test_fir_modes_match_numpy(mode):
    from repro.core import functions
    x, taps = rnd((500,)), rnd((13,))
    got = functions.fir(x, taps, mode=mode, lowering="pallas")
    want = np.convolve(np.asarray(x), np.asarray(taps), mode=mode)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("b,n,j", [(2, 512, 16), (1, 100, 3), (4, 2048, 128)])
def test_unfold_kernel(b, n, j):
    x = rnd((b, n))
    got = ops.unfold(x, j)
    want = ref.ref_unfold(x, j)
    assert got.shape == (b, n - j + 1, j)
    np.testing.assert_allclose(got, want)


@pytest.mark.parametrize("b,t,p,m", [(2, 256, 64, 8), (1, 300, 16, 12),
                                     (2, 128, 128, 4)])
def test_pfb_fir_kernel(b, t, p, m):
    frames = rnd((b, t, p))
    taps = jnp.asarray(RNG.standard_normal((m, p)), jnp.float32)
    got = ops.pfb_fir(frames, taps)
    want = ref.ref_pfb_fir(frames, taps)
    assert got.shape == (b, t - m + 1, p)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("p,m,nframes", [(32, 8, 64), (64, 4, 300)])
def test_pfb_fused_kernel(p, m, nframes):
    from repro.core import pfb as pfb_mod
    x = rnd((2, p * nframes))
    taps = jnp.asarray(pfb_mod.pfb_window(p, m), jnp.float32)
    got = ops.pfb(x, taps)
    wr, wi = ref.ref_pfb(x, taps)
    np.testing.assert_allclose(np.real(got), wr, rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.imag(got), wi, rtol=1e-3, atol=1e-3)


def test_pfb_fused_matches_unfused():
    """The fused Pallas PFB == the paper's layer-by-layer composition."""
    from repro.core import pfb as pfb_mod
    x = rnd((64 * 128,))
    taps = jnp.asarray(pfb_mod.pfb_window(64, 8), jnp.float32)
    fused = pfb_mod.pfb(x, taps, lowering="pallas")
    unfused = pfb_mod.pfb(x, taps, lowering="conv")
    np.testing.assert_allclose(np.asarray(fused), np.asarray(unfused),
                               rtol=1e-3, atol=1e-3)


def test_matmul_int8_accumulator_headroom_at_largest_tile():
    """Worst-case ±127 inputs at the largest tuned int8 tile must not
    wrap the int32 accumulator.  Saturated operands give |acc| =
    K·127·127 (wraparound needs K ≥ 2^31/127² ≈ 133k — far above any
    tuned depth); the kernel output must equal an int64 numpy
    accumulation rescaled in f32, bitwise."""
    from repro.core import quantize
    from repro.kernels import tune as ktune
    m, n, k = 512, 512, 2048
    cfg = max(ktune.space("matmul_int8").configs({"m": m, "n": n, "k": k}),
              key=lambda c: c["bm"] * c["bn"] * c["bk"])
    # all-equal rows quantize to exactly +127; random signs keep the
    # products saturated at ±16129 while exercising both acc directions
    signs = np.where(RNG.random((m, k)) < 0.5, -1.0, 1.0).astype(np.float32)
    x = jnp.asarray(7.0 * signs)
    wq = jnp.asarray(np.where(RNG.random((k, n)) < 0.5, -127, 127)
                     .astype(np.int8))
    w_scale = jnp.ones((n,), jnp.float32)
    xq, sx = quantize.quantize_symmetric(x, axis=-1)
    assert int(jnp.abs(xq).min()) == 127          # saturated as intended
    acc = np.asarray(xq, np.int64) @ np.asarray(wq, np.int64)
    assert np.abs(acc).max() < 2**31              # int32 headroom holds
    want = acc.astype(np.float32) * np.asarray(sx) * np.asarray(w_scale)
    got = np.asarray(ops.qmatmul(x, wq, w_scale, **cfg))
    assert np.array_equal(got, want)


@pytest.mark.parametrize("backend,interpret", [("cpu", True), ("tpu", False),
                                               ("gpu", None)])
def test_interpret_mode_only_on_cpu(backend, interpret, monkeypatch):
    """Kernels compile on the TPU and are interpreted on the CPU; any
    other backend raises rather than interpreting in silence."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if interpret is None:
        with pytest.raises(RuntimeError, match="neither"):
            ops._interpret()
    else:
        assert ops._interpret() is interpret
