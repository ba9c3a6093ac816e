"""Plain reference of the PFB power pipeline, and its lower-precision control.

Independent of the program: the prototype filter and the polyphase filter
bank are written out here (after the textbook construction, Price 2021),
so a change to the program's own filter shows as incorrect.

* :func:`reference` is the power spectrum in float64 numpy, from the taps
  rounded to float32 as the configuration serves them.
* :func:`control` is the same pipeline one precision step below what the
  configuration states: float32 with each DFT product taken in three
  bfloat16 passes (``Precision.HIGH``), where the configuration asks for
  full float32 products (``Precision.HIGHEST``).  The passes are written
  out, so the control computes the same on the CPU as on the chip.
"""
from __future__ import annotations

import numpy as np


def prototype_taps(n_branches: int, n_taps: int) -> np.ndarray:
    """Hamming-windowed sinc split over the branches: (M, P) with
    taps[m, p] = h(m P + p), rounded to float32."""
    p, m = n_branches, n_taps
    n = np.arange(p * m, dtype=np.float64)
    h = np.sinc(n / p - m / 2.0) * np.hamming(p * m)
    return h.reshape(m, p).astype(np.float32)


def reference(x: np.ndarray, args: dict) -> np.ndarray:
    """Power spectrum of one request ``x`` (n,) in float64: frame into P
    branches, FIR each branch with its M taps (valid part), DFT across
    the branches, |.|^2.  Returns (n/P - M + 1, P)."""
    taps = prototype_taps(args["n_branches"], args["n_taps"]).astype(
        np.float64)
    m, p = taps.shape
    frames = np.asarray(x, np.float64).reshape(-1, p)
    nf = frames.shape[0] - m + 1
    y = np.zeros((nf, p))
    for k in range(m):
        y += frames[k:k + nf] * taps[m - 1 - k]
    z = np.fft.fft(y, axis=-1)
    return z.real ** 2 + z.imag ** 2


def control(args: dict):
    """A jitted function of a batch ``xs`` (b, n): the pipeline in
    float32, with the DFT products in three bfloat16 passes
    (hi*hi + hi*lo + lo*hi) and float32 accumulation.  It returns a
    float32 array (b, n/P - M + 1, P)."""
    import jax
    import jax.numpy as jnp

    taps = prototype_taps(args["n_branches"], args["n_taps"])
    m, p = taps.shape
    k = np.arange(p)
    ang = -2.0 * np.pi * np.outer(k, k) / p
    w = np.concatenate([np.cos(ang), np.sin(ang)], axis=1).astype(np.float32)

    def split(a):
        # reduce_precision, not a round trip through bfloat16: XLA may
        # keep excess precision across a convert pair and drop the split
        hi = jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)
        lo = jax.lax.reduce_precision(a - hi, exponent_bits=8, mantissa_bits=7)
        return hi.astype(jnp.bfloat16), lo.astype(jnp.bfloat16)

    def dot3(a, b):
        (ah, al), (bh, bl) = split(a), split(b)
        one = lambda u, v: jnp.matmul(u, v, preferred_element_type=jnp.float32)
        return one(ah, bh) + one(ah, bl) + one(al, bh)

    @jax.jit
    def run(xs):
        frames = xs.reshape(xs.shape[0], -1, p)
        nf = frames.shape[1] - m + 1
        y = sum(frames[:, i:i + nf] * taps[m - 1 - i] for i in range(m))
        z = dot3(y, jnp.asarray(w))
        return z[..., :p] ** 2 + z[..., p:] ** 2

    return run
