"""Plain reference of the PFB power pipeline in the int8 tier, and its
control one tier down (int4).

* :func:`reference` is the float64 PFB power of ``pfb_power``'s reference,
  unchanged: the prototype taps and the filter bank are written out there,
  independent of the program.
* :func:`control` is the tier's pipeline with 4-bit words (``qmax`` 7) in
  place of 8-bit ones, at the tier's scale granularity: per-branch scales
  on the taps, per-(frame, branch) scales on each M-tap window of the
  frames, per-row scales on the filtered frames before the DFT and
  per-column scales on the real and imaginary DFT matrices.  Each scale
  is ``max|a| / qmax`` (at least 1e-12 / qmax) and each word
  ``clip(round(a / scale), -qmax, qmax)``; the products accumulate exactly
  in int32 and are rescaled once in float32.  Written in plain
  ``jax.numpy`` with integer contractions, so it computes the same on the
  CPU as on the chip.
"""
from __future__ import annotations

import functools

import numpy as np

from bench.reference.pfb_power import prototype_taps, reference

__all__ = ["reference", "control"]

CONTROL_QMAX = 7      # int4: one tier below the configuration's int8


def _quantize(a, axis, qmax):
    """Symmetric words of ``a`` (as floats) and their scales, one scale
    per slice along ``axis``."""
    import jax.numpy as jnp
    amax = jnp.max(jnp.abs(a), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-12) / qmax
    return jnp.clip(jnp.round(a / scale), -qmax, qmax), scale


def control(args: dict):
    """A jitted function of a batch ``xs`` (b, n): the tier's pipeline
    with 4-bit words.  It returns a float32 array
    (b, n/P - M + 1, P)."""
    import jax
    import jax.numpy as jnp

    taps = prototype_taps(args["n_branches"], args["n_taps"])
    m, p = taps.shape
    qmax = CONTROL_QMAX
    k = np.arange(p)
    ang = -2.0 * np.pi * np.outer(k, k) / p
    tq, ts = _quantize(jnp.asarray(taps), 0, qmax)           # per branch
    fr, sr = _quantize(jnp.asarray(np.cos(ang), jnp.float32), 0, qmax)
    fi, si = _quantize(jnp.asarray(np.sin(ang), jnp.float32), 0, qmax)
    tq = tq.astype(jnp.int32)
    fr, fi = fr.astype(jnp.int8), fi.astype(jnp.int8)
    dot = functools.partial(jnp.matmul, preferred_element_type=jnp.int32)

    @jax.jit
    def run(xs):
        frames = xs.reshape(xs.shape[0], -1, p).astype(jnp.float32)
        nf = frames.shape[1] - m + 1
        # window (t, p) holds frames t .. t+M-1 of branch p; frame t+i
        # meets tap M-1-i.  A running max over the M shifted views, so no
        # (M, b, t, P) stack is held
        wins = [frames[:, i:i + nf] for i in range(m)]
        amax = jnp.zeros_like(wins[0])
        for w in wins:
            amax = jnp.maximum(amax, jnp.abs(w))
        wscale = jnp.maximum(amax, 1e-12) / qmax
        acc = sum(jnp.clip(jnp.round(w / wscale), -qmax, qmax)
                  .astype(jnp.int32) * tq[m - 1 - i]
                  for i, w in enumerate(wins))
        y = acc.astype(jnp.float32) * wscale * ts
        yq, yscale = _quantize(y, -1, qmax)                   # per row
        yq = yq.astype(jnp.int8)
        zr = dot(yq, fr).astype(jnp.float32) * yscale * sr
        zi = dot(yq, fi).astype(jnp.float32) * yscale * si
        return zr ** 2 + zi ** 2

    return run
