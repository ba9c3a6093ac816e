"""Sliding-window FIR kernel — TINA §4.3 on TPU.

Direct-form cross-correlation out[b, t] = Σ_k x[b, t+k] · kern[k]
('valid'; the public wrapper handles flip/same/full by pre-flipping and
padding).

Halo handling: the output is blocked (bb, bn) and each output block
needs input [j·bn, j·bn + bn + K − 1).  Overlapping BlockSpecs can't
tile an array, so the kernel takes the SAME input array through two
blocked views — block j and block j+1 — and concatenates them in VMEM
(requires K − 1 ≤ bn; the wrapper right-pads x by one extra block).
This is the standard TPU halo-exchange-in-VMEM pattern and keeps every
access a clean (bb, bn) tile in HBM.

The tap loop reads window k as a rotate of the concatenated tile by a
traced amount (:func:`window`): Mosaic lowers that, where it refuses
the dynamic slice of a traced loop index.  The loop itself stays a
``fori_loop``, so the kernel's size does not grow with K and every
step's multiply-add rounds as it always did.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import tune

# Scalar taps live whole in SMEM: the kernel reads them one scalar at a
# time, which a dynamic lane index into a VMEM block cannot do on the TPU.
_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)


def window(xcat: jax.Array, k, size: int, axis: int) -> jax.Array:
    """``xcat[k:k + size]`` along ``axis`` for a traced offset ``k``:
    rotate the tile left by ``k``, then take a static slice."""
    n = xcat.shape[axis]
    rolled = pltpu.roll(xcat, (n - k) % n, axis)
    return jax.lax.slice_in_dim(rolled, 0, size, axis=axis)

# ctx: {"k": taps, "n": signal length, "rows": flattened batch rows}.
# Hard constraint is the halo: each output block's window must fit the
# two adjacent input blocks in VMEM (K − 1 ≤ bn); the wrapper's padding
# makes every other shape work.
TUNE_SPACE = tune.register(tune.TuneSpace(
    kernel="fir",
    params=("bb", "bn"),
    candidates=lambda ctx: tuple(
        {"bb": bb, "bn": bn}
        for bb in (8, 16) for bn in (256, 512, 1024, 2048)),
    valid=lambda cfg, ctx: (
        cfg["bb"] >= 1 and cfg["bn"] >= 1
        and ctx["k"] - 1 <= cfg["bn"]
        # x block + halo block + out block + f32 accumulator, all (bb, bn)
        and 4 * (4 * cfg["bb"] * cfg["bn"] + ctx["k"]) <= tune.VMEM_BUDGET),
    default=lambda ctx: {"bb": 8,
                         "bn": max(512, tune.pow2_at_least(ctx["k"] - 1))},
))


def _fir_kernel(x_ref, xnext_ref, k_ref, o_ref, *, ktaps: int):
    xcat = jnp.concatenate([x_ref[...], xnext_ref[...]], axis=1)  # (bb, 2bn)
    bb, bn = o_ref.shape

    def body(k, acc):
        win = window(xcat, k, bn, axis=1)
        return acc + k_ref[k] * win.astype(jnp.float32)

    acc = jax.lax.fori_loop(
        0, ktaps, body, jnp.zeros((bb, bn), jnp.float32))
    o_ref[...] = acc.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bb", "bn", "interpret"))
def fir_valid(x: jax.Array, kern: jax.Array, *, bb: int = 8, bn: int = 512,
              interpret: bool = False) -> jax.Array:
    """x: (B, N); kern: (K,) with K − 1 ≤ bn.  Returns (B, N − K + 1).
    B % bb == 0 and N % bn == 0 required (ops.py pads); the tail block
    reads one block past the valid region, so x is padded by bn here."""
    b, n = x.shape
    k = kern.shape[0]
    assert b % bb == 0 and n % bn == 0, (x.shape, (bb, bn))
    assert k - 1 <= bn, f"taps {k} exceed halo block {bn}"
    nout = n - k + 1
    nblocks = pl.cdiv(nout, bn)
    xp = jnp.pad(x, ((0, 0), (0, 2 * bn)))  # halo for the last block
    out = pl.pallas_call(
        functools.partial(_fir_kernel, ktaps=k),
        grid=(b // bb, nblocks),
        in_specs=[
            pl.BlockSpec((bb, bn), lambda i, j: (i, j)),
            pl.BlockSpec((bb, bn), lambda i, j: (i, j + 1)),
            _SMEM,
        ],
        out_specs=pl.BlockSpec((bb, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((b, nblocks * bn), x.dtype),
        interpret=interpret,
    )(xp, xp, kern.astype(jnp.float32))
    return out[:, :nout]


# int8 variant: activations quantize per WINDOW inside the kernel (each
# output position's K-sample window gets its own scale — exactly
# quantize.quantize_symmetric(windows, axis=-1), recomputed in VMEM so
# no unfolded int8 copy ever hits HBM), then an int32 MAC against the
# int8 taps and one f32 (scale · tap_scale) rescale at the epilogue.
# Working set: xcat (2·bb·bn f32) + amax/scale/acc (3·bb·bn) + out.
TUNE_SPACE_INT8 = tune.register(tune.TuneSpace(
    kernel="fir_int8",
    params=("bb", "bn"),
    candidates=lambda ctx: tuple(
        {"bb": bb, "bn": bn}
        for bb in (8, 16) for bn in (256, 512, 1024, 2048)),
    valid=lambda cfg, ctx: (
        cfg["bb"] >= 1 and cfg["bn"] >= 1
        and ctx["k"] - 1 <= cfg["bn"]
        and 4 * (6 * cfg["bb"] * cfg["bn"] + ctx["k"]) <= tune.VMEM_BUDGET),
    default=lambda ctx: {"bb": 8,
                         "bn": max(512, tune.pow2_at_least(ctx["k"] - 1))},
))


def _fir_int8_kernel(x_ref, xnext_ref, tq_ref, ts_ref, o_ref, *, ktaps: int):
    xcat = jnp.concatenate([x_ref[...], xnext_ref[...]], axis=1)  # (bb, 2bn)
    bb, bn = o_ref.shape

    def win(k):
        return window(xcat, k, bn, axis=1).astype(jnp.float32)

    # Pass 1: per-window amax (window t = samples [t, t+K)) — the exact
    # f32 max quantize_symmetric(axis=-1) computes on unfolded rows.
    def amax_body(k, amax):
        return jnp.maximum(amax, jnp.abs(win(k)))

    amax = jax.lax.fori_loop(
        0, ktaps, amax_body, jnp.zeros((bb, bn), jnp.float32))
    scale = jnp.maximum(amax, 1e-12) * (1.0 / 127.0)

    # Pass 2: int32 MAC of the quantized window against the int8 taps
    # (held in SMEM widened to int32, the width of an SMEM word).
    def mac_body(k, acc):
        q = jnp.clip(jnp.round(win(k) / scale), -127, 127).astype(jnp.int32)
        return acc + q * tq_ref[k]

    acc = jax.lax.fori_loop(
        0, ktaps, mac_body, jnp.zeros((bb, bn), jnp.int32))
    # Same left-associated (acc · x_scale) · tap_scale as quantize.qmatmul.
    o_ref[...] = acc.astype(jnp.float32) * scale * ts_ref[0]


@functools.partial(jax.jit, static_argnames=("bb", "bn", "interpret"))
def fir_valid_int8(x: jax.Array, tq: jax.Array, ts: jax.Array, *,
                   bb: int = 8, bn: int = 512,
                   interpret: bool = False) -> jax.Array:
    """x: (B, N) f32; tq: (1, K) int8 quantized (pre-flipped) taps with
    scalar scale ts (1, 1).  Returns f32 (B, N − K + 1), bit-identical
    to quantize.qfir's unfold + int8 matmul on the same pack."""
    b, n = x.shape
    k = tq.shape[1]
    assert tq.dtype == jnp.int8, tq.dtype
    assert ts.shape == (1, 1), ts.shape
    assert b % bb == 0 and n % bn == 0, (x.shape, (bb, bn))
    assert k - 1 <= bn, f"taps {k} exceed halo block {bn}"
    nout = n - k + 1
    nblocks = pl.cdiv(nout, bn)
    xp = jnp.pad(x, ((0, 0), (0, 2 * bn)))  # halo for the last block
    out = pl.pallas_call(
        functools.partial(_fir_int8_kernel, ktaps=k),
        grid=(b // bb, nblocks),
        in_specs=[
            pl.BlockSpec((bb, bn), lambda i, j: (i, j)),
            pl.BlockSpec((bb, bn), lambda i, j: (i, j + 1)),
            _SMEM,
            _SMEM,
        ],
        out_specs=pl.BlockSpec((bb, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((b, nblocks * bn), jnp.float32),
        interpret=interpret,
    )(xp, xp, tq.reshape(k).astype(jnp.int32), ts.reshape(1))
    return out[:, :nout]
