"""Unfold kernel — TINA §4.4 as pure data movement.

The paper implements Y(i, j) = X(i + j) as a standard conv with an
identity kernel: N·J² MACs for an op with zero arithmetic.  The TPU
adaptation (DESIGN.md §2) makes unfold what it really is — an
HBM→VMEM→HBM tiling:  each grid step loads two adjacent (bb, bt) input
blocks (frame-axis halo, see fir.py) and writes the window tile with J
shifted VMEM copies.  Zero MXU FLOPs.

The kernel writes (bb, J, bt) tiles — each shift a full lane-dense row —
and the wrapper swaps the last two axes: writing a lane column of a
(bb, bt, J) tile is a reshape Mosaic does not lower.  Loops over taps
are unrolled in Python, since Mosaic lowers static slices but not the
dynamic slice of a traced loop index.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import tune

# ctx: {"j": window, "n": signal length, "rows"}.  Halo: J − 1 ≤ bt;
# VMEM: two (bb, bt) input views plus the (bb, J, bt) window tile —
# the output tile dominates, so large windows force small bt.
TUNE_SPACE = tune.register(tune.TuneSpace(
    kernel="unfold",
    params=("bb", "bt"),
    candidates=lambda ctx: tuple(
        {"bb": bb, "bt": bt} for bb in (8,) for bt in (256, 512, 1024, 2048)),
    valid=lambda cfg, ctx: (
        cfg["bb"] >= 1 and cfg["bt"] >= 1
        and ctx["j"] - 1 <= cfg["bt"]
        and 4 * cfg["bb"] * cfg["bt"] * (ctx["j"] + 2) <= tune.VMEM_BUDGET),
    default=lambda ctx: {"bb": 8,
                         "bt": max(512, tune.pow2_at_least(ctx["j"] - 1))},
))


def _unfold_kernel(x_ref, xnext_ref, o_ref, *, window: int):
    bt = o_ref.shape[2]
    xcat = jnp.concatenate([x_ref[...], xnext_ref[...]], axis=1)  # (bb, 2bt)
    for j in range(window):
        o_ref[:, j, :] = xcat[:, j:j + bt]


@functools.partial(jax.jit, static_argnames=("window", "bb", "bt", "interpret"))
def unfold(x: jax.Array, window: int, *, bb: int = 8, bt: int = 512,
           interpret: bool = False) -> jax.Array:
    """x: (B, N) -> (B, N − J + 1, J).  B % bb == 0, N % bt == 0 (ops.py
    pads); J − 1 ≤ bt."""
    b, n = x.shape
    j = window
    assert b % bb == 0 and n % bt == 0, (x.shape, (bb, bt))
    assert j - 1 <= bt, f"window {j} exceeds halo block {bt}"
    nout = n - j + 1
    tblocks = pl.cdiv(nout, bt)
    xp = jnp.pad(x, ((0, 0), (0, 2 * bt)))
    out = pl.pallas_call(
        functools.partial(_unfold_kernel, window=j),
        grid=(b // bb, tblocks),
        in_specs=[
            pl.BlockSpec((bb, bt), lambda i, t: (i, t)),
            pl.BlockSpec((bb, bt), lambda i, t: (i, t + 1)),
        ],
        out_specs=pl.BlockSpec((bb, j, bt), lambda i, t: (i, 0, t)),
        out_shape=jax.ShapeDtypeStruct((b, j, tblocks * bt), x.dtype),
        interpret=interpret,
    )(xp, xp)
    return jnp.swapaxes(out[:, :, :nout], 1, 2)


# overlap_add — unfold's adjoint (the transposed conv the paper would
# use).  ctx: {"j": window, "hop", "k": j // hop overlapping frames,
# "t": frames, "rows": batch rows}.  Halo: an output frame t sums
# frames [t, t + K), so K − 1 ≤ bt; VMEM: two (bb, bt, J) frame views
# plus the (bb, bt, hop) accumulator and output.
OLA_TUNE_SPACE = tune.register(tune.TuneSpace(
    kernel="overlap_add",
    params=("bb", "bt"),
    candidates=lambda ctx: tuple(
        {"bb": bb, "bt": bt} for bb in (8, 16)
        for bt in (64, 128, 256, 512, 1024)),
    valid=lambda cfg, ctx: (
        cfg["bb"] >= 1 and cfg["bt"] >= 1
        and ctx["k"] - 1 <= cfg["bt"]
        and 4 * cfg["bb"] * cfg["bt"]
        * (2 * ctx["j"] + 2 * ctx["hop"]) <= tune.VMEM_BUDGET),
    default=lambda ctx: {"bb": 8,
                         "bt": max(64, tune.pow2_at_least(ctx["k"] - 1))},
))


def _overlap_add_kernel(x_ref, xnext_ref, o_ref, *, k: int, hop: int):
    bb, bt, _ = x_ref.shape
    xcat = jnp.concatenate([x_ref[...], xnext_ref[...]], axis=1)  # (bb, 2bt, J)

    # Ascending-m adds onto a zero accumulator reproduce the native
    # path's  acc = frames[t] tail; acc += ...  f32 summation order.
    acc = jnp.zeros((bb, bt, hop), jnp.float32)
    for m in range(k):
        seg = xcat[:, m:m + bt, (k - 1 - m) * hop:(k - m) * hop]
        acc = acc + seg.astype(jnp.float32)
    o_ref[...] = acc.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("hop", "bb", "bt", "interpret"))
def overlap_add(frames: jax.Array, hop: int, *, bb: int = 8, bt: int = 128,
                interpret: bool = False) -> jax.Array:
    """frames: (B, T, J) with hop | J -> (B, T − K + 1, hop) where
    K = J / hop: output frame t = Σ_m frames[t + m, (K−1−m)·hop : (K−m)·hop]
    (the 'valid' overlap-add used by core.functions).  B % bb == 0 and
    T % bt == 0 required (ops.py pads); K − 1 ≤ bt."""
    b, t, j = frames.shape
    assert j % hop == 0, (j, hop)
    k = j // hop
    assert b % bb == 0 and t % bt == 0, (frames.shape, (bb, bt))
    assert k - 1 <= bt, f"overlap frames {k} exceed halo block {bt}"
    nt = t - k + 1
    tblocks = pl.cdiv(nt, bt)
    xp = jnp.pad(frames, ((0, 0), (0, 2 * bt), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_overlap_add_kernel, k=k, hop=hop),
        grid=(b // bb, tblocks),
        in_specs=[
            pl.BlockSpec((bb, bt, j), lambda i, tt: (i, tt, 0)),
            pl.BlockSpec((bb, bt, j), lambda i, tt: (i, tt + 1, 0)),
        ],
        out_specs=pl.BlockSpec((bb, bt, hop), lambda i, tt: (i, tt, 0)),
        out_shape=jax.ShapeDtypeStruct((b, tblocks * bt, hop), frames.dtype),
        interpret=interpret,
    )(xp, xp)
    return out[:, :nt]
