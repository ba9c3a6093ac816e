"""Fused polyphase filter bank kernel — TINA §5.2 on TPU, fused.

The paper composes the PFB as separate NN layers (bank of FIR convs →
DFT pointwise conv) through GPU HBM, and names memory as TINA's main
limitation.  This kernel fuses both stages: each grid step computes a
(bt, P) tile of subfiltered frames in VMEM (VPU: M shifted
multiply-accumulates against the taps) and immediately feeds it to the
branch-axis DFT matmul (MXU) — the intermediate y_p(n') never touches
HBM.

Halo over the frame axis uses the two-adjacent-blocks pattern
(see fir.py); requires M − 1 ≤ bt.  Tap k's frames are a sublane
rotate of the concatenated tile (:func:`repro.kernels.fir.window`).

Grid: (B, T/bt, P/bn) for ``order="tc"`` (time-major, the historical
walk) or (B, P/bn, T/bt) for ``order="ct"`` (column-major: reuses the
F-matrix block across the whole frame axis before moving on).  No state
crosses grid steps, so both walks produce identical output — order is a
pure locality knob the tuner measures.  The FIR tile is recomputed per
DFT column block — M·bt·P VPU MACs versus bt·P·bn MXU MACs, negligible
for M ≪ P — a deliberate recompute-over-memory trade (DESIGN.md §2).

:func:`pfb_fused_int8` is the true-integer variant: the frontend
quantizes each (frame, branch) M-tap window in VMEM (per-window scales,
int32 MAC against the int8 prototype), the DFT stage re-quantizes the
subfiltered rows and hits the MXU with int8 × int8 → int32 dots, and
each output applies its f32 rescale once at the store.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import tune
from repro.kernels.fir import window

_ORDERS = ("tc", "ct")


def _grid_and_maps(order: str, b, tblocks, cblocks):
    """Grid + (x, xnext, per-op, col-op, out) index-map factories.
    ``per-op`` blocks ignore the grid (taps); ``col-op`` blocks follow
    the DFT column index c; x/xnext/out follow (batch, frame, column)."""
    if order == "ct":
        return ((b, cblocks, tblocks),
                (lambda i, c, j: (i, j, 0), lambda i, c, j: (i, j + 1, 0),
                 lambda i, c, j: (0, 0), lambda i, c, j: (0, c),
                 lambda i, c, j: (i, j, c)))
    return ((b, tblocks, cblocks),
            (lambda i, j, c: (i, j, 0), lambda i, j, c: (i, j + 1, 0),
             lambda i, j, c: (0, 0), lambda i, j, c: (0, c),
             lambda i, j, c: (i, j, c)))


# ctx: {"m": taps per branch, "p": branches, "t": frames}.  Hard
# constraints: the frame-axis halo (M − 1 ≤ bt), the DFT column
# blocking dividing P (the wrapper pads the frame axis but not the
# Fourier matrix), and a known grid order.  Working set: two (bt, P)
# frame views, the taps, two (P, bn) F-matrix blocks, the (bt, P) f32
# subfilter accumulator and two (bt, bn) outputs.
TUNE_SPACE = tune.register(tune.TuneSpace(
    kernel="pfb",
    params=("bt", "bn", "order"),
    candidates=lambda ctx: tuple(
        {"bt": bt, "bn": bn, "order": order}
        for order in _ORDERS
        for bt in (64, 128, 256, 512)
        for bn in (8, 16, 32, 64, 128, 256)
        if bn <= ctx["p"] and ctx["p"] % bn == 0),
    valid=lambda cfg, ctx: (
        cfg["bt"] >= 1 and cfg["bn"] >= 1
        and cfg.get("order", "tc") in _ORDERS
        and ctx["m"] - 1 <= cfg["bt"]
        and ctx["p"] % cfg["bn"] == 0
        and 4 * (3 * cfg["bt"] * ctx["p"] + ctx["m"] * ctx["p"]
                 + 2 * ctx["p"] * cfg["bn"]
                 + 2 * cfg["bt"] * cfg["bn"]) <= tune.VMEM_BUDGET),
    # bn: the largest divisor of P that is <= 128 — for P <= 128 that is
    # P itself (the historical min(128, P) default); for larger P it is
    # the biggest column block the n % bn == 0 constraint allows
    default=lambda ctx: {
        "bt": min(256, ctx["t"]),
        "bn": max(d for d in range(1, min(128, ctx["p"]) + 1)
                  if ctx["p"] % d == 0),
        "order": "tc"},
))

# int8 variant working set: f32 xcat (2·bt·P) + amax/scale/acc/y tiles
# (~4·bt·P f32) + int8 yq (bt·P) + taps widened to int32 (4·M·P) + int8
# F blocks (2·P·bn) + f32 scale vectors + int32/f32 output tiles (4·bt·bn).
TUNE_SPACE_INT8 = tune.register(tune.TuneSpace(
    kernel="pfb_int8",
    params=("bt", "bn", "order"),
    candidates=lambda ctx: tuple(
        {"bt": bt, "bn": bn, "order": order}
        for order in _ORDERS
        for bt in (64, 128, 256, 512)
        for bn in (8, 16, 32, 64, 128, 256)
        if bn <= ctx["p"] and ctx["p"] % bn == 0),
    valid=lambda cfg, ctx: (
        cfg["bt"] >= 1 and cfg["bn"] >= 1
        and cfg.get("order", "tc") in _ORDERS
        and ctx["m"] - 1 <= cfg["bt"]
        and ctx["p"] % cfg["bn"] == 0
        and (24 * cfg["bt"] * ctx["p"]                    # f32 frame tiles
             + cfg["bt"] * ctx["p"]                       # int8 yq
             + 4 * ctx["m"] * ctx["p"] + 4 * ctx["p"]     # taps + ts
             + 2 * ctx["p"] * cfg["bn"] + 8 * cfg["bn"]   # F blocks + scales
             + 16 * cfg["bt"] * cfg["bn"]) <= tune.VMEM_BUDGET),
    default=lambda ctx: {
        "bt": min(256, ctx["t"]),
        "bn": max(d for d in range(1, min(128, ctx["p"]) + 1)
                  if ctx["p"] % d == 0),
        "order": "tc"},
))


def _pfb_kernel(x_ref, xnext_ref, taps_ref, fr_ref, fi_ref,
                zr_ref, zi_ref, *, m: int, variant: str):
    bt = zr_ref.shape[1]
    p = x_ref.shape[2]
    xcat = jnp.concatenate([x_ref[0], xnext_ref[0]], axis=0)  # (2bt, P)

    def body(k, acc):
        win = window(xcat, k, bt, axis=0)
        # taps stored pre-reversed: row k multiplies frame offset k
        return acc + taps_ref[k, :][None, :].astype(jnp.float32) * win.astype(jnp.float32)

    y = jax.lax.fori_loop(0, m, body, jnp.zeros((bt, p), jnp.float32))

    fr, fi = fr_ref[...].astype(jnp.float32), fi_ref[...].astype(jnp.float32)
    # HIGHEST: Mosaic runs a default-precision f32 dot as one bf16 pass
    dot = functools.partial(jnp.dot, preferred_element_type=jnp.float32,
                            precision=jax.lax.Precision.HIGHEST)
    # real input signal: the complex DFT needs only 2 real matmuls
    # (the 3mult/4mult distinction applies to complex inputs — dft.py)
    del variant
    zr_ref[0] = dot(y, fr).astype(zr_ref.dtype)
    zi_ref[0] = dot(y, fi).astype(zi_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("variant", "bt", "bn", "order",
                                    "interpret"))
def pfb_fused(frames: jax.Array, taps_rev: jax.Array,
              fr: jax.Array, fi: jax.Array, *, variant: str = "4mult",
              bt: int = 256, bn: int = 128, order: str = "tc",
              interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """frames: (B, T, P) branch-decomposed signal; taps_rev: (M, P)
    pre-reversed taps; fr/fi: (P, N) Fourier matrix (N == P normally).
    Returns (zr, zi): (B, Tout_padded, N) — caller slices to T − M + 1.
    Requires T % bt == 0, P % bn == 0 (or P < bn: caller pads), M−1 ≤ bt.
    """
    b, t, p = frames.shape
    m = taps_rev.shape[0]
    n = fr.shape[1]
    assert t % bt == 0 and n % bn == 0 and p == fr.shape[0]
    assert m - 1 <= bt, f"taps {m} exceed halo block {bt}"
    assert order in _ORDERS, order
    tout = t - m + 1
    tblocks = pl.cdiv(tout, bt)
    xp = jnp.pad(frames, ((0, 0), (0, 2 * bt), (0, 0)))
    kernel = functools.partial(_pfb_kernel, m=m, variant=variant)
    grid, (map_x, map_xn, map_taps, map_f, map_o) = _grid_and_maps(
        order, b, tblocks, n // bn)
    zr, zi = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bt, p), map_x),
            pl.BlockSpec((1, bt, p), map_xn),
            pl.BlockSpec((m, p), map_taps),
            pl.BlockSpec((p, bn), map_f),
            pl.BlockSpec((p, bn), map_f),
        ],
        out_specs=[
            pl.BlockSpec((1, bt, bn), map_o),
            pl.BlockSpec((1, bt, bn), map_o),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, tblocks * bt, n), jnp.float32),
            jax.ShapeDtypeStruct((b, tblocks * bt, n), jnp.float32),
        ],
        interpret=interpret,
    )(xp, xp, taps_rev, fr, fi)
    return zr[:, :tout], zi[:, :tout]


def _pfb_int8_kernel(x_ref, xnext_ref, tq_ref, ts_ref, qr_ref, qi_ref,
                     sr_ref, si_ref, zr_ref, zi_ref, *, m: int):
    bt = zr_ref.shape[1]
    p = x_ref.shape[2]
    xcat = jnp.concatenate([x_ref[0], xnext_ref[0]], axis=0)  # (2bt, P)

    def win(k):
        return window(xcat, k, bt, axis=0).astype(jnp.float32)

    # Frontend pass 1: per-(frame, branch) amax over the M-tap window —
    # exactly quantize.quantize_symmetric(windows, axis=-2).
    def amax_body(k, amax):
        return jnp.maximum(amax, jnp.abs(win(k)))

    amax = jax.lax.fori_loop(
        0, m, amax_body, jnp.zeros((bt, p), jnp.float32))
    scale = jnp.maximum(amax, 1e-12) * (1.0 / 127.0)

    # Frontend pass 2: int32 MAC against the int8 prototype taps (held
    # widened to int32: Mosaic reads a traced row of a 32-bit block, not
    # of a packed int8 one).
    def mac_body(k, acc):
        q = jnp.clip(jnp.round(win(k) / scale), -127, 127).astype(jnp.int32)
        return acc + q * tq_ref[k, :][None, :]

    acc = jax.lax.fori_loop(0, m, mac_body, jnp.zeros((bt, p), jnp.int32))
    # (acc · window_scale) · tap_scale — quantize.qpfb_frontend's epilogue.
    y = acc.astype(jnp.float32) * scale * ts_ref[...]

    # DFT stage: re-quantize the subfiltered rows (per-row over P, the
    # qmatmul axis=-1 convention) and hit the MXU with int8 dots.
    yamax = jnp.max(jnp.abs(y), axis=-1, keepdims=True)
    yscale = jnp.maximum(yamax, 1e-12) * (1.0 / 127.0)
    yq = jnp.clip(jnp.round(y / yscale), -127, 127).astype(jnp.int8)
    dot = functools.partial(jnp.dot, preferred_element_type=jnp.int32)
    zr_ref[0] = dot(yq, qr_ref[...]).astype(jnp.float32) * yscale * sr_ref[...]
    zi_ref[0] = dot(yq, qi_ref[...]).astype(jnp.float32) * yscale * si_ref[...]


@functools.partial(jax.jit,
                   static_argnames=("bt", "bn", "order", "interpret"))
def pfb_fused_int8(frames: jax.Array, tq: jax.Array, ts: jax.Array,
                   qr: jax.Array, qi: jax.Array, sr: jax.Array,
                   si: jax.Array, *, bt: int = 256, bn: int = 128,
                   order: str = "tc",
                   interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """frames: (B, T, P) f32; tq/ts: (M, P) int8 pre-reversed prototype
    + (1, P) per-branch scales (quantize.quantize_pfb_taps pack); qr/qi:
    (P, N) int8 quantized DFM with per-col scales sr/si (1, N).
    Returns f32 (zr, zi): (B, Tout_padded, N) — caller slices to
    T − M + 1.  Bit-identical to quantize.qpfb on the same packs."""
    b, t, p = frames.shape
    m = tq.shape[0]
    n = qr.shape[1]
    assert tq.dtype == jnp.int8 and qr.dtype == jnp.int8, (tq.dtype, qr.dtype)
    assert t % bt == 0 and n % bn == 0 and p == qr.shape[0]
    assert ts.shape == (1, p) and sr.shape == (1, n) and si.shape == (1, n)
    assert m - 1 <= bt, f"taps {m} exceed halo block {bt}"
    assert order in _ORDERS, order
    tout = t - m + 1
    tblocks = pl.cdiv(tout, bt)
    xp = jnp.pad(frames, ((0, 0), (0, 2 * bt), (0, 0)))
    grid, (map_x, map_xn, map_taps, map_f, map_o) = _grid_and_maps(
        order, b, tblocks, n // bn)
    zr, zi = pl.pallas_call(
        functools.partial(_pfb_int8_kernel, m=m),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bt, p), map_x),
            pl.BlockSpec((1, bt, p), map_xn),
            pl.BlockSpec((m, p), map_taps),
            pl.BlockSpec((1, p), map_taps),
            pl.BlockSpec((p, bn), map_f),
            pl.BlockSpec((p, bn), map_f),
            pl.BlockSpec((1, bn), map_f),
            pl.BlockSpec((1, bn), map_f),
        ],
        out_specs=[
            pl.BlockSpec((1, bt, bn), map_o),
            pl.BlockSpec((1, bt, bn), map_o),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, tblocks * bt, n), jnp.float32),
            jax.ShapeDtypeStruct((b, tblocks * bt, n), jnp.float32),
        ],
        interpret=interpret,
    )(xp, xp, tq.astype(jnp.int32), ts, qr, qi, sr, si)
    return zr[:, :tout], zi[:, :tout]
