"""MXU-tiled matmul — the lowering target of the TINA pointwise conv.

Grid (M/bm, N/bn, K/bk); fp32 VMEM accumulator; block shapes default to
the MXU-native 128 multiples.  This is the kernel every TINA
matmul-as-pointwise-conv rides on (DESIGN.md §2).

Two variants live here:

  * :func:`matmul` — the f32 kernel.  Tunable over block shape AND grid
    order (``order="mn"`` walks M-major, ``"nm"`` walks N-major; K stays
    innermost in both — the accumulator scratch is only correct when
    every K step of one (i, j) tile runs consecutively).
  * :func:`matmul_int8` — true integer compute: int8 × int8 blocks hit
    the MXU dot with ``preferred_element_type=jnp.int32``, accumulate in
    an int32 VMEM scratch, and the single f32 ``(x_scale · w_scale)``
    rescale happens once at the store epilogue.  int8 tiles pack 4×
    denser in VMEM than f32, so its TuneSpace favors deeper K blocks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import tune

_ORDERS = ("mn", "nm")


def _grid_and_maps(order: str, nm_, nn_, nk_):
    """Grid + (x, y, out, row-scale, col-scale) index maps for a grid
    order.  K is always the innermost grid dim (accumulator contract)."""
    if order == "nm":
        return ((nn_, nm_, nk_),
                (lambda j, i, s: (i, s), lambda j, i, s: (s, j),
                 lambda j, i, s: (i, j), lambda j, i, s: (i, 0),
                 lambda j, i, s: (0, j)))
    return ((nm_, nn_, nk_),
            (lambda i, j, s: (i, s), lambda i, j, s: (s, j),
             lambda i, j, s: (i, j), lambda i, j, s: (i, 0),
             lambda i, j, s: (0, j)))


# ctx: {"m": rows, "n": cols, "k": inner}.  The wrapper pads every dim
# up to its block multiple, so divisibility always holds after padding;
# the hard constraints are the per-step working set fitting VMEM
# (x, y, out blocks + the f32 accumulator scratch) and the grid order
# being one the kernel knows how to walk.
TUNE_SPACE = tune.register(tune.TuneSpace(
    kernel="matmul",
    params=("bm", "bn", "bk", "order"),
    candidates=lambda ctx: (
        {"bm": 128, "bn": 128, "bk": 128, "order": "mn"},
        {"bm": 64, "bn": 128, "bk": 128, "order": "mn"},
        {"bm": 256, "bn": 128, "bk": 128, "order": "mn"},
        {"bm": 128, "bn": 256, "bk": 128, "order": "mn"},
        {"bm": 128, "bn": 128, "bk": 256, "order": "mn"},
        {"bm": 256, "bn": 256, "bk": 256, "order": "mn"},
        {"bm": 512, "bn": 256, "bk": 128, "order": "mn"},
        # N-major walks: better y-block reuse when N >> M.
        {"bm": 128, "bn": 128, "bk": 128, "order": "nm"},
        {"bm": 128, "bn": 256, "bk": 128, "order": "nm"},
        {"bm": 256, "bn": 256, "bk": 256, "order": "nm"},
    ),
    valid=lambda cfg, ctx: (
        cfg.get("order", "mn") in _ORDERS
        and min(cfg[p] for p in ("bm", "bn", "bk")) >= 1
        and 4 * (cfg["bm"] * cfg["bk"] + cfg["bk"] * cfg["bn"]
                 + 2 * cfg["bm"] * cfg["bn"]) <= tune.VMEM_BUDGET),
    default=lambda ctx: {"bm": 128, "bn": 128, "bk": 128, "order": "mn"},
))

# int8 blocks are 1 byte/element, the accumulator is int32 and the output
# f32 (4 bytes each) — so the VMEM bound weights the operand blocks 4×
# lighter and deep-K tiles become affordable.  Scale vectors ((bm, 1) and
# (1, bn) f32) are noise but counted for honesty.
TUNE_SPACE_INT8 = tune.register(tune.TuneSpace(
    kernel="matmul_int8",
    params=("bm", "bn", "bk", "order"),
    candidates=lambda ctx: (
        {"bm": 128, "bn": 128, "bk": 128, "order": "mn"},
        {"bm": 128, "bn": 128, "bk": 256, "order": "mn"},
        {"bm": 128, "bn": 128, "bk": 512, "order": "mn"},
        {"bm": 256, "bn": 128, "bk": 256, "order": "mn"},
        {"bm": 256, "bn": 256, "bk": 256, "order": "mn"},
        {"bm": 256, "bn": 256, "bk": 512, "order": "mn"},
        {"bm": 512, "bn": 256, "bk": 512, "order": "mn"},
        {"bm": 512, "bn": 512, "bk": 256, "order": "mn"},
        {"bm": 128, "bn": 128, "bk": 256, "order": "nm"},
        {"bm": 256, "bn": 256, "bk": 512, "order": "nm"},
    ),
    valid=lambda cfg, ctx: (
        cfg.get("order", "mn") in _ORDERS
        and min(cfg[p] for p in ("bm", "bn", "bk")) >= 1
        and (cfg["bm"] * cfg["bk"] + cfg["bk"] * cfg["bn"]   # int8 operands
             + 8 * cfg["bm"] * cfg["bn"]                     # int32 acc + f32 out
             + 4 * (cfg["bm"] + cfg["bn"])                   # scale vectors
             ) <= tune.VMEM_BUDGET),
    default=lambda ctx: {"bm": 128, "bn": 128, "bk": 256, "order": "mn"},
))


def _matmul_kernel(x_ref, y_ref, o_ref, acc_ref, *, nk: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # HIGHEST: Mosaic runs a default-precision f32 dot as one bf16 pass
    acc_ref[...] += jnp.dot(
        x_ref[...], y_ref[...], preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )

    @pl.when(pl.program_id(2) == nk - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "bk", "order", "interpret"))
def matmul(x: jax.Array, y: jax.Array, *, bm: int = 128, bn: int = 128,
           bk: int = 128, order: str = "mn",
           interpret: bool = False) -> jax.Array:
    """x (M, K) @ y (K, N); M, K, N must be multiples of the block shape
    (the public wrapper in ops.py pads)."""
    m, k = x.shape
    k2, n = y.shape
    assert k == k2, (x.shape, y.shape)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (x.shape, y.shape)
    assert order in _ORDERS, order
    nk = k // bk
    grid, (map_x, map_y, map_o, _, _) = _grid_and_maps(
        order, m // bm, n // bn, nk)
    return pl.pallas_call(
        functools.partial(_matmul_kernel, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), map_x),
            pl.BlockSpec((bk, bn), map_y),
        ],
        out_specs=pl.BlockSpec((bm, bn), map_o),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(x, y)


def _matmul_int8_kernel(x_ref, y_ref, sx_ref, sy_ref, o_ref, acc_ref,
                        *, nk: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # int8 × int8 on the MXU; int32 accumulate — exact, so the result is
    # bit-identical to the int32-upcast reference contraction.
    acc_ref[...] += jnp.dot(
        x_ref[...], y_ref[...], preferred_element_type=jnp.int32
    )

    @pl.when(pl.program_id(2) == nk - 1)
    def _store():
        # The one f32 epilogue: same left-associated (acc · sx) · sy as
        # the jnp path in core/quantize.py — byte-identical rescale.
        o_ref[...] = acc_ref[...].astype(jnp.float32) * sx_ref[...] * sy_ref[...]


@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "bk", "order", "interpret"))
def matmul_int8(xq: jax.Array, yq: jax.Array, sx: jax.Array, sy: jax.Array,
                *, bm: int = 128, bn: int = 128, bk: int = 256,
                order: str = "mn", interpret: bool = False) -> jax.Array:
    """int8 xq (M, K) @ int8 yq (K, N) with int32 accumulation; f32 out
    = acc · sx · sy with per-row sx (M, 1) and per-col sy (1, N) scales.
    Zero-padded rows/cols carry zero scales, so padding rescales to 0."""
    m, k = xq.shape
    k2, n = yq.shape
    assert k == k2, (xq.shape, yq.shape)
    assert xq.dtype == jnp.int8 and yq.dtype == jnp.int8, (xq.dtype, yq.dtype)
    assert sx.shape == (m, 1) and sy.shape == (1, n), (sx.shape, sy.shape)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (xq.shape, yq.shape)
    assert order in _ORDERS, order
    nk = k // bk
    grid, (map_x, map_y, map_o, map_sx, map_sy) = _grid_and_maps(
        order, m // bm, n // bn, nk)
    return pl.pallas_call(
        functools.partial(_matmul_int8_kernel, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), map_x),
            pl.BlockSpec((bk, bn), map_y),
            pl.BlockSpec((bm, 1), map_sx),
            pl.BlockSpec((1, bn), map_sy),
        ],
        out_specs=pl.BlockSpec((bm, bn), map_o),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
    )(xq, yq, sx, sy)
