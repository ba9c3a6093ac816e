"""Blocked complex DFT kernel — TINA §4.1/§4.2 on the MXU.

The DFT-as-pointwise-conv is TINA's best case on TPU: a dense Fourier
matrix matmul runs at MXU speed while FFT butterflies are memory-bound.
Complex arithmetic is the real/imag block form; two variants:

  * ``4mult`` — paper-faithful: Zr = XrFr − XiFi ; Zi = XrFi + XiFr
    (4 MXU matmuls per block step)
  * ``3mult`` — beyond-paper Karatsuba: k1 = (Xr+Xi)Fr, k2 = Xr(Fi−Fr),
    k3 = Xi(Fr+Fi); Zr = k1−k3, Zi = k1+k2 (3 matmuls, 25% fewer
    MXU FLOPs; the extra adds are VPU work that overlaps)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import tune

# ctx: {"m": rows, "n": out cols, "k": inner}.  Like matmul but every
# buffer is doubled (real + imag inputs, F matrices, accumulators,
# outputs), which halves the VMEM-feasible block volume.  The int8
# variant below shares the ctx but prices operand blocks at 1 B/elem.
TUNE_SPACE = tune.register(tune.TuneSpace(
    kernel="dft",
    params=("bm", "bn", "bk"),
    candidates=lambda ctx: (
        {"bm": 128, "bn": 128, "bk": 128},
        {"bm": 64, "bn": 128, "bk": 128},
        {"bm": 256, "bn": 128, "bk": 128},
        {"bm": 256, "bn": 256, "bk": 128},
        {"bm": 512, "bn": 128, "bk": 128},
    ),
    valid=lambda cfg, ctx: (
        min(cfg.values()) >= 1
        and 8 * (cfg["bm"] * cfg["bk"] + cfg["bk"] * cfg["bn"]
                 + 2 * cfg["bm"] * cfg["bn"]) <= tune.VMEM_BUDGET),
    default=lambda ctx: {"bm": 128, "bn": 128, "bk": 128},
))


def _dft_kernel(xr_ref, xi_ref, fr_ref, fi_ref, zr_ref, zi_ref,
                accr_ref, acci_ref, *, nk: int, variant: str):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        accr_ref[...] = jnp.zeros_like(accr_ref)
        acci_ref[...] = jnp.zeros_like(acci_ref)

    xr, xi = xr_ref[...], xi_ref[...]
    fr, fi = fr_ref[...], fi_ref[...]
    # HIGHEST: Mosaic runs a default-precision f32 dot as one bf16 pass
    dot = functools.partial(jnp.dot, preferred_element_type=jnp.float32,
                            precision=jax.lax.Precision.HIGHEST)
    if variant == "4mult":
        accr_ref[...] += dot(xr, fr) - dot(xi, fi)
        acci_ref[...] += dot(xr, fi) + dot(xi, fr)
    else:  # 3mult Karatsuba
        k1 = dot(xr + xi, fr)
        k2 = dot(xr, fi - fr)
        k3 = dot(xi, fr + fi)
        accr_ref[...] += k1 - k3
        acci_ref[...] += k1 + k2

    @pl.when(pl.program_id(2) == nk - 1)
    def _store():
        zr_ref[...] = accr_ref[...].astype(zr_ref.dtype)
        zi_ref[...] = acci_ref[...].astype(zi_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("variant", "bm", "bn", "bk", "interpret"))
def dft(xr: jax.Array, xi: jax.Array, fr: jax.Array, fi: jax.Array, *,
        variant: str = "3mult", bm: int = 128, bn: int = 128, bk: int = 128,
        interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """xr/xi: (B, L) real/imag signal; fr/fi: (L, N) (inverse) Fourier
    matrix.  Shapes must be block multiples (ops.py pads)."""
    b, l = xr.shape
    l2, n = fr.shape
    assert l == l2 and xi.shape == xr.shape and fi.shape == fr.shape
    assert b % bm == 0 and n % bn == 0 and l % bk == 0, (xr.shape, fr.shape)
    nk = l // bk
    grid = (b // bm, n // bn, nk)
    return pl.pallas_call(
        functools.partial(_dft_kernel, nk=nk, variant=variant),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, s: (i, s)),   # xr
            pl.BlockSpec((bm, bk), lambda i, j, s: (i, s)),   # xi
            pl.BlockSpec((bk, bn), lambda i, j, s: (s, j)),   # fr
            pl.BlockSpec((bk, bn), lambda i, j, s: (s, j)),   # fi
        ],
        out_specs=[
            pl.BlockSpec((bm, bn), lambda i, j, s: (i, j)),
            pl.BlockSpec((bm, bn), lambda i, j, s: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, n), xr.dtype),
            jax.ShapeDtypeStruct((b, n), xr.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32),
                        pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(xr, xi, fr, fi)


# int8 variant: one shared int8 signal block feeds BOTH Fourier-matrix
# dots (real + imag), int32 accumulators, f32 rescale at the epilogue.
# Operand blocks are 1 byte/element; acc + out stay 4 B — deep-K tiles
# get cheap exactly as in matmul_int8.
TUNE_SPACE_INT8 = tune.register(tune.TuneSpace(
    kernel="dft_int8",
    params=("bm", "bn", "bk"),
    candidates=lambda ctx: (
        {"bm": 128, "bn": 128, "bk": 128},
        {"bm": 128, "bn": 128, "bk": 256},
        {"bm": 128, "bn": 128, "bk": 512},
        {"bm": 256, "bn": 128, "bk": 256},
        {"bm": 256, "bn": 256, "bk": 256},
        {"bm": 512, "bn": 256, "bk": 512},
    ),
    valid=lambda cfg, ctx: (
        min(cfg.values()) >= 1
        and (cfg["bm"] * cfg["bk"] + 2 * cfg["bk"] * cfg["bn"]  # int8 x, Fr, Fi
             + 16 * cfg["bm"] * cfg["bn"]                       # 2 acc + 2 out
             + 4 * (cfg["bm"] + 2 * cfg["bn"])                  # scale vectors
             ) <= tune.VMEM_BUDGET),
    default=lambda ctx: {"bm": 128, "bn": 128, "bk": 256},
))


def _dft_int8_kernel(x_ref, fr_ref, fi_ref, sx_ref, sr_ref, si_ref,
                     zr_ref, zi_ref, accr_ref, acci_ref, *, nk: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        accr_ref[...] = jnp.zeros_like(accr_ref)
        acci_ref[...] = jnp.zeros_like(acci_ref)

    x = x_ref[...]
    dot = functools.partial(jnp.dot, preferred_element_type=jnp.int32)
    accr_ref[...] += dot(x, fr_ref[...])
    acci_ref[...] += dot(x, fi_ref[...])

    @pl.when(pl.program_id(2) == nk - 1)
    def _store():
        # Same left-associated (acc · x_scale) · col_scale epilogue as
        # quantize.qmatmul — bit-identical rescale.
        zr_ref[...] = accr_ref[...].astype(jnp.float32) * sx_ref[...] * sr_ref[...]
        zi_ref[...] = acci_ref[...].astype(jnp.float32) * sx_ref[...] * si_ref[...]


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def dft_int8(xq: jax.Array, fr: jax.Array, fi: jax.Array, sx: jax.Array,
             sr: jax.Array, si: jax.Array, *, bm: int = 128, bn: int = 128,
             bk: int = 256, interpret: bool = False):
    """Real-signal int8 DFT: xq (B, L) int8 rows with per-row scales
    sx (B, 1); fr/fi (L, N) int8 quantized Fourier matrix with per-col
    scales sr/si (1, N).  Returns f32 (Zr, Zi) = (Xq·Fr)·sx·sr,
    (Xq·Fi)·sx·si with exact int32 accumulation.  Complex signals take
    the 4-matmul route through ``matmul_int8`` instead (ops.qdft)."""
    b, l = xq.shape
    l2, n = fr.shape
    assert l == l2 and fi.shape == fr.shape, (xq.shape, fr.shape, fi.shape)
    assert xq.dtype == jnp.int8 and fr.dtype == jnp.int8, (xq.dtype, fr.dtype)
    assert sx.shape == (b, 1) and sr.shape == (1, n) and si.shape == (1, n)
    assert b % bm == 0 and n % bn == 0 and l % bk == 0, (xq.shape, fr.shape)
    nk = l // bk
    grid = (b // bm, n // bn, nk)
    return pl.pallas_call(
        functools.partial(_dft_int8_kernel, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, s: (i, s)),   # xq
            pl.BlockSpec((bk, bn), lambda i, j, s: (s, j)),   # fr
            pl.BlockSpec((bk, bn), lambda i, j, s: (s, j)),   # fi
            pl.BlockSpec((bm, 1), lambda i, j, s: (i, 0)),    # sx
            pl.BlockSpec((1, bn), lambda i, j, s: (0, j)),    # sr
            pl.BlockSpec((1, bn), lambda i, j, s: (0, j)),    # si
        ],
        out_specs=[
            pl.BlockSpec((bm, bn), lambda i, j, s: (i, j)),
            pl.BlockSpec((bm, bn), lambda i, j, s: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, n), jnp.float32),
            jax.ShapeDtypeStruct((b, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32),
                        pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
    )(xq, fr, fi, sx, sr, si)
