"""Public jit'd wrappers around the Pallas kernels.

These are what :mod:`repro.core.functions` dispatches to for
``lowering="pallas"``: each wrapper handles batching, padding to block
multiples, and interpret-mode selection (kernels execute via the Pallas
interpreter on the CPU so CPU CI validates the TPU kernel bodies).

Block sizes: every wrapper takes its kernel's block-size kwargs
explicitly (``None`` = the kernel's :class:`~repro.kernels.tune.TuneSpace`
default, which reproduces the historical hardcoded values).  Explicit
configs are validated against the TuneSpace *here*, at the kernel
boundary — an invalid config (e.g. FIR taps exceeding the halo block)
raises ValueError instead of tripping a mid-trace kernel assert.  The
graph autotuner (:mod:`repro.graph.autotune`) searches these same
spaces and threads its winners back through these kwargs.

Graph-level wiring lives in :mod:`repro.core.opdefs`: each op's OpDef
names the TuneSpace these wrappers validate against (``tune_space=``)
and how its pallas lowering reaches this module — a new kernel plugs
into the planner/autotuner by declaring those two fields on its OpDef,
not by editing the graph layers.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import dft as dft_kernel
from repro.kernels import elementwise as ew_kernel
from repro.kernels import fir as fir_kernel
from repro.kernels import matmul as mm_kernel
from repro.kernels import pfb as pfb_kernel
from repro.kernels import tune
from repro.kernels import unfold as unfold_kernel

Array = jax.Array


def _interpret() -> bool:
    """Compiled kernels on the TPU, the Pallas interpreter on the CPU
    (where tests validate the kernel bodies); any other backend is an
    error, so no kernel runs interpreted on an accelerator unnoticed."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels compile for the TPU and are interpreted on the "
        f"CPU; backend {backend!r} is neither — use a native lowering")


def _pad_to(x: Array, mults: tuple[int, ...]) -> Array:
    pads = [(0, (-d) % m) for d, m in zip(x.shape, mults)]
    if any(p[1] for p in pads):
        return jnp.pad(x, pads)
    return x


def _resolve(space: tune.TuneSpace, ctx: dict, **explicit) -> dict:
    """Fill missing block params from the space default and validate the
    result (ValueError on an invalid explicit config)."""
    return space.check(
        {k: v for k, v in explicit.items() if v is not None}, ctx)


def _quantize():
    # Lazy: keeps the kernels package importable without the core layer.
    from repro.core import quantize
    return quantize


# ---------------------------------------------------------------------------
def matmul(x: Array, y: Array, *, bm: int | None = None,
           bn: int | None = None, bk: int | None = None,
           order: str | None = None) -> Array:
    """x (..., M, L) @ y (L, N) through the MXU-tiled kernel."""
    m, l = x.shape[-2], x.shape[-1]
    n = y.shape[1]
    batch = x.shape[:-2]
    rows = tune.leading_rows(x.shape)          # prod(batch) * m
    cfg = _resolve(mm_kernel.TUNE_SPACE, {"m": rows, "n": n, "k": l},
                   bm=bm, bn=bn, bk=bk, order=order)
    x2 = _pad_to(x.reshape((-1, l)), (cfg["bm"], cfg["bk"]))
    y2 = _pad_to(y, (cfg["bk"], cfg["bn"]))
    out = mm_kernel.matmul(x2, y2, interpret=_interpret(), **cfg)
    return out[:rows, :n].reshape(batch + (m, n))


def _ew_flat(shape, *, bm, bn, n_in):
    ctx = {"rows": tune.leading_rows(shape), "cols": shape[-1],
           "n_in": n_in}
    return _resolve(ew_kernel.TUNE_SPACE, ctx, bm=bm, bn=bn)


def elementwise_mult(x: Array, y: Array, *, bm: int | None = None,
                     bn: int | None = None) -> Array:
    shape = jnp.broadcast_shapes(x.shape, y.shape)
    cfg = _ew_flat(shape, bm=bm, bn=bn, n_in=2)
    xb = jnp.broadcast_to(x, shape).reshape((-1, shape[-1]))
    yb = jnp.broadcast_to(y, shape).reshape((-1, shape[-1]))
    out = ew_kernel.elementwise_mult(
        _pad_to(xb, (cfg["bm"], cfg["bn"])),
        _pad_to(yb, (cfg["bm"], cfg["bn"])),
        interpret=_interpret(), **cfg)
    return out[: xb.shape[0], : xb.shape[1]].reshape(shape)


def elementwise_add(x: Array, y: Array, *, bm: int | None = None,
                    bn: int | None = None) -> Array:
    shape = jnp.broadcast_shapes(x.shape, y.shape)
    cfg = _ew_flat(shape, bm=bm, bn=bn, n_in=2)
    xb = jnp.broadcast_to(x, shape).reshape((-1, shape[-1]))
    yb = jnp.broadcast_to(y, shape).reshape((-1, shape[-1]))
    out = ew_kernel.elementwise_add(
        _pad_to(xb, (cfg["bm"], cfg["bn"])),
        _pad_to(yb, (cfg["bm"], cfg["bn"])),
        interpret=_interpret(), **cfg)
    return out[: xb.shape[0], : xb.shape[1]].reshape(shape)


def fused_elementwise(x: Array, operands: tuple, steps: tuple, *,
                      bm: int | None = None, bn: int | None = None) -> Array:
    """Fused elementwise chain — the planner's entry point (one kernel
    launch for a whole run of adjacent elementwise graph nodes).

    ``steps``: static tuple, in order, of
      ("abs2",)     — only as first step; x must be complex, out = re²+im²
      ("mul",) / ("add",) — consumes the next array from ``operands``
      ("scale", c)  — multiply by a python scalar baked into the kernel
    Operands are broadcast to x's shape.
    """
    abs2_head = bool(steps) and steps[0][0] == "abs2"
    rest = steps[1:] if abs2_head else steps
    if abs2_head:
        shape = x.shape
        heads = (jnp.real(x), jnp.imag(x))
    else:
        if jnp.iscomplexobj(x):
            raise ValueError("fused_elementwise: complex input requires an "
                             "abs2 head step")
        shape = jnp.broadcast_shapes(x.shape, *(o.shape for o in operands))
        heads = (jnp.broadcast_to(x, shape),)
    flat = [h.reshape((-1, shape[-1])) for h in heads]
    for o in operands:
        flat.append(jnp.broadcast_to(o, shape).reshape((-1, shape[-1])))
    cfg = _ew_flat(shape, bm=bm, bn=bn, n_in=len(flat))
    padded = tuple(_pad_to(f, (cfg["bm"], cfg["bn"])) for f in flat)
    out = ew_kernel.elementwise_chain(
        padded, steps=tuple(rest), abs2_head=abs2_head,
        interpret=_interpret(), **cfg)
    return out[: flat[0].shape[0], : flat[0].shape[1]].reshape(shape)


def abs2(x: Array, *, bm: int | None = None, bn: int | None = None) -> Array:
    """|x|² of a complex array in one fused kernel (re² + im²)."""
    return fused_elementwise(x, (), (("abs2",),), bm=bm, bn=bn)


def dft(xr: Array, xi: Array, fr: Array, fi: Array, *,
        variant: str = "3mult", bm: int | None = None,
        bn: int | None = None, bk: int | None = None) -> tuple[Array, Array]:
    """(B, L) real/imag through the blocked complex-DFT kernel."""
    b, l = xr.shape
    n = fr.shape[1]
    cfg = _resolve(dft_kernel.TUNE_SPACE, {"m": b, "n": n, "k": l},
                   bm=bm, bn=bn, bk=bk)
    xr2 = _pad_to(xr, (cfg["bm"], cfg["bk"]))
    xi2 = _pad_to(xi, (cfg["bm"], cfg["bk"]))
    fr2 = _pad_to(fr, (cfg["bk"], cfg["bn"]))
    fi2 = _pad_to(fi, (cfg["bk"], cfg["bn"]))
    zr, zi = dft_kernel.dft(xr2, xi2, fr2, fi2, variant=variant,
                            interpret=_interpret(), **cfg)
    return zr[:b, :n], zi[:b, :n]


def fir(x: Array, kern: Array, *, mode: str = "valid",
        bb: int | None = None, bn: int | None = None) -> Array:
    """Cross-correlation with ``kern`` (caller pre-flips for true FIR);
    mode via explicit padding then the 'valid' kernel."""
    k = kern.shape[0]
    if mode == "same":
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [((k - 1) // 2, k // 2)])
    elif mode == "full":
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(k - 1, k - 1)])
    batch = x.shape[:-1]
    n = x.shape[-1]
    cfg = _resolve(fir_kernel.TUNE_SPACE,
                   {"k": k, "n": n, "rows": tune.leading_rows(x.shape)},
                   bb=bb, bn=bn)
    x2 = _pad_to(x.reshape((-1, n)), (cfg["bb"], cfg["bn"]))
    out = fir_kernel.fir_valid(x2, kern, interpret=_interpret(), **cfg)
    rows = tune.leading_rows(x.shape)
    # padded columns shift the valid length; slice to the true one
    return out[:rows, : n - k + 1].reshape(batch + (n - k + 1,))


def unfold(x: Array, window: int, *, bb: int | None = None,
           bt: int | None = None) -> Array:
    batch = x.shape[:-1]
    n = x.shape[-1]
    cfg = _resolve(unfold_kernel.TUNE_SPACE,
                   {"j": window, "n": n, "rows": tune.leading_rows(x.shape)},
                   bb=bb, bt=bt)
    x2 = _pad_to(x.reshape((-1, n)), (cfg["bb"], cfg["bt"]))
    out = unfold_kernel.unfold(x2, window, interpret=_interpret(), **cfg)
    rows = tune.leading_rows(x.shape)
    return out[:rows, : n - window + 1].reshape(
        batch + (n - window + 1, window))


def pfb_fir(frames: Array, taps: Array, *, bt: int | None = None,
            bn: int | None = None, order: str | None = None) -> Array:
    """Frontend only: (..., T, P), (M, P) -> (..., T − M + 1, P).
    Runs the fused kernel with the identity 'DFT' (F = I) so the FIR
    path is exercised; cheaper than a separate kernel and still fused."""
    m, p = taps.shape
    batch = frames.shape[:-2]
    t = frames.shape[-2]
    cfg = _resolve(pfb_kernel.TUNE_SPACE, {"m": m, "p": p, "t": t},
                   bt=bt, bn=bn, order=order)
    f3 = frames.reshape((-1, t, p))
    f3 = jnp.pad(f3, ((0, 0), (0, (-t) % cfg["bt"]), (0, 0)))
    eye = jnp.eye(p, dtype=jnp.float32)
    zeros = jnp.zeros((p, p), jnp.float32)
    zr, _ = pfb_kernel.pfb_fused(f3, taps[::-1].astype(f3.dtype), eye, zeros,
                                 interpret=_interpret(), **cfg)
    tout = t - m + 1
    return zr[:, :tout].astype(frames.dtype).reshape(batch + (tout, p))


def pfb(x: Array, taps: Array, *, variant: str = "4mult",
        bt: int | None = None, bn: int | None = None,
        order: str | None = None) -> Array:
    """Full fused PFB: (..., n_samples), (M, P) -> complex
    (..., n_frames − M + 1, P)."""
    m, p = taps.shape
    if x.shape[-1] % p:
        raise ValueError(f"n_samples {x.shape[-1]} not divisible by P={p}")
    batch = x.shape[:-1]
    frames = x.reshape((-1, x.shape[-1] // p, p))
    t = frames.shape[1]
    cfg = _resolve(pfb_kernel.TUNE_SPACE, {"m": m, "p": p, "t": t},
                   bt=bt, bn=bn, order=order)
    frames = jnp.pad(frames, ((0, 0), (0, (-t) % cfg["bt"]), (0, 0)))
    lk = np.outer(np.arange(p), np.arange(p))
    f = np.exp(-2j * np.pi * lk / p)
    fr = jnp.asarray(f.real, jnp.float32)
    fi = jnp.asarray(f.imag, jnp.float32)
    zr, zi = pfb_kernel.pfb_fused(frames, taps[::-1].astype(frames.dtype),
                                  fr, fi, variant=variant,
                                  interpret=_interpret(), **cfg)
    tout = t - m + 1
    z = zr[:, :tout] + 1j * zi[:, :tout]
    return z.reshape(batch + (tout, p))


def overlap_add(frames: Array, hop: int, *, bb: int | None = None,
                bt: int | None = None) -> Array:
    """frames (..., T, J) with hop | J -> (..., (T − J/hop + 1) · hop)
    through the blocked transposed-conv kernel (unfold's adjoint)."""
    t, j = frames.shape[-2], frames.shape[-1]
    k = j // hop
    batch = frames.shape[:-2]
    rows = tune.leading_rows(frames.shape[:-1])   # prod(batch)
    cfg = _resolve(unfold_kernel.OLA_TUNE_SPACE,
                   {"j": j, "hop": hop, "k": k, "t": t, "rows": rows},
                   bb=bb, bt=bt)
    f3 = _pad_to(frames.reshape((-1, t, j)), (cfg["bb"], cfg["bt"], j))
    out = unfold_kernel.overlap_add(f3, hop, interpret=_interpret(), **cfg)
    nt = t - k + 1
    return out[:rows, :nt].reshape(batch + (nt * hop,))


# ---------------------------------------------------------------------------
# int8 wrappers — the qimpl lowering targets.  Activations quantize here
# (or inside the kernel, per window) with the SAME quantize_symmetric
# decisions as repro.core.quantize, and every contraction is int8 × int8
# → int32, so these are bit-identical to the jnp integer paths.
def qmatmul(x: Array, wq: Array, w_scale: Array, *, bm: int | None = None,
            bn: int | None = None, bk: int | None = None,
            order: str | None = None) -> Array:
    """x (..., L) f32 against an int8 (L, N) weight with per-col scales;
    per-row activation quantization (quantize.qmatmul's convention)."""
    quantize = _quantize()
    l = x.shape[-1]
    n = wq.shape[1]
    rows = tune.leading_rows(x.shape)             # prod of all but last
    cfg = _resolve(mm_kernel.TUNE_SPACE_INT8, {"m": rows, "n": n, "k": l},
                   bm=bm, bn=bn, bk=bk, order=order)
    xq, sx = quantize.quantize_symmetric(x.reshape((-1, l)), axis=-1)
    out = mm_kernel.matmul_int8(
        _pad_to(xq, (cfg["bm"], cfg["bk"])),
        _pad_to(wq, (cfg["bk"], cfg["bn"])),
        _pad_to(sx, (cfg["bm"], 1)),
        _pad_to(w_scale.reshape((1, -1)), (1, cfg["bn"])),
        interpret=_interpret(), **cfg)
    return out[:rows, :n].reshape(x.shape[:-1] + (n,))


def qdft(x: Array, *, inverse: bool = False, bm: int | None = None,
         bn: int | None = None, bk: int | None = None) -> Array:
    """(I)DFT with the int8-quantized Fourier matrix: real signals run
    the shared-x dft_int8 kernel (2 integer matmuls per block step);
    complex signals expand to the 4-real-matmul form through
    matmul_int8, quantizing the real/imag rows once each."""
    quantize = _quantize()
    n = x.shape[-1]
    (qr, sr), (qi, si) = quantize._qdfm(n, inverse)
    rows = tune.leading_rows(x.shape)
    cfg = _resolve(dft_kernel.TUNE_SPACE_INT8, {"m": rows, "n": n, "k": n},
                   bm=bm, bn=bn, bk=bk)
    x2 = x.reshape((-1, n))
    bm_, bn_, bk_ = cfg["bm"], cfg["bn"], cfg["bk"]
    qr_p = _pad_to(jnp.asarray(qr), (bk_, bn_))
    qi_p = _pad_to(jnp.asarray(qi), (bk_, bn_))
    sr_p = _pad_to(jnp.asarray(sr).reshape((1, -1)), (1, bn_))
    si_p = _pad_to(jnp.asarray(si).reshape((1, -1)), (1, bn_))
    if jnp.issubdtype(x2.dtype, jnp.complexfloating):
        def mm(xq, sx, wq_p, sw_p):
            o = mm_kernel.matmul_int8(
                _pad_to(xq, (bm_, bk_)), wq_p, _pad_to(sx, (bm_, 1)), sw_p,
                bm=bm_, bn=bn_, bk=bk_, interpret=_interpret())
            return o[:rows, :n]

        zrq, szr = quantize.quantize_symmetric(
            jnp.real(x2).astype(jnp.float32), axis=-1)
        ziq, szi = quantize.quantize_symmetric(
            jnp.imag(x2).astype(jnp.float32), axis=-1)
        out = ((mm(zrq, szr, qr_p, sr_p) - mm(ziq, szi, qi_p, si_p))
               + 1j * (mm(zrq, szr, qi_p, si_p) + mm(ziq, szi, qr_p, sr_p)))
    else:
        xq, sx = quantize.quantize_symmetric(x2, axis=-1)
        zr, zi = dft_kernel.dft_int8(
            _pad_to(xq, (bm_, bk_)), qr_p, qi_p, _pad_to(sx, (bm_, 1)),
            sr_p, si_p, interpret=_interpret(), **cfg)
        out = zr[:rows, :n] + 1j * zi[:rows, :n]
    return out.reshape(x.shape[:-1] + (n,))


def qfir(x: Array, tq: Array, ts: Array, *, bb: int | None = None,
         bn: int | None = None) -> Array:
    """'valid' FIR against a quantize_fir_taps pack ((K, 1) int8 taps +
    (1, 1) scale); per-window activation quantization happens inside the
    kernel."""
    k = tq.shape[0]
    batch = x.shape[:-1]
    n = x.shape[-1]
    rows = tune.leading_rows(x.shape)
    cfg = _resolve(fir_kernel.TUNE_SPACE_INT8,
                   {"k": k, "n": n, "rows": rows}, bb=bb, bn=bn)
    x2 = _pad_to(x.reshape((-1, n)), (cfg["bb"], cfg["bn"]))
    out = fir_kernel.fir_valid_int8(
        x2, tq.reshape((1, k)), ts.reshape((1, 1)),
        interpret=_interpret(), **cfg)
    return out[:rows, : n - k + 1].reshape(batch + (n - k + 1,))


def qpfb(x: Array, tq: Array, ts: Array, *, bt: int | None = None,
         bn: int | None = None, order: str | None = None) -> Array:
    """Full fused int8 PFB against a quantize_pfb_taps pack ((M, P) int8
    pre-reversed prototype + (1, P) scales): (..., n_samples) -> complex
    (..., n_frames − M + 1, P)."""
    quantize = _quantize()
    m, p = tq.shape
    if x.shape[-1] % p:
        raise ValueError(f"n_samples {x.shape[-1]} not divisible by P={p}")
    batch = x.shape[:-1]
    frames = x.reshape((-1, x.shape[-1] // p, p)).astype(jnp.float32)
    t = frames.shape[1]
    cfg = _resolve(pfb_kernel.TUNE_SPACE_INT8, {"m": m, "p": p, "t": t},
                   bt=bt, bn=bn, order=order)
    frames = jnp.pad(frames, ((0, 0), (0, (-t) % cfg["bt"]), (0, 0)))
    (qr, sr), (qi, si) = quantize._qdfm(p, False)
    zr, zi = pfb_kernel.pfb_fused_int8(
        frames, tq, ts.reshape((1, p)), jnp.asarray(qr), jnp.asarray(qi),
        jnp.asarray(sr).reshape((1, -1)), jnp.asarray(si).reshape((1, -1)),
        interpret=_interpret(), **cfg)
    tout = t - m + 1
    z = zr[:, :tout] + 1j * zi[:, :tout]
    return z.reshape(batch + (tout, p))


__all__ = ["matmul", "elementwise_mult", "elementwise_add",
           "fused_elementwise", "abs2", "dft", "fir", "unfold", "pfb_fir",
           "pfb", "overlap_add", "qmatmul", "qdft", "qfir", "qpfb"]
