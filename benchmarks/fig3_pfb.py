"""Paper Fig. 3 / §5.2: polyphase filter bank use case.

Left column = subfiltered signals only (pfb_frontend); right column =
full PFB (frontend + DFT).  Speedups are reported vs the naive NumPy
CPU baseline, exactly like the paper's figure; the jit'd direct-jnp
column reproduces the paper's "JAX" comparison."""
from __future__ import annotations

import argparse
import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import fmt_table, speedup, timeit, us
from repro.core import pfb as pfb_lib


def np_pfb_frontend(x, taps):
    m, p = taps.shape
    frames = x.reshape(-1, p)
    nfr = frames.shape[0]
    # naive loop-per-branch FIR — the paper's "naive implementation
    # written in NumPy"
    out = np.empty((nfr - m + 1, p), x.dtype)
    for b in range(p):
        out[:, b] = np.convolve(frames[:, b], taps[::-1, b][::-1],
                                mode="valid")
    return out


def np_pfb(x, taps):
    return np.fft.fft(np_pfb_frontend(x, taps), axis=-1)


def jnp_pfb(x, taps):
    m, p = taps.shape
    frames = x.reshape(-1, p)
    nfr = frames.shape[0]
    idx = jnp.arange(nfr - m + 1)[:, None] + jnp.arange(m)[None, :]
    y = jnp.einsum("tmp,mp->tp", frames[idx], taps[::-1])
    return jnp.fft.fft(y, axis=-1)


def run(n_samples=(2 ** 14, 2 ** 16, 2 ** 18), p=32, m=8, repeats=10):
    taps_np = pfb_lib.pfb_window(p, m).astype(np.float32)
    taps = jnp.asarray(taps_np)
    rng = np.random.default_rng(0)
    rows_f, rows_full = [], []
    for n in n_samples:
        x_np = rng.standard_normal(n).astype(np.float32)
        x = jnp.asarray(x_np)

        # frontend only (paper Fig. 3 left column)
        t_np = timeit(np_pfb_frontend, x_np, taps_np, repeats=repeats)
        t_tina = timeit(jax.jit(functools.partial(
            pfb_lib.pfb_frontend, lowering="native")), x, taps,
            repeats=repeats)
        t_conv = timeit(jax.jit(functools.partial(
            pfb_lib.pfb_frontend, lowering="conv")), x, taps,
            repeats=repeats)
        rows_f.append([n, us(t_np), us(t_tina), us(t_conv),
                       speedup(t_np, t_tina), speedup(t_np, t_conv)])

        # full PFB (right column)
        t_np2 = timeit(np_pfb, x_np, taps_np, repeats=repeats)
        t_jnp2 = timeit(jax.jit(jnp_pfb), x, taps, repeats=repeats)
        t_tina2 = timeit(jax.jit(functools.partial(
            pfb_lib.pfb, lowering="native")), x, taps, repeats=repeats)
        t_conv2 = timeit(jax.jit(functools.partial(
            pfb_lib.pfb, lowering="conv")), x, taps, repeats=repeats)
        rows_full.append([n, us(t_np2), us(t_jnp2), us(t_tina2), us(t_conv2),
                          speedup(t_np2, t_tina2), speedup(t_np2, t_jnp2)])

    a = fmt_table("Fig.3 left: PFB frontend (subfiltered signals)",
                  ["n", "numpy_us", "tina_us", "tina_conv_us",
                   "tina_vs_np", "conv_vs_np"], rows_f)
    b = fmt_table("Fig.3 right: full PFB (frontend + DFT)",
                  ["n", "numpy_us", "jnp_fft_us", "tina_us", "tina_conv_us",
                   "tina_vs_np", "jnp_vs_np"], rows_full)
    return a + "\n\n" + b


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sizes", type=int, nargs="+",
                    default=[2 ** 14, 2 ** 16, 2 ** 18])
    ap.add_argument("--branches", type=int, default=32)
    ap.add_argument("--taps", type=int, default=8)
    ap.add_argument("--repeats", type=int, default=10)
    args = ap.parse_args()
    print(run(tuple(args.sizes), args.branches, args.taps, args.repeats))


if __name__ == "__main__":
    from repro import compile_cache
    compile_cache.enable()
    main()
