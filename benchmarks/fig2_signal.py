"""Paper Fig. 2: runtime of the signal processing functions (DFT, IDFT,
FIR, unfolding) vs input size.  Same comparison set as fig1."""
from __future__ import annotations

import argparse
import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import fmt_table, speedup, timeit, us

OPS = ["dft", "idft", "fir", "unfold"]


def np_impl(name):
    def np_fir(x, taps):
        x2 = np.atleast_2d(x)
        return np.stack([np.convolve(r, taps, mode="valid") for r in x2]
                        ).reshape(x.shape[:-1] + (-1,))

    def np_unfold(x, j):
        idx = np.arange(x.shape[-1] - j + 1)[:, None] + np.arange(j)[None, :]
        return x[..., idx]

    return {
        "dft": lambda x: np.fft.fft(x),
        "idft": lambda z: np.fft.ifft(z),
        "fir": np_fir,
        "unfold": np_unfold,
    }[name]


def jnp_impl(name):
    return {
        "dft": lambda x: jnp.fft.fft(x),
        "idft": lambda z: jnp.fft.ifft(z),
        "fir": lambda x, t: jnp.convolve(x.reshape(-1), t, mode="valid"),
        "unfold": lambda x, j: x[..., jnp.arange(x.shape[-1] - j + 1)[:, None]
                                 + jnp.arange(j)[None, :]],
    }[name]


def run(sizes=(64, 256, 1024), repeats=20):
    from repro.core.registry import REGISTRY
    rng = np.random.default_rng(0)
    blocks = []
    for opname in OPS:
        op = REGISTRY[opname]
        rows = []
        for n in sizes:
            args_np = op.make_args(rng, n)
            args_j = [jnp.asarray(a) if isinstance(a, np.ndarray) else a
                      for a in args_np]
            t_np = timeit(np_impl(opname), *args_np, repeats=repeats)
            if opname == "fir" and args_np[0].ndim > 1:
                jfn = jax.jit(lambda x, t: jax.vmap(
                    lambda r: jnp.convolve(r, t, mode="valid"))(np.atleast_2d(x)))
            else:
                jfn = jax.jit(jnp_impl(opname))
            try:
                t_jnp = timeit(jfn, *args_j, repeats=repeats)
            except Exception:
                t_jnp = float("nan")
            # bind non-array args (e.g. unfold's window) statically
            arr_args = [a for a in args_j if hasattr(a, "shape")]
            static = [a for a in args_j if not hasattr(a, "shape")]

            def bound(lowering):
                return jax.jit(lambda *xs: op.fn(*xs, *static,
                                                 lowering=lowering))

            t_tina = timeit(bound("native"), *arr_args, repeats=repeats)
            t_conv = timeit(bound("conv"), *arr_args, repeats=repeats)
            rows.append([n, us(t_np), us(t_jnp), us(t_tina), us(t_conv),
                         speedup(t_np, t_tina)])
        blocks.append(fmt_table(
            f"Fig.2 {opname}",
            ["n", "numpy_us", "jnp_us", "tina_us", "tina_conv_us",
             "tina_vs_np"], rows))
    return "\n\n".join(blocks)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sizes", type=int, nargs="+", default=[64, 256, 1024])
    ap.add_argument("--repeats", type=int, default=20)
    args = ap.parse_args()
    print(run(tuple(args.sizes), args.repeats))


if __name__ == "__main__":
    from repro import compile_cache
    compile_cache.enable()
    main()
