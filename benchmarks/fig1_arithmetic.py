"""Paper Fig. 1: runtime of the arithmetic functions vs input size.

Columns (this container, DESIGN.md §8.2): NumPy (CPU baseline, the
paper's reference), direct-jnp (jit; the paper's "JAX" column), TINA
native (the TPU-adapted mapping, jit), TINA conv (the paper-faithful
NN-layer lowering, jit).  Pallas kernels run in interpret mode on CPU,
orders of magnitude off their TPU performance, so they are validated in
tests and excluded from CPU timing by default (--pallas adds them).
"""
from __future__ import annotations

import argparse
import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import fmt_table, speedup, timeit, us

OPS = ["elementwise_mult", "matmul", "elementwise_add", "summation"]


def np_impl(name):
    return {
        "elementwise_mult": lambda x, y: x * y,
        "matmul": lambda x, y: x @ y,
        "elementwise_add": lambda x, y: x + y,
        "summation": lambda x: x.sum(-1),
    }[name]


def jnp_impl(name):
    return {
        "elementwise_mult": lambda x, y: x * y,
        "matmul": lambda x, y: x @ y,
        "elementwise_add": lambda x, y: x + y,
        "summation": lambda x: x.sum(-1),
    }[name]


def run(sizes=(64, 256, 1024), include_pallas=False, repeats=20):
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
            t_jnp = timeit(jax.jit(jnp_impl(opname)), *args_j,
                           repeats=repeats)
            t_tina = timeit(jax.jit(functools.partial(op.fn, lowering="native")),
                            *args_j, repeats=repeats)
            row = [n, us(t_np), us(t_jnp), us(t_tina), speedup(t_np, t_tina)]
            if "conv" in op.lowerings:
                t_conv = timeit(jax.jit(functools.partial(op.fn, lowering="conv")),
                                *args_j, repeats=repeats)
                row.append(us(t_conv))
            else:
                row.append("-")
            if include_pallas and "pallas" in op.lowerings:
                t_pal = timeit(functools.partial(op.fn, lowering="pallas"),
                               *args_j, repeats=3)
                row.append(us(t_pal))
            rows.append(row)
        hdr = ["n", "numpy_us", "jnp_us", "tina_us", "tina_vs_np",
               "tina_conv_us"] + (["pallas_us"] if include_pallas else [])
        blocks.append(fmt_table(f"Fig.1 {opname}", hdr, rows))
    return "\n\n".join(blocks)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sizes", type=int, nargs="+", default=[64, 256, 1024])
    ap.add_argument("--pallas", action="store_true")
    ap.add_argument("--repeats", type=int, default=20)
    args = ap.parse_args()
    print(run(tuple(args.sizes), args.pallas, args.repeats))


if __name__ == "__main__":
    from repro import compile_cache
    compile_cache.enable()
    main()
