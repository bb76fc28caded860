"""The triangular solve of the chunkwise gated delta rule alone, beside what it
takes the place of, at ``olmo-hybrid-long-backlog``'s shapes: ``a`` ``f32[1,
30, n, 64, 64]`` against ``rhs`` ``f32[1, 30, n, 64, 288]`` for the chunk
widths the engine compiles (n = 4: 256 tokens; 2: 128; 1: 64 or fewer), SIX
systems' worth in one program as a chunk of six linear layers holds them,
each layer with an ``a`` of its own (one ``a`` for all six and the compiler
inverts it once).

    chiprun -- python tools/delta_solve_probe.py

prints, for each n, milliseconds a layer of ``jax.scipy.linalg.
solve_triangular`` and of ``ops/delta_solve.delta_solve``, what is left when
neither runs (the program mixes each result into the next right-hand side),
and each one's largest error against a float64 solve as a share of the
largest entry. A measurement: no TPU is an error.
"""

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.scipy.linalg import solve_triangular

sys.path.insert(0, ".")

from sparkdl_tpu.ops.delta_solve import ORDER, delta_solve  # noqa: E402
from sparkdl_tpu.runtime.chip import require_tpu  # noqa: E402

HEADS, WIDTH, LAYERS = 30, 288, 6


def definition(a, rhs):
    return solve_triangular(a + jnp.eye(ORDER, dtype=a.dtype), rhs,
                            lower=True, unit_diagonal=True)


def six_layers(solve):
    def run(a, rhs):
        x = rhs
        for layer in range(LAYERS):
            x = 0.5 * (solve(a[layer], x) + rhs)
        return x
    return jax.jit(run)


def ms_a_layer(fn, args, calls=20):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / calls / LAYERS * 1e3


def main():
    require_tpu()
    for n in (4, 2, 1):
        rng = np.random.default_rng(n)
        a = jnp.asarray(np.tril(0.2 * rng.standard_normal(
            (LAYERS, 1, HEADS, n, ORDER, ORDER)), -1), jnp.float32)
        rhs = jnp.asarray(rng.standard_normal((1, HEADS, n, ORDER, WIDTH)),
                          jnp.float32)
        want = np.linalg.solve(np.asarray(a[0], np.float64) + np.eye(ORDER),
                               np.asarray(rhs, np.float64))
        line = {"n": n, "mix_alone_ms": ms_a_layer(
            six_layers(lambda a, x: x), (a, rhs))}
        for name, solve in (("solve_triangular", definition),
                            ("delta_solve", delta_solve)):
            line[name + "_ms"] = ms_a_layer(six_layers(solve), (a, rhs))
            line[name + "_error"] = float(
                np.abs(np.asarray(jax.jit(solve)(a[0], rhs)) - want).max()
                / np.abs(want).max())
        print(line, flush=True)


if __name__ == "__main__":
    main()
