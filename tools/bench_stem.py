"""Chip head-to-head: whole-stem Pallas kernel vs XLA's stem fusions.

VERDICT r4 directive 1 done-criterion support: either the kernel beats
the XLA stem (then it's wired into the bench path) or this measurement
is the committed proof that the whole-stem lever is dead. Prints one
JSON line with both times and the oracle error ON HARDWARE.

TPU only: a kernel timing from the Pallas interpreter is not a
measurement.
    python tools/bench_stem.py
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def scan_time(fn, operands, steps, repeats=3):
    """bench_attention.py's measurement discipline (PERF.md): chained
    scan steps inside one jit, forced scalar read, empty-dispatch
    baseline subtracted."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    first, rest = operands[0], operands[1:]

    @jax.jit
    def many(first, *rest):
        def body(acc, i):
            ff = first + i.astype(first.dtype)  # u8-safe perturbation
            return acc + fn(ff, *rest), None
        acc, _ = lax.scan(body, jnp.float32(0), jnp.arange(steps))
        return acc

    @jax.jit
    def trivial(x):
        return x.astype(jnp.float32).ravel()[0]

    float(many(first, *rest))
    float(trivial(first))
    t0 = time.perf_counter()
    for _ in range(repeats):
        out = many(first, *rest)
    float(out)
    dt = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(repeats):
        z = trivial(first)
    float(z)
    base = time.perf_counter() - t0
    return max(dt - base, 1e-9) / (steps * repeats)


def main() -> None:
    import jax
    import jax.numpy as jnp

    from sparkdl_tpu.models.registry import build_flax_model
    from sparkdl_tpu.ops.fold import fold_tf_preprocess
    from sparkdl_tpu.ops.stem_fused import (
        fold_stem_params,
        inception_stem_fused,
        pack_stem_params,
        stem_reference,
    )

    from sparkdl_tpu.runtime.chip import require_tpu

    require_tpu()
    platform = jax.default_backend()
    batch = int(os.environ.get("BENCH_BATCH", 128))
    steps = int(os.environ.get("BENCH_STEPS", 20))
    size = 299
    interpret = False

    _, variables = build_flax_model("InceptionV3", weights=None,
                                    include_top=False)
    variables = fold_tf_preprocess(variables)
    folded = fold_stem_params(variables)
    packed = pack_stem_params(folded)

    rng = np.random.default_rng(0)
    x = jax.device_put(
        rng.integers(0, 256, (batch, size, size, 3), dtype=np.uint8))

    def kernel_fn(x):
        return inception_stem_fused(x, packed, dtype=jnp.bfloat16,
                                    interpret=interpret)

    def xla_fn(x):
        return stem_reference(x, folded, dtype=jnp.bfloat16)

    # correctness on hardware first: a wrong kernel must not print a time
    ko = jax.jit(kernel_fn)(x[:8])
    xo = jax.jit(xla_fn)(x[:8])
    err = float(jnp.max(jnp.abs(ko.astype(jnp.float32)
                                - xo.astype(jnp.float32))))
    rel = err / float(jnp.max(jnp.abs(xo.astype(jnp.float32))) + 1e-9)
    assert rel < 0.05, f"stem kernel diverged on chip: abs {err} rel {rel}"

    t_k = scan_time(lambda xx: kernel_fn(xx).astype(jnp.float32).sum(),
                    (x,), steps)
    t_x = scan_time(lambda xx: xla_fn(xx).astype(jnp.float32).sum(),
                    (x,), steps)
    print(json.dumps({
        "metric": f"whole-stem Pallas kernel vs XLA stem "
                  f"({platform}, {size}px, batch {batch})",
        "value": round(t_x / t_k, 3),
        "unit": "x (>1 = kernel wins)",
        "vs_baseline": round(t_x / t_k, 3),
        "detail": {
            "kernel_ms": round(t_k * 1e3, 3),
            "xla_stem_ms": round(t_x * 1e3, 3),
            "max_abs_err": round(err, 4),
            "rel_err": round(rel, 5),
        },
    }))


if __name__ == "__main__":
    main()
