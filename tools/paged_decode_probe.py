"""The paged one-query attention kernel alone, beside what it takes the place
of, at ``olmo-hybrid-long-backlog``'s shapes: 16 rows over a pool
``bf16[2, 8192, 16, 3840]``, a table of 512 entries a row, depths drawn as
the cell draws its prompts (lognormal, median 2048, clipped 128-7168).

    chiprun -- python tools/paged_decode_probe.py

prints, for each draw of depths, milliseconds a layer of
``kv_pool.layer_rows`` + ``gpt.merged_axis_attention`` and of
``ops/paged_decode.paged_decode_attention``, what the live columns' bytes
need at the chip's peak, and the largest difference between the two
results. A measurement: no TPU is an error.
"""

import math
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, ".")

from benchmark.peaks import peak_for  # noqa: E402
from sparkdl_tpu.models.gpt import merged_axis_attention  # noqa: E402
from sparkdl_tpu.models.kv_pool import layer_rows  # noqa: E402
from sparkdl_tpu.ops.paged_decode import paged_decode_attention  # noqa: E402
from sparkdl_tpu.runtime.chip import require_tpu  # noqa: E402

ROWS, HEADS, HEAD, BS, BLOCKS, NB, LAYERS = 16, 30, 128, 16, 8192, 512, 2


def draw(seed):
    """A table and depths as a tick of the cell holds them."""
    rng = np.random.default_rng(seed)
    prompts = np.clip(np.exp(math.log(2048) + rng.standard_normal(ROWS)),
                      128, 7168)
    depth = (prompts + rng.integers(0, 300, ROWS)).astype(np.int32)
    table = np.full((ROWS, NB), BLOCKS, np.int32)
    perm, at = rng.permutation(BLOCKS), 0
    for s, d in enumerate(depth):
        n = -(-int(d) // BS)
        table[s, :n] = perm[at:at + n]
        at += n
    return jnp.asarray(table), jnp.asarray(depth)


def gathered(q, k, v, table, idx, k_new, v_new):
    k_old, v_old = layer_rows({"k": k, "v": v}, 1, table, q.dtype)
    return merged_axis_attention(q, k_old, v_old, k_new, v_new, idx)


def in_place(q, k, v, table, idx, k_new, v_new):
    return paged_decode_attention(q, k, v, 1, table, idx, k_new, v_new)


def ms_a_call(fn, args, calls=20):
    out = jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / calls * 1e3, out


def main():
    require_tpu()
    peak = peak_for(jax.devices()[0].device_kind).hbm_bytes_per_s
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    pool = (LAYERS, BLOCKS, BS, HEADS * HEAD)
    k = jax.random.normal(keys[0], pool, jnp.bfloat16)
    v = jax.random.normal(keys[1], pool, jnp.bfloat16)
    q = jax.random.normal(keys[2], (ROWS, 1, HEADS, HEAD), jnp.bfloat16)
    k_new = jax.random.normal(keys[3], (ROWS, 1, HEADS * HEAD), jnp.bfloat16)
    v_new = jax.random.normal(keys[4], (ROWS, 1, HEADS * HEAD), jnp.bfloat16)
    fns = {"gathered": jax.jit(gathered), "in_place": jax.jit(in_place)}
    for seed in (1, 2, 3):
        table, idx = draw(seed)
        live = int(np.asarray(idx).sum())
        need = live * HEADS * HEAD * 2 * 2 / peak * 1e3
        line = {"seed": seed, "live_cols": live,
                "deepest": int(np.asarray(idx).max()),
                "bytes_need_ms": round(need, 3)}
        outs = {}
        for name, fn in fns.items():
            line[name + "_ms"], outs[name] = ms_a_call(
                fn, (q, k, v, table, idx, k_new, v_new))
        line["max_abs_diff"] = float(jnp.abs(
            outs["gathered"].astype(jnp.float32)
            - outs["in_place"].astype(jnp.float32)).max())
        print(line, flush=True)


if __name__ == "__main__":
    main()
