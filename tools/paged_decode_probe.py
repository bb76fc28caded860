"""The paged one-query attention kernel alone, beside what it takes the place
of, at a cell's shapes:

``--family olmo`` (``olmo-hybrid-long-backlog``): 16 rows of 30 heads of 128
over a pool ``bf16[2, 8192, 16, 3840]``, depths drawn as the cell draws its
prompts (lognormal, median 2048, clipped 128-7168);
``--family mimo`` (``mimo-flash-reasoning-backlog``): 32 rows of 64 query
heads over 4 K/V heads, keys of 192 over values of 128, K ``bf16[2, 32768,
16, 768]`` and V ``bf16[2, 32768, 16, 512]`` (median 1024, clipped
128-6596, the deepest prompt the cell's strata reach, and up to 750 tokens of
an answer under way);
``--family lfm2`` (``lfm2-concurrent-chat-backlog``): 64 rows of 32 query
heads over 8 K/V heads of 64, K and V ``bf16[2, 16384, 16, 512]`` (median
512, clipped 96-2737, up to 650 tokens of an answer under way).

A table of 512 entries a row for the first two, of 256 for the third.

    chiprun -- python tools/paged_decode_probe.py --family mimo

``--group-bytes N [N ...]`` measures the kernel again with a step of its
loop moving that many bytes (``paged_decode._GROUP_BYTES``; a group's span
of tokens is what both products run over, whatever a row's depth).

prints, for each draw of depths, milliseconds a layer of
``kv_pool.layer_rows`` + the family's attention over gathered rows and of
``ops/paged_decode.paged_decode_attention``, what the live columns' bytes
need at the chip's peak, and the largest difference between the two
results. A measurement: no TPU is an error.
"""

import argparse
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
from sparkdl_tpu.models.mimo_v2_flash import merged_sink_attention  # noqa: E402
from sparkdl_tpu.ops import paged_decode  # noqa: E402
from sparkdl_tpu.ops.paged_decode import paged_decode_attention  # noqa: E402
from sparkdl_tpu.runtime.chip import require_tpu  # noqa: E402

BS, LAYERS = 16, 2
#: rows, query heads, K/V heads, key head, value head, blocks of the pool,
#: entries of a row's table, median and clip of a prompt, tokens of an
#: answer under way
FAMILIES = {
    "olmo": (16, 30, 30, 128, 128, 8192, 512, 2048, (128, 7168), 300),
    "mimo": (32, 64, 4, 192, 128, 32768, 512, 1024, (128, 6596), 750),
    "lfm2": (64, 32, 8, 64, 64, 16384, 256, 512, (96, 2737), 650),
}


def draw(seed, rows, blocks, nb, median, clip, answer):
    """A table and depths as a tick of the cell holds them."""
    rng = np.random.default_rng(seed)
    prompts = np.clip(np.exp(math.log(median) + rng.standard_normal(rows)),
                      *clip)
    depth = (prompts + rng.integers(0, answer, rows)).astype(np.int32)
    table = np.full((rows, nb), blocks, np.int32)
    perm, at = rng.permutation(blocks), 0
    for s, d in enumerate(depth):
        n = -(-int(d) // BS)
        table[s, :n] = perm[at:at + n]
        at += n
    return jnp.asarray(table), jnp.asarray(depth)


def gathered(q, k, v, table, idx, k_new, v_new):
    k_old, v_old = layer_rows({"k": k, "v": v}, 1, table, q.dtype)
    if k.shape == v.shape and k.shape[-1] == q.shape[2] * q.shape[3]:
        out = merged_axis_attention(q, k_old, v_old, k_new, v_new, idx)
        return out.reshape(q.shape[0], 1, -1)
    seen = jnp.arange(k_old.shape[1])[None, :] < idx[:, None]
    return merged_sink_attention(
        q[:, 0], k_old, v_old, k_new[:, 0], v_new[:, 0], seen, None,
        k.shape[-1] // q.shape[-1])[:, None]


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
    parser = argparse.ArgumentParser()
    parser.add_argument("--family", choices=sorted(FAMILIES), default="olmo")
    parser.add_argument("--group-bytes", type=int, nargs="*", default=[])
    args = parser.parse_args()
    family = args.family
    (rows, heads, kv_heads, dk, dv, blocks, nb, median, clip,
     answer) = FAMILIES[family]
    require_tpu()
    peak = peak_for(jax.devices()[0].device_kind).hbm_bytes_per_s
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    pool = (LAYERS, blocks, BS)
    k = jax.random.normal(keys[0], pool + (kv_heads * dk,), jnp.bfloat16)
    v = jax.random.normal(keys[1], pool + (kv_heads * dv,), jnp.bfloat16)
    q = jax.random.normal(keys[2], (rows, 1, heads, dk), jnp.bfloat16)
    k_new = jax.random.normal(keys[3], (rows, 1, kv_heads * dk), jnp.bfloat16)
    v_new = jax.random.normal(keys[4], (rows, 1, kv_heads * dv), jnp.bfloat16)
    fns = {"gathered": jax.jit(gathered), "in_place": jax.jit(in_place)}

    def with_group(n):
        def fn(*a):
            # read while the kernel is traced, which is inside this call
            was, paged_decode._GROUP_BYTES = paged_decode._GROUP_BYTES, n
            try:
                return in_place(*a)
            finally:
                paged_decode._GROUP_BYTES = was
        return jax.jit(fn)

    fns.update({f"in_place_{n}": with_group(n) for n in args.group_bytes})
    for seed in (1, 2, 3):
        table, idx = draw(seed, rows, blocks, nb, median, clip, answer)
        live = int(np.asarray(idx).sum())
        need = live * kv_heads * (dk + dv) * 2 / peak * 1e3
        line = {"family": family, "seed": seed, "live_cols": live,
                "deepest": int(np.asarray(idx).max()),
                "blocks": int(np.sum(-(-np.asarray(idx) // BS))),
                "bytes_need_ms": round(need, 3)}
        outs = {}
        for name, fn in fns.items():
            line[name + "_ms"], outs[name] = ms_a_call(
                fn, (q, k, v, table, idx, k_new, v_new))
        line["max_abs_diff"] = max(
            float(jnp.abs(outs["gathered"].astype(jnp.float32)
                          - out.astype(jnp.float32)).max())
            for name, out in outs.items() if name != "gathered")
        print(line, flush=True)


if __name__ == "__main__":
    main()
