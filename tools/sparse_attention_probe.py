"""A ``glm_moe_dsa`` step's selection and attention of ONE layer alone, in
both forms, at the widths on either side of the rule
``ops/sparse_attention.attends_in_place``: what its constant
``IN_PLACE_SELECTIONS`` was measured with.

32 rows of 64 absorbed queries of 640 over a ``latent`` pool ``bf16[1,
65536, 16, 640]``, a selection of 2,048 columns, tables of 8,192, 16,384
and 32,768 columns (4, 8 and 16 selections), and at each width two draws of
the rows' depths: as ``glm52-sparse-agent-backlog`` draws its contexts (a
prompt lognormal, median 2,048, clipped 512-8,192, and up to 768 tokens of
an answer under way; clipped to the width), and every row at the table's
whole width (what a deployment at that ``max_len`` runs when it is full).

    chiprun -- python tools/sparse_attention_probe.py

For each, milliseconds a call of

- ``gather``: ``pick_columns`` (a sort) then ``selected_columns`` +
  ``absorbed_attention`` (2,048 columns a row fetched one by one);
- ``in_place``: ``select_mask`` (a bisection) then ``attend_in_place`` (the
  rows' live blocks read where the pool keeps them, under the mask);

the selection and the attention apart: a ``full`` layer runs both, a
``shared`` layer the attention alone, and the published pattern has three
``shared`` layers a ``full`` one, which is what ``five_layers_ms`` adds up
(two selections, five attentions: the benchmark's cut). ``--group-bytes N
..`` measures the kernel again with a step of its loop moving that many
bytes. A measurement: no TPU is an error.
"""

import argparse
import json
import math
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, ".")

from sparkdl_tpu.ops import paged_decode  # noqa: E402
from sparkdl_tpu.ops.sparse_attention import (  # noqa: E402
    absorbed_attention,
    attend_in_place,
    pick_columns,
    select_mask,
    selected_columns,
)
from sparkdl_tpu.runtime.chip import require_tpu  # noqa: E402

ROWS, HEADS, WIDTH, BS, BLOCKS, TOPK = 32, 64, 640, 16, 65536, 2048
SCALE = 1 / math.sqrt(256)


def draw(seed, width, full):
    """A table, the rows' depths and an indexer's scores over the table's
    columns (this call's own column at position ``idx``, ``-inf`` past
    it), as a tick holds them."""
    rng = np.random.default_rng(seed)
    if full:
        depth = np.full(ROWS, width - 1, np.int32)
    else:
        prompts = np.clip(np.exp(math.log(2048) + 0.6
                                 * rng.standard_normal(ROWS)), 512, 8192)
        depth = np.minimum(prompts + rng.integers(0, 768, ROWS),
                           width - 1).astype(np.int32)
    table = np.full((ROWS, width // BS), BLOCKS, np.int32)
    perm, at = rng.permutation(BLOCKS), 0
    for s, d in enumerate(depth):
        n = -(-int(d + 1) // BS)
        table[s, :n] = perm[at:at + n]
        at += n
    scores = rng.standard_normal((ROWS, width)).astype(np.float32)
    scores[np.arange(width)[None, :] > depth[:, None]] = -np.inf
    return jnp.asarray(table), jnp.asarray(depth), jnp.asarray(scores)


def pick(scores):
    return pick_columns(scores, TOPK)


def mask(scores):
    return select_mask(scores, TOPK)


def gather(pool, table, idx, q, new, picked):
    old, seen, new_seen = selected_columns(
        {"latent": pool, "table": table}, 0, picked, idx)
    return absorbed_attention(q, old, seen, new, new_seen, SCALE)


def in_place(pool, table, idx, q, new, picked):
    return attend_in_place({"latent": pool, "table": table}, 0, q, picked,
                           idx, new, SCALE)


def ms_a_call(fn, args, calls=20):
    out = jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / calls * 1e3, out


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--widths", type=int, nargs="*",
                        default=[8192, 16384, 32768])
    parser.add_argument("--group-bytes", type=int, nargs="*", default=[])
    parser.add_argument("--out", default="chiprun_out/sparse_attention_probe"
                                         ".jsonl")
    args = parser.parse_args()
    require_tpu()
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    pool = jax.random.normal(keys[0], (1, BLOCKS, BS, WIDTH), jnp.bfloat16)
    q = jax.random.normal(keys[1], (ROWS, HEADS, WIDTH), jnp.bfloat16)
    new = jax.random.normal(keys[2], (ROWS, WIDTH), jnp.bfloat16)

    def with_group(n):
        def fn(*a):
            # read while the kernel is traced, which is inside this call
            was, paged_decode._GROUP_BYTES = paged_decode._GROUP_BYTES, n
            try:
                return in_place(*a)
            finally:
                paged_decode._GROUP_BYTES = was
        return jax.jit(fn)

    selects = {"gather": jax.jit(pick), "in_place": jax.jit(mask)}
    attends = {"gather": jax.jit(gather), "in_place": jax.jit(in_place)}
    attends.update({f"in_place_{n}": with_group(n)
                    for n in args.group_bytes})
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "a") as log:
        for width in args.widths:
            for full in (False, True):
                for seed in (1, 2):
                    table, idx, scores = draw(seed, width, full)
                    line = {"width": width, "selections": width // TOPK,
                            "depths": "full" if full else "cell",
                            "seed": seed,
                            "live_cols": int(np.asarray(idx).sum()),
                            "deepest": int(np.asarray(idx).max())}
                    picked, mixes = {}, {}
                    for name, fn in selects.items():
                        line[f"select_{name}_ms"], picked[name] = ms_a_call(
                            fn, (scores,))
                    for name, fn in attends.items():
                        form = "gather" if name == "gather" else "in_place"
                        line[f"attend_{name}_ms"], mixes[name] = ms_a_call(
                            fn, (pool, table, idx, q, new, picked[form]))
                    for form in ("gather", "in_place"):
                        line[f"five_layers_{form}_ms"] = (
                            2 * line[f"select_{form}_ms"]
                            + 5 * line[f"attend_{form}_ms"])
                    pos, taken = (np.asarray(a) for a in picked["gather"])
                    as_mask = np.zeros(scores.shape, bool)
                    np.put_along_axis(as_mask, pos, taken, axis=1)
                    line["same_selection"] = bool(
                        (as_mask == np.asarray(picked["in_place"])).all())
                    line["max_abs_diff"] = max(
                        float(jnp.abs(mixes["gather"] - mix).max())
                        for name, mix in mixes.items() if name != "gather")
                    line["mix_std"] = float(mixes["gather"].std())
                    print(json.dumps(line), flush=True)
                    log.write(json.dumps(line) + "\n")
                    if full:
                        break       # every row alike: one draw says it all


if __name__ == "__main__":
    main()
