"""Serving a sparse-expert model with mixed sliding and full attention
(the ``afmoe`` family, Arcee Trinity) through the same engine as GPT-2.

``ContinuousGPTEngine`` asks the configuration for its family
(``config.serving_family()``): the module, the K/V heads and head size of
the block pool, which layers see a window only. Nothing else changes for
the caller. The expert layer drops no token, so a request gets the same
tokens alone and in a full batch; this script checks that, on a tiny
random-weight model (every kind of layer of the published pattern).

Run: python examples/online_serving_afmoe.py [--requests N]
"""

from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from sparkdl_tpu.models.afmoe import AfmoeConfig, AfmoeLMHeadModel
from sparkdl_tpu.serving import ContinuousGPTEngine


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=6)
    args = ap.parse_args()

    # 1 dense + 3 sliding + 1 full layer, 8 experts of which 2 a token and
    # one shared, 2 KV heads under 4 query heads, a window of 32 tokens
    cfg = AfmoeConfig.tiny()
    model = AfmoeLMHeadModel(cfg)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8), jnp.int32))
    rng = np.random.default_rng(7)
    # prompts shorter and longer than the window
    cases = [(rng.integers(0, cfg.vocab_size, int(n)).astype(np.int32), 8)
             for n in rng.integers(4, 80, args.requests)]

    with ContinuousGPTEngine(cfg, variables, n_slots=4, max_len=128,
                             prefill_chunk=32) as engine:
        alone = [engine.submit(p, n).result(timeout=300) for p, n in cases[:2]]
        together = [f.result(timeout=300)
                    for f in [engine.submit(p, n) for p, n in cases]]
        snap = engine.snapshot()

    def greedy(prompt, n):   # the uncached forward, one token at a time
        ids = list(prompt)
        for _ in range(n):
            logits, _ = model.apply(variables, jnp.asarray(ids)[None])
            ids.append(int(jnp.argmax(logits[0, -1])))
        return ids[len(prompt):]

    ok = all(list(t) == greedy(p, n) for (p, n), t in zip(cases, together))
    ok &= all(list(a) == list(t) for a, t in zip(alone, together))
    for (p, _), t in zip(cases, together):
        print(f"prompt of {len(p):2d} tokens -> {list(map(int, t))}")
    print(f"(token, expert) pairs computed: {snap['expert_rows']}, none "
          f"dropped; K/V bytes a token: {snap['kv']['bytes_per_token']}")
    print("alone == in a batch == uncached greedy:", ok)
    if not ok:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
