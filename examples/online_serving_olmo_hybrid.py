"""Serving a hybrid of linear and full attention (the ``olmo_hybrid``
family, AllenAI Olmo Hybrid) through the same engine as GPT-2.

Three of every four layers are gated-delta-rule linear attention: they keep
no K/V, only ONE recurrent state a request (and the last inputs of three
short convolutions), whatever the context's length. ``ContinuousGPTEngine``
asks the configuration for its family (``config.serving_family()``): which
layers keep K/V in the block pool, which keep a state by slot, and its
shapes. A prompt is prefilled in chunks (the chunkwise form of the rule, the
state carried from chunk to chunk) and decoded one token a tick (the
one-token form); this script checks, on a tiny random-weight model, that
both give the tokens of the uncached forward, alone and in a batch, and that
a repeated prompt is prefilled whole (a cached prefix's blocks do not hold
the state at its boundary).

Run: python examples/online_serving_olmo_hybrid.py [--requests N]
"""

from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from sparkdl_tpu.models.olmo_hybrid import (
    OlmoHybridConfig,
    OlmoHybridLMHeadModel,
)
from sparkdl_tpu.serving import ContinuousGPTEngine


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=6)
    args = ap.parse_args()

    # the benchmark's rehearsal sizes: two periods of linear, linear,
    # linear, full; 4 linear heads of 8 x 16 and 4 attention heads of 16
    cfg = OlmoHybridConfig.tiny(
        layer_types=OlmoHybridConfig.tiny().layer_types * 2)
    model = OlmoHybridLMHeadModel(cfg)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8), jnp.int32))
    rng = np.random.default_rng(7)
    # prompts of less than one prefill chunk of 32 and of several
    cases = [(rng.integers(0, cfg.vocab_size, int(n)).astype(np.int32), 8)
             for n in rng.integers(4, 100, args.requests)]

    with ContinuousGPTEngine(cfg, variables, n_slots=4, max_len=128,
                             prefill_chunk=32) as engine:
        alone = [engine.submit(p, n).result(timeout=300) for p, n in cases[:2]]
        together = [f.result(timeout=300)
                    for f in [engine.submit(p, n) for p, n in cases]]
        kv = engine.snapshot()["kv"]

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
    print(f"K/V bytes a token (2 full layers of 8): {kv['bytes_per_token']}; "
          f"recurrent state a slot (6 linear layers): "
          f"{kv['state_bytes_per_slot']} bytes, whatever the context; "
          f"tokens of repeated prompts prefilled again: "
          f"{kv['prefix_passed_up']}")
    print("alone == in a batch == uncached greedy:", ok)
    if not ok:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
