"""Sequence-parallel prefill parity (ISSUE 13): ``sp_prefill`` under
both collective schedules vs the single-device dense forward, at every
shard count of the 8-device conftest mesh and on non-divisible-remainder
prompts.

Parity tiers (measured on this harness, PERF.md):

- **allgather, sp <= 2, sp-divisible prompt**: logits AND K/V
  BITWISE-identical to the unsharded forward (12/12 seeds) — the
  serving engine's sp∈{1,2} contract rides this tier; its chunk widths
  are always pow2-bucketed, hence always sp-divisible.
- **allgather, any sp / remainder prompts**: greedy tokens bitwise,
  logits allclose — the internal right-pad changes XLA:CPU's SIMD
  reduction widths, shifting last-bit rounding on ~1% of elements.
- **ring, any sp**: greedy tokens bitwise, logits allclose — the online
  softmax re-associates the accumulation, exact up to fp.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparkdl_tpu.models.gpt import GPTConfig, GPTLMHeadModel, sp_prefill
from sparkdl_tpu.partition.mesh_factory import make_mesh

PROMPT_LEN = 21   # deliberately not divisible by any sp > 1
EVEN_LEN = 24     # divides every tested sp: the bitwise tier


@pytest.fixture(scope="module")
def bundle():
    cfg = GPTConfig.tiny()
    model = GPTLMHeadModel(cfg)
    variables = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    rng = np.random.default_rng(3)
    ids = jnp.asarray(
        rng.integers(1, cfg.vocab_size, (2, PROMPT_LEN)), jnp.int32)
    even_ids = jnp.asarray(
        rng.integers(1, cfg.vocab_size, (2, EVEN_LEN)), jnp.int32)
    # the references are COMPILED forwards, like sp_prefill's program
    # and every program the serving engine runs: the bitwise tier is
    # sharded-vs-unsharded under one compiler, not jit-vs-eager
    forward = jax.jit(lambda v, x: model.apply(v, x)[0])
    ref_logits = forward(variables, ids)
    even_ref = forward(variables, even_ids)
    return (cfg, variables, ids, np.asarray(ref_logits),
            even_ids, np.asarray(even_ref))


def _sp_model(cfg, mode):
    return GPTLMHeadModel(
        dataclasses.replace(cfg, attn_impl="ring", sp_mode=mode))


@pytest.mark.parametrize("sp", [1, 2, 4, 8])
@pytest.mark.parametrize("mode", ["allgather", "ring"])
def test_sp_prefill_parity_every_shard_count(bundle, sp, mode):
    """Remainder prompt (21 tokens): greedy tokens bitwise and logits
    allclose at every shard count, both collective schedules."""
    cfg, variables, ids, ref, _, _ = bundle
    mesh = make_mesh(dp=1, sp=sp, devices=jax.devices()[:sp])
    logits, cache = sp_prefill(_sp_model(cfg, mode), variables, ids, mesh)
    logits = np.asarray(logits)
    assert logits.shape == ref.shape  # remainder pad sliced off
    np.testing.assert_array_equal(
        logits.argmax(-1), ref.argmax(-1))
    np.testing.assert_allclose(logits, ref, atol=2e-5)
    assert int(cache["idx"]) == PROMPT_LEN


@pytest.mark.parametrize("sp", [1, 2])
def test_sp_prefill_bitwise_tier(bundle, sp):
    """The serving contract's tier: allgather at sp<=2 on an
    sp-divisible prompt is FULL-LOGITS bitwise vs the unsharded
    forward (the engine's chunk widths are always pow2-bucketed, so
    its shards always sit in this tier)."""
    cfg, variables, _, _, even_ids, even_ref = bundle
    mesh = make_mesh(dp=1, sp=sp, devices=jax.devices()[:sp])
    logits, _ = sp_prefill(
        _sp_model(cfg, "allgather"), variables, even_ids, mesh)
    np.testing.assert_array_equal(np.asarray(logits), even_ref)


def test_sp_prefill_kv_matches_cached_prefill(bundle):
    """The returned K/V must equal what the cached (init_cache) prefill
    writes — the handoff contract: sp_prefill's cache can seed decode.
    Bitwise on the sp-divisible tier."""
    from sparkdl_tpu.models.gpt import init_cache

    cfg, variables, _, _, even_ids, _ = bundle
    mesh = make_mesh(dp=1, sp=2, devices=jax.devices()[:2])
    _, cache = sp_prefill(
        _sp_model(cfg, "allgather"), variables, even_ids, mesh)
    model = GPTLMHeadModel(cfg)
    dense_cache = init_cache(cfg, even_ids.shape[0], EVEN_LEN)
    _, dense_cache = jax.jit(
        lambda v, x, c: model.apply(v, x, cache=c))(
            variables, even_ids, dense_cache)
    np.testing.assert_array_equal(
        np.asarray(cache["k"]), np.asarray(dense_cache["k"]))
    np.testing.assert_array_equal(
        np.asarray(cache["v"]), np.asarray(dense_cache["v"]))


def test_sp_prefill_requires_ring_impl(bundle):
    cfg, variables, ids, _, _, _ = bundle
    mesh = make_mesh(dp=1, sp=2, devices=jax.devices()[:2])
    with pytest.raises(ValueError, match="attn_impl='ring'"):
        sp_prefill(GPTLMHeadModel(cfg), variables, ids, mesh)


def test_sp_prefill_learned_positions_guard(bundle):
    cfg, variables, ids, _, _, _ = bundle
    short = dataclasses.replace(
        cfg, attn_impl="ring", positions="learned", max_seq_len=16)
    mesh = make_mesh(dp=1, sp=2, devices=jax.devices()[:2])
    with pytest.raises(ValueError, match="position table"):
        sp_prefill(GPTLMHeadModel(short), variables, ids, mesh)
