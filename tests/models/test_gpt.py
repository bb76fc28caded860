"""GPT decoder family: causality, attention-impl oracles, KV-cache decode
equality, generate() vs. manual argmax decode, tp sharding, MoE variant."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparkdl_tpu.models.gpt import (
    GPTConfig,
    GPTLMHeadModel,
    apply_rope,
    generate,
    init_cache,
)
from sparkdl_tpu.parallel.tensor_parallel import init_sharded
from sparkdl_tpu.runtime.mesh import MeshSpec


@pytest.fixture(scope="module")
def tiny():
    cfg = GPTConfig.tiny()
    model = GPTLMHeadModel(cfg)
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 12)), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ids)
    return cfg, model, params, ids


def test_rope_identity_at_position_zero():
    x = jnp.asarray(np.random.default_rng(0).standard_normal((1, 1, 2, 8)),
                    jnp.float32)
    pos = jnp.zeros((1, 1), jnp.int32)
    np.testing.assert_allclose(np.asarray(apply_rope(x, pos)), np.asarray(x),
                               atol=1e-6)


def test_causal_future_tokens_do_not_affect_past(tiny):
    cfg, model, params, ids = tiny
    logits, _ = model.apply(params, ids)
    changed = ids.at[:, -1].set((ids[:, -1] + 1) % cfg.vocab_size)
    logits2, _ = model.apply(params, changed)
    # All positions except the last are unaffected by the last token.
    np.testing.assert_allclose(
        np.asarray(logits[:, :-1]), np.asarray(logits2[:, :-1]), atol=1e-5
    )
    assert not np.allclose(np.asarray(logits[:, -1]),
                           np.asarray(logits2[:, -1]))


def test_flash_matches_full(tiny):
    cfg, model, params, ids = tiny
    logits_full, _ = model.apply(params, ids)
    flash_model = GPTLMHeadModel(
        GPTConfig.tiny(attn_impl="flash")
    )
    logits_flash, _ = flash_model.apply(params, ids)
    np.testing.assert_allclose(
        np.asarray(logits_full), np.asarray(logits_flash), atol=2e-4
    )


def test_cached_decode_matches_full_forward(tiny):
    cfg, model, params, ids = tiny
    b, l = ids.shape
    logits_full, _ = model.apply(params, ids)

    # Prefill l-1 tokens, then decode the last token with the cache.
    cache = init_cache(cfg, b, l)
    logits_pre, cache = model.apply(params, ids[:, :-1], cache=cache)
    np.testing.assert_allclose(
        np.asarray(logits_pre), np.asarray(logits_full[:, :-1]), atol=1e-4
    )
    logits_last, cache = model.apply(params, ids[:, -1:], cache=cache)
    np.testing.assert_allclose(
        np.asarray(logits_last[:, 0]), np.asarray(logits_full[:, -1]),
        atol=1e-4,
    )
    assert int(cache["idx"]) == l


def test_cached_decode_flash_matches_full_forward(tiny):
    """VERDICT r2 next #5 done-criterion: the cached-vs-full oracle with
    flash decode enabled — the opt-in ops/flash_decode kernel covers the
    KV-cached single-token step (dense is the measured-faster default,
    PERF.md round 5)."""
    cfg, _, params, ids = tiny
    flash_model = GPTLMHeadModel(
        GPTConfig.tiny(attn_impl="flash", flash_decode=True))
    b, l = ids.shape
    logits_full, _ = flash_model.apply(params, ids)

    cache = init_cache(cfg, b, l)
    _, cache = flash_model.apply(params, ids[:, :-1], cache=cache)
    logits_last, cache = flash_model.apply(params, ids[:, -1:], cache=cache)
    np.testing.assert_allclose(
        np.asarray(logits_last[:, 0]), np.asarray(logits_full[:, -1]),
        atol=2e-4,
    )
    assert int(cache["idx"]) == l

    # and generate() under jit routes every scan step through the kernel
    out_flash = jax.jit(
        lambda p, x: generate(flash_model, p, x, 4)
    )(params, ids[:, :4])
    out_full = jax.jit(
        lambda p, x: generate(GPTLMHeadModel(cfg), p, x, 4)
    )(params, ids[:, :4])
    np.testing.assert_array_equal(np.asarray(out_flash),
                                  np.asarray(out_full))


def test_cached_prefill_flash_matches_full_forward(tiny):
    """VERDICT r4 directive 5 done-criterion: cached PREFILL with flash
    enabled runs the flash kernel over the written prefix (causal
    q-offset), not the dense [B,H,L,max_len] path — and matches the full
    forward. Chunked prefill exercises a nonzero static q_offset."""
    cfg, _, params, ids = tiny
    flash_model = GPTLMHeadModel(GPTConfig.tiny(attn_impl="flash"))
    b, l = ids.shape
    logits_full, _ = flash_model.apply(params, ids)

    # one-shot prefill (idx=0) into a much larger buffer: O(L) keys, and
    # the unwritten tail of the buffer must not affect the result
    cache = init_cache(cfg, b, 4 * l)
    logits_pre, cache = flash_model.apply(params, ids, cache=cache)
    np.testing.assert_allclose(
        np.asarray(logits_pre), np.asarray(logits_full), atol=2e-4
    )
    assert int(cache["idx"]) == l

    # chunked prefill: second chunk lands at concrete idx=l//2 > 0
    cache = init_cache(cfg, b, 4 * l)
    _, cache = flash_model.apply(params, ids[:, : l // 2], cache=cache)
    logits2, cache = flash_model.apply(params, ids[:, l // 2:], cache=cache)
    np.testing.assert_allclose(
        np.asarray(logits2), np.asarray(logits_full[:, l // 2:]), atol=2e-4
    )
    assert int(cache["idx"]) == l


def test_generate_greedy_matches_manual_argmax(tiny):
    cfg, model, params, ids = tiny
    prompt = ids[:, :4]
    n_new = 5
    out = jax.jit(
        lambda p, x: generate(model, p, x, n_new)
    )(params, prompt)
    assert out.shape == (2, 4 + n_new)
    np.testing.assert_array_equal(np.asarray(out[:, :4]), np.asarray(prompt))

    # Oracle: uncached greedy decode via repeated full forwards.
    seq = prompt
    for _ in range(n_new):
        logits, _ = model.apply(params, seq)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(seq.dtype)
        seq = jnp.concatenate([seq, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(seq))


def test_sample_logits_topk_topp():
    """Truncation semantics on a hand-built distribution."""
    from sparkdl_tpu.models.gpt import sample_logits

    logits = jnp.log(jnp.asarray(
        [[0.5, 0.25, 0.15, 0.06, 0.04]], jnp.float32))
    keys = jax.random.split(jax.random.PRNGKey(0), 300)

    # top_k=1 is greedy regardless of temperature
    toks = jnp.stack([
        sample_logits(logits, k, temperature=1.0, top_k=1) for k in keys[:20]
    ])
    assert set(np.asarray(toks).ravel()) == {0}

    # top_k=2 only emits the two largest
    toks = jnp.stack([
        sample_logits(logits, k, temperature=1.0, top_k=2) for k in keys
    ])
    assert set(np.asarray(toks).ravel()) <= {0, 1}

    # top_p=0.7: nucleus {0.5, 0.25} (preceding mass 0, 0.5 < 0.7; token 2
    # has preceding mass 0.75 — excluded)
    toks = jnp.stack([
        sample_logits(logits, k, temperature=1.0, top_p=0.7) for k in keys
    ])
    assert set(np.asarray(toks).ravel()) <= {0, 1}

    # top_k beyond the vocab clamps (HF parity: serving defaults like 50
    # must not crash tiny-vocab models) == plain sampling per key
    for k in keys[:5]:
        np.testing.assert_array_equal(
            np.asarray(sample_logits(logits, k, temperature=1.0,
                                     top_k=50)),
            np.asarray(sample_logits(logits, k, temperature=1.0)),
        )
    with pytest.raises(ValueError, match="top_k"):
        sample_logits(logits, keys[0], temperature=1.0, top_k=0)

    # top_p=1.0 keeps everything: identical to plain sampling per key
    for k in keys[:10]:
        np.testing.assert_array_equal(
            np.asarray(sample_logits(logits, k, temperature=1.0,
                                     top_p=1.0)),
            np.asarray(sample_logits(logits, k, temperature=1.0)),
        )


def test_generate_topk_topp_paths(tiny):
    cfg, model, params, ids = tiny
    prompt = ids[:, :4]
    out = jax.jit(lambda p, x: generate(
        model, p, x, 5, temperature=0.8, top_k=3,
        rng=jax.random.PRNGKey(1),
    ))(params, prompt)
    assert out.shape == (2, 9)
    out2 = jax.jit(lambda p, x: generate(
        model, p, x, 5, temperature=0.8, top_p=0.9,
        rng=jax.random.PRNGKey(1),
    ))(params, prompt)
    assert out2.shape == (2, 9)

    key = jax.random.PRNGKey(0)
    with pytest.raises(ValueError, match="temperature"):
        generate(model, params, prompt, 2, top_k=3)
    with pytest.raises(ValueError, match="top_p"):
        generate(model, params, prompt, 2, temperature=1.0, top_p=1.5,
                 rng=key)
    with pytest.raises(ValueError, match="top_k"):
        generate(model, params, prompt, 2, temperature=1.0, top_k=0,
                 rng=key)


def test_generate_sampling_runs_and_differs_by_rng(tiny):
    cfg, model, params, ids = tiny
    prompt = ids[:, :3]
    a = generate(model, params, prompt, 6, temperature=1.0,
                 rng=jax.random.PRNGKey(1))
    bth = generate(model, params, prompt, 6, temperature=1.0,
                   rng=jax.random.PRNGKey(2))
    assert a.shape == bth.shape == (2, 9)
    assert not np.array_equal(np.asarray(a), np.asarray(bth))


@pytest.mark.slow
def test_ring_gpt_matches_full(tiny):
    """attn_impl='ring' under an sp mesh (global RoPE positions passed per
    shard) must equal the unsharded full-attention forward."""
    cfg, model, params, ids = tiny
    from flax.core import meta

    # Unbox the Partitioned metadata: inside shard_map every mesh axis is
    # Manual and flax's boxed sharding constraints cannot apply.
    params = meta.unbox(params)
    logits_full, _ = model.apply(params, ids[:, :8])  # 8 = divisible by sp

    from jax.sharding import PartitionSpec as P

    mesh = MeshSpec(dp=2, sp=4).build()
    ring_model = GPTLMHeadModel(GPTConfig.tiny(attn_impl="ring"))
    b, l = 2, 8
    pos = jnp.broadcast_to(jnp.arange(l), (b, l))

    def local(ids_l, pos_l):
        return ring_model.apply(params, ids_l, positions=pos_l)[0]

    got = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P("dp", "sp"), P("dp", "sp")),
        out_specs=P("dp", "sp"),
        check_vma=False,
    )(ids[:, :8], pos)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(logits_full), atol=2e-4
    )


def test_generate_max_len_validated(tiny):
    cfg, model, params, ids = tiny
    with pytest.raises(ValueError, match="max_len"):
        generate(model, params, ids[:, :4], 8, max_len=6)


def test_eager_cache_overflow_raises(tiny):
    cfg, model, params, ids = tiny
    cache = init_cache(cfg, 2, 6)
    _, cache = model.apply(params, ids[:, :4], cache=cache)
    _, cache = model.apply(params, ids[:, 4:6], cache=cache)  # exactly full
    with pytest.raises(ValueError, match="KV cache overflow"):
        model.apply(params, ids[:, 6:7], cache=cache)


def test_tp_sharded_matches_unsharded(tiny):
    """dp+tp forward through SPMDPartitioner's EXPLICIT shardings on
    the jit boundary matches the unsharded forward."""
    cfg, model, params, ids = tiny
    from sparkdl_tpu.partition import GPT_RULES, SPMDPartitioner, make_mesh

    part = SPMDPartitioner(make_mesh(dp=2, tp=4), GPT_RULES)
    sharded = part.shard_params(params)
    f = part.wrap_apply(lambda p, x: model.apply(p, x)[0], params)
    logits_tp = f(sharded, part.shard_batch(ids))
    from flax.core import meta

    logits_local, _ = model.apply(meta.unbox(params), ids)
    np.testing.assert_allclose(
        np.asarray(logits_tp), np.asarray(logits_local), atol=1e-4
    )


def test_tp_implicit_propagation_matches_eager(tiny):
    """The IMPLICIT dp+tp form (committed params, bare jit, GSPMD
    propagation) agrees with the eager forward on the same committed
    params — BatchedRunner's bare-jit compile over a model-sharded
    partitioner relies on it."""
    cfg, model, params, ids = tiny
    mesh = MeshSpec(dp=2, tp=4).build()
    sharded = init_sharded(model, jax.random.PRNGKey(0), [ids], mesh)
    with jax.set_mesh(mesh):
        logits_tp, _ = jax.jit(lambda p, x: model.apply(p, x))(sharded, ids)
    logits_local, _ = model.apply(jax.tree.map(jnp.asarray, sharded), ids)
    err = float(np.max(np.abs(np.asarray(logits_tp)
                              - np.asarray(logits_local))))
    assert err < 1e-4, (
        f"implicit GSPMD propagation diverges from eager (max abs err "
        f"{err})"
    )


def test_hf_gpt2_weight_fidelity():
    """Converted HF GPT-2 weights: our forward == the torch forward."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")

    from sparkdl_tpu.models.gpt import load_hf_gpt2

    hf_cfg = transformers.GPT2Config(
        vocab_size=96, n_positions=32, n_embd=16, n_layer=2, n_head=2,
        resid_pdrop=0.0, embd_pdrop=0.0, attn_pdrop=0.0,
    )
    hf = transformers.GPT2LMHeadModel(hf_cfg).eval()
    cfg, variables = load_hf_gpt2(hf)
    model = GPTLMHeadModel(cfg)

    rng = np.random.default_rng(3)
    ids = rng.integers(0, 96, (2, 10))
    with torch.no_grad():
        want = hf(torch.tensor(ids)).logits.numpy()
    got, _ = model.apply(variables, jnp.asarray(ids, jnp.int32))
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-4)

    # KV-cached greedy generation works on the converted weights too.
    out = generate(model, variables, jnp.asarray(ids[:, :4], jnp.int32), 4)
    assert out.shape == (2, 8)


def test_moe_gpt_forward_backward():
    cfg = GPTConfig.tiny(num_experts=4, moe_every=2)
    model = GPTLMHeadModel(cfg)
    rng = np.random.default_rng(1)
    ids = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 8)), jnp.int32)
    mesh = MeshSpec(dp=2, ep=4).build()
    params = init_sharded(model, jax.random.PRNGKey(0), [ids], mesh)
    # Block 1 (index 1) is MoE, block 0 dense.
    assert "moe_mlp" in params["params"]["h_1"]
    assert "moe_mlp" not in params["params"]["h_0"]

    def loss(p):
        logits, _ = model.apply(p, ids)
        logp = jax.nn.log_softmax(logits[:, :-1])
        tgt = ids[:, 1:]
        return -jnp.mean(jnp.take_along_axis(logp, tgt[..., None], -1))

    with jax.set_mesh(mesh):
        val, g = jax.jit(jax.value_and_grad(loss))(params)
    assert np.isfinite(float(val))
    assert all(np.all(np.isfinite(np.asarray(l))) for l in jax.tree.leaves(g))
