"""The ``mimo_v2_flash`` family (``models/mimo_v2_flash.py``) at the
benchmark's rehearsal size, float32, seeded random weights, against the plain
reference (``benchmark/reference_mimo_v2_flash.py``): logits through each of
the three cache contracts at contexts past two windows, with a chunk wider
than the window and a padded last chunk; the ring a chunk leaves; the sink,
the partial rotation and the value scale; the share of an expert layer that
a chip holds; the variants it refuses."""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import manifest as mf
from benchmark import reference_mimo_v2_flash as ref
from benchmark.harness import Run
from benchmark.runners import serve_mimo_v2_flash
from sparkdl_tpu.models import kv_pool
from sparkdl_tpu.models.mimo_v2_flash import (
    FULL,
    WINDOW,
    MimoExperts,
    MimoV2FlashConfig,
    MimoV2FlashLMHeadModel,
    config_from_hf_mimo_v2_flash,
    init_mimo_v2_flash_cache,
    merged_sink_attention,
    partial_rope,
    ring_positions,
    sink_attention,
)

SEED = 2**31 + 37
TOL = 2e-5   # float32 on the CPU; the logits' standard deviation is 0.16
CELL = "mimo-flash-reasoning-backlog"


def rehearsal_hf() -> dict:
    """The model's keys of the benchmark's configuration at its rehearsal
    sizes (hidden 64, 8 query heads of 24 over 2 and 4 K/V heads, values of
    16, window 16, 2 held experts of the router's 8, top-2, vocabulary 512,
    the same seven layers)."""
    run = Run(cell=mf.resolve_cell(CELL), seed=SEED, seconds=1.0,
              trace=False, rehearse=True, t_process=0.0)
    return serve_mimo_v2_flash.hf_config(run.config())


def program_config(hf: dict, **kw) -> MimoV2FlashConfig:
    return config_from_hf_mimo_v2_flash(
        {k: hf[k] for k in serve_mimo_v2_flash.HF_KEYS if k in hf},
        first_expert=hf["first_expert"], experts_held=hf["experts_held"],
        **kw)


@pytest.fixture(scope="module")
def bundle():
    hf = rehearsal_hf()
    cfg = program_config(hf)
    model = MimoV2FlashLMHeadModel(cfg)
    variables = serve_mimo_v2_flash.program_variables(model, hf, "float32",
                                                      SEED)
    ids = np.asarray(jax.random.randint(
        jax.random.PRNGKey(1), (2, 96), 0, cfg.vocab_size), np.int32)
    want = np.stack([np.asarray(ref.mimo_logits(SEED, hf, row, "float32"))
                     for row in ids])
    return hf, cfg, model, variables, ids, want


def _no_cache(cfg, model, variables, ids):
    return jax.jit(lambda ids: model.apply(variables, ids)[0])(
        jnp.asarray(ids))


#: (real tokens, program width) of the dense contract's calls: a chunk of 40
#: is wider than the window of 16 twice over; 23 real tokens in a width of 32
#: are a padded last chunk; then one token a call to position 96 (six windows)
CHUNKS = ((40, 40), (23, 32)) + ((1, 1),) * 33


def _dense_cache(cfg, model, variables, ids):
    cache = init_mimo_v2_flash_cache(cfg, ids.shape[0], 128)
    step = jax.jit(lambda c, t: model.apply(variables, t, cache=c))
    out, pos = [], 0
    for n, width in CHUNKS:
        chunk = np.zeros((ids.shape[0], width), np.int32)
        chunk[:, :n] = ids[:, pos:pos + n]
        logits, new = step(
            dict({k: cache[k] for k in ("k", "v", "win_k", "win_v")},
                 idx=jnp.asarray(pos, jnp.int32), n=jnp.asarray(n, jnp.int32)),
            jnp.asarray(chunk))
        cache = new
        out.append(logits[:, :n])
        pos += n
    assert pos == ids.shape[1]
    assert cache["expert_counts"].shape == (6, cfg.held)
    return jnp.concatenate(out, axis=1)


def _paged_cache(cfg, model, variables, ids):
    """Two rows at DIFFERENT depths over one block pool and one pair of
    rings: each row's prompt goes in through the dense contract (40 and 56
    tokens, both past two windows) and is installed as the engine installs
    it, then both decode together through the paged contract, one token a
    call, each at its own depth."""
    fam = cfg.serving_family()
    bs, n_blocks = 16, 16
    pool = kv_pool.init_block_pool(cfg, n_blocks, bs, n_slots=2)
    pool = {k: np.array(v) for k, v in pool.items()}
    table = np.full((2, 8), n_blocks, np.int32)
    table[0, :6] = [3, 9, 1, 12, 7, 14]
    table[1, :6] = [5, 0, 11, 2, 13, 8]
    lens = [40, 56]
    logits = [None, None]
    prefill = jax.jit(lambda ids, cache: model.apply(variables, ids,
                                                     cache=cache))
    for r, n in enumerate(lens):
        out, cache = prefill(jnp.asarray(ids[r:r + 1, :n]),
                             init_mimo_v2_flash_cache(cfg, 1, 64))
        for pos in range(n):
            for name in ("k", "v"):
                pool[name][:, table[r, pos // bs], pos % bs] = np.asarray(
                    cache[name][:, 0, pos])
        for name in ("win_k", "win_v"):
            pool[name][:, r] = np.asarray(cache[name][:, 0])
        logits[r] = [out[0]]
    assert pool["win_k"].shape == (fam.state_layers, 2, 16, 4 * 24)
    step = jax.jit(lambda pool, table, idx, tok: model.apply(
        variables, tok, cache=dict(pool, table=table, idx=idx,
                                   live=jnp.ones((2,), bool))))
    idx = np.array(lens, np.int32)
    for _ in range(ids.shape[1] - max(lens)):
        tok = np.stack([ids[r, idx[r]] for r in range(2)])[:, None]
        out, new = step({k: jnp.asarray(v) for k, v in pool.items()},
                        jnp.asarray(table), jnp.asarray(idx),
                        jnp.asarray(tok))
        for r in range(2):
            pos = int(idx[r])
            for name in ("k", "v"):
                pool[name][:, table[r, pos // bs], pos % bs] = np.asarray(
                    new[name][:, r, 0])
            logits[r].append(out[r])
        for name in ("win_k", "win_v"):
            pool[name] = np.array(new[name])
        idx = idx + 1
    return [jnp.concatenate(x, axis=0) for x in logits]


@pytest.mark.parametrize("contract", ["none", "dense", "paged"])
def test_logits_equal_the_references_through_each_cache_contract(
        bundle, contract):
    _, cfg, model, variables, ids, want = bundle
    assert want.std() > 0.1
    if contract == "paged":
        got = _paged_cache(cfg, model, variables, ids)
        for r, n in enumerate((40, 56)):
            upto = n + (ids.shape[1] - 56)
            np.testing.assert_allclose(np.asarray(got[r]),
                                       want[r, :upto], atol=TOL)
        return
    fn = {"none": _no_cache, "dense": _dense_cache}[contract]
    np.testing.assert_allclose(
        np.asarray(fn(cfg, model, variables, ids)), want, atol=TOL)


@pytest.mark.parametrize("n, width, start", [
    (40, 40, 0), (23, 32, 40), (5, 8, 3), (16, 16, 16), (1, 8, 70)])
def test_the_ring_after_a_chunk_is_the_last_window_of_real_columns(
        bundle, n, width, start):
    """Whatever the chunk's width and pad: slot ``p % 16`` of every window
    layer's ring holds the column of the latest REAL position ``p``, which
    is what the same tokens leave when they go in one at a time."""
    _, cfg, model, variables, ids, _ = bundle
    row = ids[:1]
    names = ("k", "v", "win_k", "win_v")
    # (one compiled program a shape: the same calls, eagerly, take four times
    # as long)
    apply = jax.jit(lambda ids, cache: model.apply(variables, ids,
                                                   cache=cache))
    _, cache = apply(jnp.asarray(row[:, :start + n]),
                     init_mimo_v2_flash_cache(cfg, 1, 128))
    want = {name: np.asarray(cache[name]) for name in ("win_k", "win_v")}
    cache = init_mimo_v2_flash_cache(cfg, 1, 128)
    if start:
        _, cache = apply(jnp.asarray(row[:, :start]), cache)
    chunk = np.full((1, width), 7, np.int32)     # the pad is a real token id
    chunk[:, :n] = row[:, start:start + n]
    _, got = apply(
        jnp.asarray(chunk),
        dict({k: cache[k] for k in names},
             idx=jnp.asarray(start, jnp.int32),
             n=jnp.asarray(n, jnp.int32)))
    for name in want:
        np.testing.assert_allclose(np.asarray(got[name]), want[name],
                                   atol=1e-6, err_msg=name)
    held = np.asarray(ring_positions(start + n, 16))
    assert sorted(held[held >= 0]) == list(
        range(max(0, start + n - 16), start + n))


def test_a_rings_bytes_do_not_grow_with_the_context():
    cfg = MimoV2FlashConfig()          # the published widths
    fam = cfg.serving_family()
    assert (fam.layers, fam.pool_layers, fam.state_layers) == (48, 9, 39)
    assert fam.ring_columns == 128 and fam.window_layers == 0
    assert fam.state_arrays == (
        ("win_k", (128, 8 * 192), cfg.dtype),
        ("win_v", (128, 8 * 128), cfg.dtype))
    assert (fam.kv_tail, fam.v_tail) == ((768,), (512,))
    cut = dataclasses.replace(
        cfg, hybrid_layer_pattern=(FULL,) + (WINDOW,) * 5 + (FULL,),
        moe_layer_freq=(0,) + (1,) * 6, experts_held=16, dtype=jnp.bfloat16)
    fam = cut.serving_family()
    # five layers x 128 columns x (8 x 192 + 8 x 128) values x 2 bytes
    assert fam.state_bytes_per_slot == 5 * 128 * 2560 * 2 == 3276800
    assert 32 * fam.state_bytes_per_slot == 104857600      # 0.105 GB
    for max_len in (1024, 16384):
        pool = jax.eval_shape(lambda n=max_len: kv_pool.init_block_pool(
            cut, 32 * n // 16, 16, n_slots=32))
        assert pool["win_k"].shape == (5, 32, 128, 1536)
        assert pool["win_v"].shape == (5, 32, 128, 1024)
        assert pool["k"].shape == (2, 32 * max_len // 16, 16, 768)
        assert pool["v"].shape == (2, 32 * max_len // 16, 16, 512)


def test_k_and_v_may_differ_in_their_tail_and_the_rule_says_how():
    # equal heads: both tails are kv_tail's, as they were
    for heads, dim in ((4, 128), (25, 64), (30, 128)):
        tail = kv_pool.kv_tail(heads, dim)
        assert kv_pool.kv_tails(heads, dim) == (tail, tail)
        assert kv_pool.kv_tails(heads, dim, dim) == (tail, tail)
    # 4 heads of 192 go on one axis; V's 4 x 128 could keep two, and joins
    # K on one (512: four lane tiles, no pad)
    assert kv_pool.kv_tails(4, 192, 128) == ((768,), (512,))
    assert kv_pool.kv_tails(8, 256, 128) == ((8, 256), (8, 128))
    assert kv_pool.kv_tails(2, 24, 16) == ((128,), (128,))


def test_the_sink_takes_weight_and_gives_no_value():
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(size=(1, 5, 4, 6)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 5, 2, 6)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 5, 2, 3)), jnp.float32)
    mask = jnp.tril(jnp.ones((5, 5), bool))[None]
    sink = jnp.asarray([0.5, -1.0, 2.0, 0.0])
    got = np.asarray(sink_attention(q, k, v, mask, sink, jnp.float32))
    # the sink as one more key whose value is zero
    k1 = jnp.concatenate([k, jnp.zeros((1, 1, 2, 6))], 1)
    v1 = jnp.concatenate([v, jnp.zeros((1, 1, 2, 3))], 1)
    s = jnp.einsum("blhd,bkhd->bhlk", q,
                   jnp.repeat(k1, 2, axis=2)) / np.sqrt(6)
    s = s.at[..., 5].set(sink[None, :, None])
    seen = jnp.concatenate([mask, jnp.ones((1, 5, 1), bool)], -1)
    p = jax.nn.softmax(jnp.where(seen[:, None], s, -1e30), -1)
    want = jnp.einsum("bhlk,bkhd->blhd", p, jnp.repeat(v1, 2, axis=2))
    np.testing.assert_allclose(got, np.asarray(want).reshape(1, 5, 12),
                               atol=1e-6)
    bare = np.asarray(sink_attention(q, k, v, mask, None, jnp.float32))
    assert np.abs(bare).sum() > np.abs(got).sum()


@pytest.mark.parametrize("sink", [None, "learned"])
def test_the_merged_axis_step_is_the_per_head_attention(sink):
    """One query a row over K and V on merged axes (block-diagonal queries)
    equals the per-head form with the new column appended."""
    rng = np.random.default_rng(4)
    s, h, g, dk, dv, w = 3, 8, 2, 6, 4, 10
    q = jnp.asarray(rng.normal(size=(s, h, dk)), jnp.float32)
    k_old = jnp.asarray(rng.normal(size=(s, w, g, dk)), jnp.float32)
    v_old = jnp.asarray(rng.normal(size=(s, w, g, dv)), jnp.float32)
    k_new = jnp.asarray(rng.normal(size=(s, g, dk)), jnp.float32)
    v_new = jnp.asarray(rng.normal(size=(s, g, dv)), jnp.float32)
    seen = jnp.asarray(rng.random((s, w)) < 0.6)
    sinks = (jnp.asarray(rng.normal(size=(h,)), jnp.float32)
             if sink else None)
    got = merged_sink_attention(
        q, k_old.reshape(s, w, -1), v_old.reshape(s, w, -1),
        k_new.reshape(s, -1), v_new.reshape(s, -1), seen, sinks, g)
    mask = jnp.concatenate([seen, jnp.ones((s, 1), bool)], 1)[:, None]
    want = sink_attention(
        q[:, None], jnp.concatenate([k_old, k_new[:, None]], 1),
        jnp.concatenate([v_old, v_new[:, None]], 1), mask, sinks,
        jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want[:, 0]),
                               atol=1e-6)


def test_only_the_first_values_of_a_head_are_rotated():
    x = jnp.asarray(np.random.default_rng(5).normal(size=(1, 4, 2, 24)),
                    jnp.float32)
    pos = jnp.asarray([[0, 3, 17, 100]])
    out = np.asarray(partial_rope(x, pos, 10000.0, 8))
    np.testing.assert_array_equal(out[..., 8:], np.asarray(x)[..., 8:])
    np.testing.assert_array_equal(out[:, 0], np.asarray(x)[:, 0])  # angle 0
    assert np.abs(out[:, 1:, :, :8] - np.asarray(x)[:, 1:, :, :8]).min() > 0
    # pairs are (i, i + 4): norms of each pair are kept
    pair = lambda a: a[..., :4] ** 2 + a[..., 4:8] ** 2  # noqa: E731
    np.testing.assert_allclose(pair(out), pair(np.asarray(x)), rtol=1e-5)
    assert MimoV2FlashConfig().rotary_dim == 64


def test_a_token_behind_the_window_moves_a_full_layer_alone():
    """Change token 0 of 40: a window layer's output at positions 16.. does
    not move (its window is 16), a full layer's does."""
    from sparkdl_tpu.models.mimo_v2_flash import MimoAttention

    cfg = MimoV2FlashConfig.tiny(dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 40, cfg.hidden_size))
    x2 = x.at[:, 0].add(1.0)
    for kind, reach in ((WINDOW, 16), (FULL, None)):
        attn = MimoAttention(cfg, kind, 0)
        params = attn.init(jax.random.PRNGKey(3), x, cache=None)
        a, _ = attn.apply(params, x, cache=None)
        b, _ = attn.apply(params, x2, cache=None)
        moved = np.abs(np.asarray(a - b)).max(-1)[0]
        assert moved[0] > 1e-6
        if reach is None:
            assert (moved[1:] > 1e-7).all()
        else:
            assert (moved[reach:] == 0).all() and (moved[:reach] > 0).all()


@pytest.mark.parametrize("held", [2, 4, 8])
def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_references(held):
    """Every chip routes over all 8 experts and computes its own experts'
    part; the parts of all the shares add up to the uncut reference's layer
    output, nothing counted twice (the family has no shared expert)."""
    hf = dict(rehearsal_hf(), experts_held=8, first_expert=0)   # uncut
    w = ref.layer_weights(SEED, 1, hf, "float32")
    h = jax.random.normal(jax.random.PRNGKey(4), (40, int(hf["hidden_size"])))
    with jax.default_matmul_precision("highest"):
        want, sel = ref._expert_layer(json.dumps(hf, sort_keys=True), w, h,
                                      "f32")
    assert np.abs(np.asarray(want)).max() > 1e-3
    total = jnp.zeros_like(want)
    rows = 0
    for first in range(0, 8, held):
        cfg = dataclasses.replace(program_config(hf), first_expert=first,
                                  experts_held=held)
        params = {"router": w["moe.router"],
                  "expert_bias": w["moe.expert_bias"]}
        for name in ("experts_gate", "experts_up", "experts_down"):
            params[name] = w["moe." + name][first:first + held]
        out, counts = MimoExperts(cfg).apply({"params": params}, h[None])
        assert counts.shape == (held,)
        # what a share computes is its own experts' rows alone
        assert int(counts.sum()) == int(
            ((sel >= first) & (sel < first + held)).sum())
        total = total + out[0]
        rows += int(counts.sum())
    assert rows == 40 * 2, "every (token, expert) pair is computed once"
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), atol=TOL)


def test_the_reference_is_given_the_same_share_as_the_program(bundle):
    hf, cfg, _, _, _, _ = bundle
    assert (cfg.num_experts, cfg.held, cfg.first_expert) == (8, 2, 0)
    s = ref.mimo_sizes(hf)
    assert (s["experts"], s["held"], s["first"]) == (8, 2, 0)
    leaves = ref.layer_leaves(hf, 1)
    assert leaves["moe.router"][0] == (64, 8)
    assert leaves["moe.experts_gate"][0] == (2, 64, 32)
    assert "moe.shared.gate_proj" not in leaves
    assert "attn.sink" in leaves and "attn.sink" not in ref.layer_leaves(hf, 0)


def test_a_rows_result_does_not_depend_on_who_shares_its_batch(bundle):
    _, _, model, variables, ids, _ = bundle
    apply = jax.jit(lambda ids: model.apply(variables, ids)[0])
    both, alone = apply(jnp.asarray(ids[:, :48])), apply(
        jnp.asarray(ids[1:, :48]))
    np.testing.assert_allclose(np.asarray(alone[0]), np.asarray(both[1]),
                               atol=1e-6)


def test_variants_the_forward_does_not_compute_are_refused():
    hf = rehearsal_hf()
    base = {k: hf[k] for k in serve_mimo_v2_flash.HF_KEYS if k in hf}
    assert config_from_hf_mimo_v2_flash(base).num_layers == 7
    for key, value, said in (
            ("model_type", "afmoe", "not a mimo_v2_flash"),
            ("n_group", 2, "group-limited"),
            ("topk_group", 2, "group-limited"),
            ("n_shared_experts", 1, "shared expert"),
            ("add_full_attention_sink_bias", True, "full-attention"),
            ("add_swa_attention_sink_bias", False, "without their sink"),
            ("rope_scaling", {"rope_type": "yarn", "factor": 4.0},
             "rope_scaling"),
            ("scoring_func", "softmax", "sigmoid"),
            ("topk_method", "greedy", "noaux_tc"),
            ("hidden_act", "gelu", "silu"),
            ("attention_bias", True, "biases"),
            ("tie_word_embeddings", True, "tied head"),
            ("swa_head_dim", 32, "swa_head_dim"),
            ("swa_v_head_dim", 32, "swa_v_head_dim"),
            ("swa_num_attention_heads", 4, "swa_num_attention_heads"),
            ("sliding_window_size", 8, "disagree"),
            ("num_hidden_layers", 6, "disagree")):
        with pytest.raises(ValueError, match=said):
            config_from_hf_mimo_v2_flash({**base, key: value})
    # a default rope_scaling (the later release's spelling) changes nothing
    config_from_hf_mimo_v2_flash(
        {**base, "rope_scaling": {"rope_type": "default", "type": "default"}})
    with pytest.raises(ValueError, match="not among"):
        config_from_hf_mimo_v2_flash(base, first_expert=7, experts_held=2)
    with pytest.raises(ValueError, match="disagree in length"):
        MimoV2FlashConfig.tiny(moe_layer_freq=(0, 1))
    model = MimoV2FlashLMHeadModel(MimoV2FlashConfig.tiny())
    with pytest.raises(ValueError, match="one token a row"):
        jax.eval_shape(lambda: model.init(
            jax.random.PRNGKey(0), jnp.zeros((2, 3), jnp.int32),
            cache={"table": jnp.zeros((2, 1), jnp.int32)}))
