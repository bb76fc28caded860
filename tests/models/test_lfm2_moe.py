"""The ``lfm2_moe`` family (``models/lfm2_moe.py``) at the benchmark's
rehearsal size, float32, seeded random weights, against the plain reference
(``benchmark/reference_lfm2_moe.py``, which convolves each whole sequence
once and keeps no tail): logits through each of the three cache contracts;
the tail a chunk leaves, across a boundary at every offset, under a padded
width and after a prompt of one token; the two forms of the convolution; the
share of an expert layer that a chip holds, with this router's epsilon; the
variants ``config_from_hf_lfm2_moe`` refuses."""

import dataclasses
import inspect
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import manifest as mf
from benchmark import reference_lfm2_moe as ref
from benchmark.harness import Run
from benchmark.runners import serve_lfm2_moe
from sparkdl_tpu.models import kv_pool
from sparkdl_tpu.models.lfm2_moe import (
    CONV,
    FULL,
    ROUTE_NORM_EPS,
    Lfm2Experts,
    Lfm2MoeConfig,
    Lfm2MoeLMHeadModel,
    config_from_hf_lfm2_moe,
    init_lfm2_moe_cache,
    short_conv_chunk,
    short_conv_step,
)
from sparkdl_tpu.parallel.moe_dropless import route_sigmoid_topk

SEED = 2**31 + 42
#: float32 on the CPU: the program and the reference differ in the ORDER of
#: their sums alone (the reference convolves with ``lax.conv``, attends a
#: block of queries at a time and gives each expert its own rows), which at
#: logits of standard deviation 0.9 is a few 1e-6; a dropped tail, a pad
#: that entered one or a wrong pairing of the rotation moves them by 1e-1
TOL = 2e-5
CELL = "lfm2-concurrent-chat-backlog"


def rehearsal_hf() -> dict:
    """The model's keys of the benchmark's configuration at its rehearsal
    sizes (hidden 64, 8 query heads of 8 over 2 K/V heads, 8 experts of 32,
    top-2, vocabulary 512; a dense convolution, then one whole period)."""
    run = Run(cell=mf.resolve_cell(CELL), seed=SEED, seconds=1.0,
              trace=False, rehearse=True, t_process=0.0)
    return serve_lfm2_moe.hf_config(run.config())


@pytest.fixture(scope="module")
def bundle():
    hf = rehearsal_hf()
    cfg = config_from_hf_lfm2_moe(hf)
    model = Lfm2MoeLMHeadModel(cfg)
    variables = serve_lfm2_moe.program_variables(model, hf, "float32", SEED)
    ids = np.asarray(jax.random.randint(
        jax.random.PRNGKey(1), (2, 48), 0, cfg.vocab_size), np.int32)
    want = np.stack([np.asarray(ref.lfm2_logits(SEED, hf, row, "float32"))
                     for row in ids])
    return hf, cfg, model, variables, ids, want


_DENSE = {}


def _dense(model, variables, cache, chunk, pos, n):
    """One call of the dense contract: ``n`` real tokens of ``chunk`` at
    position ``pos`` (the engine's private prefill cache). One program a
    shape, kept for the file."""
    fn = _DENSE.get(id(model))
    if fn is None:
        fn = _DENSE[id(model)] = jax.jit(
            lambda variables, cache, chunk: model.apply(
                variables, chunk, cache=cache))
    return fn(variables,
              dict({k: cache[k] for k in ("k", "v", "conv")},
                   idx=jnp.asarray(pos, jnp.int32),
                   n=jnp.asarray(n, jnp.int32)), jnp.asarray(chunk))


def _prefill(cfg, model, variables, rows, pieces, max_len=48):
    """``rows`` ``[B, length]`` through the dense contract in calls of
    ``pieces`` = ((real tokens, program width), ...): logits of the real
    tokens ``[B, sum n, vocab]`` and the cache."""
    rows = np.atleast_2d(rows)
    cache = init_lfm2_moe_cache(cfg, rows.shape[0], max_len)
    out, pos = [], 0
    for n, width in pieces:
        chunk = np.zeros((rows.shape[0], width), np.int32)
        chunk[:, :n] = rows[:, pos:pos + n]
        logits, cache = _dense(model, variables, cache, chunk, pos, n)
        out.append(logits[:, :n])
        pos += n
    return jnp.concatenate(out, axis=1), cache


#: (real tokens, program width) of the dense contract's calls: a chunk, a
#: padded one (5 real tokens in a width of 8), then one token a call
CHUNKS = ((16, 16), (5, 8)) + ((1, 1),) * 27


def _paged_cache(cfg, model, variables, ids):
    """Two rows at DIFFERENT depths over one block pool and one array of
    tails: each row's prompt goes in through the dense contract (19 and 30
    tokens) and is installed as the engine installs it, then both decode
    together through the paged contract, one token a call, each at its own
    depth."""
    fam = cfg.serving_family()
    bs, n_blocks = 16, 8
    pool = {k: np.array(v) for k, v in kv_pool.init_block_pool(
        cfg, n_blocks, bs, n_slots=2).items()}
    assert pool["conv"].shape == (fam.state_layers, 2, 2, cfg.hidden_size)
    table = np.full((2, 4), n_blocks, np.int32)
    table[0, :3] = [3, 6, 1]
    table[1, :3] = [5, 0, 7]
    lens = [19, 30]
    logits = [None, None]
    for r, n in enumerate(lens):
        out, cache = _prefill(cfg, model, variables, ids[r, :n],
                              ((n, n),))
        for pos in range(n):
            for name in ("k", "v"):
                pool[name][:, table[r, pos // bs], pos % bs] = np.asarray(
                    cache[name][:, 0, pos])
        pool["conv"][:, r] = np.asarray(cache["conv"][:, 0])
        logits[r] = [out[0]]
    step = jax.jit(lambda pool, table, idx, tok: model.apply(
        variables, tok, cache=dict(pool, table=table, idx=idx,
                                   live=jnp.ones((2,), bool))))
    idx = np.array(lens, np.int32)
    for _ in range(ids.shape[1] - max(lens)):
        tok = np.stack([ids[r, idx[r]] for r in range(2)])[:, None]
        out, new = step({k: jnp.asarray(v) for k, v in pool.items()},
                        jnp.asarray(table), jnp.asarray(idx),
                        jnp.asarray(tok))
        assert new["expert_counts"].shape == (cfg.expert_layers, cfg.held)
        for r in range(2):
            pos = int(idx[r])
            for name in ("k", "v"):
                pool[name][:, table[r, pos // bs], pos % bs] = np.asarray(
                    new[name][:, r, 0])
            logits[r].append(out[r])
        pool["conv"] = np.array(new["conv"])
        idx = idx + 1
    return [jnp.concatenate(x, axis=0) for x in logits]


@pytest.mark.parametrize("contract", ["none", "dense", "paged"])
def test_logits_equal_the_references_through_each_cache_contract(
        bundle, contract):
    _, cfg, model, variables, ids, want = bundle
    assert want.std() > 0.1
    if contract == "paged":
        got = _paged_cache(cfg, model, variables, ids)
        for r, n in enumerate((19, 30)):
            upto = n + (ids.shape[1] - 30)
            np.testing.assert_allclose(np.asarray(got[r]), want[r, :upto],
                                       atol=TOL)
    elif contract == "dense":
        got, _ = _prefill(cfg, model, variables, ids, CHUNKS)
        np.testing.assert_allclose(np.asarray(got), want, atol=TOL)
    else:
        got = jax.jit(lambda ids: model.apply(variables, ids)[0])(
            jnp.asarray(ids))
        np.testing.assert_allclose(np.asarray(got), want, atol=TOL)


#: a "chunk" of 8 here: prompts of 1, 2, 3 and chunk - 1, chunk, chunk + 1
#: tokens, each chunk in the power-of-two width the engine would give it
@pytest.mark.parametrize("n, pieces", [
    (1, ((1, 8),)), (2, ((2, 8),)), (3, ((3, 8),)), (7, ((7, 8),)),
    (8, ((8, 8),)), (9, ((8, 8), (1, 8)))])
def test_a_short_prompts_logits_and_tail_whatever_the_padded_width(
        bundle, n, pieces):
    """The last token's logits are the reference's, and the tail handed on
    is the one an UNPADDED call over the same tokens leaves: it ends at
    token ``n``, not at the padded width, and no pad's input entered it."""
    _, cfg, model, variables, ids, want = bundle
    got, cache = _prefill(cfg, model, variables, ids[0], pieces)
    np.testing.assert_allclose(np.asarray(got[0]), want[0, :n], atol=TOL)
    _, plain = _prefill(cfg, model, variables, ids[0], ((n, n),))
    np.testing.assert_allclose(np.asarray(cache["conv"]),
                               np.asarray(plain["conv"]), atol=TOL)
    tails = np.asarray(cache["conv"])[:, 0]         # [conv layers, 2, hidden]
    assert (np.abs(tails[:, 1]) > 0).any(axis=-1).all()
    if n == 1:
        # a prompt of one token leaves [0, z_0]
        assert (tails[:, 0] == 0).all()


@pytest.mark.parametrize("cut", range(1, 12))
def test_the_tail_carries_across_a_boundary_at_every_offset(bundle, cut):
    """Twelve tokens in two calls cut at ``cut``, the second padded to 16:
    the tokens behind the boundary read the two inputs before it out of the
    tail."""
    _, cfg, model, variables, ids, want = bundle
    got, _ = _prefill(cfg, model, variables, ids[1],
                      ((cut, cut), (12 - cut, 16)))
    np.testing.assert_allclose(np.asarray(got[0]), want[1, :12], atol=TOL)
    # the fault this guards against shows: history lost at the boundary
    lost = np.asarray(ref.lfm2_logits(
        SEED, bundle[0], ids[1, :12], "float32", f"tail_zeroed_{cut}"))
    assert np.abs(lost - want[1, :12]).max() > 100 * TOL


def test_the_step_and_the_chunk_are_one_convolution():
    rng = np.random.default_rng(3)
    z = jnp.asarray(rng.normal(size=(2, 9, 16)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(3, 16)), jnp.float32)
    tail0 = jnp.asarray(rng.normal(size=(2, 2, 16)), jnp.float32)
    want, tail_c = short_conv_chunk(z, tail0, w)
    tail, got = tail0, []
    for t in range(9):
        c, tail = short_conv_step(z[:, t], tail, w)
        got.append(c)
    np.testing.assert_allclose(np.stack(got, 1), want, atol=1e-6)
    np.testing.assert_array_equal(tail, tail_c)
    np.testing.assert_array_equal(tail_c, z[:, -2:])
    # c_t = w0 z_{t-2} + w1 z_{t-1} + w2 z_t, the tail before z_0
    ext = jnp.concatenate([tail0, z], 1)
    np.testing.assert_allclose(
        want[:, 4], w[0] * ext[:, 4] + w[1] * ext[:, 5] + w[2] * ext[:, 6],
        atol=1e-6)
    # a padded chunk hands on the tail at token n; a row that is not live
    # keeps its tail bit for bit
    _, at5 = short_conv_chunk(z, tail0, w, jnp.asarray(5))
    np.testing.assert_array_equal(at5, z[:, 3:5])
    _, at1 = short_conv_chunk(z, tail0, w, jnp.asarray(1))
    np.testing.assert_array_equal(at1[:, 0], tail0[:, 1])
    _, kept = short_conv_step(z[:, 0], tail0, w,
                              live=jnp.asarray([True, False]))
    np.testing.assert_array_equal(kept[1], tail0[1])
    np.testing.assert_array_equal(kept[0, 1], z[0, 0])


def test_what_a_convolution_keeps_does_not_grow_with_the_context():
    cfg = Lfm2MoeConfig(dtype=jnp.bfloat16)
    fam = cfg.serving_family()
    assert (fam.layers, fam.kv_layers, fam.state_layers) == (40, 10, 30)
    assert fam.state_arrays == (("conv", (2, 2048), jnp.bfloat16),)
    assert (fam.tail_columns, fam.ring_columns) == (2, 0)
    assert fam.state_bytes_per_slot == 30 * 2 * 2048 * 2
    # 8 K/V heads of 64: no whole lane tile a head, so one merged axis, and
    # that axis of 512 is whole tiles with no pad: the paged kernel's rule
    # takes it (a value head under a tile is taken by lane at a row's end)
    assert fam.kv_tail == fam.v_tail == (512,)
    assert fam.decode_reads_in_place is True and fam.paged_only
    assert (fam.expert_layers, fam.experts, fam.experts_per_token) == (
        38, 64, 4)
    cut = dataclasses.replace(cfg, layer_types=cfg.layer_types[:10])
    fam = cut.serving_family()
    assert (fam.kv_layers, fam.state_layers, fam.expert_layers) == (2, 8, 8)
    assert fam.state_bytes_per_slot == 65536


def test_the_head_is_the_embedding(bundle):
    _, cfg, model, variables, ids, _ = bundle
    assert "lm_head" not in variables["params"]
    other = jax.tree.map(lambda a: a, variables)
    other["params"]["embed_tokens"] = variables["params"]["embed_tokens"].at[
        7].multiply(2.0)
    apply = jax.jit(lambda v, ids: model.apply(v, ids)[0])
    a = apply(variables, jnp.asarray(ids[:1, :8]))
    b = apply(other, jnp.asarray(ids[:1, :8]))
    # token 7 is not among the inputs: only ITS logit moved, and doubled
    assert 7 not in ids[0, :8]
    np.testing.assert_allclose(b[..., 7], 2.0 * a[..., 7], rtol=1e-5)
    np.testing.assert_allclose(np.delete(b, 7, -1), np.delete(a, 7, -1),
                               atol=1e-6)


@pytest.mark.parametrize("held", [2, 4, 8])
def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_references(held):
    """Every chip routes over all 8 experts and computes its own experts'
    part; the parts of all the shares add up to the uncut reference's layer
    output, nothing counted twice (the family has no shared expert), with
    this router's epsilon in both."""
    hf = rehearsal_hf()
    w = ref.layer_weights(SEED, 2, hf, "float32")
    h = jax.random.normal(jax.random.PRNGKey(4), (40, int(hf["hidden_size"])))
    with jax.default_matmul_precision("highest"):
        want, sel, wt = ref._expert_layer(json.dumps(hf, sort_keys=True), w,
                                          h, "f32")
    assert np.abs(np.asarray(want)).max() > 1e-3
    # the weights sum to s / (s + 1e-6), not to one and not to s / (s + 1e-20)
    assert ((1 - wt.sum(-1)) > 1e-7).all() and ((1 - wt.sum(-1)) < 1e-5).all()
    total = jnp.zeros_like(want)
    rows = 0
    for first in range(0, 8, held):
        cfg = config_from_hf_lfm2_moe(hf, first_expert=first,
                                      experts_held=held)
        params = {"router": w["moe.router"],
                  "expert_bias": w["moe.expert_bias"]}
        for name in ("experts_gate", "experts_up", "experts_down"):
            params[name] = w["moe." + name][first:first + held]
        out, counts = Lfm2Experts(cfg).apply({"params": params}, h[None])
        assert counts.shape == (held,)
        assert int(counts.sum()) == int(
            ((sel >= first) & (sel < first + held)).sum())
        total = total + out[0]
        rows += int(counts.sum())
    assert rows == 40 * 2, "every (token, expert) pair is computed once"
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), atol=TOL)


def test_the_routers_epsilon_is_an_argument_whose_default_is_the_parents():
    """``route_sigmoid_topk`` divides by the weights' sum + ``norm_eps``;
    afmoe and MiMo call it without one and get the parent's 1e-20, bit for
    bit and in the lowered text; this family passes 1e-6."""
    assert inspect.signature(route_sigmoid_topk).parameters[
        "norm_eps"].default == 1e-20
    assert ROUTE_NORM_EPS == ref.ROUTE_NORM_EPS == 1e-6
    rng = np.random.default_rng(0)
    h = jnp.asarray(rng.normal(size=(33, 64)), jnp.float32)
    kernel = jnp.asarray(0.1 * rng.normal(size=(64, 16)), jnp.float32)
    bias = jnp.asarray(0.05 * rng.normal(size=(16,)), jnp.float32)

    def parents(h, kernel, bias):
        s = jax.nn.sigmoid(jnp.dot(h, kernel,
                                   precision=jax.lax.Precision.HIGHEST))
        _, sel = jax.lax.top_k(s + bias, 4)
        w = jnp.take_along_axis(s, sel, axis=-1)
        return sel.astype(jnp.int32), w / (
            jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * 2.5

    sel, w = route_sigmoid_topk(h, kernel, bias, 4, route_scale=2.5)
    sel0, w0 = parents(h, kernel, bias)
    np.testing.assert_array_equal(sel, sel0)
    np.testing.assert_array_equal(w, w0)

    def text(**kw):
        return jax.jit(lambda h: route_sigmoid_topk(
            h, kernel, bias, 4, **kw)).lower(h).as_text()

    assert text() == text(norm_eps=1e-20) != text(norm_eps=1e-6)
    _, w6 = route_sigmoid_topk(h, kernel, bias, 4, norm_eps=1e-6)
    assert float(jnp.abs(w6.sum(-1) - 1).max()) > 1e-7
    # the two families that share the function pass no epsilon of their own
    from sparkdl_tpu.models import afmoe, mimo_v2_flash
    for module in (afmoe, mimo_v2_flash):
        assert " norm_eps=" not in inspect.getsource(module)


def test_a_rows_result_does_not_depend_on_who_shares_its_batch(bundle):
    _, _, model, variables, ids, _ = bundle
    apply = jax.jit(lambda ids: model.apply(variables, ids)[0])
    both, alone = apply(jnp.asarray(ids[:, :24])), apply(
        jnp.asarray(ids[1:, :24]))
    np.testing.assert_allclose(np.asarray(alone[0]), np.asarray(both[1]),
                               atol=1e-6)


def published_hf() -> dict:
    """The catalog row's ``config``: the configuration's file with its two
    reduced keys put back as ``published`` states them."""
    cfg = dict(mf.resolve_cell(CELL).config)
    hf = {k: cfg[k] for k in serve_lfm2_moe.HF_KEYS}
    hf["num_hidden_layers"] = cfg["published"]["num_hidden_layers"]
    hf["layer_types"] = ([CONV, CONV] + [FULL, CONV, CONV, CONV] * 9
                         + [FULL, CONV])
    return hf


def test_the_catalog_rows_config_is_taken_verbatim():
    hf = published_hf()
    cfg = config_from_hf_lfm2_moe(hf, dtype=jnp.bfloat16)
    assert cfg == Lfm2MoeConfig(dtype=jnp.bfloat16), "the defaults ARE the row"
    assert (cfg.num_layers, cfg.layers_of(CONV), cfg.layers_of(FULL)) == (
        40, 30, 10)
    assert (cfg.head_dim, cfg.rope_theta, cfg.conv_L_cache) == (64, 1e6, 3)
    assert (cfg.num_dense_layers, cfg.expert_layers) == (2, 38)
    # the cut the benchmark runs: 5,267,090,176 parameters by the issue's
    # arithmetic (the tied embedding counted once)
    cut = dict(hf, num_hidden_layers=10, layer_types=hf["layer_types"][:10])
    assert ref.seeded_parameters(cut) == 5_267_090_176
    # at 2 bytes each but the float32 gains, router kernels and biases
    extra = 2 * (10 * 2 * 2048 + 2048 + 2 * 2 * 64
                 + 8 * (2048 * 64 + 64))
    assert ref.seeded_weight_bytes(cut) == 2 * 5_267_090_176 + extra


@pytest.mark.parametrize("key, value, said", [
    ("model_type", "lfm2", "not an lfm2_moe"),
    ("rope_parameters", {"rope_theta": 1e6, "rope_type": "yarn"},
     "rope_type 'yarn'"),
    ("rope_scaling", {"type": "linear", "factor": 2.0}, "rope_type 'linear'"),
    ("rope_parameters", {"rope_type": "default"}, "no rope_theta"),
    ("layer_types", ["conv", "sliding_attention", "conv", "conv", "conv"],
     "sliding_attention"),
    ("num_hidden_layers", 4, "disagree"),
    ("conv_bias", True, "conv_bias"),
    ("scoring_func", "softmax", "sigmoid"),
    ("n_group", 2, "n_group"),
    ("topk_group", 2, "topk_group"),
    ("n_shared_experts", 1, "shared expert"),
    ("hidden_act", "gelu", "silu"),
    ("attention_bias", True, "attention biases"),
    ("tie_word_embeddings", False, "untied head"),
    ("num_attention_heads", 7, "no multiple of the heads"),
])
def test_a_variant_the_forward_does_not_compute_is_refused_by_name(
        key, value, said):
    base = rehearsal_hf()
    assert config_from_hf_lfm2_moe(base).num_layers == 5
    with pytest.raises(ValueError, match=said):
        config_from_hf_lfm2_moe({**base, key: value})


def test_the_configs_own_refusals_and_the_paged_contracts_width():
    base = rehearsal_hf()
    with pytest.raises(ValueError, match="not among"):
        config_from_hf_lfm2_moe(base, first_expert=7, experts_held=2)
    with pytest.raises(ValueError, match="unknown layer types"):
        Lfm2MoeConfig.tiny(layer_types=(CONV, "linear_attention"))
    with pytest.raises(ValueError, match="keeps no tail"):
        Lfm2MoeConfig.tiny(conv_L_cache=1)
    with pytest.raises(ValueError, match="multiple of num_kv_heads"):
        Lfm2MoeConfig.tiny(num_kv_heads=3)
    # a default rope_scaling beside rope_parameters changes nothing
    config_from_hf_lfm2_moe({**base, "rope_scaling": {"type": "default"}})
    model = Lfm2MoeLMHeadModel(Lfm2MoeConfig.tiny())
    with pytest.raises(ValueError, match="one token a row"):
        jax.eval_shape(lambda: model.init(
            jax.random.PRNGKey(0), jnp.zeros((2, 3), jnp.int32),
            cache={"table": jnp.zeros((2, 1), jnp.int32)}))
