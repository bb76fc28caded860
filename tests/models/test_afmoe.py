"""The ``afmoe`` family (``models/afmoe.py``, ``parallel/moe_dropless.py``) at
the benchmark's rehearsal size, float32, seeded random weights, against the
plain reference (``benchmark/reference_afmoe.py``): logits through each of
the three cache contracts, the router's rules, the window, and the share of
an expert layer that a chip holds."""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import manifest as mf
from benchmark import reference_afmoe as ref
from benchmark.harness import Run
from benchmark.runners import serve_afmoe
from sparkdl_tpu.models.afmoe import (
    FULL,
    SLIDING,
    AfmoeBlock,
    AfmoeConfig,
    AfmoeExperts,
    AfmoeLMHeadModel,
    config_from_hf_afmoe,
    init_afmoe_cache,
)
from sparkdl_tpu.parallel.moe_dropless import (
    dropless_experts,
    route_sigmoid_topk,
)

SEED = 2**31 + 29
TOL = 2e-5   # float32 on the CPU; the logits' standard deviation is 0.16


def rehearsal_hf() -> dict:
    """The model's keys of the benchmark's configuration at its rehearsal
    sizes (hidden 64, 4 heads over 2 KV heads of 16, 8 experts top-2 of
    width 32, window 32, vocabulary 512, the same five layer kinds)."""
    cell = mf.resolve_cell("trinity-mixed-backlog")
    run = Run(cell=cell, seed=SEED, seconds=1.0, trace=False, rehearse=True,
              t_process=0.0)
    return serve_afmoe.hf_config(run.config())


@pytest.fixture(scope="module")
def bundle():
    hf = rehearsal_hf()
    cfg = config_from_hf_afmoe(hf)
    model = AfmoeLMHeadModel(cfg)
    variables = serve_afmoe.program_variables(model, hf, "float32", SEED)
    ids = np.asarray(jax.random.randint(
        jax.random.PRNGKey(1), (2, 96), 0, cfg.vocab_size), np.int32)
    want = np.stack([np.asarray(ref.afmoe_logits(SEED, hf, row, "float32"))
                     for row in ids])
    return hf, cfg, model, variables, ids, want


def _no_cache(cfg, model, variables, ids):
    return jax.jit(lambda ids: model.apply(variables, ids)[0])(
        jnp.asarray(ids))


def _dense_cache(cfg, model, variables, ids):
    """Prefill 50 tokens (past the window of 32), then one token a call."""
    cache = init_afmoe_cache(cfg, ids.shape[0], 128)
    logits, cache = model.apply(variables, jnp.asarray(ids[:, :50]),
                                cache=cache)
    out = [logits]
    step = jax.jit(lambda c, t: model.apply(variables, t, cache=c))
    for t in range(50, ids.shape[1]):
        logits, cache = step({k: cache[k] for k in ("k", "v", "idx")},
                             jnp.asarray(ids[:, t:t + 1]))
        out.append(logits)
    assert cache["expert_counts"].shape == (4, cfg.num_experts)
    return jnp.concatenate(out, axis=1)


def _paged_cache(cfg, model, variables, ids):
    """Two rows at DIFFERENT depths over one block pool: each row's prompt
    goes in alone through the paged contract (40 and 56 tokens, both past
    the window), then both decode together, one token a call, each at its
    own depth. The new columns the model hands back are written into the
    pool at (block, offset), as the engine does."""
    bs, n_blocks = 16, 16
    shape = (cfg.num_layers, n_blocks, bs, cfg.num_kv_heads, cfg.head_dim)
    pool = {"k": np.zeros(shape, np.float32), "v": np.zeros(shape, np.float32)}
    # scattered blocks, and the sentinel (n_blocks) past each row's reach
    table = np.full((2, 8), n_blocks, np.int32)
    table[0, :6] = [3, 9, 1, 12, 7, 14]
    table[1, :6] = [5, 0, 11, 2, 13, 8]
    lens = [40, 56]
    logits = [None, None]

    def write(new, rows, idx, width):
        for j, r in enumerate(rows):
            for t in range(width):
                pos = int(idx[j]) + t
                blk, off = table[r, pos // bs], pos % bs
                for name in ("k", "v"):
                    pool[name][:, blk, off] = np.asarray(new[name][:, j, t])

    prefill = jax.jit(lambda ids, cache: model.apply(variables, ids,
                                                     cache=cache))
    for r, n in enumerate(lens):
        idx = np.zeros((1,), np.int32)
        out, new = prefill(
            jnp.asarray(ids[r:r + 1, :n]),
            {"k": jnp.asarray(pool["k"]), "v": jnp.asarray(pool["v"]),
             "table": jnp.asarray(table[r:r + 1]), "idx": idx})
        write(new, [r], idx, n)
        logits[r] = [out[0]]
    step = jax.jit(lambda pool, table, idx, tok: model.apply(
        variables, tok, cache=dict(pool, table=table, idx=idx)))
    idx = np.array(lens, np.int32)
    for _ in range(ids.shape[1] - max(lens)):
        tok = np.stack([ids[r, idx[r]] for r in range(2)])[:, None]
        out, new = step({k: jnp.asarray(v) for k, v in pool.items()},
                        jnp.asarray(table), jnp.asarray(idx),
                        jnp.asarray(tok))
        write(new, [0, 1], idx, 1)
        for r in range(2):
            logits[r].append(out[r])
        idx = idx + 1
    return [jnp.concatenate(x, axis=0) for x in logits]


@pytest.mark.parametrize("contract", ["none", "dense", "paged"])
def test_logits_equal_the_references_through_each_cache_contract(
        bundle, contract):
    hf, cfg, model, variables, ids, want = bundle
    got = {"none": _no_cache, "dense": _dense_cache,
           "paged": _paged_cache}[contract](cfg, model, variables, ids)
    for r in range(2):
        n = got[r].shape[0]
        assert n > 60, "the contexts end inside the window"
        np.testing.assert_allclose(np.asarray(got[r]), want[r, :n], atol=TOL)


def test_selection_is_by_biased_scores_and_weights_by_the_unbiased():
    rng = np.random.default_rng(0)
    h = jnp.asarray(rng.normal(size=(6, 16)), jnp.float32)
    kernel = jnp.asarray(rng.normal(size=(16, 8)), jnp.float32)
    bias = np.zeros((8,), np.float32)
    s = np.asarray(jax.nn.sigmoid(h @ kernel))
    sel0, w0 = route_sigmoid_topk(h, kernel, jnp.asarray(bias), 2,
                                  route_scale=2.826)
    assert (np.sort(np.asarray(sel0)) == np.sort(np.argsort(-s)[:, :2])).all()
    # a bias lifts every row's WEAKEST expert into the selection ...
    weakest = s.argmin(-1)
    for row, e in enumerate(weakest):
        b = bias.copy()
        b[e] = 2.0
        sel, w = route_sigmoid_topk(h, kernel, jnp.asarray(b), 2,
                                    route_scale=2.826)
        sel, w = np.asarray(sel[row]), np.asarray(w[row])
        assert e in sel and e not in np.asarray(sel0[row])
        # ... and its weight is still its UNBIASED score's share
        picked = s[row, sel]
        np.testing.assert_allclose(w, 2.826 * picked / picked.sum(),
                                   rtol=1e-6)
    np.testing.assert_allclose(np.asarray(w0).sum(-1), 2.826, rtol=1e-6)
    _, raw = route_sigmoid_topk(h, kernel, jnp.asarray(bias), 2,
                                route_norm=False)
    np.testing.assert_allclose(np.sort(np.asarray(raw)),
                               np.sort(-np.sort(-s)[:, :2]), rtol=1e-6)


@pytest.mark.parametrize("kind", [SLIDING, FULL])
def test_a_token_behind_the_window_moves_a_full_layer_alone(kind):
    cfg = AfmoeConfig.tiny(layer_types=(kind,), num_dense_layers=1)
    block = AfmoeBlock(cfg, 0)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 48, cfg.hidden_size))
    variables = block.init(jax.random.PRNGKey(3), x, cache=None)
    y0 = block.apply(variables, x, cache=None)[0]
    # token 0 lies 33 and more behind positions 33.. (the window is 32)
    y1 = block.apply(variables, x.at[0, 0].add(1.0), cache=None)[0]
    moved = np.abs(np.asarray(y1 - y0)).max(-1)[0]
    assert (moved[1:32] > 1e-6).all(), "inside the window it is seen"
    if kind == SLIDING:
        assert (moved[32:] == 0).all()
    else:
        assert (moved[32:] > 1e-6).all()


@pytest.mark.parametrize("held", [2, 4, 8])
def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_references(held):
    """Every chip routes over all 8 experts and computes its own experts'
    part plus the shared expert; the parts of all the shares, with the
    shared expert counted once, are the uncut reference's layer output."""
    hf = rehearsal_hf()
    w = ref.layer_weights(SEED, 1, hf, "float32")
    h = jax.random.normal(jax.random.PRNGKey(4), (40, int(hf["hidden_size"])))
    hf_json = json.dumps(hf, sort_keys=True)
    with jax.default_matmul_precision("highest"):
        want, _ = ref._expert_layer(hf_json, w, h, "f32")
        shared = ref._mlp_programs(hf_json, "f32")[1](
            h, w["moe.shared.gate_proj"], w["moe.shared.up_proj"],
            w["moe.shared.down_proj"])
    total = jnp.zeros_like(want)
    rows = 0
    for first in range(0, 8, held):
        cfg = dataclasses.replace(config_from_hf_afmoe(hf),
                                  first_expert=first, experts_held=held)
        params = {"router": w["moe.router"],
                  "expert_bias": w["moe.expert_bias"],
                  "shared": {k: w["moe.shared." + k] for k in
                             ("gate_proj", "up_proj", "down_proj")}}
        for name in ("experts_gate", "experts_up", "experts_down"):
            params[name] = w["moe." + name][first:first + held]
        out, counts = AfmoeExperts(cfg).apply({"params": params}, h[None])
        total = total + (out[0] - shared)
        rows += int(counts.sum())
        assert counts.shape == (held,)
    assert rows == 40 * 2, "every (token, expert) pair is computed once"
    np.testing.assert_allclose(np.asarray(total + shared), np.asarray(want),
                               atol=TOL)


def test_a_rows_result_does_not_depend_on_who_shares_its_batch():
    rng = np.random.default_rng(5)
    h = jnp.asarray(rng.normal(size=(24, 16)), jnp.float32)
    gate, up = (jnp.asarray(rng.normal(size=(8, 16, 12)), jnp.float32)
                for _ in range(2))
    down = jnp.asarray(rng.normal(size=(8, 12, 16)), jnp.float32)
    sel, w = route_sigmoid_topk(
        h, jnp.asarray(rng.normal(size=(16, 8)), jnp.float32),
        jnp.zeros((8,)), 3)
    together, counts = dropless_experts(h, sel, w, gate, up, down)
    assert int(counts.sum()) == 24 * 3
    alone, _ = dropless_experts(h[5:6], sel[5:6], w[5:6], gate, up, down)
    np.testing.assert_array_equal(np.asarray(alone[0]),
                                  np.asarray(together[5]))


def test_variants_the_forward_does_not_compute_are_refused():
    hf = rehearsal_hf()
    for key, value in (("score_func", "softmax"), ("n_group", 2),
                       ("rope_scaling", {"type": "yarn"}),
                       ("tie_word_embeddings", True),
                       ("num_shared_experts", 2), ("model_type", "gpt2")):
        with pytest.raises(ValueError):
            config_from_hf_afmoe({**hf, key: value})
    with pytest.raises(ValueError):
        AfmoeConfig.tiny(first_expert=6, experts_held=4)
