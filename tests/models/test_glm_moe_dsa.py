"""The ``glm_moe_dsa`` family (``models/glm_moe_dsa.py``) at the benchmark's
rehearsal size, float32, seeded random weights, against the plain reference
(``benchmark/reference_glm_moe_dsa.py``, expanded form, a plain ``top_k``):
logits through each of the three cache contracts at contexts past the tiny
selection (16 columns) and under it; the selection itself, exactly; its
hand-down from a ``full`` layer to the ``shared`` layers after it; the share
of an expert layer that a chip holds; the parameter count of the benchmark's
cut; the variants it refuses."""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import manifest as mf
from benchmark import reference_glm_moe_dsa as ref
from benchmark.harness import Run
from benchmark.runners import serve_glm_moe_dsa
from sparkdl_tpu.models import kv_pool
from sparkdl_tpu.models.glm_moe_dsa import (
    FULL,
    SHARED,
    GlmExperts,
    GlmMoeDsaConfig,
    GlmMoeDsaLMHeadModel,
    config_from_hf_glm_moe_dsa,
    init_glm_moe_dsa_cache,
    rope_interleaved,
)
from sparkdl_tpu.ops import sparse_attention

SEED = 2**31 + 44
TOL = 2e-5   # float32 on the CPU; the logits' standard deviation is 0.16
CELL = "glm52-sparse-agent-backlog"
NAMES = ("latent", "index_k")


def rehearsal_hf() -> dict:
    """The model's keys of the benchmark's configuration at its rehearsal
    sizes (hidden 64, 4 heads of 12 + 8 over a latent of 16, 4 index heads
    of 16, a selection of 16 columns, 2 held experts of the router's 8,
    top-2, vocabulary 512, the same five layers)."""
    run = Run(cell=mf.resolve_cell(CELL), seed=SEED, seconds=1.0,
              trace=False, rehearse=True, t_process=0.0)
    return serve_glm_moe_dsa.hf_config(run.config())


def program_config(hf: dict, **kw) -> GlmMoeDsaConfig:
    return config_from_hf_glm_moe_dsa(
        {k: hf[k] for k in serve_glm_moe_dsa.HF_KEYS if k in hf},
        first_expert=hf["first_expert"], experts_held=hf["experts_held"],
        **kw)


@pytest.fixture(scope="module")
def bundle():
    hf = rehearsal_hf()
    cfg = program_config(hf)
    model = GlmMoeDsaLMHeadModel(cfg)
    variables = serve_glm_moe_dsa.program_variables(model, hf, "float32",
                                                    SEED)
    ids = np.asarray(jax.random.randint(
        jax.random.PRNGKey(1), (2, 96), 0, cfg.vocab_size), np.int32)
    want = np.stack([np.asarray(ref.glm_logits(SEED, hf, row, "float32"))
                     for row in ids])
    return hf, cfg, model, variables, ids, want


def _no_cache(cfg, model, variables, ids):
    return jax.jit(lambda ids: model.apply(variables, ids)[0])(
        jnp.asarray(ids))


#: (real tokens, program width) of the dense contract's calls: 8 tokens are
#: under the selection's 16 columns, a chunk of 40 crosses it, 23 real tokens
#: in a width of 32 are a padded last chunk; then one token a call to 96
CHUNKS = ((8, 8), (40, 40), (23, 32)) + ((1, 1),) * 25


def _dense_cache(cfg, model, variables, ids):
    cache = init_glm_moe_dsa_cache(cfg, ids.shape[0], 128)
    step = jax.jit(lambda c, t: model.apply(variables, t, cache=c))
    out, pos = [], 0
    for n, width in CHUNKS:
        chunk = np.zeros((ids.shape[0], width), np.int32)
        chunk[:, :n] = ids[:, pos:pos + n]
        logits, new = step(
            dict({k: cache[k] for k in NAMES},
                 idx=jnp.asarray(pos, jnp.int32)), jnp.asarray(chunk))
        cache = new
        out.append(logits[:, :n])
        pos += n
    assert pos == ids.shape[1]
    assert cache["expert_counts"].shape == (4, cfg.held)
    return jnp.concatenate(out, axis=1)


def _paged_rows(cfg, model, variables, ids, lens, n_blocks=16, bs=16):
    """Two rows at DIFFERENT depths over one block pool: each row's prompt
    goes in through the dense contract and is installed as the engine
    installs it (whole blocks, by array name). Returns ``(pool, table, first
    logits)``."""
    pool = kv_pool.init_block_pool(cfg, n_blocks, bs)
    pool = {k: np.array(v) for k, v in pool.items()}
    table = np.full((2, 8), n_blocks, np.int32)
    table[0, :6] = [3, 9, 1, 12, 7, 14]
    table[1, :6] = [5, 0, 11, 2, 13, 8]
    logits = [None, None]
    prefill = jax.jit(lambda ids, cache: model.apply(variables, ids,
                                                     cache=cache))
    for r, n in enumerate(lens):
        out, cache = prefill(jnp.asarray(ids[r:r + 1, :n]),
                             init_glm_moe_dsa_cache(cfg, 1, 96))
        logits[r] = out[0]
        for name in NAMES:
            rows = np.asarray(cache[name])[:, 0]          # [layers, 96, ...]
            for b in range(6):
                pool[name][:, table[r, b]] = rows[:, b * bs:(b + 1) * bs]
    return pool, table, logits


def _paged_cache(cfg, model, variables, ids):
    """Both rows decode together through the paged contract, one token a
    call, each at its own depth: 12 (under the selection: every column is
    attended and no indexer scores) and 56 (past it)."""
    lens = [12, 56]
    pool, table, logits = _paged_rows(cfg, model, variables, ids, lens)
    pool = {k: jnp.asarray(v) for k, v in pool.items()}
    step = jax.jit(lambda pool, tok, idx, nb: model.apply(
        variables, tok, cache=dict(pool, table=jnp.asarray(table[:, :nb]),
                                   idx=idx)), static_argnums=(3,))
    idx = np.array(lens, np.int32)
    outs = [[], []]
    for t in range(30):
        tok = np.stack([ids[r, idx[r]] for r in range(2)])[:, None]
        nb = -(-(int(idx.max()) + 1) // 16)
        lg, new = step(pool, jnp.asarray(tok), jnp.asarray(idx), nb)
        rows = np.arange(2)
        blk, off = table[rows, idx // 16], idx % 16
        pool = kv_pool.scatter_columns(
            pool, jnp.asarray(blk), jnp.asarray(off),
            *(new[name][:, :, 0] for name in NAMES), names=NAMES)
        for r in range(2):
            outs[r].append(lg[r, 0])
        idx = idx + 1
    return [jnp.concatenate([logits[r], jnp.stack(outs[r])])
            for r in range(2)], lens


def test_the_whole_forward_is_the_references(bundle):
    hf, cfg, model, variables, ids, want = bundle
    got = np.asarray(_no_cache(cfg, model, variables, ids))
    assert np.abs(got - want).max() < TOL
    assert want.std() > 0.05


def test_chunks_through_the_dense_cache_are_the_whole_forward(bundle):
    """8 tokens (under the selection), a chunk of 40 across it, a padded
    chunk, then a token a call: every position's logits are the
    reference's full forward's."""
    hf, cfg, model, variables, ids, want = bundle
    got = np.asarray(_dense_cache(cfg, model, variables, ids))
    assert np.abs(got - want).max() < TOL


#: the two forms of a step past the selection's size, by the rule
#: ``sparse_attention.attends_in_place``: the tables here are no wider than
#: 8 selections (128 columns), which the rule as it stands takes IN PLACE
#: (the selection a mask, the rows' live blocks read where the pool keeps
#: them); with its constant at 1 no width is inside it and the step picks
#: positions and reads them one by one
FORMS = [pytest.param(None, id="in place under a mask"),
         pytest.param(1, id="picked positions read one by one")]


@pytest.fixture(params=FORMS)
def in_place(request, monkeypatch):
    if request.param is not None:
        monkeypatch.setattr(sparse_attention, "IN_PLACE_SELECTIONS",
                            request.param)
    return request.param is None


def test_prefill_then_steps_through_the_pool_are_the_whole_forward(
        bundle, in_place):
    """A row 12 deep (under the selection) and one 56 deep (past it) step
    together through one pool, 30 tokens each: the absorbed form over the
    selected columns gives the reference's expanded logits, in both forms
    of the step."""
    hf, cfg, model, variables, ids, want = bundle
    got, lens = _paged_cache(cfg, model, variables, ids)
    for r, n in enumerate(lens):
        assert got[r].shape[0] == n + 30
        assert np.abs(np.asarray(got[r]) - want[r, :n + 30]).max() < TOL, r


def _picked_sets(mask_row):
    return set(np.nonzero(mask_row)[0].tolist())


def test_the_selection_is_the_references_exactly(bundle, in_place):
    """``S_t`` of every query of both ``full`` layers, from the program's
    whole forward (a mask) and from a paged step (a mask over the table's
    columns where it attends in place, positions where it does not), equals
    the reference's plain ``top_k`` as a SET, exactly, in float32."""
    hf, cfg, model, variables, ids, want = bundle
    with jax.default_matmul_precision("highest"):
        _, extras = ref.glm_hidden(SEED, hf, ids[:1], "float32",
                                   keep_picked=True)
    _, state = model.apply(variables, jnp.asarray(ids[:1]),
                           mutable=["intermediates"])
    kinds = list(hf["indexer_types"])
    for layer, kind in enumerate(kinds):
        if kind != FULL:
            continue
        mine = np.asarray(state["intermediates"][f"layers_{layer}"]["attn"][
            "picked"][0])[0]
        theirs = extras["picked"][layer][0]
        assert mine.shape == theirs.shape == (96, 96)
        assert (mine == theirs).all(), layer
        # the selection bites: a query past 16 keeps 16 of its columns
        assert theirs[60].sum() == 16 and theirs[5].sum() == 6
    # a step at depth 56: the positions it picks are the reference's row 56
    lens = [12, 56]
    pool, table, _ = _paged_rows(cfg, model, variables, ids[[0, 0]], lens)
    idx = np.array(lens, np.int32)
    tok = np.stack([ids[0, n] for n in lens])[:, None]
    _, state = model.apply(
        variables, jnp.asarray(tok),
        cache=dict({k: jnp.asarray(v) for k, v in pool.items()},
                   table=jnp.asarray(table[:, :4]), idx=jnp.asarray(idx)),
        mutable=["intermediates"])
    for layer, kind in enumerate(kinds):
        if kind != FULL:
            continue
        picked = state["intermediates"][f"layers_{layer}"]["attn"][
            "picked"][0]
        assert isinstance(picked, tuple) != in_place
        for r, n in enumerate(lens):
            if in_place:
                got = _picked_sets(np.asarray(picked[r]))
            else:
                pos, taken = picked
                got = set(np.asarray(pos[r])[np.asarray(taken[r])].tolist())
            assert got == _picked_sets(extras["picked"][layer][0][n]), (
                layer, r)


def test_a_shared_layer_attends_the_selection_it_was_handed(bundle):
    """The three ``shared`` layers use layer 0's selection: with the
    reference made to give them the LAST 16 columns instead (a wrong
    hand-down), or every column, the logits past the selection's size move
    far beyond the tolerance, and under it they do not."""
    hf, cfg, model, variables, ids, want = bundle
    got = np.asarray(_no_cache(cfg, model, variables, ids))
    for control in ("shared_last", "all_columns"):
        wrong = np.asarray(ref.glm_logits(SEED, hf, ids[0], "float32",
                                          control))
        assert np.abs(wrong[:16] - want[0, :16]).max() < TOL, control
        assert np.abs(wrong[40:] - want[0, 40:]).max() > 100 * TOL, control
        assert np.abs(got[0, 40:] - wrong[40:]).max() > 100 * TOL, control
    # and the ReLU is in the indexer: without it another selection
    wrong = np.asarray(ref.glm_logits(SEED, hf, ids[0], "float32", "no_relu"))
    assert np.abs(wrong[40:] - want[0, 40:]).max() > 100 * TOL


def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer(bundle):
    """Four shares of 2 experts each of the router's 8: the routed parts of
    all four, with the shared expert counted ONCE, are the uncut
    reference's layer; the program's layer on share ``i`` is the shared
    expert plus share ``i``'s routed part."""
    hf, cfg, model, variables, ids, want = bundle
    layer = 2
    uncut = dict(hf, experts_held=hf["n_routed_experts"], first_expert=0)
    h = jax.random.normal(jax.random.PRNGKey(5), (48, cfg.hidden_size))
    with jax.default_matmul_precision("highest"):
        w_all = ref.layer_weights(SEED, layer, uncut, "float32")
        shared = np.asarray(ref.shared_part(uncut, w_all, h))
        whole = np.asarray(ref.routed_part(uncut, w_all, h)[0]) + shared
    total = shared.copy()
    for first in range(0, 8, 2):
        share_cfg = dataclasses.replace(cfg, first_expert=first,
                                        experts_held=2)
        params = {
            "router": w_all["moe.router"],
            "expert_bias": w_all["moe.expert_bias"],
            **{f"experts_{k}": w_all[f"moe.experts_{k}"][first:first + 2]
               for k in ("gate", "up", "down")},
            "shared": {k: w_all[f"moe.shared.{k}_proj"]
                       for k in ("gate", "up", "down")}}
        params["shared"] = {f"{k}_proj": v
                            for k, v in params["shared"].items()}
        out, counts = GlmExperts(share_cfg).apply(
            {"params": params}, h[None])
        assert counts.shape == (2,)
        total += np.asarray(out[0]) - shared
    assert np.abs(total - whole).max() < TOL
    assert np.abs(whole - shared).max() > 100 * TOL


def test_rotary_turns_interleaved_pairs_and_leaves_the_rest(bundle):
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 3, 2, 16))
    pos = jnp.asarray([[0, 5, 9]])
    got = np.asarray(rope_interleaved(x, pos, 8e6, 8))
    np.testing.assert_array_equal(got[..., 8:], np.asarray(x)[..., 8:])
    np.testing.assert_allclose(got[0, 0], np.asarray(x)[0, 0], atol=1e-7)
    want = np.asarray(ref._rope(x[0], pos[0], 8e6, 8))
    np.testing.assert_allclose(got[0], want, atol=1e-6)
    # pairs are (2i, 2i + 1): the norm of each pair is kept
    a, b = np.asarray(x)[0, 1, 0, :8], got[0, 1, 0, :8]
    np.testing.assert_allclose(a[0::2] ** 2 + a[1::2] ** 2,
                               b[0::2] ** 2 + b[1::2] ** 2, rtol=1e-5)


def test_the_cut_holds_the_issues_count_of_parameters():
    """The benchmark's configuration as run: 3,882.7 M parameters, by the
    program's own variables tree and by the reference's tables, and the
    pool's two arrays over 5 and 2 layers."""
    cfg_file = json.load(open(mf.resolve_cell(CELL).root
                              + "/benchmark/configs/glm-5.2-serve.json"))
    hf = serve_glm_moe_dsa.hf_config(cfg_file)
    mcfg = program_config(hf, dtype=jnp.bfloat16)
    shapes = jax.eval_shape(lambda: GlmMoeDsaLMHeadModel(mcfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    n = sum(a.size for a in jax.tree.leaves(shapes))
    assert n == ref.seeded_parameters(hf) == 3_882_696_704
    assert round(n / 1e6, 1) == 3882.7
    assert "3,882.7 M" in cfg_file["assumed"]["parameters"]
    fam = mcfg.serving_family()
    assert fam.pool_arrays == (("latent", 5, (640,)), ("index_k", 2, (128,)))
    assert fam.selected_columns == 2048
    assert (fam.expert_layers, fam.experts, fam.experts_per_token) == (
        4, 16, 8)


@pytest.mark.parametrize("change, what", [
    ({"n_group": 2}, "group-limited"),
    ({"topk_group": 2}, "group-limited"),
    ({"indexer_types": [SHARED, FULL, SHARED, SHARED, FULL]}, "shared"),
    ({"rope_parameters": {"rope_theta": 8e6, "rope_type": "yarn"}},
     "rope_type"),
    ({"n_shared_experts": 2}, "one shared expert"),
    ({"rope_interleave": False}, "interleaved"),
    ({"scoring_func": "softmax"}, "sigmoid"),
    ({"topk_method": "greedy"}, "noaux_tc"),
    ({"hidden_act": "gelu"}, "silu"),
    ({"attention_bias": True}, "biases"),
    ({"tie_word_embeddings": True}, "tied"),
    ({"index_topk_pattern": [1, 2]}, "index_topk_pattern"),
    ({"num_hidden_layers": 6}, "disagree"),
    ({"model_type": "deepseek_v32"}, "not a glm_moe_dsa"),
    ({"qk_head_dim": 24}, "qk_head_dim"),
])
def test_what_the_forward_does_not_compute_is_refused(change, what):
    hf = {k: v for k, v in rehearsal_hf().items()
          if k in serve_glm_moe_dsa.HF_KEYS}
    with pytest.raises(ValueError, match=what):
        config_from_hf_glm_moe_dsa({**hf, **change})


def test_a_share_outside_the_router_is_refused():
    with pytest.raises(ValueError, match="not among"):
        GlmMoeDsaConfig.tiny(first_expert=7, experts_held=2)
    with pytest.raises(ValueError, match="start with 'full'"):
        GlmMoeDsaConfig.tiny(indexer_types=(SHARED, FULL, FULL, FULL, FULL))
