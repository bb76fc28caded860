"""The ``olmo_hybrid`` family (``models/olmo_hybrid.py``) at the benchmark's
rehearsal size, float32, seeded random weights: the three forms of the gated
delta rule against each other, the module against the plain token-by-token
reference (``benchmark/reference_olmo_hybrid.py``) through each cache
contract, chunk splits, the pad mask, and the variants it refuses. The
chunkwise form solves its triangular systems on one of two paths
(``ops/delta_solve.py``: its kernel where its rule takes the widths,
``solve_triangular`` where not): what holds of the form is a case of each
(:data:`PATHS`)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import manifest as mf
from benchmark import reference_olmo_hybrid as ref
from benchmark.harness import Run
from benchmark.runners import serve_olmo_hybrid
from sparkdl_tpu.models import kv_pool
from sparkdl_tpu.models.olmo_hybrid import (
    FULL,
    LINEAR,
    SUB_CHUNK,
    OlmoHybridConfig,
    OlmoHybridLMHeadModel,
    config_from_hf_olmo_hybrid,
    gated_delta_chunked,
    gated_delta_recurrent,
    gated_delta_step,
    init_olmo_hybrid_cache,
)
from sparkdl_tpu.ops import delta_solve

SEED = 2**31 + 31
TOL = 5e-5   # float32 on the CPU (the chunkwise form rounds in another order than
             # the recurrence); the logits' standard deviation is 0.16


#: (d_k, d_v) of a linear head: the tiny configuration's, which the solve
#: kernel's rule refuses, and the narrowest it takes (one lane tile)
PATHS = [pytest.param((8, 16), id="solve_triangular"),
         pytest.param((32, 96), id="delta_solve kernel")]


def rehearsal_hf() -> dict:
    """The model's keys of the benchmark's configuration at its rehearsal
    sizes (hidden 64, 4 heads of 16, 4 linear heads of 8 x 16, vocabulary
    512, two periods of linear, linear, linear, full)."""
    cell = mf.resolve_cell("olmo-hybrid-long-backlog")
    run = Run(cell=cell, seed=SEED, seconds=1.0, trace=False, rehearse=True,
              t_process=0.0)
    return serve_olmo_hybrid.hf_config(run.config())


def _bundle(hf):
    cfg = config_from_hf_olmo_hybrid(hf)
    model = OlmoHybridLMHeadModel(cfg)
    variables = serve_olmo_hybrid.program_variables(model, hf, "float32",
                                                    SEED)
    ids = np.asarray(jax.random.randint(
        jax.random.PRNGKey(1), (2, 96), 0, cfg.vocab_size), np.int32)
    want = np.stack([np.asarray(ref.hybrid_logits(SEED, hf, row, "float32"))
                     for row in ids])
    return hf, cfg, model, variables, ids, want


@pytest.fixture(scope="module")
def bundle():
    return _bundle(rehearsal_hf())


@pytest.fixture(scope="module")
def kernel_bundle():
    """The rehearsal's configuration with linear heads of 32 x 96: the
    narrowest whose chunkwise form solves in the kernel."""
    dk, dv = PATHS[1].values[0]
    return _bundle({**rehearsal_hf(), "linear_key_head_dim": dk,
                    "linear_value_head_dim": dv})


# -- the rule's three forms -----------------------------------------------------

def _rule_inputs(length, b=2, h=3, dk=8, dv=16, dtype=np.float64, seed=0,
                 dims=None):
    """q, k L2-normalised; decays from 0.2 to 0.999 a token; beta in (0, 2),
    a good half of it over 1 (negative eigenvalues of the transition).
    ``dims`` is ``(dk, dv)`` as :data:`PATHS` gives them."""
    if dims is not None:
        dk, dv = dims
        assert delta_solve.solves_in_kernel(
            SUB_CHUNK, dk + dv, jnp.float32) is (dims != (8, 16))
    rng = np.random.default_rng(seed)

    def unit(x):
        return x / np.linalg.norm(x, axis=-1, keepdims=True)

    q = unit(rng.normal(size=(b, length, h, dk))) / np.sqrt(dk)
    k = unit(rng.normal(size=(b, length, h, dk)))
    v = rng.normal(size=(b, length, h, dv))
    g = -np.exp(rng.uniform(np.log(1e-3), np.log(1.6), (b, length, h)))
    beta = 2.0 / (1.0 + np.exp(-rng.normal(size=(b, length, h))))
    state = rng.normal(size=(b, h, dk, dv))
    return tuple(jnp.asarray(x, dtype) for x in (q, k, v, g, beta, state))


@pytest.fixture()
def float64():
    jax.config.update("jax_enable_x64", True)
    yield
    jax.config.update("jax_enable_x64", False)


@pytest.mark.parametrize("dims", PATHS)
def test_chunkwise_equals_one_token_equals_the_recurrence_in_float64(
        float64, dims):
    """200 tokens (three whole sub-chunks and a part of a fourth) from a
    state that is not zero: the WY form, the one-token update applied 200
    times, and a recurrence written out here in numpy."""
    q, k, v, g, beta, state = _rule_inputs(200, dims=dims)
    assert float((beta > 1).mean()) > 0.3
    want_o = np.zeros(v.shape)
    s = np.array(state)
    for t in range(200):
        a = np.exp(np.asarray(g[:, t]))[..., None, None]
        s = s * a
        seen = np.einsum("bhkv,bhk->bhv", s, np.asarray(k[:, t]))
        s = s + np.einsum(
            "bhk,bhv->bhkv",
            np.asarray(beta[:, t])[..., None] * np.asarray(k[:, t]),
            np.asarray(v[:, t]) - seen)
        want_o[:, t] = np.einsum("bhkv,bhk->bhv", s, np.asarray(q[:, t]))
    one_o, one_s = gated_delta_recurrent(q, k, v, g, beta, state)
    # (the module's forms compute in float32 whatever they are given)
    chunk_o, chunk_s = gated_delta_chunked(q, k, v, g, beta, state)
    np.testing.assert_allclose(np.asarray(one_o), want_o, atol=1e-5)
    np.testing.assert_allclose(np.asarray(one_s), s, atol=1e-5)
    np.testing.assert_allclose(np.asarray(chunk_o), want_o, atol=2e-5)
    np.testing.assert_allclose(np.asarray(chunk_s), s, atol=2e-5)
    assert chunk_s.dtype == jnp.float32 and chunk_o.shape == v.shape


@pytest.mark.parametrize("dims", PATHS)
def test_repeated_keys_and_beta_two_do_not_blow_the_solve_up(dims):
    """Every key of a sub-chunk the same and beta at its largest: the
    triangular system is 2 x the all-ones lower triangle, whose powers grow
    as 2^k while its inverse stays bounded. Forward substitution keeps to
    the recurrence; a product of powers would not."""
    q, k, v, g, beta, state = _rule_inputs(SUB_CHUNK, dtype=np.float32,
                                           dims=dims)
    k = jnp.broadcast_to(k[:, :1], k.shape)
    beta = jnp.full_like(beta, 2.0)
    g = jnp.zeros_like(g)
    want_o, want_s = gated_delta_recurrent(q, k, v, g, beta, state)
    got_o, got_s = gated_delta_chunked(q, k, v, g, beta, state)
    scale = float(jnp.abs(want_s).max())
    assert np.isfinite(scale) and scale < 1e3
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s),
                               atol=1e-3 * scale)
    np.testing.assert_allclose(np.asarray(got_o), np.asarray(want_o),
                               atol=1e-3 * scale)


@pytest.mark.parametrize("dims", PATHS)
@pytest.mark.parametrize("cut", [1, 17, 63, 64, 65, 100, 128, 199])
def test_a_chunk_split_anywhere_gives_the_whole_prompts_state(cut, dims):
    """The state carried across a chunk boundary at every kind of offset of
    a sub-chunk (its first token, its last, the one after, in between)."""
    q, k, v, g, beta, state = _rule_inputs(200, dtype=np.float32, dims=dims)
    whole_o, whole_s = gated_delta_chunked(q, k, v, g, beta, state)
    head = [x[:, :cut] for x in (q, k, v, g, beta)]
    tail = [x[:, cut:] for x in (q, k, v, g, beta)]
    o1, s1 = gated_delta_chunked(*head, state)
    o2, s2 = gated_delta_chunked(*tail, s1)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(whole_s),
                               atol=2e-5)
    np.testing.assert_allclose(
        np.asarray(jnp.concatenate([o1, o2], axis=1)), np.asarray(whole_o),
        atol=2e-5)


def test_one_token_is_the_step_and_a_masked_token_moves_nothing():
    q, k, v, g, beta, state = _rule_inputs(1, dtype=np.float32)
    o, s = gated_delta_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0],
                            state)
    assert o.shape == (2, 3, 16) and not np.allclose(s, state)
    # beta 0 and g 0: the state bit for bit, whatever k and v are
    zero = jnp.zeros_like(g[:, 0])
    _, kept = gated_delta_step(q[:, 0], k[:, 0], v[:, 0], zero, zero, state)
    assert np.array_equal(np.asarray(kept), np.asarray(state))


# -- the module against the reference ---------------------------------------------

def _no_cache(cfg, model, variables, ids):
    return jax.jit(lambda ids: model.apply(variables, ids)[0])(
        jnp.asarray(ids))


def _dense_cache(cfg, model, variables, ids):
    """Prefill 50 tokens in chunks of 32 and 18 (the second padded to 32
    with its real count handed over, as the engine's chunk programs do),
    then one token a call."""
    cache = init_olmo_hybrid_cache(cfg, ids.shape[0], 128)
    logits, cache = model.apply(variables, jnp.asarray(ids[:, :32]),
                                cache=cache)
    out = [logits]
    padded = np.zeros((ids.shape[0], 32), np.int32)
    padded[:, :18] = ids[:, 32:50]
    logits, cache = model.apply(
        variables, jnp.asarray(padded),
        cache=dict(cache, n=jnp.asarray(18, jnp.int32)))
    out.append(logits[:, :18])
    cache["idx"] = jnp.asarray(50, jnp.int32)
    step = jax.jit(lambda c, t: model.apply(variables, t, cache=c))
    for t in range(50, ids.shape[1]):
        logits, cache = step(cache, jnp.asarray(ids[:, t:t + 1]))
        out.append(logits)
    assert cache["state"].shape == (
        6, ids.shape[0], 4, cfg.linear_key_head_dim,
        cfg.linear_value_head_dim)
    assert cache["k"].shape[0] == 2      # the FULL layers alone
    return jnp.concatenate(out, axis=1)


def _paged_cache(cfg, model, variables, ids):
    """Two rows at DIFFERENT depths over one block pool: each row's prompt
    goes in through the dense contract (40 and 56 tokens) and is installed,
    K/V into the row's blocks and the state into the row's SLOT; then both
    decode together, one token a call, each at its own depth, a third slot
    idle beside them whose state must not move."""
    bs, n_blocks, slots = 16, 16, 3
    pool = {k: np.array(v) for k, v in kv_pool.init_block_pool(
        cfg, n_blocks, bs, n_slots=slots).items()}
    assert pool["k"].shape == (2, n_blocks, bs, 128)    # 4 x 16 merged, padded
    assert pool["state"].shape == (6, slots, 4, 8, 16)
    assert pool["conv"].shape == (6, slots, 3, 4 * (8 + 8 + 16))
    rng = np.random.default_rng(0)
    pool["state"][:, 2] = rng.normal(size=pool["state"][:, 2].shape)
    idle = pool["state"][:, 2].copy()
    table = np.full((slots, 8), n_blocks, np.int32)
    table[0, :6] = [3, 9, 1, 12, 7, 14]
    table[1, :6] = [5, 0, 11, 2, 13, 8]
    lens = [40, 56]
    logits = [None, None]
    prefill = jax.jit(lambda ids, cache: model.apply(variables, ids,
                                                     cache=cache))
    for r, n in enumerate(lens):
        out, cache = prefill(jnp.asarray(ids[r:r + 1, :n]),
                             init_olmo_hybrid_cache(cfg, 1, 64))
        logits[r] = [out[0]]
        for pos in range(n):
            blk, off = table[r, pos // bs], pos % bs
            for name in ("k", "v"):
                pool[name][:, blk, off] = kv_pool.kv_stored(
                    np.asarray(cache[name][:, 0, pos]), (128,))
        pool["state"][:, r] = np.asarray(cache["state"][:, 0])
        pool["conv"][:, r] = np.asarray(cache["conv"][:, 0])
    step = jax.jit(lambda pool, table, idx, tok, live: model.apply(
        variables, tok, cache=dict(pool, table=table, idx=idx, live=live)))
    idx = np.array(lens + [0], np.int32)
    live = np.array([True, True, False])
    for _ in range(ids.shape[1] - max(lens)):
        tok = np.stack([ids[0, idx[0]], ids[1, idx[1]], 7])[:, None]
        out, new = step({k: jnp.asarray(v) for k, v in pool.items()},
                        jnp.asarray(table), jnp.asarray(idx),
                        jnp.asarray(tok), jnp.asarray(live))
        for r in range(2):
            blk, off = table[r, idx[r] // bs], idx[r] % bs
            for name in ("k", "v"):
                pool[name][:, blk, off] = np.asarray(new[name][:, r, 0])
            logits[r].append(out[r])
        pool["state"] = np.array(new["state"])
        pool["conv"] = np.array(new["conv"])
        idx[:2] += 1
    assert np.array_equal(pool["state"][:, 2], idle)
    return [jnp.concatenate(x, axis=0) for x in logits]


@pytest.mark.parametrize("which, contract", [
    ("bundle", "none"), ("bundle", "dense"), ("bundle", "paged"),
    ("kernel_bundle", "dense")])
def test_logits_equal_the_references_through_each_cache_contract(
        request, which, contract):
    hf, cfg, model, variables, ids, want = request.getfixturevalue(which)
    assert cfg.serving_family().scan_solved_in_kernel is (
        which == "kernel_bundle")
    got = {"none": _no_cache, "dense": _dense_cache,
           "paged": _paged_cache}[contract](cfg, model, variables, ids)
    for r in range(2):
        n = got[r].shape[0]
        assert n >= 80
        np.testing.assert_allclose(np.asarray(got[r]), want[r, :n], atol=TOL)


@pytest.mark.parametrize("dims", PATHS)
def test_masked_tokens_behind_a_chunk_leave_the_state_bit_for_bit(dims):
    """The rule alone: 100 tokens, and the same 100 with 28 more behind them
    whose beta and g are 0 and whose q, k, v are anything: the state after
    both is the same bits (the masked tokens add exact zeros)."""
    q, k, v, g, beta, state = _rule_inputs(128, dtype=np.float32, dims=dims)
    real = (jnp.arange(128) < 100)[None, :, None]
    _, alone = gated_delta_chunked(
        *(x[:, :100] for x in (q, k, v, g, beta)), state)
    o, masked = gated_delta_chunked(
        q, k, v, jnp.where(real, g, 0.0), jnp.where(real, beta, 0.0), state)
    assert np.array_equal(np.asarray(alone), np.asarray(masked))
    _, moved = gated_delta_chunked(q, k, v, g, beta, state)
    assert not np.allclose(np.asarray(alone), np.asarray(moved), atol=1e-3)
    assert bool(jnp.isfinite(o).all())


@pytest.mark.parametrize("which", ["bundle", "kernel_bundle"])
def test_a_padded_chunk_leaves_the_state_and_tails_of_the_unpadded_one(
        request, which):
    """100 real tokens alone, and the same 100 at the head of a chunk padded
    to 128 with token 0 and the real count handed over. The first layer's
    state and convolution tails are the same BITS (its inputs are); the
    layers over it are given inputs from products of another shape, which
    round in another order, and agree to float32's last digits. Without the
    count the pad moves every layer's state and tails."""
    hf, cfg, model, variables, ids, _ = request.getfixturevalue(which)
    row = np.concatenate([ids[0], ids[1]])[None, :100]
    padded = np.zeros((1, 128), np.int32)
    padded[:, :100] = row
    fresh = init_olmo_hybrid_cache(cfg, 1, 128)
    _, alone = model.apply(variables, jnp.asarray(row), cache=fresh)
    _, masked = model.apply(
        variables, jnp.asarray(padded),
        cache=dict(fresh, n=jnp.asarray(100, jnp.int32)))
    _, unmasked = model.apply(variables, jnp.asarray(padded), cache=fresh)
    for name in ("state", "conv"):
        a, m, u = (np.asarray(c[name]) for c in (alone, masked, unmasked))
        assert np.array_equal(a[0], m[0]), name
        np.testing.assert_allclose(m, a, rtol=1e-4, atol=1e-6, err_msg=name)
        for layer in range(a.shape[0]):
            assert not np.allclose(a[layer], u[layer], rtol=1e-3,
                                   atol=1e-5), (name, layer)
    assert float(jnp.abs(alone["state"]).max()) > 0


def test_negative_eigenvalues_are_exercised_at_the_seeded_weights(bundle):
    """Some beta over 1 on the rehearsal's own inputs: the doubling is not
    a dead branch of the test."""
    hf, cfg, model, variables, ids, _ = bundle
    assert cfg.linear_allow_neg_eigval
    p = variables["params"]["layers_1"]["linear_attn"]
    x = variables["params"]["embed_tokens"][ids[0]]
    beta = 2.0 * jax.nn.sigmoid(x @ p["b_proj"])
    assert 0.2 < float((beta > 1).mean()) < 0.8
    single = np.asarray(ref.hybrid_logits(SEED, hf, ids[0], "float32",
                                          "beta_single"))
    want = np.asarray(ref.hybrid_logits(SEED, hf, ids[0], "float32"))
    assert np.abs(single - want).max() > 100 * TOL


def test_a_rows_result_does_not_depend_on_who_shares_its_batch(bundle):
    hf, cfg, model, variables, ids, _ = bundle
    apply = jax.jit(lambda ids: model.apply(variables, ids)[0])
    both, alone = apply(jnp.asarray(ids[:, :48])), apply(
        jnp.asarray(ids[1:, :48]))
    np.testing.assert_allclose(np.asarray(both[1]), np.asarray(alone[0]),
                               atol=TOL)


def test_the_family_says_which_layers_keep_what():
    cfg = OlmoHybridConfig()
    fam = cfg.serving_family()
    assert (fam.layers, fam.pool_layers, fam.state_layers) == (32, 8, 24)
    assert fam.paged_only and fam.kv_tail == (3840,)
    shapes = {name: (shape, jnp.dtype(dtype))
              for name, shape, dtype in fam.state_arrays}
    assert shapes == {"state": ((30, 96, 192), jnp.dtype("float32")),
                      "conv": ((3, 11520), jnp.dtype("float32"))}
    assert cfg.index_in_kind(3) == 0 and cfg.index_in_kind(7) == 1
    assert cfg.index_in_kind(4) == 3 and cfg.layer_types[4] == LINEAR
    assert cfg.layer_types[3] == FULL
    # 2.21 MB of float32 state a slot a layer, and the tails
    assert fam.state_bytes_per_slot == 24 * (30 * 96 * 192 * 4
                                             + 3 * 11520 * 4)


def test_variants_the_forward_does_not_compute_are_refused():
    hf = rehearsal_hf()
    assert config_from_hf_olmo_hybrid(hf).num_layers == 8
    assert config_from_hf_olmo_hybrid(hf).head_dim == 16
    for key, value in (("rope_parameters", {"rope_theta": 500000.0}),
                       ("attention_bias", True),
                       ("tie_word_embeddings", True),
                       ("num_key_value_heads", 2),
                       ("hidden_act", "gelu"),
                       ("model_type", "olmo3"),
                       ("linear_num_key_heads", 2),
                       ("num_hidden_layers", 7)):
        with pytest.raises(ValueError):
            config_from_hf_olmo_hybrid({**hf, key: value})
    with pytest.raises(ValueError, match="one token a row"):
        cfg = OlmoHybridConfig.tiny()
        model = OlmoHybridLMHeadModel(cfg)
        pool = kv_pool.init_block_pool(cfg, 4, 16, n_slots=1)
        jax.eval_shape(lambda: model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32),
            cache=dict(pool, table=jnp.zeros((1, 2), jnp.int32),
                       idx=jnp.zeros((1,), jnp.int32))))
