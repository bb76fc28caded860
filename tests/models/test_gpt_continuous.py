"""Per-slot KV cache oracle: the continuous-batching building block.

Contract (models/gpt.py init_cache per_slot=True): a cache whose ``idx``
is per-row decodes every row at its own depth, and each row's tokens are
identical to continuing that row alone in its own scalar-idx cache — the
property that lets the serving engine admit/retire rows mid-stream
without perturbing their neighbors.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparkdl_tpu.models.gpt import (
    GPTConfig,
    GPTLMHeadModel,
    init_cache,
    merged_axis_attention,
)

MAX_LEN = 32


def _prefill_single(model, variables, prompt, max_len=MAX_LEN):
    """Batch-1 scalar-idx prefill; returns (first greedy token, cache)."""
    ids = jnp.asarray([prompt], jnp.int32)
    cache = init_cache(model.config, 1, max_len)
    logits, cache = model.apply(variables, ids, cache=cache)
    return int(jnp.argmax(logits[0, -1])), cache


def _decode_single(model, variables, cache, tok, steps):
    """Reference: greedy scalar-idx decode, one row alone."""
    toks = []
    for _ in range(steps):
        toks.append(tok)
        logits, cache = model.apply(
            variables, jnp.asarray([[tok]], jnp.int32), cache=cache
        )
        tok = int(jnp.argmax(logits[0, -1]))
    return toks


@pytest.mark.parametrize("positions", ["rope", "learned"])
def test_per_slot_decode_matches_single_row(positions):
    cfg = GPTConfig.tiny(positions=positions)
    model = GPTLMHeadModel(cfg)
    variables = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )
    prompts = [[5, 3, 9, 2, 7], [1, 4], [6, 8, 6]]
    steps = 5

    # build the shared per-slot cache from independent batch-1 prefills
    shared = init_cache(cfg, len(prompts), MAX_LEN, per_slot=True)
    toks = []
    for s, p in enumerate(prompts):
        tok, single = _prefill_single(model, variables, p)
        shared["k"] = shared["k"].at[:, s].set(single["k"][:, 0])
        shared["v"] = shared["v"].at[:, s].set(single["v"][:, 0])
        shared["idx"] = shared["idx"].at[s].set(single["idx"])
        toks.append(tok)

    got = [[] for _ in prompts]
    tok_arr = jnp.asarray(toks, jnp.int32)
    for _ in range(steps):
        for s in range(len(prompts)):
            got[s].append(int(tok_arr[s]))
        logits, shared = model.apply(
            variables, tok_arr[:, None], cache=shared
        )
        tok_arr = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)

    for s, p in enumerate(prompts):
        tok, single = _prefill_single(model, variables, p)
        want = _decode_single(model, variables, single, tok, steps)
        assert got[s] == want, f"slot {s} diverged (prompt {p})"


def test_per_slot_rows_are_independent():
    """Retiring a slot (its cache becoming garbage) must not change the
    tokens of the remaining rows — the join/leave invariant."""
    cfg = GPTConfig.tiny()
    model = GPTLMHeadModel(cfg)
    variables = model.init(
        jax.random.PRNGKey(1), jnp.zeros((1, 8), jnp.int32)
    )
    tok, single = _prefill_single(model, variables, [5, 3, 9])

    shared = init_cache(cfg, 2, MAX_LEN, per_slot=True)
    shared["k"] = shared["k"].at[:, 0].set(single["k"][:, 0])
    shared["v"] = shared["v"].at[:, 0].set(single["v"][:, 0])
    shared["idx"] = shared["idx"].at[0].set(single["idx"])
    # slot 1: garbage (random K/V at a different depth), as after a retire
    key = jax.random.PRNGKey(2)
    shared["k"] = shared["k"].at[:, 1].set(
        jax.random.normal(key, shared["k"].shape[0:1] + shared["k"].shape[2:],
                          shared["k"].dtype)
    )
    shared["idx"] = shared["idx"].at[1].set(17)

    toks = []
    tok_arr = jnp.asarray([tok, 0], jnp.int32)
    for _ in range(4):
        toks.append(int(tok_arr[0]))
        logits, shared = model.apply(variables, tok_arr[:, None],
                                     cache=shared)
        tok_arr = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)

    tok, single = _prefill_single(model, variables, [5, 3, 9])
    assert toks == _decode_single(model, variables, single, tok, 4)


def test_per_slot_multi_token_step_matches_sequential():
    """The L=k per-slot step (the speculative-verify building block)
    must produce, at every position, the argmax that k sequential
    single-token steps produce when fed the same tokens — each row at
    its own depth."""
    cfg = GPTConfig.tiny()
    model = GPTLMHeadModel(cfg)
    variables = model.init(
        jax.random.PRNGKey(3), jnp.zeros((1, 8), jnp.int32)
    )
    tok0, row0 = _prefill_single(model, variables, [5, 3, 9])
    tok1, row1 = _prefill_single(model, variables, [7, 2, 8, 4, 1])
    span = np.asarray([[tok0, 11, 6], [tok1, 3, 9]], np.int32)

    # reference: sequential single-token steps over a shared per-slot
    # cache, forced to consume span[:, j] at step j
    seq = init_cache(cfg, 2, MAX_LEN, per_slot=True)
    for b, row in enumerate((row0, row1)):
        for name in ("k", "v"):
            seq[name] = seq[name].at[:, b].set(row[name][:, 0])
        seq["idx"] = seq["idx"].at[b].set(row["idx"])
    want = []
    for j in range(span.shape[1]):
        logits, seq = model.apply(
            variables, jnp.asarray(span[:, j:j + 1]), cache=seq)
        want.append(np.asarray(jnp.argmax(logits[:, -1], axis=-1)))

    multi = init_cache(cfg, 2, MAX_LEN, per_slot=True)
    for b, row in enumerate((row0, row1)):
        for name in ("k", "v"):
            multi[name] = multi[name].at[:, b].set(row[name][:, 0])
        multi["idx"] = multi["idx"].at[b].set(row["idx"])
    logits, multi = model.apply(
        variables, jnp.asarray(span), cache=multi)
    got = np.asarray(jnp.argmax(logits, axis=-1))  # [B, L]
    np.testing.assert_array_equal(got, np.stack(want, axis=1))
    np.testing.assert_array_equal(
        np.asarray(multi["idx"]), np.asarray(seq["idx"]))
    # K/V match to float tolerance only: an L=k projection GEMM rounds
    # differently than k L=1 GEMMs (same math, different shapes) — the
    # serving contract is TOKEN identity, pinned at the engine level
    # across every draft k (tests/serving/test_spec_decode.py), the
    # same discipline as chunked-vs-dense prefill
    for name in ("k", "v"):
        np.testing.assert_allclose(
            np.asarray(multi[name]), np.asarray(seq[name]),
            rtol=1e-5, atol=1e-5)


def test_per_slot_overflowed_slot_drops_write():
    """An idle slot whose idx sits past the buffer matches no column: the
    write is dropped (no clamp-corruption of column T-1) and live rows are
    untouched."""
    cfg = GPTConfig.tiny()
    model = GPTLMHeadModel(cfg)
    variables = model.init(
        jax.random.PRNGKey(4), jnp.zeros((1, 8), jnp.int32)
    )
    cache = init_cache(cfg, 2, MAX_LEN, per_slot=True)
    cache["idx"] = jnp.asarray([0, MAX_LEN + 3], jnp.int32)
    before_last_col = np.asarray(cache["k"][:, 1, -1])
    _, cache = model.apply(variables, jnp.ones((2, 1), jnp.int32),
                           cache=cache)
    np.testing.assert_array_equal(
        np.asarray(cache["k"][:, 1, -1]), before_last_col
    )
    assert int(cache["idx"][1]) == MAX_LEN + 4


# -- the few-query attention over one merged axis (ISSUE 30) ------------------

BS = 4  # a block of the pool the rows below came through


def _einsum_attention(q, k_old, v_old, k_new, v_new, idx, kv_mask):
    """The form the product replaced, in float64 numpy: this call's columns
    written into the rows at ``[idx, idx+L)``, heads apart, one causal
    softmax over the row."""
    s, l, h, d = q.shape
    out = np.zeros((s, l, h, d))
    for row in range(s):
        k = np.array(k_old[row, :, :h * d], np.float64).reshape(-1, h, d)
        v = np.array(v_old[row, :, :h * d], np.float64).reshape(-1, h, d)
        at = int(idx[row])
        k[at:at + l] = np.asarray(k_new[row, :, :h * d]).reshape(l, h, d)
        v[at:at + l] = np.asarray(v_new[row, :, :h * d]).reshape(l, h, d)
        scores = np.einsum("qhd,khd->hqk", np.asarray(q[row], np.float64),
                           k) / np.sqrt(d)
        seen = np.arange(k.shape[0])[None, :] <= at + np.arange(l)[:, None]
        if kv_mask is not None:
            # the new columns are always real; the mask is about the rows
            seen &= np.asarray(kv_mask[row])[None, :] | (
                np.arange(k.shape[0])[None, :] >= at)
        scores = np.where(seen[None], scores, -np.inf)
        p = np.exp(scores - scores.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        out[row] = np.einsum("hqk,khd->qhd", p, v)
    return out


@pytest.mark.parametrize("masked", [False, True],
                         ids=["all_columns", "left_pad_masked"])
@pytest.mark.parametrize("merged", [32, 128],
                         ids=["heads_fill_the_axis", "axis_zero_padded"])
@pytest.mark.parametrize("queries", [1, 4])
def test_product_over_the_merged_axis_is_the_einsum_attention(
        queries, merged, masked):
    """``merged_axis_attention`` against the einsum form, float32 against
    float64: one query and a span of four; rows at different depths; a row
    with nothing behind it (an idle slot's sentinel entries: every old
    column hidden); a new column at a block's first and at its last
    offset; the merged axis as wide as the heads and zero-padded to a lane
    tile (the pool's storage), whose pad must change nothing."""
    s, h, d, w = 5, 2, 16, 6 * BS
    rng = np.random.default_rng(queries + merged)

    def rows(*shape):
        x = np.zeros(shape + (merged,), np.float32)
        x[..., :h * d] = rng.normal(size=shape + (h * d,))
        return jnp.asarray(x)

    q = jnp.asarray(rng.normal(size=(s, queries, h, d)), jnp.float32)
    k_old, v_old = rows(s, w), rows(s, w)
    k_new, v_new = rows(s, queries), rows(s, queries)
    # depths: mid-block, nothing yet, a block's first offset, its last,
    # and deep enough that the span ends on the row's last column
    idx = jnp.asarray([9, 0, 2 * BS, 3 * BS - 1, w - queries], jnp.int32)
    kv_mask = None
    if masked:
        kv_mask = jnp.asarray(
            np.arange(w)[None, :] >= np.asarray([3, 0, 1, 5, 2])[:, None])
    got = merged_axis_attention(q, k_old, v_old, k_new, v_new, idx,
                                kv_mask=kv_mask)
    assert got.shape == (s, queries, h, d) and got.dtype == jnp.float32
    want = _einsum_attention(q, k_old, v_old, k_new, v_new, idx, kv_mask)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-6)
    # what lies at and past a row's depth is never read: garbage there
    # (columns of rejected drafts, a retired slot's blocks) changes nothing
    junk = jnp.where(np.arange(w)[None, :, None] >= np.asarray(idx)[:, None,
                                                              None],
                     1e4, k_old)
    again = merged_axis_attention(q, junk, junk * 0 + v_old, k_new, v_new,
                                  idx, kv_mask=kv_mask)
    np.testing.assert_array_equal(np.asarray(again), np.asarray(got))


def test_a_shared_block_reads_the_same_for_both_rows():
    """Two rows whose first block is ONE physical block (a copy-on-write
    prefix) and whose queries agree get the same answer while they read
    only that block, and differ once a row's own column joins."""
    h, d, merged = 2, 16, 128
    rng = np.random.default_rng(7)
    block = rng.normal(size=(BS, merged)).astype(np.float32)
    block[:, h * d:] = 0
    k_old = jnp.asarray(np.stack([np.concatenate(
        [block, rng.normal(size=(BS, merged))]) for _ in range(2)]),
        jnp.float32).at[..., h * d:].set(0)
    q = jnp.asarray(np.repeat(rng.normal(size=(1, 1, h, d)), 2, 0),
                    jnp.float32)
    new = jnp.zeros((2, 1, merged), jnp.float32).at[1, :, :h * d].set(3.0)
    idx = jnp.asarray([BS, BS], jnp.int32)
    out = merged_axis_attention(q, k_old, k_old, new * 0, new * 0, idx)
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(out[1]))
    out = merged_axis_attention(q, k_old, k_old, new, new, idx)
    assert np.abs(np.asarray(out[0]) - np.asarray(out[1])).max() > 1e-3
