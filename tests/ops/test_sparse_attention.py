"""The two stages of a learned sparse attention (``ops/sparse_attention.py``)
on the CPU, float32: the indexer's scores against their definition; the
selection as a mask and as positions against a stable ``top_k``, ties to the
lower position in both; the absorbed attention over stored columns against
the expanded form over per-head K and V; the selected read through a block
table, token by token, with this call's own column standing in at its
position."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparkdl_tpu.ops.sparse_attention import (
    INDEX_HEAD_GROUP,
    absorbed_attention,
    index_scores,
    pick_columns,
    select_mask,
    selected_columns,
)


def _rand(key, *shape):
    return jax.random.normal(jax.random.PRNGKey(key), shape, jnp.float32)


@pytest.mark.parametrize("heads", [4, INDEX_HEAD_GROUP, 3 * INDEX_HEAD_GROUP])
def test_index_scores_are_the_weighted_rectified_products(heads):
    """One pass or several passes of heads: the same sum."""
    q, k, w = _rand(0, 2, 5, heads, 16), _rand(1, 2, 40, 16), _rand(
        2, 2, 5, heads)
    got = np.asarray(index_scores(q, k, w))
    want = np.einsum(
        "blh,blhw->blw", np.asarray(w),
        np.maximum(np.einsum("blhd,bwd->blhw", np.asarray(q), np.asarray(k)),
                   0))
    assert got.shape == (2, 5, 40)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # w has either sign: so has I
    assert (got > 0).any() and (got < 0).any()


def _stable_top_k_sets(scores, k):
    sets = []
    for row in np.asarray(scores):
        order = sorted(range(len(row)), key=lambda j: (-row[j], j))
        sets.append({j for j in order[:k] if row[j] > -np.inf})
    return sets


def test_the_mask_and_the_positions_are_a_stable_top_k_with_ties():
    """Scores with many equal values at the k-th place, columns that may not
    be taken, rows with fewer allowed columns than k: the mask form and the
    position form take the SAME set, the one a stable sort takes."""
    rng = np.random.default_rng(3)
    scores = rng.integers(0, 6, (7, 50)).astype(np.float32)
    scores[0, 10:] = -np.inf            # 10 allowed < k
    scores[1, :] = 2.0                  # all tied: the first k
    scores[2, 30:] = -np.inf
    k = 16
    want = _stable_top_k_sets(scores, k)
    mask = np.asarray(select_mask(jnp.asarray(scores), k))
    pos, taken = (np.asarray(a) for a in pick_columns(jnp.asarray(scores), k))
    for r in range(scores.shape[0]):
        assert set(np.nonzero(mask[r])[0].tolist()) == want[r], r
        assert set(pos[r][taken[r]].tolist()) == want[r], r
    assert mask[0].sum() == 10 and taken[0].sum() == 10
    assert set(np.nonzero(mask[1])[0].tolist()) == set(range(k))
    # no more columns than k: every allowed one
    few = jnp.asarray(scores[:, :12])
    np.testing.assert_array_equal(np.asarray(select_mask(few, k)),
                                  np.asarray(few) > -np.inf)


def test_absorbed_over_stored_columns_is_expanded_over_heads():
    """``q~ = q_nope W_uk^T`` against ``[c_kv | k_r | pad]`` and ``(sum p
    c_kv) W_uv`` equal the softmax over per-head ``k = c_kv W_uk`` and ``v =
    c_kv W_uv``, with some columns unseen and this call's own column beside
    the old ones."""
    s, h, c, dr, dn, dv, k, pad = 3, 4, 16, 8, 12, 10, 20, 8
    q_nope, q_rope = _rand(0, s, h, dn), _rand(1, s, h, dr)
    w_uk, w_uv = _rand(2, c, h, dn), _rand(3, c, h, dv)
    old = jnp.concatenate([_rand(4, s, k, c + dr),
                           jnp.zeros((s, k, pad))], -1)
    new = jnp.concatenate([_rand(5, s, c + dr), jnp.zeros((s, pad))], -1)
    seen = jnp.asarray(np.random.default_rng(0).random((s, k)) < 0.6)
    new_seen = jnp.asarray([True, False, True])
    scale = 1 / math.sqrt(dn + dr)
    q_full = jnp.concatenate(
        [jnp.einsum("shn,chn->shc", q_nope, w_uk), q_rope,
         jnp.zeros((s, h, pad))], -1)
    mix = absorbed_attention(q_full, old, seen, new, new_seen, scale)
    got = jnp.einsum("shc,chv->shv", mix[..., :c], w_uv)
    # expanded: every column's own K and V a head, the new column last
    cols = jnp.concatenate([old, new[:, None]], 1)
    vis = jnp.concatenate([seen, new_seen[:, None]], 1)
    k_nope = jnp.einsum("skc,chn->skhn", cols[..., :c], w_uk)
    v = jnp.einsum("skc,chv->skhv", cols[..., :c], w_uv)
    sc = (jnp.einsum("shn,skhn->shk", q_nope, k_nope)
          + jnp.einsum("shr,skr->shk", q_rope, cols[..., c:c + dr])) * scale
    p = jax.nn.softmax(jnp.where(vis[:, None], sc, -1e30), -1)
    want = jnp.einsum("shk,skhv->shv", p, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_the_selected_read_goes_through_the_table_token_by_token():
    """Rows at depths 37 and 9 over a pool of blocks of 4 in another order:
    each picked position is read from ITS block and offset of ITS layer; the
    position ``idx`` is the call's own column and is not read; with nothing
    picked every column of the table comes back, seen to the row's depth."""
    layers, blocks, bs, width = 3, 24, 4, 8
    pool = _rand(0, layers, blocks, bs, width)
    table = np.full((2, 12), blocks, np.int32)
    table[0, :10] = [5, 17, 2, 9, 21, 0, 13, 7, 19, 11]
    table[1, :3] = [3, 8, 15]
    idx = jnp.asarray([37, 9])
    cache = {"latent": pool, "table": jnp.asarray(table), "idx": idx}
    pos = jnp.asarray([[0, 36, 37, 13, 5, 22], [9, 2, 8, 0, 40, 41]])
    taken = jnp.asarray([[True] * 6, [True, True, True, True, False, False]])
    old, seen, new_seen = selected_columns(cache, 1, (pos, taken), idx)
    assert old.shape == (2, 6, width)
    for r in range(2):
        for j in range(6):
            p = int(pos[r, j])
            if bool(seen[r, j]):
                want = pool[1, table[r, p // bs], p % bs]
                np.testing.assert_array_equal(np.asarray(old[r, j]),
                                              np.asarray(want))
    np.testing.assert_array_equal(
        np.asarray(seen), [[True, True, False, True, True, True],
                           [False, True, True, True, False, False]])
    np.testing.assert_array_equal(np.asarray(new_seen), [True, True])
    # a row whose own column was not picked does not attend it
    _, _, gone = selected_columns(cache, 1, (pos.at[1, 0].set(1), taken), idx)
    np.testing.assert_array_equal(np.asarray(gone), [True, False])
    old, seen, new_seen = selected_columns(cache, 2, None, idx)
    assert old.shape == (2, 12 * bs, width)
    np.testing.assert_array_equal(np.asarray(seen).sum(1), [37, 9])
    np.testing.assert_array_equal(np.asarray(old[0, 4:8]),
                                  np.asarray(pool[2, 17]))
    assert bool(new_seen.all())
