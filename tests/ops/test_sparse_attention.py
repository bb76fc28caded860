"""The two stages of a learned sparse attention (``ops/sparse_attention.py``)
on the CPU, float32: the indexer's scores against their definition; the
selection as a mask and as positions against a stable ``top_k``, ties to the
lower position in both; the absorbed attention over stored columns against
the expanded form over per-head K and V; the selected read through a block
table, token by token, with this call's own column standing in at its
position."""

import math
import re

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


# -- the step that attends in place (ISSUE 45) --------------------------------------

ROWS, HEADS, CP, BS, BLOCKS, LAYERS, K = 4, 4, 128, 16, 48, 2, 16


def _step_case(depths, nb, scores_of, free=(), seed=0):
    """A ``latent`` pool of random columns, a table that scatters each live
    row's blocks over it (rows in ``free`` hold the sentinel), one absorbed
    query and one new column a row, and an indexer's scores over the
    table's columns as ``scores_of(rng, depths, width)`` makes them, cut to
    each row's depth with its own column at position ``idx``."""
    rng = np.random.default_rng(seed)
    pool = jnp.asarray(rng.standard_normal((LAYERS, BLOCKS, BS, CP)),
                       jnp.float32)
    table = np.full((ROWS, nb), BLOCKS, np.int32)
    perm, at = rng.permutation(BLOCKS), 0
    for s, d in enumerate(depths):
        n = 0 if s in free else -(-(d + 1) // BS)
        table[s, :n] = perm[at:at + n]
        at += n
    idx = np.asarray(depths, np.int32)
    scores = np.asarray(scores_of(rng, idx, nb * BS), np.float32)
    scores[np.arange(nb * BS)[None, :] > idx[:, None]] = -np.inf
    cache = {"latent": pool, "table": jnp.asarray(table)}
    q = jnp.asarray(rng.standard_normal((ROWS, HEADS, CP)), jnp.float32)
    new = jnp.asarray(rng.standard_normal((ROWS, CP)), jnp.float32)
    return cache, jnp.asarray(idx), jnp.asarray(scores), q, new


def _random(rng, idx, width):
    return rng.standard_normal((ROWS, width))


def _tied(rng, idx, width):
    # four values over 128 columns: the k-th place is always a tie
    return rng.integers(0, 4, (ROWS, width))


def _own(score):
    def scores_of(rng, idx, width):
        out = rng.standard_normal((ROWS, width))
        out[np.arange(ROWS), idx] = score
        return out
    scores_of.own_picked = score > 0
    return scores_of


def _nothing_in_the_first_group(rng, idx, width):
    # a group is two blocks here: no row picks any of its first 32 columns
    out = rng.standard_normal((ROWS, width))
    out[:, :2 * BS] -= 100
    return out


@pytest.mark.parametrize("depths, nb, scores_of, free", [
    pytest.param((127, 3, 64, 40), 8, _random, (),
                 id="rows at very different depths"),
    pytest.param((10, 15, 16, 17), 8, _random, (),
                 id="rows no deeper than the selection, and just past it"),
    pytest.param((100, 33, 64, 16), 8, _tied, (),
                 id="ties at the k-th score go to the lower position"),
    pytest.param((100, 33, 64, 20), 8, _own(50.0), (),
                 id="the own column picked"),
    pytest.param((100, 33, 64, 20), 8, _own(-50.0), (),
                 id="the own column not picked"),
    pytest.param((100, 70, 64, 50), 8, _nothing_in_the_first_group, (),
                 id="a first group that holds no picked column"),
    pytest.param((100, 5, 64, 0), 8, _random, (1,),
                 id="a row with no block, and one at depth 0"),
    pytest.param((31, 32, 33, 47), 3, _random, (),
                 id="a table that is no whole count of groups"),
])
@pytest.mark.parametrize("layer", [0, 1])
def test_in_place_under_a_mask_is_the_selected_read_and_the_absorbed_attention(
        monkeypatch, depths, nb, scores_of, free, layer):
    """``attend_in_place`` (the rows' live blocks read where the pool keeps
    them, the selection a mask) beside ``selected_columns`` +
    ``absorbed_attention`` (the same selection as positions, read one by
    one), float32 under the Pallas interpreter, groups of two blocks: the
    same mix, whatever the rows' depths, ties, the own column's fate and
    where in the table the picked columns lie."""
    from sparkdl_tpu.ops import paged_decode, sparse_attention

    monkeypatch.setattr(paged_decode, "_GROUP_BYTES", 2 * BS * CP * 4)
    cache, idx, scores, q, new = _step_case(depths, nb, scores_of, free)
    scale = 1 / math.sqrt(20)
    mask = select_mask(scores, K)
    pos, taken = pick_columns(scores, K)
    want = absorbed_attention(
        q, *selected_columns(dict(cache, idx=idx), layer, (pos, taken),
                             idx)[:2], new,
        (taken & (pos == idx[:, None])).any(-1), scale)
    got = sparse_attention.attend_in_place(cache, layer, q, mask, idx, new,
                                           scale)
    assert got.shape == want.shape == (ROWS, HEADS, CP)
    assert got.dtype == jnp.float32
    live = [r for r in range(ROWS) if r not in free]
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               rtol=2e-5, atol=2e-5)
    # the cases are what their names say
    own_picked = np.asarray(mask)[np.arange(ROWS), np.asarray(idx)]
    if scores_of is _tied:
        kth = np.sort(np.asarray(scores), -1)[:, -K]
        assert ((np.asarray(scores) == kth[:, None]).sum(-1)[:3] > 1).all()
    if scores_of is _nothing_in_the_first_group:
        assert not np.asarray(mask)[:, :2 * BS].any()
    if hasattr(scores_of, "own_picked"):
        # (row 3 is 20 deep, 21 columns with its own: over the selection's
        # 16 either way; rows 0-2 are deeper)
        assert (own_picked == scores_of.own_picked).all()
    # a row that holds no block read nothing: its own column alone
    for r in free:
        np.testing.assert_allclose(
            np.asarray(got)[r], np.broadcast_to(np.asarray(new)[r],
                                                (HEADS, CP)), rtol=1e-6)


@pytest.mark.parametrize("width, selection, taken", [
    (2048, 2048, False),        # no wider than the selection: nothing picked
    (4096, 2048, True), (16384, 2048, True),
    (16400, 2048, False), (32768, 2048, False),
    (64, 16, True), (128, 16, True), (144, 16, False),
])
def test_the_rule_is_a_tables_width_in_selections(width, selection, taken):
    from sparkdl_tpu.ops.sparse_attention import (
        IN_PLACE_SELECTIONS,
        attends_in_place,
    )

    assert IN_PLACE_SELECTIONS == 8
    assert attends_in_place(width, selection) is taken


@pytest.mark.parametrize("nb, in_place", [(8, True), (16, False)])
def test_a_step_holds_the_kernel_inside_the_rule_and_the_gather_past_it(
        nb, in_place):
    """The tiny family's paged step (a selection of 16 columns, blocks of
    16) over a table of 8 selections and over one of 16, as traced: inside
    the rule every layer calls the kernel and nothing gathers ``[rows x 16,
    128]`` columns one by one or sorts; past it every layer gathers them,
    each ``full`` layer sorts, and nothing calls the kernel."""
    from sparkdl_tpu.models.glm_moe_dsa import (
        GlmMoeDsaConfig,
        GlmMoeDsaLMHeadModel,
    )

    cfg = GlmMoeDsaConfig.tiny()
    model = GlmMoeDsaLMHeadModel(cfg)
    variables = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    rows, blocks = 2, 64

    def step(variables, latent, index_k, table, idx, tok):
        return model.apply(variables, tok, cache={
            "latent": latent, "index_k": index_k, "table": table,
            "idx": idx})[0]

    text = str(jax.make_jaxpr(step)(
        variables,
        jax.ShapeDtypeStruct((cfg.num_layers, blocks, 16, 128), jnp.float32),
        jax.ShapeDtypeStruct((cfg.full_layers, blocks, 16, 16), jnp.float32),
        jax.ShapeDtypeStruct((rows, nb), jnp.int32),
        jax.ShapeDtypeStruct((rows,), jnp.int32),
        jax.ShapeDtypeStruct((rows, 1), jnp.int32)))
    kernels = text.count("pallas_call[")
    picked_reads = len(re.findall(r":f32\[2,16,128\] = gather", text))
    # (the selection's: the experts' routers take their top 2)
    sorts = len(re.findall(r"= top_k\[axis=1 k=16\]", text))
    if in_place:
        assert (kernels, picked_reads, sorts) == (cfg.num_layers, 0, 0)
    else:
        assert (kernels, picked_reads, sorts) == (0, cfg.num_layers,
                                                  cfg.full_layers)
