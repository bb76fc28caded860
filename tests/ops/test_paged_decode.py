"""The paged one-query attention kernel (``ops/paged_decode.py``, ISSUE 35)
under the Pallas interpreter, beside what it takes the place of on the same
pool, table and ``idx``: ``kv_pool.layer_rows`` + ``merged_axis_attention``.
Small shapes with heads of 128 on one unpadded axis, float32, groups of TWO
blocks (the kernel derives a group from its shapes and a byte count: the
count is made small here, so that a table of eight blocks is four groups).
Since ISSUE 37 a GROUP of query heads may share each K/V head and a key head
may differ from a value head in size: those shapes beside a plain softmax a
head (:func:`_plain`), which knows nothing of merged axes. Since ISSUE 43 a
VALUE head may be under a lane tile if V's axis is whole tiles (LFM2's 8 x 64
on 512): the row's end is then a select by lane, beside the same plain
softmax."""

import jax.numpy as jnp
import numpy as np
import pytest

from sparkdl_tpu.models.gpt import merged_axis_attention
from sparkdl_tpu.models.kv_pool import kv_tail, kv_tails, layer_rows
from sparkdl_tpu.ops import paged_decode

ROWS, HEADS, HEAD, BS, BLOCKS, LAYERS = 4, 2, 128, 16, 48, 2
GROUP = 2  # blocks: a group's edge every 32 columns


#: (query heads, K/V heads, key head, value head)
EQUAL = (HEADS, HEADS, HEAD, HEAD)
SHAPES = [
    pytest.param(EQUAL, id="as many K/V heads as query heads, one size"),
    pytest.param((32, 2, 192, 128),
                 id="MiMo's: 16 query heads a K/V head, keys of 192 over "
                    "values of 128"),
    pytest.param((8, 4, 64, 256),
                 id="2 a K/V head, a key head of half a lane tile under "
                    "values of two"),
]


def _small_groups(monkeypatch, kv_heads=HEADS, head=HEAD):
    monkeypatch.setattr(paged_decode, "_GROUP_BYTES",
                        GROUP * BS * kv_heads * head * 4)


@pytest.fixture
def small_groups(monkeypatch):
    _small_groups(monkeypatch)


def _case(depths, nb, free=(), dtype=jnp.float32, seed=0, shape=EQUAL):
    """A pool of random K and V, one query and one new column a row, and a
    table that scatters each live row's blocks over the pool; rows in
    ``free`` hold the sentinel throughout (and whatever ``idx`` says)."""
    heads, kv_heads, dk, dv = shape
    rng = np.random.default_rng(seed)

    def normal(*shape):
        return jnp.asarray(rng.standard_normal(shape), dtype)

    pool = {"k": normal(LAYERS, BLOCKS, BS, kv_heads * dk),
            "v": normal(LAYERS, BLOCKS, BS, kv_heads * dv)}
    table = np.full((ROWS, nb), BLOCKS, np.int32)
    perm, at = rng.permutation(BLOCKS), 0
    for s, d in enumerate(depths):
        n = 0 if s in free else -(-d // BS)
        table[s, :n] = perm[at:at + n]
        at += n
    return (pool, jnp.asarray(table), jnp.asarray(depths, jnp.int32),
            normal(ROWS, 1, heads, dk), normal(ROWS, 1, kv_heads * dk),
            normal(ROWS, 1, kv_heads * dv))


def _both(layer, pool, table, idx, q, k_new, v_new):
    k_old, v_old = layer_rows(pool, layer, table, q.dtype)
    want = merged_axis_attention(q, k_old, v_old, k_new, v_new, idx)
    got = paged_decode.paged_decode_attention(
        q, pool["k"], pool["v"], layer, table, idx, k_new, v_new)
    return (np.asarray(want, np.float32),
            np.asarray(got, np.float32).reshape(want.shape))


def _plain(layer, pool, table, idx, q, k_new, v_new):
    """Softmax attention a row and a head in float64 numpy: the row's blocks
    through its table to its depth, then this call's own column; query head
    ``h`` reads K/V head ``h // (H / G)``. A free row (the sentinel first)
    sees its own column alone. ``[S, H, Dv]``."""
    rows, _, heads, dk = q.shape
    kv_heads = pool["k"].shape[-1] // dk
    k, v, q, k_new, v_new = (
        np.asarray(a, np.float64)
        for a in (pool["k"][layer], pool["v"][layer], q, k_new, v_new))
    table, idx = np.asarray(table), np.asarray(idx)
    out = np.zeros((rows, heads, v.shape[-1] // kv_heads))
    for s in range(rows):
        depth = 0 if table[s, 0] >= BLOCKS else int(idx[s])
        blocks = table[s, :-(-depth // BS)]
        ks = np.concatenate([k[blocks].reshape(-1, k.shape[-1])[:depth],
                             k_new[s]]).reshape(depth + 1, kv_heads, dk)
        vs = np.concatenate([v[blocks].reshape(-1, v.shape[-1])[:depth],
                             v_new[s]]).reshape(depth + 1, kv_heads, -1)
        for h in range(heads):
            g = h // (heads // kv_heads)
            x = ks[:, g] @ q[s, 0, h] / np.sqrt(dk)
            p = np.exp(x - x.max())
            out[s, h] = (p / p.sum()) @ vs[:, g]
    return out


@pytest.mark.parametrize("depths, nb, layer", [
    pytest.param((127, 3, 64, 17), 8, 1, id="rows of very unequal depth"),
    pytest.param((0, 5, 0, 100), 8, 0, id="rows at depth 0: own column only"),
    pytest.param((15, 16, 17, 1), 8, 1, id="on and past a block's edge"),
    pytest.param((31, 32, 33, 64), 8, 0, id="on and past a group's edge"),
    pytest.param((65, 96, 97, 127), 8, 1, id="the last group, part and whole"),
    pytest.param((9, 15, 0, 1), 1, 1, id="nb 1: a table narrower than a group"),
    pytest.param((9, 31, 17, 32), 2, 0, id="nb 2: one group"),
    pytest.param((63, 31, 33, 2), 4, 1, id="nb 4"),
    pytest.param((255, 130, 7, 200), 16, 0, id="nb 16, layer 0"),
    pytest.param((255, 130, 7, 200), 16, 1, id="nb 16, layer 1"),
])
def test_the_kernel_is_the_gather_and_the_merged_axis_attention(
        small_groups, depths, nb, layer):
    want, got = _both(layer, *_case(depths, nb))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_a_free_row_reads_nothing_and_its_output_is_finite(small_groups):
    """A slot that holds no block (the sentinel throughout its table row)
    fetches nothing whatever its ``idx`` says; nobody reads its output, and
    it is finite. The rows beside it are what they would be without it."""
    case = _case((40, 37, 100, 90), 8, free=(1, 3))
    want, got = _both(1, *case)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got[[0, 2]], want[[0, 2]], rtol=1e-5,
                               atol=1e-5)
    # a free row's own column is all it saw
    v_new = np.asarray(case[-1]).reshape(ROWS, 1, HEADS, HEAD)
    np.testing.assert_allclose(got[[1, 3]], v_new[[1, 3]], rtol=1e-5,
                               atol=1e-5)


def test_stored_bfloat16_keeps_the_merged_axis_attentions_precisions(
        small_groups):
    want, got = _both(1, *_case((127, 3, 64, 17), 8, dtype=jnp.bfloat16))
    # both cast the softmax's weights to bfloat16 before the product with V
    # (one normalised, one under the running maximum): a bfloat16 step apart
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)


def _beside_the_plain_softmax(monkeypatch, shape, depths, free=(),
                              dtype=jnp.float32, tol=1e-5):
    """The kernel at ``shape`` over eight blocks a row in groups of two,
    layer 1, against :func:`_plain`."""
    heads, kv_heads, dk, dv = shape
    _small_groups(monkeypatch, kv_heads, dk)
    case = _case(depths, 8, free=free, dtype=dtype, shape=shape)
    pool, table, idx, q, k_new, v_new = case
    got = paged_decode.paged_decode_attention(
        q, pool["k"], pool["v"], 1, table, idx, k_new, v_new)
    assert got.shape == (ROWS, 1, heads * dv) and got.dtype == dtype
    np.testing.assert_allclose(
        np.asarray(got, np.float32).reshape(ROWS, heads, dv),
        _plain(1, *case), rtol=tol, atol=tol)


@pytest.mark.parametrize("depths, free", [
    pytest.param((127, 3, 64, 17), (), id="rows of very unequal depth"),
    pytest.param((0, 5, 0, 100), (),
                 id="rows at depth 0 and one that ends inside a block"),
    pytest.param((31, 32, 33, 64), (),
                 id="depths that end on and inside a group"),
    pytest.param((40, 37, 100, 90), (1, 3),
                 id="two rows hold the sentinel throughout"),
])
@pytest.mark.parametrize("shape", SHAPES)
def test_grouped_query_heads_over_keys_and_values_of_unequal_width(
        monkeypatch, shape, depths, free):
    """The kernel against a plain softmax a head: each of a K/V head's
    query heads keeps that head's columns of its own row and no other's."""
    _beside_the_plain_softmax(monkeypatch, shape, depths, free=free)


#: value heads of half a lane tile: the row's end takes the diagonal by lane
HALF_TILE = [
    pytest.param((32, 8, 64, 64),
                 id="LFM2's: 32 query heads over 8 K/V heads of 64 on 512"),
    pytest.param((16, 4, 192, 64),
                 id="4 a K/V head, keys of 192 over values of 64 on 256"),
]


@pytest.mark.parametrize("dtype, tol", [
    pytest.param(jnp.float32, 1e-5, id="float32"),
    pytest.param(jnp.bfloat16, 2e-2, id="bfloat16"),
])
@pytest.mark.parametrize("depths", [
    pytest.param((0, 1, 9, 16), id="depth 0, one column, part of a block, "
                                   "exactly a block"),
    pytest.param((33, 64, 100, 127), id="more than one group, part and "
                                        "whole"),
])
@pytest.mark.parametrize("shape", HALF_TILE)
def test_value_heads_under_a_lane_tile_keep_their_own_columns_by_lane(
        monkeypatch, shape, depths, dtype, tol):
    """A value head of 64 on an axis of whole tiles: a column slice a K/V
    head would cut a tile, so each K/V head's query heads' rows are taken
    where the lane is one of that head's. Against the plain softmax a head,
    with the limits the 128-wide cases are held to (stored bfloat16: the
    weights are cast before the product with V, a bfloat16 step)."""
    _beside_the_plain_softmax(monkeypatch, shape, depths, dtype=dtype,
                              tol=tol)


def test_the_grouped_kernel_is_the_gather_and_mimos_merged_attention(
        monkeypatch):
    """At MiMo-V2-Flash's head sizes in bfloat16, beside what its full
    layers ran before: ``layer_rows`` + ``merged_sink_attention`` with no
    sink. Both cast the weights to bfloat16 before the product with V."""
    from sparkdl_tpu.models.mimo_v2_flash import merged_sink_attention

    shape = (32, 2, 192, 128)
    _small_groups(monkeypatch, 2, 192)
    pool, table, idx, q, k_new, v_new = _case(
        (127, 3, 64, 17), 8, dtype=jnp.bfloat16, shape=shape)
    k_old, v_old = layer_rows(pool, 1, table, q.dtype)
    seen = jnp.arange(k_old.shape[1])[None, :] < idx[:, None]
    want = merged_sink_attention(q[:, 0], k_old, v_old, k_new[:, 0],
                                 v_new[:, 0], seen, None, 2)
    got = paged_decode.paged_decode_attention(
        q, pool["k"], pool["v"], 1, table, idx, k_new, v_new)
    np.testing.assert_allclose(np.asarray(got[:, 0], np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-2, atol=2e-2)


def test_more_than_one_query_a_row_raises():
    pool, table, idx, q, k_new, v_new = _case((5, 6, 7, 8), 2)
    wide = jnp.concatenate([q, q], axis=1)
    with pytest.raises(ValueError, match="one query a row"):
        paged_decode.paged_decode_attention(
            wide, pool["k"], pool["v"], 0, table, idx,
            jnp.concatenate([k_new, k_new], axis=1),
            jnp.concatenate([v_new, v_new], axis=1))


def test_a_pool_with_another_dtype_or_shape_raises():
    pool, table, idx, q, k_new, v_new = _case((5, 6, 7, 8), 2)
    with pytest.raises(ValueError, match="no scales"):
        paged_decode.paged_decode_attention(
            q, pool["k"].astype(jnp.int8), pool["v"].astype(jnp.int8), 0,
            table, idx, k_new, v_new)
    # two heads of 96: an axis of 192, a tile and a half (heads of 64 on
    # this pool's axis of 256 are taken since ISSUE 43)
    with pytest.raises(ValueError, match="whole lane tiles"):
        paged_decode.paged_decode_attention(
            q[..., :96], pool["k"][..., :192], pool["v"][..., :192],
            0, table, idx, k_new[..., :192], v_new[..., :192])


@pytest.mark.parametrize("which", ["k", "v"])
def test_k_or_v_of_another_dtype_than_the_querys_raises(which):
    """Unequal dtypes are refused an array at a time: the kernel multiplies
    operands as stored and carries no scales."""
    pool, table, idx, q, k_new, v_new = _case((5, 6, 7, 8), 2)
    pool = dict(pool, **{which: pool[which].astype(jnp.bfloat16)})
    with pytest.raises(ValueError, match="no scales"):
        paged_decode.paged_decode_attention(
            q, pool["k"], pool["v"], 0, table, idx, k_new, v_new)


def test_query_heads_that_no_count_of_kv_heads_divides_raise():
    pool, table, idx, q, k_new, v_new = _case(
        (5, 6, 7, 8), 2, shape=(3, 2, 128, 128))
    with pytest.raises(ValueError, match="under 3 query heads"):
        paged_decode.paged_decode_attention(
            q, pool["k"], pool["v"], 0, table, idx, k_new, v_new)


@pytest.mark.parametrize("heads, head, taken", [
    pytest.param(30, 128, True, id="Olmo-Hybrid: 30 x 128 on one axis"),
    pytest.param(3, 256, True, id="heads of two lane tiles"),
    pytest.param(25, 64, False, id="GPT-2 XL: 25 x 64 padded to 1664"),
    pytest.param(4, 128, False, id="Trinity: a per-head pool (4, 128)"),
    pytest.param(32, 128, False, id="heads that fill sublane tiles"),
    pytest.param(4, 16, False, id="a tiny configuration"),
    pytest.param(8, 64, True,
                 id="LFM2: 8 x 64 on one unpadded axis of 512, whole tiles"),
    pytest.param(2, 64, True, id="2 x 64: an axis of one tile"),
    pytest.param(9, 64, False, id="9 x 64 padded to 640"),
])
def test_the_rule_by_which_a_pool_is_read_in_place(heads, head, taken):
    tail = kv_tail(heads, head)
    assert paged_decode.reads_in_place(tail, tail, heads, head) is taken


@pytest.mark.parametrize("kv_heads, dk, dv, taken", [
    pytest.param(4, 192, 128, True,
                 id="MiMo-V2-Flash's full layers: 768 over 512"),
    pytest.param(8, 192, 128, True,
                 id="MiMo-V2-Flash's window layers' heads: 1536 over 1024"),
    pytest.param(2, 64, 128, True,
                 id="a key head of half a tile on an axis of one"),
    pytest.param(3, 128, 256, True, id="values wider than keys"),
    pytest.param(4, 128, 256, False,
                 id="4 heads of whole tiles each keep their own axis"),
    pytest.param(4, 192, 64, True,
                 id="a value head of 64: V's axis of 256 is whole tiles"),
    pytest.param(2, 192, 32, False,
                 id="V's 2 x 32 padded to an axis of 128"),
    pytest.param(2, 96, 128, False,
                 id="K's axis of 192 is no whole count of lane tiles"),
    pytest.param(2, 24, 16, False, id="the tiny mimo_v2_flash"),
])
def test_the_rule_takes_keys_and_values_of_unequal_width(kv_heads, dk, dv,
                                                         taken):
    """K's and V's tails as ``kv_pool.kv_tails`` lays such a family's pool
    out: each on one axis; each AXIS decides, neither head need be whole
    tiles if its axis is."""
    k_tail, v_tail = kv_tails(kv_heads, dk, dv)
    assert paged_decode.reads_in_place(
        k_tail, v_tail, kv_heads, dk, dv) is taken


@pytest.mark.parametrize("k_tail, v_tail", [
    pytest.param((1664,), (1664,), id="GPT-2 XL's padded axis, 25 x 64"),
    pytest.param((4, 128), (4, 128), id="a per-head tail"),
    pytest.param((768,), (4, 128), id="V alone keeps its heads apart"),
    pytest.param((768,), (640,), id="V's axis padded"),
    pytest.param((640,), (640,), id="8 x 64 padded to 640"),
    pytest.param((48,), (32,), id="2 x 24 over 2 x 16: an axis of 32"),
])
def test_tails_the_rule_refuses_whatever_the_heads(k_tail, v_tail):
    for kv_heads, dk, dv in ((25, 64, 64), (4, 128, 128), (4, 192, 128),
                             (8, 64, 64), (2, 24, 16)):
        assert not paged_decode.reads_in_place(k_tail, v_tail, kv_heads,
                                               dk, dv)


def test_a_group_is_two_megabytes_of_blocks_and_no_more_than_the_table():
    block = 16 * 3840 * 2      # Olmo-Hybrid's: 122,880 bytes
    assert paged_decode._group_blocks(512, block) == 16
    assert paged_decode._group_blocks(8, block) == 8
    assert paged_decode._group_blocks(512, 64 << 20) == 1
    # MiMo-V2-Flash's block of K, the wider array: 24,576 bytes, 64 blocks
    assert paged_decode._group_blocks(512, 16 * 768 * 2) == 64


# -- ONE array that is keys and values both, under a bias (ISSUE 45) ----------------

def _latent_plain(pool, layer, table, depth, q, bias, scale):
    """``(acc, m, l)`` a row and a head in float64 numpy: the row's blocks
    of the ONE array through its table to its depth, each column the key of
    its score and the value of the sum, the row's bias added to every
    head's scores."""
    rows, heads, width = q.shape
    cols, q = np.asarray(pool[layer], np.float64), np.asarray(q, np.float64)
    acc, m, l = (np.zeros((rows, heads, width)),
                 np.full((rows, heads, 1), -1e30), np.zeros((rows, heads, 1)))
    for s in range(rows):
        d = int(depth[s])
        if d == 0:
            continue
        seen = cols[np.asarray(table)[s, :-(-d // BS)]].reshape(-1, width)[:d]
        x = q[s] @ seen.T * scale
        if bias is not None:
            x = x + np.asarray(bias, np.float64)[s, :d]
        m[s, :, 0] = x.max(-1)
        p = np.exp(x - m[s])
        l[s, :, 0], acc[s] = p.sum(-1), p @ seen
    return acc, m, l


@pytest.mark.parametrize("depths, nb, biased, scale", [
    pytest.param((127, 3, 64, 17), 8, False, None,
                 id="no bias, the scale of the stored width"),
    pytest.param((127, 3, 64, 17), 8, True, 0.25,
                 id="a bias a row and the caller's scale"),
    pytest.param((0, 33, 32, 31), 8, True, 0.25,
                 id="a row of depth 0, rows on and past a group's edge"),
    pytest.param((40, 47, 20, 5), 3, True, 0.25,
                 id="a table that is no whole count of groups"),
])
def test_one_array_is_keys_and_values_both_under_a_bias(
        monkeypatch, depths, nb, biased, scale):
    """``paged_decode_partial`` with no V array: four query heads over ONE
    K/V head of 256 that both products read, a bias ``[S, W]`` (0 on two
    columns of three, ``-1e30`` on the third) added to every head's scores
    and the caller's scale, beside the plain softmax's parts."""
    heads, width = 4, 256
    _small_groups(monkeypatch, 1, width)
    rng = np.random.default_rng(1)
    pool = jnp.asarray(rng.standard_normal((LAYERS, BLOCKS, BS, width)),
                       jnp.float32)
    table = np.full((ROWS, nb), BLOCKS - 1, np.int32)
    perm, at = rng.permutation(BLOCKS), 0
    for s, d in enumerate(depths):
        n = -(-d // BS)
        table[s, :n] = perm[at:at + n]
        at += n
    q = jnp.asarray(rng.standard_normal((ROWS, heads, width)), jnp.float32)
    bias = None
    if biased:
        bias = jnp.where(jnp.arange(nb * BS)[None, :] % 3 < 2, 0.0, -1e30
                         ) * jnp.ones((ROWS, 1))
    depth = jnp.asarray(depths, jnp.int32)
    acc, m, l = paged_decode.paged_decode_partial(
        q, pool, None, 1, jnp.asarray(table), depth, bias=bias, scale=scale)
    assert acc.shape == (ROWS, 1, heads, width) and acc.dtype == jnp.float32
    want = _latent_plain(pool, 1, table, depths, q, bias,
                         1 / np.sqrt(width) if scale is None else scale)
    for got, ref in zip((acc[:, 0], m, l), want):
        np.testing.assert_allclose(np.asarray(got), ref, rtol=2e-5,
                                   atol=2e-5)


def test_a_bias_that_leaves_a_whole_group_out_is_undone_by_the_next():
    """No column of the first group (two blocks) is attended: what the
    group left (weights of one under a maximum of ``-1e30``) is multiplied
    by exactly zero when the first attended column comes."""
    heads, width, depths = 4, 128, (100, 64, 33, 40)
    rng = np.random.default_rng(2)
    pool = jnp.asarray(rng.standard_normal((LAYERS, BLOCKS, BS, width)),
                       jnp.float32)
    table = jnp.asarray(rng.permutation(BLOCKS)[:ROWS * 8].reshape(ROWS, 8),
                        jnp.int32)
    q = jnp.asarray(rng.standard_normal((ROWS, heads, width)), jnp.float32)
    bias = jnp.where(jnp.arange(8 * BS)[None, :] >= 2 * BS, 0.0, -1e30
                     ) * jnp.ones((ROWS, 1))
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(paged_decode, "_GROUP_BYTES", GROUP * BS * width * 4)
        got = paged_decode.paged_decode_partial(
            q, pool, None, 0, table, jnp.asarray(depths, jnp.int32),
            bias=bias, scale=0.3)
    want = _latent_plain(pool, 0, table, depths, q, bias, 0.3)
    for a, b in zip((got[0][:, 0], got[1], got[2]), want):
        np.testing.assert_allclose(np.asarray(a), b, rtol=2e-5, atol=2e-5)


def test_one_array_of_another_shape_or_dtype_raises():
    pool = jnp.zeros((LAYERS, BLOCKS, BS, 192), jnp.float32)
    table, depth = jnp.zeros((ROWS, 2), jnp.int32), jnp.ones((ROWS,), jnp.int32)
    with pytest.raises(ValueError, match="whole lane tiles"):
        paged_decode.paged_decode_partial(
            jnp.zeros((ROWS, 4, 192)), pool, None, 0, table, depth)
    with pytest.raises(ValueError, match="no scales"):
        paged_decode.paged_decode_partial(
            jnp.zeros((ROWS, 4, 256), jnp.bfloat16),
            jnp.zeros((LAYERS, BLOCKS, BS, 256), jnp.float32), None, 0,
            table, depth)
