"""The paged one-query attention kernel (``ops/paged_decode.py``, ISSUE 35)
under the Pallas interpreter, beside what it takes the place of on the same
pool, table and ``idx``: ``kv_pool.layer_rows`` + ``merged_axis_attention``.
Small shapes with heads of 128 on one unpadded axis, float32, groups of TWO
blocks (the kernel derives a group from its shapes and a byte count: the
count is made small here, so that a table of eight blocks is four groups)."""

import jax.numpy as jnp
import numpy as np
import pytest

from sparkdl_tpu.models.gpt import merged_axis_attention
from sparkdl_tpu.models.kv_pool import kv_tail, layer_rows
from sparkdl_tpu.ops import paged_decode

ROWS, HEADS, HEAD, BS, BLOCKS, LAYERS = 4, 2, 128, 16, 48, 2
GROUP = 2  # blocks: a group's edge every 32 columns


@pytest.fixture
def small_groups(monkeypatch):
    monkeypatch.setattr(paged_decode, "_GROUP_BYTES",
                        GROUP * BS * HEADS * HEAD * 4)


def _case(depths, nb, free=(), dtype=jnp.float32, seed=0):
    """A pool of random K and V, one query and one new column a row, and a
    table that scatters each live row's blocks over the pool; rows in
    ``free`` hold the sentinel throughout (and whatever ``idx`` says)."""
    rng = np.random.default_rng(seed)

    def normal(*shape):
        return jnp.asarray(rng.standard_normal(shape), dtype)

    pool = {"k": normal(LAYERS, BLOCKS, BS, HEADS * HEAD),
            "v": normal(LAYERS, BLOCKS, BS, HEADS * HEAD)}
    table = np.full((ROWS, nb), BLOCKS, np.int32)
    perm, at = rng.permutation(BLOCKS), 0
    for s, d in enumerate(depths):
        n = 0 if s in free else -(-d // BS)
        table[s, :n] = perm[at:at + n]
        at += n
    return (pool, jnp.asarray(table), jnp.asarray(depths, jnp.int32),
            normal(ROWS, 1, HEADS, HEAD), normal(ROWS, 1, HEADS * HEAD),
            normal(ROWS, 1, HEADS * HEAD))


def _both(layer, pool, table, idx, q, k_new, v_new):
    k_old, v_old = layer_rows(pool, layer, table, q.dtype)
    want = merged_axis_attention(q, k_old, v_old, k_new, v_new, idx)
    got = paged_decode.paged_decode_attention(
        q, pool["k"], pool["v"], layer, table, idx, k_new, v_new)
    return np.asarray(want, np.float32), np.asarray(got, np.float32)


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
    with pytest.raises(ValueError, match="whole lane tiles"):
        paged_decode.paged_decode_attention(
            q.reshape(ROWS, 1, 2 * HEADS, HEAD // 2), pool["k"], pool["v"],
            0, table, idx, k_new, v_new)


@pytest.mark.parametrize("heads, head, taken", [
    pytest.param(30, 128, True, id="Olmo-Hybrid: 30 x 128 on one axis"),
    pytest.param(3, 256, True, id="heads of two lane tiles"),
    pytest.param(25, 64, False, id="GPT-2 XL: 25 x 64 padded to 1664"),
    pytest.param(4, 128, False, id="Trinity: a per-head pool (4, 128)"),
    pytest.param(32, 128, False, id="heads that fill sublane tiles"),
    pytest.param(4, 16, False, id="a tiny configuration"),
])
def test_the_rule_by_which_a_pool_is_read_in_place(heads, head, taken):
    assert paged_decode.reads_in_place(
        kv_tail(heads, head), heads, head) is taken


def test_a_group_is_two_megabytes_of_blocks_and_no_more_than_the_table():
    block = 16 * 3840 * 2      # Olmo-Hybrid's: 122,880 bytes
    assert paged_decode._group_blocks(512, block) == 16
    assert paged_decode._group_blocks(8, block) == 8
    assert paged_decode._group_blocks(512, 64 << 20) == 1
