"""``models/kv_pool.py`` alone: how K and V lie in the device pool, with no
engine around it (ISSUE 31). Both trailing shapes (heads of 64 side by side
on one merged axis padded to whole lane tiles; heads of 128 on their own
axis) under the three storage dtypes. What is written is what is read; the
sentinel id (``blocks``, one past the last) clips on a read and drops on a
write; a layer's rows through a table are the pool indexed by hand. CPU,
counts only, and op by op (under ``jit`` the compiler may round a scale's
division another way: the engine's suites hold the compiled programs)."""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from sparkdl_tpu.models import kv_pool
from sparkdl_tpu.models.family import ServingFamily

LAYERS, BLOCKS, BS = 2, 6, 4
#: (kv_heads, head_dim) -> the pool's trailing axes
HEADS = {"merged": (3, 64), "per_head": (2, 128)}
TAILS = {"merged": (256,), "per_head": (2, 128)}


@dataclasses.dataclass(frozen=True)
class _Config:
    """All ``init_block_pool`` asks of a configuration."""

    kv_heads: int
    head_dim: int

    def serving_family(self):
        return ServingFamily(module=None, layers=LAYERS,
                             kv_heads=self.kv_heads, head_dim=self.head_dim,
                             dtype=jnp.float32)


def _pool(tail, dtype):
    pool = kv_pool.init_block_pool(_Config(*HEADS[tail]), BLOCKS, BS, dtype)
    assert pool["k"].shape == (LAYERS, BLOCKS, BS) + TAILS[tail]
    assert set(pool) == ({"k", "v", "k_scale", "v_scale"} if dtype == "int8"
                         else {"k", "v"})
    return pool


def _values(seed, *lead, tail):
    return jnp.asarray(np.random.default_rng(seed).normal(
        size=lead + TAILS[tail]).astype(np.float32))


def _as_stored(x, tail, dtype):
    """What the pool hands back for ``x``, by the storage dtype's own
    rule, computed without the pool."""
    if dtype == "fp32":
        return x
    if dtype == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    return kv_pool.dequantize_kv(
        *kv_pool.quantize_kv(x, len(TAILS[tail])), jnp.float32)


both = pytest.mark.parametrize("tail", sorted(TAILS))
every = pytest.mark.parametrize("dtype", kv_pool.KV_DTYPES)


def test_the_tail_follows_the_head_size():
    for name, heads in HEADS.items():
        assert kv_pool.kv_tail(*heads) == TAILS[name]
    # GPT-2 XL's 25 heads of 64: 1600 values padded to 13 lane tiles
    assert kv_pool.kv_tail(25, 64) == (1664,)


@both
def test_stored_and_per_head_are_inverses(tail):
    heads = HEADS[tail]
    x = jnp.asarray(np.random.default_rng(0).normal(
        size=(3, 5) + heads).astype(np.float32))
    stored = kv_pool.kv_stored(x, TAILS[tail])
    assert stored.shape == (3, 5) + TAILS[tail]
    np.testing.assert_array_equal(kv_pool.kv_per_head(stored, *heads), x)
    # the pad of a merged axis is zeros
    assert float(jnp.abs(stored.reshape(3, 5, -1)[
        ..., heads[0] * heads[1]:]).sum()) == 0.0


@both
@every
def test_blocks_written_are_the_blocks_read(tail, dtype):
    pool = _pool(tail, dtype)
    ids = jnp.asarray([4, 1, 3], jnp.int32)
    k, v = (_values(s, LAYERS, 3, BS, tail=tail) for s in (1, 2))
    pool = kv_pool.write_kv_blocks(pool, ids, k, v)
    gk, gv = kv_pool.gather_blocks_as(pool, ids, jnp.float32)
    np.testing.assert_array_equal(gk, _as_stored(k, tail, dtype))
    np.testing.assert_array_equal(gv, _as_stored(v, tail, dtype))
    # blocks that were not named stay as they were
    rest = kv_pool.gather_blocks(pool, jnp.asarray([0, 2, 5], jnp.int32))
    assert all(float(jnp.abs(a.astype(jnp.float32)).sum()) == 0.0
               for a in rest.values())
    # read back and written again (a copy-on-write prefix, a handoff), the
    # stored bytes are the SAME bytes: int8 requantises exactly
    again = kv_pool.write_kv_blocks(
        pool, jnp.asarray([0, 2, 5], jnp.int32), gk, gv)
    first = kv_pool.gather_blocks(again, ids)
    second = kv_pool.gather_blocks(again, jnp.asarray([0, 2, 5], jnp.int32))
    for name in pool:
        np.testing.assert_array_equal(first[name], second[name])


@both
@every
def test_a_sentinel_id_writes_nothing_and_reads_the_last_block(tail, dtype):
    pool = _pool(tail, dtype)
    k, v = (_values(s, LAYERS, 2, BS, tail=tail) for s in (3, 4))
    pool = kv_pool.write_kv_blocks(
        pool, jnp.asarray([BLOCKS - 1, 0], jnp.int32), k, v)
    before = {n: np.asarray(a) for n, a in pool.items()}
    # a block bound for the sentinel, beside one bound for block 2
    k2, v2 = (_values(s, LAYERS, 2, BS, tail=tail) for s in (5, 6))
    pool = kv_pool.write_kv_blocks(
        pool, jnp.asarray([BLOCKS, 2], jnp.int32), k2, v2)
    for name, a in pool.items():
        a = np.asarray(a)
        np.testing.assert_array_equal(
            np.delete(a, 2, axis=1), np.delete(before[name], 2, axis=1))
    np.testing.assert_array_equal(
        kv_pool.gather_blocks_as(pool, jnp.asarray([2]), jnp.float32)[0],
        _as_stored(k2[:, 1:], tail, dtype))
    # read, the sentinel clips to the last block
    got = kv_pool.gather_blocks(pool, jnp.asarray([BLOCKS, BLOCKS - 1]))
    for name in pool:
        np.testing.assert_array_equal(got[name][:, 0], got[name][:, 1])
        np.testing.assert_array_equal(got[name][:, 0], before[name][:, -1])
    # raw blocks (a resumed park) obey the same rule
    raw = kv_pool.gather_blocks(pool, jnp.asarray([0, 2]))
    moved = kv_pool.write_blocks(
        pool, jnp.asarray([BLOCKS, 4], jnp.int32), raw)
    for name in pool:
        np.testing.assert_array_equal(moved[name][:, 4], pool[name][:, 2])
        np.testing.assert_array_equal(
            np.delete(np.asarray(moved[name]), 4, axis=1),
            np.delete(np.asarray(pool[name]), 4, axis=1))


@both
@every
@pytest.mark.parametrize("index", ["a_tick", "a_verify_span"])
def test_columns_land_at_block_and_offset_and_a_sentinel_drops(
        tail, dtype, index):
    pool = _pool(tail, dtype)
    # 3 rows; row 1's block is the sentinel (an idle slot)
    blk = jnp.asarray([5, BLOCKS, 0], jnp.int32)
    off = jnp.asarray([3, 1, 0], jnp.int32)
    if index == "a_verify_span":
        # two columns a row, the second in the next offset or block
        blk = jnp.stack([blk, jnp.asarray([2, BLOCKS, 0], jnp.int32)], 1)
        off = jnp.stack([off, jnp.asarray([0, 2, 1], jnp.int32)], 1)
    k, v = (_values(s, LAYERS, *blk.shape, tail=tail) for s in (7, 8))
    pool = kv_pool.scatter_columns(pool, blk, off, k, v)
    want_k = np.zeros((LAYERS, BLOCKS, BS) + TAILS[tail], np.float32)
    want_v = np.zeros_like(want_k)
    sk, sv = _as_stored(k, tail, dtype), _as_stored(v, tail, dtype)
    for at in np.ndindex(*blk.shape):
        if int(blk[at]) < BLOCKS:
            want_k[:, int(blk[at]), int(off[at])] = sk[(slice(None),) + at]
            want_v[:, int(blk[at]), int(off[at])] = sv[(slice(None),) + at]
    every_block = jnp.arange(BLOCKS)
    gk, gv = kv_pool.gather_blocks_as(pool, every_block, jnp.float32)
    np.testing.assert_array_equal(gk, want_k)
    np.testing.assert_array_equal(gv, want_v)


@both
@every
@pytest.mark.parametrize("entries", ["the_full_table", "a_windows_sub_table"])
def test_a_layers_rows_are_the_pool_indexed_by_hand(tail, dtype, entries):
    pool = _pool(tail, dtype)
    k, v = (_values(s, LAYERS, BLOCKS, BS, tail=tail) for s in (9, 10))
    pool = kv_pool.write_kv_blocks(pool, jnp.arange(BLOCKS), k, v)
    # 2 rows x 3 entries, a sentinel among them
    table = np.asarray([[3, 0, BLOCKS], [5, 5, 1]], np.int32)
    if entries == "a_windows_sub_table":
        # the entries a window covers, from a start of each row's own
        table = np.take_along_axis(
            table, np.asarray([[1], [0]]) + np.arange(2)[None, :], axis=1)
    for layer in range(LAYERS):
        rk, rv = kv_pool.layer_rows(
            dict(pool, table="not read", idx="not read"), layer,
            jnp.asarray(table), jnp.float32)
        assert rk.shape == (2, table.shape[1] * BS) + TAILS[tail]
        for got, wrote in ((rk, k), (rv, v)):
            stored = np.asarray(_as_stored(wrote, tail, dtype))
            by_hand = np.stack([
                np.concatenate([stored[layer, min(b, BLOCKS - 1)]
                                for b in row]) for row in table])
            np.testing.assert_array_equal(got, by_hand)
