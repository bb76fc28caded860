"""``models/kv_pool.py`` over a pool whose two block arrays the family names
itself (``ServingFamily.block_arrays``), each over its OWN layers and with
its own trailing axes: made, gathered, written by block and by column, read
through a table; and the pools of the families that name none are what they
were."""

import jax.numpy as jnp
import numpy as np
import pytest

from sparkdl_tpu.models import kv_pool
from sparkdl_tpu.models.afmoe import AfmoeConfig
from sparkdl_tpu.models.glm_moe_dsa import GlmMoeDsaConfig
from sparkdl_tpu.models.gpt import GPTConfig
from sparkdl_tpu.models.mimo_v2_flash import MimoV2FlashConfig
from sparkdl_tpu.serving.kv_blocks import kv_bytes_per_token

BLOCKS, BS = 6, 4
NAMES = ("latent", "index_k")


@pytest.fixture()
def pool():
    cfg = GlmMoeDsaConfig.tiny()
    pool = kv_pool.init_block_pool(cfg, BLOCKS, BS)
    rng = np.random.default_rng(0)
    return cfg, {name: jnp.asarray(rng.normal(size=a.shape), a.dtype)
                 for name, a in pool.items()}


def test_the_pool_is_the_two_arrays_the_family_names(pool):
    cfg, p = pool
    assert {k: v.shape for k, v in p.items()} == {
        "latent": (5, BLOCKS, BS, 128), "index_k": (2, BLOCKS, BS, 16)}
    assert tuple(n for n, _, _ in cfg.serving_family().pool_arrays) == NAMES
    assert kv_pool.n_blocks(p, NAMES) == BLOCKS
    assert set(kv_pool.block_arrays(p, NAMES)) == set(NAMES)
    assert kv_pool.slot_arrays(p, NAMES) == {}
    assert kv_bytes_per_token(cfg) == (5 * 128 + 2 * 16) * 4
    assert kv_bytes_per_token(cfg, "bf16") == (5 * 128 + 2 * 16) * 2
    assert kv_bytes_per_token(cfg, "int8") == 5 * (128 + 4) + 2 * (16 + 4)


@pytest.mark.parametrize("cfg, names, per_token", [
    (GPTConfig.tiny(), ("k", "v"), None),
    (AfmoeConfig.tiny(), ("k", "v"), 5 * 2 * 128 * 4),
    (MimoV2FlashConfig.tiny(), ("k", "v"), 2 * (128 + 128) * 4),
])
def test_a_family_that_names_none_keeps_k_and_v(cfg, names, per_token):
    p = kv_pool.init_block_pool(cfg, BLOCKS, BS, n_slots=2)
    fam = cfg.serving_family()
    assert names == kv_pool.KV
    assert set(kv_pool.block_arrays(p)) == set(names)
    assert set(kv_pool.slot_arrays(p)) == set(p) - set(names) == {
        name for name, _, _ in fam.state_arrays}
    assert fam.block_arrays == ()
    assert [n for n, _, _ in fam.pool_arrays] == ["k", "v"]
    assert all(layers == fam.pool_layers for _, layers, _ in fam.pool_arrays)
    assert p["k"].shape == (fam.pool_layers, BLOCKS, BS) + fam.kv_tail
    assert p["v"].shape == (fam.pool_layers, BLOCKS, BS) + fam.v_tail
    if per_token is not None:
        assert kv_bytes_per_token(cfg) == per_token
    int8 = kv_pool.init_block_pool(cfg, BLOCKS, BS, "int8", n_slots=2) \
        if not fam.paged_only else None
    if int8 is not None:
        assert {"k_scale", "v_scale"} <= set(int8)
        assert set(kv_pool.block_arrays(int8)) == {
            "k", "v", "k_scale", "v_scale"}


def test_the_names_are_the_familys_alone():
    """A family that names a pair this module has never heard of gets its
    pool, and every function tells it from an array by slot by the names it
    is GIVEN: ``kv_pool`` keeps no list of any family's arrays."""
    import dataclasses

    cfg = GlmMoeDsaConfig.tiny()
    names = ("columns_a", "keys_b")
    fam = dataclasses.replace(
        cfg.serving_family(), block_arrays=((names[0], 3, (8,)),
                                            (names[1], 1, (4,))),
        state_layers=2, state_arrays=(("memory", (5,), jnp.float32),))

    class Named:
        def serving_family(self):
            return fam

    p = kv_pool.init_block_pool(Named(), BLOCKS, BS, "int8", n_slots=2)
    assert {k: v.shape for k, v in p.items()} == {
        "columns_a": (3, BLOCKS, BS, 8), "columns_a_scale": (3, BLOCKS, BS),
        "keys_b": (1, BLOCKS, BS, 4), "keys_b_scale": (1, BLOCKS, BS),
        "memory": (2, 2, 5)}
    assert set(kv_pool.slot_arrays(p, names)) == {"memory"}
    assert kv_pool.n_blocks(p, names) == BLOCKS
    out = kv_pool.scatter_columns(
        p, jnp.asarray([2, BLOCKS]), jnp.asarray([1, 0]),
        jnp.full((3, 2, 8), 5.0), jnp.full((1, 2, 4), 3.0), names=names)
    first, second = kv_pool.gather_blocks_as(
        out, jnp.asarray([2]), jnp.float32, names)
    np.testing.assert_allclose(np.asarray(first[:, 0, 1]), 5.0, rtol=1e-2)
    np.testing.assert_allclose(np.asarray(second[:, 0, 1]), 3.0, rtol=1e-2)
    assert float(jnp.abs(first[:, 0, 0]).max()) == 0.0
    assert out["memory"] is p["memory"]


def test_blocks_are_gathered_and_written_over_each_arrays_own_layers(pool):
    _, p = pool
    ids = jnp.asarray([4, 1, BLOCKS])            # the sentinel clips
    got = kv_pool.gather_blocks(p, ids, NAMES)
    assert got["latent"].shape == (5, 3, BS, 128)
    assert got["index_k"].shape == (2, 3, BS, 16)
    for name in p:
        np.testing.assert_array_equal(np.asarray(got[name][:, 0]),
                                      np.asarray(p[name][:, 4]))
        np.testing.assert_array_equal(np.asarray(got[name][:, 2]),
                                      np.asarray(p[name][:, BLOCKS - 1]))
    first, second = kv_pool.gather_blocks_as(p, ids, jnp.float32, NAMES)
    assert first.shape[0] == 5 and second.shape[0] == 2
    new = {"latent": jnp.ones((5, 2, BS, 128)),
           "index_k": 2 * jnp.ones((2, 2, BS, 16))}
    out = kv_pool.write_blocks(p, jnp.asarray([2, BLOCKS]), new)
    assert float(out["latent"][:, 2].min()) == 1.0
    assert float(out["index_k"][:, 2].max()) == 2.0
    for name in p:                                # the sentinel wrote nothing
        keep = [0, 1, 3, 4, 5]
        np.testing.assert_array_equal(np.asarray(out[name][:, keep]),
                                      np.asarray(p[name][:, keep]))
    again = kv_pool.write_kv_blocks(p, jnp.asarray([0, 3]),
                                    new["latent"], new["index_k"], NAMES)
    assert float(again["latent"][:, 3].min()) == 1.0
    assert float(again["index_k"][:, 0].min()) == 2.0


def test_columns_are_scattered_over_each_arrays_own_layers(pool):
    _, p = pool
    blk, off = jnp.asarray([3, BLOCKS, 0]), jnp.asarray([1, 2, 3])
    lat = jnp.full((5, 3, 128), 7.0)
    key = jnp.full((2, 3, 16), 9.0)
    out = kv_pool.scatter_columns(p, blk, off, lat, key, names=NAMES)
    assert float(out["latent"][:, 3, 1].min()) == 7.0
    assert float(out["index_k"][:, 0, 3].min()) == 9.0
    untouched = np.ones((BLOCKS, BS), bool)
    untouched[3, 1] = untouched[0, 3] = False
    for name in p:                                # row 1's sentinel dropped
        np.testing.assert_array_equal(
            np.asarray(out[name])[:, untouched],
            np.asarray(p[name])[:, untouched])


def test_a_layers_rows_come_through_the_table_by_array_name(pool):
    _, p = pool
    table = jnp.asarray([[2, 5, BLOCKS], [0, BLOCKS, BLOCKS]])
    rows, = kv_pool.layer_rows(p, 1, table, jnp.float32, names=("index_k",))
    assert rows.shape == (2, 3 * BS, 16)
    np.testing.assert_array_equal(np.asarray(rows[0, BS:2 * BS]),
                                  np.asarray(p["index_k"][1, 5]))
    lat, = kv_pool.layer_rows(p, 4, table, jnp.float32, names=("latent",))
    assert lat.shape == (2, 3 * BS, 128)
    np.testing.assert_array_equal(np.asarray(lat[1, :BS]),
                                  np.asarray(p["latent"][4, 0]))
