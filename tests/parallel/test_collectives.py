"""Collective helpers on the 8-device CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from sparkdl_tpu.parallel.collectives import (
    all_gather_params,
    cross_replica_mean,
    global_norm,
    psum_grads,
    reduce_scatter_grads,
)
from sparkdl_tpu.runtime.mesh import MeshSpec


def test_cross_replica_mean_is_horovod_allreduce():
    mesh = MeshSpec(dp=8).build()
    x = jnp.arange(8.0).reshape(8, 1)  # one value per dp peer

    out = jax.shard_map(
        lambda t: cross_replica_mean({"g": t}, "dp")["g"],
        mesh=mesh, in_specs=P("dp"), out_specs=P("dp"),
    )(x)
    np.testing.assert_allclose(np.asarray(out), np.full((8, 1), 3.5))


def test_reduce_scatter_then_all_gather_roundtrip():
    mesh = MeshSpec(dp=1, fsdp=8).build()
    g = jnp.asarray(np.random.default_rng(0).standard_normal((16, 4), np.float32))

    def body(g_local):
        # every peer holds the same replica of g; rs sums 8 copies
        shard = reduce_scatter_grads({"w": g_local}, "fsdp")["w"]
        full = all_gather_params({"w": shard}, "fsdp")["w"]
        return full

    # all_gather output is value-replicated but VMA-inferred as varying;
    # check_vma=False is the documented escape hatch.
    out = jax.shard_map(
        body, mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False
    )(g)
    np.testing.assert_allclose(np.asarray(out), np.asarray(g) * 8, rtol=1e-6)


def test_rs_ag_roundtrip_preserves_non_divisible_leaves():
    """A bias of shape (3,) on an fsdp=8 axis must come back shape (3,),
    not 8 stacked copies (full_shapes tells the gather what was sharded)."""
    mesh = MeshSpec(dp=1, fsdp=8).build()
    tree = {
        "w": jnp.asarray(np.random.default_rng(1).standard_normal((16, 4), np.float32)),
        "b": jnp.arange(3.0),
    }
    full_shapes = jax.eval_shape(lambda t: t, tree)

    def body(t):
        shard = reduce_scatter_grads(t, "fsdp")
        return all_gather_params(shard, "fsdp", full_shapes=full_shapes)

    out = jax.shard_map(
        body, mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False
    )(tree)
    assert out["b"].shape == (3,)
    assert out["w"].shape == (16, 4)
    np.testing.assert_allclose(np.asarray(out["b"]), np.arange(3.0) * 8)


def test_psum_and_global_norm():
    mesh = MeshSpec(dp=8).build()
    x = jnp.ones((8, 3))

    def body(t):
        s = psum_grads({"g": t}, "dp")["g"]
        n = global_norm({"g": t}, "dp")
        return s, jnp.broadcast_to(n, (1,))

    s, n = jax.shard_map(
        body, mesh=mesh, in_specs=P("dp"), out_specs=(P("dp"), P("dp")),
    )(x)
    np.testing.assert_allclose(np.asarray(s), np.full((8, 3), 8.0))
    # 24 ones -> sqrt(24)
    np.testing.assert_allclose(np.asarray(n), np.full(8, np.sqrt(24.0)), rtol=1e-6)
