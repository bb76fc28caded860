"""Tensor-parallel layers: sharding metadata + numerical oracle under a
tp mesh (GSPMD inserts the collectives; outputs must equal plain dense)."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from sparkdl_tpu.parallel.tensor_parallel import (
    TPMlpBlock,
    init_sharded,
    param_shardings,
)
from sparkdl_tpu.runtime.mesh import MeshSpec


def test_tp_mlp_matches_plain_mlp():
    mesh = MeshSpec(dp=2, tp=4).build()
    model = TPMlpBlock(hidden_features=32, out_features=16)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((4, 8, 16), np.float32))

    params = init_sharded(model, jax.random.PRNGKey(0), [x], mesh)

    # Kernels landed sharded the Megatron way.
    up = params["params"]["up"]["kernel"]
    down = params["params"]["down"]["kernel"]
    assert up.sharding.spec == P(None, "tp")
    assert down.sharding.spec == P("tp", None)

    with jax.set_mesh(mesh):
        y = jax.jit(lambda p, x: model.apply(p, x))(params, x)

    # Oracle: same params, plain matmul math on one device.
    up_np, down_np = np.asarray(up), np.asarray(down)
    up_b = np.asarray(params["params"]["up"]["bias"])
    down_b = np.asarray(params["params"]["down"]["bias"])
    h = np.asarray(jax.nn.gelu(np.asarray(x) @ up_np + up_b))
    want = h @ down_np + down_b
    np.testing.assert_allclose(np.asarray(y), want, atol=1e-5)


def test_param_shardings_replicates_unboxed():
    mesh = MeshSpec(dp=8).build()
    tree = {"w": jnp.ones((2, 2))}
    sh = param_shardings(tree, mesh)
    assert isinstance(sh["w"], NamedSharding)
    assert sh["w"].spec == P()


def test_tp_grads_flow():
    mesh = MeshSpec(dp=1, tp=8).build()
    model = TPMlpBlock(hidden_features=64, out_features=8)
    x = jnp.ones((2, 4, 8))
    params = init_sharded(model, jax.random.PRNGKey(1), [x], mesh)

    def loss(p):
        return jnp.mean(model.apply(p, x) ** 2)

    with jax.set_mesh(mesh):
        g = jax.jit(jax.grad(loss))(params)
    leaves = jax.tree.leaves(g)
    assert leaves and all(np.all(np.isfinite(np.asarray(l))) for l in leaves)
