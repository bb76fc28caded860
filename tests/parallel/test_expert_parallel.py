"""Expert-parallel MoE: routing invariants, ep sharding metadata, and the
sharded-vs-unsharded numerical oracle (GSPMD all-to-all must not change
the math)."""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from sparkdl_tpu.parallel.expert_parallel import (
    MoEMlpBlock,
    moe_aux_losses,
    top_k_dispatch,
)
from sparkdl_tpu.parallel.tensor_parallel import init_sharded
from sparkdl_tpu.runtime.mesh import MeshSpec


def _gates(g=2, s=16, e=4, seed=0):
    rng = np.random.default_rng(seed)
    logits = jnp.asarray(rng.standard_normal((g, s, e)), jnp.float32)
    return jax.nn.softmax(logits, axis=-1)


class TestTopKDispatch:
    def test_every_token_routed_k_times_with_ample_capacity(self):
        gates = _gates()
        k = 2
        combine, dispatch, _ = top_k_dispatch(gates, k=k, capacity=32)
        # Each token occupies exactly k (expert, slot) cells...
        per_token = jnp.sum(dispatch, axis=(2, 3))
        np.testing.assert_array_equal(np.asarray(per_token), k)
        # ...whose combine weights are its top-k gate values.
        top2 = jnp.sort(gates, axis=-1)[..., -k:].sum(-1)
        np.testing.assert_allclose(
            np.asarray(jnp.sum(combine, axis=(2, 3))), np.asarray(top2),
            rtol=1e-6,
        )

    def test_no_capacity_slot_double_booked(self):
        combine, dispatch, _ = top_k_dispatch(_gates(s=64), k=2, capacity=8)
        # Within one expert's capacity slot, at most one token lands.
        per_slot = jnp.sum(dispatch, axis=1)  # [G, E, C]
        assert int(jnp.max(per_slot)) <= 1

    def test_capacity_overflow_drops_tokens(self):
        gates = _gates(s=64)
        combine, dispatch, _ = top_k_dispatch(gates, k=2, capacity=2)
        routed = int(jnp.sum(dispatch))
        assert routed <= 2 * 4 * 2 * 2  # G * E * C * (full slots)
        assert routed > 0
        assert np.all(np.isfinite(np.asarray(combine)))

    def test_k_out_of_range_rejected(self):
        import pytest

        with pytest.raises(ValueError, match="must be in"):
            top_k_dispatch(_gates(e=2), k=3, capacity=8)
        with pytest.raises(ValueError, match="must be in"):
            top_k_dispatch(_gates(e=2), k=0, capacity=8)

    def test_underflowed_gates_not_double_counted(self):
        # Token whose 3rd-choice gate underflowed to exactly 0: argmax of
        # the all-zero remainder points at expert 0 again — it must NOT be
        # re-dispatched there with its full original weight.
        gates = jnp.asarray([[[0.6, 0.4, 0.0, 0.0]]], jnp.float32)
        combine, dispatch, _ = top_k_dispatch(gates, k=3, capacity=4)
        np.testing.assert_allclose(
            float(jnp.sum(combine)), 1.0, rtol=1e-6
        )
        # Expert 0 holds the token exactly once.
        assert int(jnp.sum(dispatch[0, 0, 0])) == 1

    def test_aux_loss_is_one_when_balanced(self):
        g, s, e = 2, 32, 4
        uniform = jnp.full((g, s, e), 1.0 / e)
        _, _, aux = top_k_dispatch(uniform, k=2, capacity=s)
        np.testing.assert_allclose(float(aux), 1.0, rtol=1e-6)

    def test_aux_loss_prefers_balance(self):
        g, s, e = 1, 32, 4
        uniform = jnp.full((g, s, e), 1.0 / e)
        collapsed = jax.nn.softmax(
            jnp.tile(jnp.array([10.0, 0.0, 0.0, 0.0]), (g, s, 1)), axis=-1
        )
        _, _, aux_u = top_k_dispatch(uniform, k=1, capacity=s)
        _, _, aux_c = top_k_dispatch(collapsed, k=1, capacity=s)
        assert float(aux_c) > float(aux_u)


class TestMoEMlpBlock:
    def _build(self, mesh, num_experts=4, k=2, cf=4.0):
        model = MoEMlpBlock(
            num_experts=num_experts, hidden_features=32, k=k,
            capacity_factor=cf,
        )
        rng = np.random.default_rng(1)
        x = jnp.asarray(rng.standard_normal((4, 16, 8)), jnp.float32)
        params = init_sharded(model, jax.random.PRNGKey(0), [x], mesh)
        return model, params, x

    def test_ep_sharding_metadata(self):
        mesh = MeshSpec(dp=2, ep=4).build()
        model, params, x = self._build(mesh)
        wi = params["params"]["wi"]
        wo = params["params"]["wo"]
        assert wi.sharding.spec == P("ep", None, None)
        assert wo.sharding.spec == P("ep", None, None)
        router = params["params"]["router"]["kernel"]
        assert router.sharding.spec == P()

    def test_sharded_matches_single_device_oracle(self):
        mesh = MeshSpec(dp=2, ep=4).build()
        model, params, x = self._build(mesh)
        with jax.set_mesh(mesh):
            data = jax.device_put(x, NamedSharding(mesh, P(("dp", "fsdp"))))
            y_sharded = jax.jit(lambda p, x: model.apply(p, x))(params, data)
        # Oracle: identical params applied on one device, no mesh.
        params_local = jax.tree.map(np.asarray, params)
        y_local = model.apply(
            jax.tree.map(jnp.asarray, params_local), x
        )
        np.testing.assert_allclose(
            np.asarray(y_sharded), np.asarray(y_local), atol=1e-5
        )

    def test_2d_input_and_residual_shape(self):
        mesh = MeshSpec(dp=8).build()
        model = MoEMlpBlock(num_experts=2, hidden_features=16, k=1)
        x = jnp.ones((10, 8))
        params = init_sharded(model, jax.random.PRNGKey(0), [x], mesh)
        with jax.set_mesh(mesh):
            y = jax.jit(lambda p, x: model.apply(p, x))(params, x)
        assert y.shape == x.shape

    def test_grads_and_aux_losses(self):
        mesh = MeshSpec(dp=1, ep=8).build()
        model, params, x = self._build(mesh, num_experts=8, k=2)

        def loss(p):
            y, inters = model.apply(p, x, mutable=["intermediates"])
            aux = moe_aux_losses(inters["intermediates"])
            return (
                jnp.mean(y**2)
                + 0.01 * aux["aux_loss"]
                + 0.001 * aux["router_z_loss"]
            )

        with jax.set_mesh(mesh):
            val, g = jax.jit(jax.value_and_grad(loss))(params)
        assert np.isfinite(float(val))
        leaves = jax.tree.leaves(g)
        assert leaves and all(
            np.all(np.isfinite(np.asarray(l))) for l in leaves
        )
        # Router must receive gradient through the combine weights.
        router_g = g["params"]["router"]["kernel"]
        assert float(jnp.sum(jnp.abs(router_g))) > 0

    def test_dropped_tokens_get_zero_output(self):
        model = MoEMlpBlock(
            num_experts=2, hidden_features=8, k=1, capacity_factor=1e-9
        )
        x = jnp.ones((1, 6, 4))
        params = model.init(jax.random.PRNGKey(0), x)
        # capacity clamps to 1 slot per expert: at most 2 of 6 tokens non-zero.
        from flax.core import meta

        y = model.apply(meta.unbox(params), x)
        nonzero_rows = int(jnp.sum(jnp.any(y[0] != 0, axis=-1)))
        assert nonzero_rows <= 2
