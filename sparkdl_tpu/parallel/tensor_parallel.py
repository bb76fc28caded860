"""Tensor parallelism: Megatron-style column/row-parallel layers, TPU form.

On GPU+NCCL this is hand-written all-reduce calls between matmul halves; on
TPU the idiomatic form is GSPMD: the layers below carry *sharding metadata*
on their kernels (``nn.with_partitioning``) and sharding constraints on
activations, and XLA inserts the ICI collectives during partitioning. The
pairing is the classic one:

- :class:`ColumnParallelDense` — kernel split on the **output** dim
  (``tp``); activations come out tp-sharded, no communication.
- :class:`RowParallelDense` — kernel split on the **input** dim; the
  partial products are summed by an all-reduce XLA places at the output.

``ColumnParallelDense -> gelu -> RowParallelDense`` therefore costs exactly
one psum per MLP block, the Megatron recipe, without a single explicit
collective in the model code.

Use :func:`init_sharded` to initialise a module's params already placed
according to their metadata over a mesh (eval_shape + jit, so the full
params never materialise on one device).
"""

from __future__ import annotations

from typing import Any, Callable, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Dtype = Any


def constrain_dim(x: jax.Array, axis: str, dim: int = -1) -> jax.Array:
    """Constrain one dim of ``x`` to ``axis``; the others stay UNCONSTRAINED
    so GSPMD keeps whatever batch/sequence sharding is in flight. ``dim=-1``
    is the tp feature-dim form; expert_parallel uses ``dim=0`` for the
    leading expert dim. No-op outside a mesh context (single-device tests)
    or under shard_map over the axis (arrays are already per-device blocks);
    a mesh without the axis is a real error and propagates."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return x
    if axis not in mesh.axis_names:
        raise ValueError(
            f"axis {axis!r} not in the active mesh axes {mesh.axis_names}"
        )
    if axis in mesh.manual_axes:
        return x
    parts: list = [P.UNCONSTRAINED] * x.ndim
    parts[dim] = axis
    return lax.with_sharding_constraint(x, P(*parts))


class ColumnParallelDense(nn.Module):
    """Dense with kernel sharded [in, out/tp]; output stays tp-sharded."""

    features: int
    tp_axis: str = "tp"
    use_bias: bool = True
    dtype: Dtype = jnp.float32
    kernel_init: Callable = nn.initializers.lecun_normal()

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        kernel = self.param(
            "kernel",
            nn.with_partitioning(self.kernel_init, (None, self.tp_axis)),
            (x.shape[-1], self.features),
            self.dtype,
        )
        y = jnp.dot(x.astype(self.dtype), kernel)
        if self.use_bias:
            bias = self.param(
                "bias",
                nn.with_partitioning(nn.initializers.zeros_init(), (self.tp_axis,)),
                (self.features,),
                self.dtype,
            )
            y = y + bias
        return constrain_dim(y, self.tp_axis)


class RowParallelDense(nn.Module):
    """Dense with kernel sharded [in/tp, out]; XLA all-reduces the output."""

    features: int
    tp_axis: str = "tp"
    use_bias: bool = True
    dtype: Dtype = jnp.float32
    kernel_init: Callable = nn.initializers.lecun_normal()

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        kernel = self.param(
            "kernel",
            nn.with_partitioning(self.kernel_init, (self.tp_axis, None)),
            (x.shape[-1], self.features),
            self.dtype,
        )
        y = jnp.dot(x.astype(self.dtype), kernel)
        if self.use_bias:
            # Bias is replicated; added once, after the implicit reduce.
            bias = self.param(
                "bias",
                nn.with_partitioning(nn.initializers.zeros_init(), (None,)),
                (self.features,),
                self.dtype,
            )
            y = y + bias
        return y


class TPMlpBlock(nn.Module):
    """Column-parallel up-projection -> activation -> row-parallel down.

    One ICI all-reduce per block (the Megatron MLP shape)."""

    hidden_features: int
    out_features: int
    tp_axis: str = "tp"
    activation: Callable = nn.gelu
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        h = ColumnParallelDense(
            self.hidden_features, tp_axis=self.tp_axis, dtype=self.dtype,
            name="up",
        )(x)
        h = self.activation(h)
        return RowParallelDense(
            self.out_features, tp_axis=self.tp_axis, dtype=self.dtype,
            name="down",
        )(h)


def param_shardings(params: Any, mesh: Mesh) -> Any:
    """Pytree of NamedShardings from the boxed partitioning metadata."""
    def _one(leaf):
        if isinstance(leaf, nn.Partitioned):
            return NamedSharding(mesh, leaf.get_partition_spec())
        return NamedSharding(mesh, P())

    return jax.tree.map(
        _one, params, is_leaf=lambda x: isinstance(x, nn.Partitioned)
    )


def init_sharded(
    module: nn.Module,
    rng: jax.Array,
    sample_inputs: Sequence[jax.Array],
    mesh: Mesh,
) -> Any:
    """Initialise params directly into their annotated shardings.

    eval_shape first, then a jitted init with out_shardings — so no device
    ever holds the unsharded model (how a >HBM model must be initialised).
    Returns the *unboxed* param pytree, placed on the mesh.
    """
    abstract = jax.eval_shape(module.init, rng, *sample_inputs)
    shardings = param_shardings(abstract, mesh)

    def _init(r):
        variables = module.init(r, *sample_inputs)
        return nn.meta.unbox(variables)

    with jax.set_mesh(mesh):
        return jax.jit(_init, out_shardings=shardings)(rng)
