"""Dtype policy for TPU execution.

The reference runs TF graphs at whatever dtype the frozen graph was built
with (float32 everywhere; see SURVEY.md 2.15/2.18). On TPU the MXU natively
multiplies bfloat16 with float32 accumulation, so the idiomatic policy is
float32 parameters / bfloat16 compute / float32 outputs. This module is the
single switch for that choice.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class DtypePolicy:
    """Dtype policy applied by models and transformers.

    Attributes:
      param_dtype: dtype parameters are stored in (master copy).
      compute_dtype: dtype activations/matmuls run in.
      output_dtype: dtype returned to the caller (DataFrame columns).
    """

    param_dtype: jnp.dtype = jnp.float32
    compute_dtype: jnp.dtype = jnp.bfloat16
    output_dtype: jnp.dtype = jnp.float32

    def cast_inputs(self, x):
        return jax.tree_util.tree_map(
            lambda a: a.astype(self.compute_dtype)
            if jnp.issubdtype(a.dtype, jnp.floating)
            else a,
            x,
        )

    def cast_outputs(self, x):
        return jax.tree_util.tree_map(
            lambda a: a.astype(self.output_dtype)
            if jnp.issubdtype(a.dtype, jnp.floating)
            else a,
            x,
        )


def default_policy(platform: str | None = None) -> DtypePolicy:
    """bfloat16 compute on TPU, float32 elsewhere (CPU tests stay exact)."""
    if platform is None:
        platform = jax.default_backend()
    if platform == "tpu":
        return DtypePolicy()
    return DtypePolicy(compute_dtype=jnp.float32)


#: Policy that disables mixed precision entirely (used by oracle tests).
FLOAT32 = DtypePolicy(compute_dtype=jnp.float32)
