"""Pipeline parallelism over the ``pp`` mesh axis.

GPipe-style schedule in SPMD form: every ``pp`` peer holds one stage's
params; activations hop stage-to-stage via ``ppermute`` while microbatches
stream in, so after the pp-1-step fill the pipe computes all stages
concurrently. The whole schedule is one ``lax.scan`` — no Python-level
round trips, fully differentiable, and XLA overlaps the neighbour permute
with the stage compute.

The reference has nothing like this (SURVEY.md 2.11: no PP anywhere); it
exists here because a framework claiming model-scale training on TPU pods
needs stages that exceed one chip's HBM.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P


def stack_stage_params(per_stage_params: list[Any]) -> Any:
    """Stack per-stage param pytrees along a new leading (pp) dim."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *per_stage_params)


def _pipeline_local(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    params: Any,
    x_mb: jax.Array,
    *,
    axis_name: str,
) -> jax.Array:
    """Per-device schedule. x_mb: [num_mb, mb, ...] replicated on all peers;
    params: this stage's pytree (leading pp dim already squeezed)."""
    pp = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    is_first = my_idx == 0
    is_last = my_idx == pp - 1
    num_mb = x_mb.shape[0]
    total_steps = num_mb + pp - 1
    perm = [(i, (i + 1) % pp) for i in range(pp)]

    # Output microbatch shape = stage_fn output shape (probe without FLOPs).
    # Contract: every stage maps activations to the SAME shape/dtype, so the
    # inter-stage buffer and the injected input share it.
    out_shape = jax.eval_shape(stage_fn, params, x_mb[0])
    out_buf = jnp.zeros((num_mb,) + out_shape.shape, out_shape.dtype)

    def step(carry, t):
        recv, out_buf = carry
        # Stage 0 injects microbatch t (zeros once the pipe is draining);
        # later stages consume what the previous stage sent last step.
        feed_idx = jnp.clip(t, 0, num_mb - 1)
        my_in = jnp.where(is_first, x_mb[feed_idx], recv)
        y = stage_fn(params, my_in)
        # Last stage commits finished microbatch t-(pp-1).
        out_idx = jnp.clip(t - (pp - 1), 0, num_mb - 1)
        valid = is_last & (t >= pp - 1) & (t - (pp - 1) < num_mb)
        committed = jnp.where(valid, y, out_buf[out_idx])
        out_buf = out_buf.at[out_idx].set(committed)
        # Hand activations to the next stage (the last->first wrap lands on
        # stage 0, which ignores it — it always injects fresh input).
        recv = lax.ppermute(y, axis_name, perm)
        return (recv, out_buf), None

    # The loop body makes the carries device-varying (ppermute / axis_index
    # selects); mark the initial values as such for the VMA type system.
    # Older jax has no VMA typing (lax.pcast) and needs no declaration.
    recv0 = jnp.zeros(out_shape.shape, out_shape.dtype)
    if hasattr(lax, "pcast"):
        recv0 = lax.pcast(recv0, (axis_name,), to="varying")
        out_buf = lax.pcast(out_buf, (axis_name,), to="varying")
    (_, out_buf), _ = lax.scan(step, (recv0, out_buf), jnp.arange(total_steps))
    # Only the last stage holds real outputs; broadcast over the ring.
    out_buf = jnp.where(is_last, out_buf, jnp.zeros_like(out_buf))
    return lax.psum(out_buf, axis_name)


def pipeline_apply(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stacked_params: Any,
    x: jax.Array,
    mesh: Mesh,
    *,
    num_microbatches: int,
    axis_name: str = "pp",
) -> jax.Array:
    """Run ``x`` through ``pp`` chained stages of ``stage_fn``.

    ``stacked_params``: per-stage pytrees stacked on dim 0 (length = pp axis
    size, see :func:`stack_stage_params`); each stage must map activations
    to activations of the same shape (the usual transformer-block contract).
    ``x``: [B, ...] with B divisible by ``num_microbatches``.
    """
    if x.shape[0] % num_microbatches:
        raise ValueError(
            f"batch {x.shape[0]} not divisible by microbatches {num_microbatches}"
        )
    x_mb = x.reshape((num_microbatches, x.shape[0] // num_microbatches) + x.shape[1:])

    def local(params, x_mb):
        params = jax.tree.map(lambda p: jnp.squeeze(p, 0), params)
        return _pipeline_local(stage_fn, params, x_mb, axis_name=axis_name)

    out_mb = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(axis_name), P()),
        out_specs=P(),
    )(stacked_params, x_mb)
    return out_mb.reshape((x.shape[0],) + out_mb.shape[2:])
