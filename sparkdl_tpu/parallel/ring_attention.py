"""Ring attention: exact attention over sequences sharded on the ``sp`` axis.

Long-context support the reference never had (SURVEY.md §5 "Long-context:
entirely absent") but that is first-class here: each ``sp`` peer holds one
sequence block of Q/K/V; K/V blocks rotate around the ring via ``ppermute``
while every device folds each visiting block into a numerically-stable
online softmax (flash-attention style running max/denominator). Peak memory
per device is O(L/sp · L/sp) for the score block; communication is sp-1
neighbour hops riding ICI, overlapped by XLA with the block matmuls.

The math is the blockwise-parallel form of

    softmax(Q K^T / sqrt(d)) V

computed as sp partial reductions — results are exact (up to fp) vs. full
attention, which is what the oracle test asserts.
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

_NEG_INF = -1e30  # large-negative instead of -inf: keeps exp()/where() NaN-free


def _block_attend(q, k, v, o, m, l, *, q_offset, k_offset, causal, scale,
                  kv_mask=None):
    """Fold one visiting K/V block into the running (o, m, l) accumulators.

    q: [B, Lq, H, D]   k, v: [B, Lk, H, D]
    o: [B, Lq, H, D] f32 accumulator (un-normalised)
    m: [B, H, Lq] f32 running max,  l: [B, H, Lq] f32 running denominator
    kv_mask: optional [B, Lk] bool — False keys are masked out (padding).
    """
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    if causal:
        lq, lk = q.shape[1], k.shape[1]
        q_pos = q_offset + jnp.arange(lq)
        k_pos = k_offset + jnp.arange(lk)
        mask = q_pos[:, None] >= k_pos[None, :]
        s = jnp.where(mask[None, None], s, _NEG_INF)
    if kv_mask is not None:
        s = jnp.where(kv_mask[:, None, None, :], s, _NEG_INF)

    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    correction = jnp.exp(m - m_new)  # [B, H, Lq]
    p = jnp.exp(s - m_new[..., None])  # [B, H, Lq, Lk]
    l_new = l * correction + jnp.sum(p, axis=-1)
    pv = jnp.einsum(
        "bhqk,bkhd->bqhd", p, v.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    o_new = o * correction.transpose(0, 2, 1)[..., None] + pv
    return o_new, m_new, l_new


def _ring_attention_local(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    kv_mask: jax.Array | None = None,
    *,
    axis_name: str,
    causal: bool,
    scale: float | None,
) -> jax.Array:
    """Per-device body; call inside shard_map with q/k/v local blocks.

    kv_mask: optional [B, Lk_local] bool padding mask for this device's
    keys; it rides the ring alongside its K/V block.
    """
    orig_dtype = q.dtype
    b, lq, h, d = q.shape
    lk = k.shape[1]
    if scale is None:
        scale = 1.0 / (d ** 0.5)

    axis_size = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    o0 = jnp.zeros((b, lq, h, d), jnp.float32)
    m0 = jnp.full((b, h, lq), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, lq), jnp.float32)
    # Accumulators become device-varying inside the loop (they mix in q/k/v,
    # which vary over the mesh axes of the enclosing shard_map); the scan
    # carry type must declare that up front.
    vma = tuple(jax.typeof(q).vma)
    if vma:
        o0, m0, l0 = (
            lax.pcast(t, vma, to="varying") for t in (o0, m0, l0)
        )
    masked = kv_mask is not None

    def step(carry, i):
        o, m, l, k_blk, v_blk, mask_blk = carry
        kv_idx = (my_idx - i) % axis_size  # whose block we hold at hop i
        o, m, l = _block_attend(
            q, k_blk, v_blk, o, m, l,
            q_offset=my_idx * lq, k_offset=kv_idx * lk,
            causal=causal, scale=scale,
            kv_mask=mask_blk if masked else None,
        )
        # Rotate K/V (and the padding mask, when present) to the next peer
        # (skipping the hop after the final fold would be ideal; one extra
        # hop keeps the scan body uniform and XLA overlaps it with the
        # epilogue anyway).
        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        if masked:
            mask_blk = lax.ppermute(mask_blk, axis_name, perm)
        return (o, m, l, k_blk, v_blk, mask_blk), None

    carry0 = (o0, m0, l0, k, v, kv_mask if masked else jnp.zeros((), bool))
    (o, m, l, *_), _ = lax.scan(step, carry0, jnp.arange(axis_size))
    denom = jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]  # [B,Lq,H,1]
    return (o / denom).astype(orig_dtype)


def ring_self_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    kv_mask: jax.Array | None = None,
    *,
    axis_name: str = "sp",
    causal: bool = False,
    scale: float | None = None,
) -> jax.Array:
    """Ring attention on already-local [B, L/sp, H, D] blocks.

    Use this form inside a model that is itself under shard_map/pjit with
    sequence dim sharded on ``axis_name``. ``kv_mask``: [B, L/sp] bool
    padding mask for this device's keys.
    """
    return _ring_attention_local(
        q, k, v, kv_mask, axis_name=axis_name, causal=causal, scale=scale
    )


def allgather_self_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    kv_mask: jax.Array | None = None,
    *,
    axis_name: str = "sp",
    causal: bool = False,
) -> jax.Array:
    """All-gather attention on already-local [B, L/sp, H, D] blocks.

    The small-``sp`` alternative to the ring schedule: gather every
    peer's K/V once (one tiled all-gather riding ICI) and run the dense
    masked softmax for the LOCAL query shard over the FULL key sequence
    — scale by division, mask with the global causal offsets, softmax
    over the whole row at once. Because each query row's math is then
    EXACTLY the single-device full-attention computation (no online
    max/denominator re-association), the result is **bitwise-identical**
    to unsharded attention — the property the serving prefill's parity
    contract rides. Memory is O(L) gathered keys per chip (vs the
    ring's O(L/sp)), which is why the ring stays the long-context /
    large-``sp`` schedule.
    """
    import math

    b, lq, h, d = q.shape
    kg = lax.all_gather(k, axis_name, axis=1, tiled=True)  # [B, L, H, D]
    vg = lax.all_gather(v, axis_name, axis=1, tiled=True)
    my_idx = lax.axis_index(axis_name)
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q, kg, preferred_element_type=jnp.float32,
    ) / math.sqrt(d)
    if causal:
        q_pos = my_idx * lq + jnp.arange(lq)
        k_pos = jnp.arange(kg.shape[1])
        mask = q_pos[:, None] >= k_pos[None, :]
        s = jnp.where(mask[None, None], s, _NEG_INF)
    if kv_mask is not None:
        mg = lax.all_gather(kv_mask, axis_name, axis=1, tiled=True)
        s = jnp.where(mg[:, None, None, :], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, vg)


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    *,
    kv_mask: jax.Array | None = None,
    axis_name: str = "sp",
    causal: bool = False,
    scale: float | None = None,
    batch_axes: Sequence[str] = ("dp", "fsdp"),
) -> jax.Array:
    """Ring attention on global [B, L, H, D] arrays over ``mesh``.

    Shards the sequence dim over ``axis_name`` (and batch over
    ``batch_axes``), runs the ring, returns the global [B, L, H, D] result.
    ``kv_mask``: optional [B, L] bool — False key positions (padding) are
    excluded from attention.
    """
    spec = P(tuple(batch_axes), axis_name, None, None)
    mask_spec = P(tuple(batch_axes), axis_name)
    fn = functools.partial(
        _ring_attention_local, axis_name=axis_name, causal=causal, scale=scale
    )
    if kv_mask is None:
        return jax.shard_map(
            fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec
        )(q, k, v)
    return jax.shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec, mask_spec), out_specs=spec
    )(q, k, v, kv_mask)
