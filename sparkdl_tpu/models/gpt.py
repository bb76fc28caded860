"""Decoder-only (GPT-style) language-model family, TPU-first.

The reference has no decoder models (its zoo is ImageNet CNNs; SURVEY.md
2.1) — this family exists because a complete TPU framework must cover the
dominant modern model shape. Design:

- **RoPE** rotary positions (no position table, length-extrapolating,
  TPU-friendly elementwise math that XLA fuses into the projections).
- **Causal attention** with the same impl dispatch as BERT: ``full``
  (masked softmax), ``flash`` (fused Pallas kernel, scores never hit HBM),
  ``ring`` (exact sequence-parallel attention over the ``sp`` axis for
  long context).
- **Tensor parallel by construction**: qkv/out and MLP kernels carry
  Megatron-style sharding metadata (``parallel.tensor_parallel``).
- **Optional MoE MLP** (``num_experts > 0``): every ``moe_every``-th block
  swaps its dense MLP for ``parallel.expert_parallel.MoEMlpBlock`` —
  dp x tp x ep compose in one model.
- **KV-cache generation**: an explicit functional cache (a pytree passed
  in and returned), so prefill + single-token decode jit cleanly and
  :func:`generate` is one ``lax.scan`` with no Python-level round trips.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from sparkdl_tpu.models.family import ServingFamily
from sparkdl_tpu.models.kv_pool import kv_per_head, kv_stored, layer_rows
from sparkdl_tpu.parallel.expert_parallel import MoEMlpBlock
from sparkdl_tpu.parallel.ring_attention import ring_self_attention
from sparkdl_tpu.parallel.tensor_parallel import (
    ColumnParallelDense,
    RowParallelDense,
)

_NEG_INF = -1e30


@dataclasses.dataclass(frozen=True)
class GPTConfig:
    vocab_size: int = 50257
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate_size: int = 3072
    max_seq_len: int = 1024
    #: "rope" (default) or "learned" (GPT-2-style position table — required
    #: for HF GPT-2 weight fidelity, see :func:`load_hf_gpt2`)
    positions: str = "rope"
    rope_base: float = 10000.0
    layer_norm_eps: float = 1e-5
    dropout: float = 0.0
    #: "full" | "flash" (Pallas fused kernels) | "ring" (sp-sharded).
    #: "flash" covers the uncached forward (ops/flash_attention) AND
    #: cached prefill with a concrete idx (flash over the written prefix
    #: with a static causal q-offset — O(idx+L) keys, not O(max_len);
    #: 7.6x vs dense-over-buffer on chip). Only a traced-idx prefill
    #: (jitted streaming callers) falls back to the dense masked path.
    attn_impl: str = "full"
    #: opt-in ops/flash_decode kernel for the single-token cached step.
    #: Default OFF: chip-measured 0.24x of the dense path at serving
    #: shape (batch 64, L=4096, bench_attention.py round 5) — XLA's
    #: dense decode runs at the HBM roofline while the kernel's
    #: half-lane-tile D=64 blocks and per-(b,h) programs read the cache
    #: inefficiently. The kernel stays correct (oracle + ragged start
    #: masking) for shapes where streaming wins.
    flash_decode: bool = False
    sp_axis: str = "sp"
    #: collective schedule for ``attn_impl='ring'``: "ring" rotates K/V
    #: shards via ppermute with an online softmax (O(L/sp) resident
    #: keys, exact up to fp accumulation order); "allgather" gathers the
    #: K/V shards once and runs the dense masked softmax per query shard
    #: — BITWISE-identical to the single-device full path, the right
    #: choice at small sp where the gathered keys fit (serving uses it
    #: for the sp∈{1,2} prefill parity contract).
    sp_mode: str = "ring"
    #: 0 = dense MLPs; >0 = MoE with this many experts
    num_experts: int = 0
    moe_every: int = 2  #: every Nth block is MoE (when num_experts > 0)
    moe_k: int = 2
    moe_capacity_factor: float = 2.0
    dtype: Any = jnp.float32

    def __post_init__(self):
        # Loud at construction: a typo'd sp_mode would otherwise fall
        # through to the ring schedule and silently trade away the
        # allgather path's bitwise-parity guarantee.
        if self.sp_mode not in ("ring", "allgather"):
            raise ValueError(
                f"unknown sp_mode {self.sp_mode!r}: expected 'ring' or "
                "'allgather'"
            )

    @classmethod
    def tiny(cls, **kw) -> "GPTConfig":
        """Test-sized config (oracle/unit tests)."""
        defaults = dict(
            vocab_size=128, hidden_size=32, num_layers=2, num_heads=2,
            intermediate_size=64, max_seq_len=64, dropout=0.0,
        )
        defaults.update(kw)
        return cls(**defaults)

    def serving_family(self) -> ServingFamily:
        """What ``ContinuousGPTEngine`` reads of this family
        (``models/family.py``): as many K/V heads as query heads, every
        layer full attention, no experts it counts."""
        return ServingFamily(
            module=GPTLMHeadModel(self), layers=self.num_layers,
            kv_heads=self.num_heads,
            head_dim=self.hidden_size // self.num_heads, dtype=self.dtype,
            max_positions=(self.max_seq_len if self.positions == "learned"
                           else None))


def apply_rope(x: jax.Array, positions: jax.Array,
               base: float = 10000.0) -> jax.Array:
    """Rotary position embedding. x: [B, L, H, D]; positions: [B, L]."""
    half = x.shape[-1] // 2
    freqs = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[:, :, None].astype(jnp.float32) * freqs  # [B, L, half]
    cos = jnp.cos(ang)[:, :, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[:, :, None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def init_cache(config: GPTConfig, batch: int, max_len: int,
               per_slot: bool = False) -> dict:
    """Zeroed KV cache for :func:`generate` / incremental decode.

    Layout: k/v stacked over layers, [num_layers, B, max_len, H, D];
    ``idx`` is the number of positions already written — a scalar for the
    lockstep :func:`generate` path, or (``per_slot=True``) a per-row [B]
    vector where every batch row (slot) decodes at its own depth: the
    dense twin of the paged cache ``serving.continuous`` decodes through
    (tests/models/test_gpt_continuous.py). Per-slot steps
    write this call's L tokens at columns ``[idx[b], idx[b]+L)`` of each
    row — L=1 is the classic decode step, L=k is the speculative verify
    pass that scores a whole draft span in one dispatch. Prefill a
    joining row in its own scalar-idx cache and scatter it in.
    """
    hd = config.hidden_size // config.num_heads
    shape = (config.num_layers, batch, max_len, config.num_heads, hd)
    return {
        "k": jnp.zeros(shape, config.dtype),
        "v": jnp.zeros(shape, config.dtype),
        "idx": jnp.zeros((batch,) if per_slot else (), jnp.int32),
    }


def merged_axis_attention(q, k_old, v_old, k_new, v_new, idx,
                          kv_mask=None):
    """Attention of a FEW queries a row (one decode token, a verify span)
    over K and V that keep their heads side by side on one axis, every row
    at its own depth: the per-slot cached step of :class:`GPTAttention`.

    ``q`` ``[S, L, H, D]``; ``k_old``/``v_old`` ``[S, W, C]``, the rows as
    the cache holds them (``C >= H*D``, columns past ``H*D`` zero: the
    pool's pad), of which row ``s`` sees the columns before ``idx[s]`` (and
    inside ``kv_mask`` ``[S, W]``, where given); ``k_new``/``v_new``
    ``[S, L, C]``, this call's own columns, of which query ``l`` sees
    ``0..l``. Returns ``[S, L, H, D]``.

    K is never reshaped to heads (on the chip ``[.., 25, 64]`` is a padded
    copy of every row): the scores are ONE product over the merged axis
    against the queries laid block-diagonally, ``Qbd[s, (l, h), h*D + d] =
    q[s, l, h, d]`` and zero elsewhere, so the columns of other heads add
    exact zeros; the weighted sum is ``p[s, (l, h), :] @ V[s]`` ->
    ``[L*H, C]``, of which head ``h`` keeps its own ``D`` columns. That is
    ``H`` times the useful products, on a step that is bound by bytes. The
    new columns are not written into the gathered rows (a copy of every
    row): their scores and values join the softmax and the sum beside the
    old ones. Same precision as the einsum form: operands as given,
    float32 scores and softmax, ``p`` cast to the operands' dtype before
    the second product.
    """
    s, l, h, d = q.shape
    c = k_old.shape[-1]
    # own[h, c]: column c of the merged axis is head h's
    own = jnp.arange(c)[None, :] // d == jnp.arange(h)[:, None]
    qbd = jnp.where(own, kv_stored(q, (c,))[:, :, None], 0).reshape(
        s, l * h, c)

    def scores(k, mask):
        x = jnp.einsum("snc,swc->snw", qbd, k,
                       preferred_element_type=jnp.float32) / math.sqrt(d)
        return jnp.where(mask, x.reshape(s, l, h, -1), _NEG_INF)

    seen = jnp.arange(k_old.shape[1])[None, :] < idx[:, None]
    if kv_mask is not None:
        seen = seen & kv_mask
    s_old = scores(k_old, seen[:, None, None, :])
    s_new = scores(
        k_new, (jnp.arange(l)[None, :] <= jnp.arange(l)[:, None])[
            None, :, None, :])
    top = jnp.maximum(s_old.max(-1), s_new.max(-1))[..., None]
    e_old, e_new = jnp.exp(s_old - top), jnp.exp(s_new - top)
    total = e_old.sum(-1, keepdims=True) + e_new.sum(-1, keepdims=True)

    def weighted(e, v):
        p = (e / total).astype(q.dtype).reshape(s, l * h, -1)
        return jnp.einsum("snw,swc->snc", p, v,
                          preferred_element_type=jnp.float32)

    r = (weighted(e_old, v_old) + weighted(e_new, v_new)).reshape(s, l, h, c)
    out = jnp.where(own, r, 0).sum(2)[..., :h * d]
    return out.astype(q.dtype).reshape(s, l, h, d)


class GPTAttention(nn.Module):
    config: GPTConfig
    layer_idx: int

    @nn.compact
    def __call__(self, x, *, cache: Optional[dict], train: bool,
                 positions: Optional[jax.Array] = None,
                 attention_mask: Optional[jax.Array] = None,
                 return_kv: bool = False):
        c = self.config
        h, nh = c.hidden_size, c.num_heads
        hd = h // nh
        b, l = x.shape[0], x.shape[1]

        q = ColumnParallelDense(h, dtype=c.dtype, name="q_proj")(x)
        k = ColumnParallelDense(h, dtype=c.dtype, name="k_proj")(x)
        v = ColumnParallelDense(h, dtype=c.dtype, name="v_proj")(x)
        q, k, v = (t.reshape(b, l, nh, hd) for t in (q, k, v))

        idx = cache["idx"] if cache is not None else jnp.zeros((), jnp.int32)
        #: per-slot cache: idx is [B] — every row decodes at its own depth
        #: (continuous batching); scalar idx is the lockstep generate path
        per_slot = jnp.ndim(idx) == 1
        if c.positions == "rope":
            if positions is None:
                # [1|B, L] -> broadcast: scalar idx rows share positions,
                # per-slot rows each count from their own depth
                positions = jnp.reshape(idx, (-1, 1)) + jnp.arange(l)[None, :]
                positions = jnp.broadcast_to(positions, (b, l))
            q = apply_rope(q, positions, c.rope_base)
            k = apply_rope(k, positions, c.rope_base)

        if cache is not None and per_slot:
            # The per-slot step (continuous batching): every row at its own
            # depth, few queries a row (L=1 is the classic decode step; L=k
            # the speculative verify span and a chained step). ONE attention
            # for both layouts (merged_axis_attention), over K and V with
            # the heads side by side on one axis, so a paged cache and a
            # dense one compare like with like: a paged cache's rows
            # come through the block table as the pool stores them
            # (kv_pool.layer_rows: never a dense all-layer view of the
            # pool, never a reshape to heads; a pool whose heads fill
            # whole lane tiles keeps ``[.., H, D]`` and its rows are merged
            # here, a reshape that moves nothing); a dense cache's are its
            # own buffer, heads merged. This call's columns join the
            # softmax beside them, they are not written into the rows
            # first.
            paged = "table" in cache
            if paged:
                k_old, v_old = (
                    x.reshape(b, x.shape[1], -1) if x.ndim > 3 else x
                    for x in layer_rows(cache, self.layer_idx,
                                        cache["table"], c.dtype))
            else:
                layer_k = cache["k"][self.layer_idx]
                layer_v = cache["v"][self.layer_idx]
                k_old = layer_k.reshape(b, -1, h)
                v_old = layer_v.reshape(b, -1, h)
            # the new columns on the rows' axis (a pool's zero pad included)
            k_new = kv_stored(k.astype(c.dtype), k_old.shape[2:])
            v_new = kv_stored(v.astype(c.dtype), v_old.shape[2:])
            ctx = merged_axis_attention(q, k_old, v_old, k_new, v_new, idx,
                                        kv_mask=attention_mask)
            if paged:
                # a paged cache hands back only this call's L new columns,
                # as the pool stores them: the caller owns the pool and
                # writes them at (block, offset) itself
                tail = cache["k"].shape[3:]
                new_entry = (k_new.reshape(b, l, *tail),
                             v_new.reshape(b, l, *tail))
            else:
                # a dense cache hands back its updated layer: a true
                # indexed scatter touching B x L columns at [idx[b],
                # idx[b]+L), not a masked rewrite of the whole buffer.
                # mode="drop" keeps the contract for rows whose columns lie
                # past the buffer (idle/retired slots the serving engine
                # has not reassigned yet): the write is dropped (never
                # clamped onto column max_len-1) and the row stays
                # garbage-but-finite — admission control owns capacity,
                # not this kernel.
                rows = jnp.arange(b)[:, None]
                cols = idx[:, None] + jnp.arange(l)[None, :]
                new_entry = (
                    layer_k.at[rows, cols].set(k.astype(c.dtype),
                                               mode="drop"),
                    layer_v.at[rows, cols].set(v.astype(c.dtype),
                                               mode="drop"))
        elif cache is not None:
            # Lockstep (scalar idx): write this call's keys/values at
            # [idx, idx+L), then attend over the full buffer with a
            # position mask — one code path for prefill (L>1) and decode
            # (L=1), both jittable (idx is traced). Overflow past the
            # buffer would silently clamp the write while the mask keeps
            # advancing — catch it whenever idx is concrete (eager
            # streaming drivers; generate() pre-validates its scan).
            layer_k = cache["k"][self.layer_idx]
            layer_v = cache["v"][self.layer_idx]
            max_len = layer_k.shape[1]
            if (not isinstance(idx, jax.core.Tracer)
                    and int(idx) + l > max_len):
                raise ValueError(
                    f"KV cache overflow: idx {int(idx)} + {l} new tokens > "
                    f"cache max_len {max_len}"
                )
            # the cache's own trailing axes: heads and head size apart
            # (init_cache), or side by side on one merged axis as the
            # serving pool stores them (the engine's private prefill
            # cache, whose rows then go into the pool as they lie)
            at = (0, idx) + (0,) * (layer_k.ndim - 2)
            ck = jax.lax.dynamic_update_slice(
                layer_k, kv_stored(k.astype(c.dtype), layer_k.shape[2:]), at)
            cv = jax.lax.dynamic_update_slice(
                layer_v, kv_stored(v.astype(c.dtype), layer_v.shape[2:]), at)
            new_entry = (ck, cv)
            ck, cv = kv_per_head(ck, nh, hd), kv_per_head(cv, nh, hd)
            if c.attn_impl == "flash" and l == 1 and c.flash_decode:
                # opt-in single-query flash decode (see GPTConfig:
                # dense wins at serving shapes; kernel kept for shapes
                # where streaming the cache beats the score round-trip)
                from sparkdl_tpu.ops.flash_decode import flash_decode

                start = None
                if attention_mask is not None:
                    # left-padded rows: first valid buffer column per row
                    start = jnp.argmax(
                        attention_mask.astype(jnp.int32), axis=1
                    )
                ctx = flash_decode(q, ck, cv, idx, start=start)
            elif (c.attn_impl == "flash" and l > 1
                  and not isinstance(idx, jax.core.Tracer)):
                # cached PREFILL with concrete idx (generate()'s eager
                # prefill is always idx=0): flash over the WRITTEN prefix
                # only — O(idx+L) keys per query instead of the dense
                # path's O(max_len) over every unwritten buffer column.
                # Queries sit at global positions [idx, idx+L), hence the
                # static q_offset in the kernel's causal mask.
                from sparkdl_tpu.ops.flash_attention import flash_attention

                end = int(idx) + l
                kv_mask = (attention_mask[:, :end]
                           if attention_mask is not None else None)
                ctx = flash_attention(
                    q, ck[:, :end], cv[:, :end], kv_mask,
                    causal=True, q_offset=int(idx),
                )
            else:
                # prefill (L>1) and non-flash decode: dense masked path
                q_pos = idx + jnp.arange(l)[None, :]  # [1, L]
                k_pos = jnp.arange(max_len)  # [max_len]
                mask = (k_pos[None, None, :] <= q_pos[:, :, None])[:, None]
                if attention_mask is not None:
                    # [B, max_len] buffer-column validity (pad columns of
                    # left-padded ragged prompts are False forever)
                    mask = mask & attention_mask[:, None, None, :]
                s = jnp.einsum(
                    "bqhd,bkhd->bhqk", q, ck,
                    preferred_element_type=jnp.float32,
                ) / math.sqrt(hd)
                s = jnp.where(mask, s, _NEG_INF)
                p = jax.nn.softmax(s, axis=-1).astype(c.dtype)
                ctx = jnp.einsum("bhqk,bkhd->bqhd", p, cv)
        else:
            # return_kv: hand the (post-rope) K/V of this uncached
            # forward to the caller — the prefill half of sequence
            # parallelism (sp_prefill): each sp shard's K/V row feeds
            # the serving cache without a second projection pass.
            new_entry = (k.astype(c.dtype), v.astype(c.dtype)) \
                if return_kv else None
            if attention_mask is not None and c.attn_impl != "full":
                raise ValueError(
                    "attention_mask on the uncached forward requires "
                    f"attn_impl='full' (got {c.attn_impl!r}); the flash/"
                    "ring kernels take ragged batches only through the "
                    "KV-cached generate() path"
                )
            if c.attn_impl == "flash":
                from sparkdl_tpu.ops.flash_attention import flash_attention

                ctx = flash_attention(q, k, v, causal=True)
            elif c.attn_impl == "ring" and c.sp_mode == "allgather":
                from sparkdl_tpu.parallel.ring_attention import (
                    allgather_self_attention,
                )

                ctx = allgather_self_attention(
                    q, k, v, axis_name=c.sp_axis, causal=True
                )
            elif c.attn_impl == "ring":
                ctx = ring_self_attention(
                    q, k, v, axis_name=c.sp_axis, causal=True
                )
            else:
                s = jnp.einsum(
                    "bqhd,bkhd->bhqk", q, k,
                    preferred_element_type=jnp.float32,
                ) / math.sqrt(hd)
                causal = jnp.tril(jnp.ones((l, l), bool))[None, None]
                if attention_mask is not None:
                    causal = causal & attention_mask[:, None, None, :]
                s = jnp.where(causal, s, _NEG_INF)
                p = jax.nn.softmax(s, axis=-1).astype(c.dtype)
                p = nn.Dropout(c.dropout, deterministic=not train)(p)
                ctx = jnp.einsum("bhqk,bkhd->bqhd", p, v)

        out = RowParallelDense(h, dtype=c.dtype, name="out_proj")(
            ctx.reshape(b, l, h)
        )
        return out, new_entry


class GPTBlock(nn.Module):
    config: GPTConfig
    layer_idx: int

    @nn.compact
    def __call__(self, x, *, cache: Optional[dict], train: bool,
                 positions: Optional[jax.Array] = None,
                 attention_mask: Optional[jax.Array] = None,
                 return_kv: bool = False):
        c = self.config
        a, new_entry = GPTAttention(c, self.layer_idx, name="attn")(
            nn.LayerNorm(epsilon=c.layer_norm_eps, dtype=c.dtype,
                         name="ln_1")(x),
            cache=cache, train=train, positions=positions,
            attention_mask=attention_mask, return_kv=return_kv,
        )
        x = x + nn.Dropout(c.dropout, deterministic=not train)(a)

        h = nn.LayerNorm(epsilon=c.layer_norm_eps, dtype=c.dtype,
                         name="ln_2")(x)
        is_moe = c.num_experts > 0 and (self.layer_idx % c.moe_every
                                        == c.moe_every - 1)
        if is_moe:
            m = MoEMlpBlock(
                num_experts=c.num_experts,
                hidden_features=c.intermediate_size,
                k=c.moe_k, capacity_factor=c.moe_capacity_factor,
                dtype=c.dtype, name="moe_mlp",
            )(h)
        else:
            up = ColumnParallelDense(c.intermediate_size, dtype=c.dtype,
                                     name="up")(h)
            m = RowParallelDense(c.hidden_size, dtype=c.dtype, name="down")(
                nn.gelu(up)
            )
        x = x + nn.Dropout(c.dropout, deterministic=not train)(m)
        return x, new_entry


class GPTLMHeadModel(nn.Module):
    """Decoder LM. ``__call__(input_ids, cache=None)`` -> (logits, cache).

    Without a cache: full causal forward (training / scoring), attention
    impl per ``config.attn_impl``. With a cache from :func:`init_cache`:
    writes K/V at ``cache['idx']`` and returns the updated cache —
    the building block :func:`generate` scans. A PER-SLOT cache
    (``init_cache(..., per_slot=True)``, ``idx`` [B]) decodes every row at
    its own depth with a per-row causal mask and per-row K/V scatter —
    through :func:`merged_axis_attention`; L=1 is the classic decode step
    and L=k scores a whole speculative draft span in one pass.

    A PAGED cache (it holds a ``table`` entry: ``{"k", "v"[, "k_scale",
    "v_scale"], "table", "idx"}``, the pool of
    :func:`~sparkdl_tpu.models.kv_pool.init_block_pool` with
    the ``[S, nb]`` live head of the block table and per-slot ``idx``)
    runs the same per-slot step, but every layer gathers only its own
    live blocks through the table
    (:func:`~sparkdl_tpu.models.kv_pool.layer_rows`) and the
    returned ``k``/``v`` are THIS call's new columns,
    ``[layers, S, L, C]`` at the compute dtype with the heads on the
    pool's one merged axis — the caller writes them into its pool at
    (block, offset); the pool is never returned.

    ``positions``: optional [B, L] global token positions for RoPE.
    REQUIRED under ``attn_impl='ring'`` (sequence sharded on ``sp``): each
    shard must pass its global positions, not 0..L/sp-1 — the ring kernel
    offsets its causal mask globally, and RoPE must agree with it.

    ``attention_mask``: optional key-validity mask excluding positions
    from every attention softmax (False = masked). Shape [B, L] (over
    this call's keys) on the uncached forward; [B, max_len] (over BUFFER
    columns) on the cached path, where pad columns of left-padded ragged
    prompts stay False for the whole generation. :func:`generate` builds
    both from its ``attention_mask`` argument.
    """

    config: GPTConfig

    @nn.compact
    def __call__(self, input_ids, *, cache: Optional[dict] = None,
                 train: bool = False,
                 positions: Optional[jax.Array] = None,
                 attention_mask: Optional[jax.Array] = None,
                 return_kv: bool = False):
        c = self.config
        wte = nn.Embed(c.vocab_size, c.hidden_size, dtype=c.dtype,
                       name="wte")
        x = wte(input_ids)
        if c.positions == "learned":
            b, l = input_ids.shape
            idx = cache["idx"] if cache is not None else jnp.zeros((), jnp.int32)
            pos = positions
            if pos is None:
                pos = jnp.broadcast_to(
                    jnp.reshape(idx, (-1, 1)) + jnp.arange(l)[None, :], (b, l)
                )
            x = x + nn.Embed(c.max_seq_len, c.hidden_size, dtype=c.dtype,
                             name="wpe")(pos)
        x = nn.Dropout(c.dropout, deterministic=not train)(x)

        new_ks, new_vs = [], []
        for i in range(c.num_layers):
            x, entry = GPTBlock(c, i, name=f"h_{i}")(
                x, cache=cache, train=train, positions=positions,
                attention_mask=attention_mask, return_kv=return_kv,
            )
            if entry is not None:
                new_ks.append(entry[0])
                new_vs.append(entry[1])

        x = nn.LayerNorm(epsilon=c.layer_norm_eps, dtype=c.dtype,
                         name="ln_f")(x)
        logits = wte.attend(x).astype(jnp.float32)  # weight-tied LM head

        if cache is not None:
            cache = {
                "k": jnp.stack(new_ks),
                "v": jnp.stack(new_vs),
                "idx": cache["idx"] + input_ids.shape[1],
            }
        elif return_kv:
            # uncached KV-returning forward (the sp prefill building
            # block): k/v stacked over layers for THIS call's tokens —
            # under shard_map, the caller's local shard; ``idx`` is the
            # local token count (a global prefill offsets it itself)
            cache = {
                "k": jnp.stack(new_ks),
                "v": jnp.stack(new_vs),
                "idx": jnp.asarray(input_ids.shape[1], jnp.int32),
            }
        return logits, cache


# ---------------------------------------------------------------------------
# HuggingFace GPT-2 weight conversion (torch state dict -> this pytree)
# ---------------------------------------------------------------------------

def config_from_hf_gpt2(hf_config) -> GPTConfig:
    """GPTConfig reproducing an HF ``GPT2Config`` (learned positions,
    tanh-gelu MLP — both already this module's conventions). Variants this
    forward cannot reproduce are rejected rather than silently diverging."""
    act = getattr(hf_config, "activation_function", "gelu_new")
    if act not in ("gelu_new", "gelu_pytorch_tanh"):
        raise ValueError(
            f"unsupported GPT-2 activation {act!r}: this forward uses "
            "tanh-gelu (gelu_new)"
        )
    if not getattr(hf_config, "scale_attn_weights", True) or getattr(
        hf_config, "scale_attn_by_inverse_layer_idx", False
    ):
        raise ValueError(
            "unsupported GPT-2 attention scaling variant (requires "
            "scale_attn_weights=True, scale_attn_by_inverse_layer_idx=False)"
        )
    return GPTConfig(
        vocab_size=hf_config.vocab_size,
        hidden_size=hf_config.n_embd,
        num_layers=hf_config.n_layer,
        num_heads=hf_config.n_head,
        intermediate_size=hf_config.n_inner or 4 * hf_config.n_embd,
        max_seq_len=hf_config.n_positions,
        positions="learned",
        layer_norm_eps=hf_config.layer_norm_epsilon,
        dropout=0.0,
    )


def load_hf_gpt2(hf_model) -> "tuple[GPTConfig, dict]":
    """Convert an HF ``GPT2Model``/``GPT2LMHeadModel`` (torch) into this
    module's (config, variables). GPT-2's Conv1D stores weights [in, out],
    the same layout as flax Dense kernels — no transposes; the fused
    c_attn splits into q/k/v. Oracle-tested: logits match the torch
    forward on the same tokens (tests/models/test_gpt.py)."""
    import numpy as np

    base = getattr(hf_model, "transformer", hf_model)  # LMHead or bare
    cfg = config_from_hf_gpt2(base.config)
    e = cfg.hidden_size

    def _np(t):
        return np.asarray(t.detach().cpu().numpy())

    def _ln(mod):
        return {"scale": _np(mod.weight), "bias": _np(mod.bias)}

    params: dict = {
        "wte": {"embedding": _np(base.wte.weight)},
        "wpe": {"embedding": _np(base.wpe.weight)},
        "ln_f": _ln(base.ln_f),
    }
    for i, blk in enumerate(base.h):
        w = _np(blk.attn.c_attn.weight)  # [E, 3E]
        bias = _np(blk.attn.c_attn.bias)  # [3E]
        qw, kw, vw = w[:, :e], w[:, e:2 * e], w[:, 2 * e:]
        qb, kb, vb = bias[:e], bias[e:2 * e], bias[2 * e:]
        params[f"h_{i}"] = {
            "ln_1": _ln(blk.ln_1),
            "ln_2": _ln(blk.ln_2),
            "attn": {
                "q_proj": {"kernel": qw, "bias": qb},
                "k_proj": {"kernel": kw, "bias": kb},
                "v_proj": {"kernel": vw, "bias": vb},
                "out_proj": {
                    "kernel": _np(blk.attn.c_proj.weight),
                    "bias": _np(blk.attn.c_proj.bias),
                },
            },
            "up": {
                "kernel": _np(blk.mlp.c_fc.weight),
                "bias": _np(blk.mlp.c_fc.bias),
            },
            "down": {
                "kernel": _np(blk.mlp.c_proj.weight),
                "bias": _np(blk.mlp.c_proj.bias),
            },
        }
    return cfg, {"params": params}


def sample_logits(
    logits: jax.Array, key: jax.Array, *,
    temperature: float, top_k: "int | None" = None,
    top_p: "float | None" = None,
) -> jax.Array:
    """One sampling step over [B, V] logits, jit-safe.

    temperature 0 = greedy (top_k/top_p ignored); otherwise temperature
    scaling, then optional top-k truncation, then optional top-p
    (nucleus) truncation — the standard serving controls, composable.
    """
    if temperature <= 0:
        return jnp.argmax(logits, axis=-1)
    logits = logits / temperature
    if top_k is not None:
        if top_k < 1:
            raise ValueError(f"top_k must be >= 1, got {top_k}")
        # HF-parity clamp: top_k beyond the vocab keeps everything
        # (serving defaults like 50 must not crash tiny-vocab models)
        top_k = min(top_k, logits.shape[-1])
        kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, _NEG_INF, logits)
    if top_p is not None:
        sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        csum = jnp.cumsum(probs, axis=-1)
        # keep every token whose preceding cumulative mass is < top_p
        # (the first token is always kept)
        keep = csum - probs < top_p
        cutoff = jnp.min(
            jnp.where(keep, sorted_logits, jnp.inf), axis=-1,
            keepdims=True,
        )
        logits = jnp.where(logits < cutoff, _NEG_INF, logits)
    return jax.random.categorical(key, logits, axis=-1)


def generate(
    model: GPTLMHeadModel,
    variables: Any,
    prompt_ids: jax.Array,
    max_new_tokens: int,
    *,
    temperature: float = 0.0,
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
    rng: Optional[jax.Array] = None,
    max_len: Optional[int] = None,
    attention_mask: Optional[jax.Array] = None,
) -> jax.Array:
    """Autoregressive decode: prefill the prompt, then one lax.scan step
    per token (KV-cached, single jittable program — no Python loop).

    temperature 0 = greedy; >0 = sampled (requires ``rng``), with
    optional ``top_k`` / ``top_p`` (nucleus) truncation.
    Returns [B, prompt_len + max_new_tokens] token ids.

    Ragged batches: ``attention_mask`` ([B, prompt_len], 1 = real token)
    decodes unequal-length prompts together. Prompts must be LEFT-padded
    (the serving convention: every row's last prompt token sits in the
    final column, so one logits column feeds sampling for all rows). Pad
    columns are excluded from every attention softmax, and per-row RoPE/
    learned positions count real tokens only — under GREEDY decoding
    (temperature=0) row b of the output equals the unbatched ``generate``
    of row b's unpadded prompt (oracle: tests/models/test_gpt_ragged.py);
    sampled runs draw per-step noise shaped by the whole batch, so
    sampled rows match only in distribution. Output rows keep their left
    pads: ``[pads, prompt, generated]``.

    Multi-chip serving: sharding-transparent. Commit ``prompt_ids`` (and
    ``attention_mask``) to a dp mesh (``runtime.mesh.batch_sharding``)
    and the prefill, every scan-carried cache update, and sampling run
    SPMD over the local chips, token-identical to the unsharded run
    (tests/models/test_gpt_dp.py).
    """
    b, lp = prompt_ids.shape
    if max_len is None:
        max_len = lp + max_new_tokens
    elif max_len < lp + max_new_tokens:
        raise ValueError(
            f"max_len={max_len} < prompt_len {lp} + max_new_tokens "
            f"{max_new_tokens}: cache writes would silently clamp"
        )
    if (model.config.positions == "learned"
            and lp + max_new_tokens > model.config.max_seq_len):
        # RoPE extrapolates; a learned position table does not — lookups
        # past it would silently clamp to the last row.
        raise ValueError(
            f"prompt_len {lp} + max_new_tokens {max_new_tokens} exceeds the "
            f"learned position table (max_seq_len={model.config.max_seq_len})"
        )
    if temperature > 0 and rng is None:
        raise ValueError("sampling (temperature>0) requires rng")
    if temperature <= 0 and (top_k is not None or top_p is not None):
        raise ValueError(
            "top_k/top_p only apply when sampling (temperature > 0)"
        )
    if top_k is not None and top_k < 1:
        raise ValueError(f"top_k must be >= 1, got {top_k}")
    if top_p is not None and not (0.0 < top_p <= 1.0):
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")
    if rng is None:
        rng = jax.random.PRNGKey(0)

    def sample(logits, key):
        return sample_logits(logits, key, temperature=temperature,
                             top_k=top_k, top_p=top_p)

    positions = key_valid = pad_len = None
    if attention_mask is not None:
        if attention_mask.shape != (b, lp):
            raise ValueError(
                f"attention_mask shape {attention_mask.shape} != prompt "
                f"shape {(b, lp)}"
            )
        mask = jnp.asarray(attention_mask).astype(bool)
        # left-padded = rows non-decreasing (0...0 1...1), ≥1 real token.
        # Value checks need concrete data — inside a jitted caller the
        # mask is a tracer and the contract is the caller's to honor.
        if not isinstance(mask, jax.core.Tracer):
            if not bool(jnp.all(mask[:, 1:] >= mask[:, :-1])):
                raise ValueError(
                    "attention_mask must be left-padded (each row "
                    "0...01...1); right-padded prompts cannot share a "
                    "sampling column"
                )
            if not bool(jnp.all(mask[:, -1])):
                raise ValueError("every row needs at least one real token")
        pad_len = lp - mask.sum(axis=1)  # [B]
        # logical positions: pads clamp to 0 (masked out of attention)
        positions = jnp.clip(jnp.cumsum(mask, axis=1) - 1, 0)
        # buffer-column validity for the WHOLE generation: pad columns
        # stay False; every generated column is real
        key_valid = jnp.concatenate(
            [mask, jnp.ones((b, max_len - lp), bool)], axis=1
        )

    cache = init_cache(model.config, b, max_len)
    logits, cache = model.apply(variables, prompt_ids, cache=cache,
                                positions=positions,
                                attention_mask=key_valid)
    rng, key = jax.random.split(rng)
    tok = sample(logits[:, -1], key)

    def step(carry, _):
        cache, tok, rng = carry
        pos = (None if pad_len is None
               else (cache["idx"] - pad_len)[:, None])
        logits, cache = model.apply(variables, tok[:, None], cache=cache,
                                    positions=pos,
                                    attention_mask=key_valid)
        rng, key = jax.random.split(rng)
        nxt = sample(logits[:, -1], key)
        return (cache, nxt, rng), tok

    # step i consumes the token at position lp+i and emits it; after N
    # steps ``toks`` holds exactly the N generated tokens (the final
    # carry's token is the N+1th, beyond max_new_tokens — dropped).
    _, toks = jax.lax.scan(
        step, (cache, tok, rng), None, length=max_new_tokens
    )
    return jnp.concatenate([prompt_ids, toks.swapaxes(0, 1)], axis=1)


@functools.lru_cache(maxsize=8)
def _sp_prefill_program(model: GPTLMHeadModel, mesh: Any):
    """The jitted sp-sharded forward of :func:`sp_prefill`, one per
    (model, mesh): compiled, because an eager ``shard_map`` dispatches
    every primitive of the model as its own SPMD program."""
    from jax.sharding import PartitionSpec as P

    axis = model.config.sp_axis

    def local(variables, ids_l, pos_l):
        logits, kv = model.apply(
            variables, ids_l, positions=pos_l, return_kv=True)
        return logits, kv["k"], kv["v"]

    return jax.jit(jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(), P(None, axis), P(None, axis)),
        out_specs=(P(None, axis), P(None, None, axis),
                   P(None, None, axis)),
    ))


def sp_prefill(
    model: GPTLMHeadModel,
    variables: Any,
    prompt_ids: jax.Array,
    mesh: Any,
) -> "tuple[jax.Array, dict]":
    """Sequence-parallel prompt prefill: shard the TOKENS of one (long)
    prompt contiguously across the mesh's ``sp`` chips and run ONE
    forward in which every chip computes its token shard's Q/K/V and
    attention follows ``config.sp_mode``:

    - ``"ring"`` — K/V shards rotate around the ring via ``ppermute``
      (:func:`~sparkdl_tpu.parallel.ring_attention.ring_self_attention`),
      each hop folding the visiting block into an online softmax with
      causal masking per (query-shard, key-shard) offset pair. O(L/sp)
      resident keys per chip — the long-context schedule. Exact up to
      fp accumulation order.
    - ``"allgather"`` — gather the K/V shards once, dense masked
      softmax per query shard: **bitwise-identical** logits to the
      jitted unsharded forward (the serving parity contract: the
      engine's programs are all compiled), right for small ``sp``
      where the gathered keys fit.

    Requires ``config.attn_impl == "ring"``. Prompts whose length does
    not divide ``sp`` are right-padded internally (pad keys sit causally
    AFTER every real query, so they are invisible without a mask) and
    the pad positions sliced off the outputs. Returns
    ``(logits [B, L, V], cache)`` where ``cache`` is an
    :func:`init_cache`-shaped pytree holding the prompt's K/V (k/v
    ``[layers, B, L, H, D]``, ``idx = L``) — ready to seed decode.
    """
    c = model.config
    axis = c.sp_axis
    if c.attn_impl != "ring":
        raise ValueError(
            f"sp_prefill requires attn_impl='ring' (sp_mode="
            f"'ring'|'allgather'), got attn_impl={c.attn_impl!r}"
        )
    sp = int(mesh.shape[axis])
    b, l = prompt_ids.shape
    pad = (-l) % sp
    lpad = l + pad
    if c.positions == "learned" and lpad > c.max_seq_len:
        raise ValueError(
            f"prompt_len {l} (padded to {lpad} for sp={sp}) exceeds the "
            f"learned position table (max_seq_len={c.max_seq_len})"
        )
    ids = jnp.pad(jnp.asarray(prompt_ids, jnp.int32), ((0, 0), (0, pad)))
    # GLOBAL positions per shard — the ring kernel offsets its causal
    # mask globally and RoPE must agree with it (model docstring)
    positions = jnp.broadcast_to(jnp.arange(lpad)[None, :], (b, lpad))

    # unbox OUTSIDE the manual region: the params enter replicated
    # (in_specs P()), and flax would otherwise turn each boxed param's
    # tp metadata into a sharding constraint inside shard_map, where
    # every mesh axis is Manual and no constraint may name one
    logits, ks, vs = _sp_prefill_program(model, mesh)(
        nn.meta.unbox(variables), ids, positions)
    cache = {"k": ks[:, :, :l], "v": vs[:, :, :l],
             "idx": jnp.asarray(l, jnp.int32)}
    return logits[:, :l], cache
