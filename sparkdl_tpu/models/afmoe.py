"""The ``afmoe`` decoder family (Arcee Trinity): sparse experts under mixed
sliding-window and full attention.

A layer, as the published config and the ``afmoe`` modelling code of
``transformers`` give it (``benchmark/reference_afmoe.py`` is the plain
float32 statement of the same equations):

- four RMS norms a layer (before and AFTER attention, before and after the
  MLP), in float32; the embedding is scaled by ``sqrt(hidden)``;
- ``num_kv_heads`` K/V heads under ``num_heads`` query heads, a per-head RMS
  norm of q and k, rotary positions on SLIDING layers only, a sigmoid gate
  on the attention output;
- sliding layers see the last ``sliding_window`` positions, full layers
  (every ``global_attn_every_n_layers``-th) everything before them;
- the first ``num_dense_layers`` MLPs are SwiGLU; the rest are expert
  layers: sigmoid scores, the top ``k`` by score plus bias, weights from the
  unbiased scores, normalised and scaled, one shared expert on every token,
  and NO token dropped (``parallel/moe_dropless.py``);
- an untied head.

The module keeps :class:`~sparkdl_tpu.models.gpt.GPTLMHeadModel`'s three
cache contracts (none; dense ``{"k", "v", "idx"}`` with a scalar ``idx``;
paged ``{"k", "v", "table", "idx"}``), so ``ContinuousGPTEngine`` serves it
through the same programs (``AfmoeConfig.serving_family``). A sliding layer
reads only what its window covers: of a dense cache a slice of ``window +
L`` columns, of a paged one the table entries that cover the window (a
static width, a per-row start). A cached call also hands back
``expert_counts`` ``[expert_layers, experts_held]``: rows each held expert
was given.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from sparkdl_tpu.models.family import ServingFamily, window_blocks
from sparkdl_tpu.models.gpt import apply_rope
from sparkdl_tpu.models.kv_pool import kv_per_head, kv_stored, layer_rows
from sparkdl_tpu.parallel.moe_dropless import (
    dropless_experts,
    route_sigmoid_topk,
)

_NEG_INF = -1e30
SLIDING, FULL = "sliding_attention", "full_attention"


@dataclasses.dataclass(frozen=True)
class AfmoeConfig:
    vocab_size: int = 200192
    hidden_size: int = 2048
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: int = 128
    intermediate_size: int = 6144        #: a dense layer's SwiGLU width
    moe_intermediate_size: int = 1024    #: an expert's, and the shared one's
    num_experts: int = 128
    num_experts_per_tok: int = 8
    num_dense_layers: int = 2
    layer_types: "tuple[str, ...]" = (SLIDING, SLIDING, SLIDING, FULL) * 8
    sliding_window: int = 2048
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    route_norm: bool = True
    route_scale: float = 2.826
    mup_enabled: bool = True
    #: the experts THIS chip holds of every expert layer (routing is over
    #: all ``num_experts``); None holds them all
    first_expert: int = 0
    experts_held: "int | None" = None
    dtype: Any = jnp.float32

    def __post_init__(self):
        bad = set(self.layer_types) - {SLIDING, FULL}
        if bad:
            raise ValueError(f"unknown layer types {sorted(bad)}")
        if self.num_heads % self.num_kv_heads:
            raise ValueError("num_heads must be a multiple of num_kv_heads")
        held = self.held
        if not (0 <= self.first_expert
                and self.first_expert + held <= self.num_experts):
            raise ValueError(
                f"experts [{self.first_expert}, {self.first_expert + held}) "
                f"are not among the {self.num_experts}")

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @property
    def held(self) -> int:
        return (self.num_experts if self.experts_held is None
                else self.experts_held)

    @classmethod
    def tiny(cls, **kw) -> "AfmoeConfig":
        """Test-sized: every kind of layer of the published pattern (one
        dense sliding, three sliding and one full expert layers)."""
        defaults = dict(
            vocab_size=512, hidden_size=64, num_heads=4, num_kv_heads=2,
            head_dim=16, intermediate_size=96, moe_intermediate_size=32,
            num_experts=8, num_experts_per_tok=2, num_dense_layers=1,
            layer_types=(SLIDING, SLIDING, SLIDING, SLIDING, FULL),
            sliding_window=32,
        )
        defaults.update(kw)
        return cls(**defaults)

    def serving_family(self) -> ServingFamily:
        n_window = sum(t == SLIDING for t in self.layer_types)
        return ServingFamily(
            module=AfmoeLMHeadModel(self), layers=self.num_layers,
            kv_heads=self.num_kv_heads, head_dim=self.head_dim,
            dtype=self.dtype, max_positions=None,
            window_layers=n_window, window=self.sliding_window,
            expert_layers=max(self.num_layers - self.num_dense_layers, 0),
            experts=self.held, experts_per_token=self.num_experts_per_tok,
            paged_only=True)


def config_from_hf_afmoe(hf: dict, **kw) -> AfmoeConfig:
    """AfmoeConfig from the keys of an ``afmoe`` ``config.json``. Variants
    this forward does not compute are refused, not approximated."""
    if hf.get("model_type", "afmoe") != "afmoe":
        raise ValueError(f"not an afmoe config: {hf.get('model_type')!r}")
    if hf.get("score_func", "sigmoid") != "sigmoid":
        raise ValueError("only sigmoid router scores are implemented")
    if hf.get("rope_scaling") is not None:
        raise ValueError("rope_scaling is not implemented")
    if hf.get("hidden_act", "silu") != "silu":
        raise ValueError("only silu gated MLPs are implemented")
    if (hf.get("n_group", 1), hf.get("topk_group", 1)) != (1, 1):
        raise ValueError("group-limited routing is not implemented")
    if hf.get("num_shared_experts", 1) != 1:
        raise ValueError("exactly one shared expert is implemented")
    if hf.get("tie_word_embeddings", False):
        raise ValueError("a tied head is not implemented")
    return AfmoeConfig(
        vocab_size=int(hf["vocab_size"]), hidden_size=int(hf["hidden_size"]),
        num_heads=int(hf["num_attention_heads"]),
        num_kv_heads=int(hf["num_key_value_heads"]),
        head_dim=int(hf["head_dim"]),
        intermediate_size=int(hf["intermediate_size"]),
        moe_intermediate_size=int(hf["moe_intermediate_size"]),
        num_experts=int(hf["num_experts"]),
        num_experts_per_tok=int(hf["num_experts_per_tok"]),
        num_dense_layers=int(hf["num_dense_layers"]),
        layer_types=tuple(hf["layer_types"]),
        sliding_window=int(hf["sliding_window"]),
        rope_theta=float(hf.get("rope_theta", 10000.0)),
        rms_norm_eps=float(hf.get("rms_norm_eps", 1e-5)),
        route_norm=bool(hf.get("route_norm", True)),
        route_scale=float(hf.get("route_scale", 1.0)),
        mup_enabled=bool(hf.get("mup_enabled", False)),
        **kw)


def rms_norm(x: jax.Array, gain: jax.Array, eps: float) -> jax.Array:
    """``x / sqrt(mean(x^2) + eps) * gain`` over the last axis, in float32,
    handed back in ``x``'s dtype."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * gain.astype(jnp.float32)).astype(x.dtype)


def _kernel(mod: nn.Module, name: str, shape: "tuple[int, ...]",
            dtype: Any = None) -> jax.Array:
    return mod.param(name, nn.initializers.normal(0.02), shape,
                     dtype or mod.config.dtype)


def _gain(mod: nn.Module, name: str, n: int) -> jax.Array:
    return mod.param(name, nn.initializers.ones, (n,), jnp.float32)


def _grouped_attention(q, k, v, mask, dtype):
    """Softmax attention with each K/V head shared by a group of query
    heads. q [B, L, H, D]; k, v [B, K, G, D]; mask [B|1, L, K] bool."""
    b, l, h, d = q.shape
    g = k.shape[2]
    q = q.reshape(b, l, g, h // g, d)
    s = jnp.einsum("blgrd,bkgd->bgrlk", q, k,
                   preferred_element_type=jnp.float32) / math.sqrt(d)
    s = jnp.where(mask[:, None, None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(dtype)
    return jnp.einsum("bgrlk,bkgd->blgrd", p, v).reshape(b, l, h * d)


class AfmoeAttention(nn.Module):
    config: AfmoeConfig
    layer_idx: int

    @nn.compact
    def __call__(self, x, *, cache: Optional[dict],
                 positions: Optional[jax.Array] = None):
        c = self.config
        b, l, hid = x.shape
        nh, ng, hd = c.num_heads, c.num_kv_heads, c.head_dim
        sliding = c.layer_types[self.layer_idx] == SLIDING
        window = c.sliding_window

        q = jnp.dot(x, _kernel(self, "q_proj", (hid, nh * hd)))
        k = jnp.dot(x, _kernel(self, "k_proj", (hid, ng * hd)))
        v = jnp.dot(x, _kernel(self, "v_proj", (hid, ng * hd)))
        gate = jnp.dot(x, _kernel(self, "gate_proj", (hid, nh * hd)))
        q = rms_norm(q.reshape(b, l, nh, hd), _gain(self, "q_norm", hd),
                     c.rms_norm_eps)
        k = rms_norm(k.reshape(b, l, ng, hd), _gain(self, "k_norm", hd),
                     c.rms_norm_eps)
        v = v.reshape(b, l, ng, hd)

        idx = cache["idx"] if cache is not None else jnp.zeros((), jnp.int32)
        # [1|B, L] positions of this call's tokens: the causal and window
        # masks always count from the cache's depth; rotary takes the
        # caller's ``positions`` where it gives them (the engine clamps a
        # padded chunk's tail)
        q_pos = jnp.reshape(idx, (-1, 1)) + jnp.arange(l)[None, :]
        if sliding:
            rope_pos = jnp.broadcast_to(
                q_pos if positions is None else positions, (b, l))
            q = apply_rope(q, rope_pos, c.rope_theta)
            k = apply_rope(k, rope_pos, c.rope_theta)

        def visible(k_pos):
            # [1|B, L, K]: key j is seen by query i iff 0 <= i - j < window
            # (sliding) or 0 <= i - j (full)
            gap = q_pos[:, :, None] - k_pos[:, None, :]
            seen = gap >= 0
            return seen & (gap < window) if sliding else seen

        new_entry = None
        if cache is None:
            ctx = _grouped_attention(
                q, k, v, visible(jnp.arange(l)[None, :]), c.dtype)
        elif "table" in cache:
            # paged: this layer's blocks through the table, a sliding layer
            # only the entries its window covers: a static width, a per-row
            # start
            table = cache["table"]
            nb, bs = table.shape[1], cache["k"].shape[2]
            wb = window_blocks(window, nb, bs, l) if sliding else nb
            first = jnp.clip((idx + l - 1) // bs - (wb - 1), 0, nb - wb)
            sub = (table if wb == nb else jnp.take_along_axis(
                table, first[:, None] + jnp.arange(wb)[None, :], axis=1))
            ck, cv = (kv_per_head(x, ng, hd) for x in layer_rows(
                cache, self.layer_idx, sub, c.dtype))
            rows = jnp.arange(b)[:, None]
            cols = q_pos - (first * bs)[:, None]
            ck = ck.at[rows, cols].set(k.astype(c.dtype), mode="drop")
            cv = cv.at[rows, cols].set(v.astype(c.dtype), mode="drop")
            # the new columns go back as the pool stores a token
            tail = cache["k"].shape[3:]
            new_entry = (kv_stored(k.astype(c.dtype), tail),
                         kv_stored(v.astype(c.dtype), tail))
            k_pos = (first * bs)[:, None] + jnp.arange(wb * bs)[None, :]
            ctx = _grouped_attention(q, ck, cv, visible(k_pos), c.dtype)
        else:
            # dense cache, scalar idx (prefill, chunked prefill, lockstep
            # decode): write [idx, idx + L), then a sliding layer reads the
            # window + L columns that end at idx + L alone
            if jnp.ndim(idx) != 0:
                raise ValueError(
                    "the afmoe family's dense cache takes a scalar idx; "
                    "per-slot decode is the paged cache's")
            # in the cache's own trailing axes: the engine's private
            # prefill cache keeps a token as its pool stores it
            layer_k = cache["k"][self.layer_idx]
            at = (0, idx) + (0,) * (layer_k.ndim - 2)
            tail = layer_k.shape[2:]
            ck = jax.lax.dynamic_update_slice(
                layer_k, kv_stored(k.astype(c.dtype), tail), at)
            cv = jax.lax.dynamic_update_slice(
                cache["v"][self.layer_idx], kv_stored(v.astype(c.dtype), tail),
                at)
            new_entry = (ck, cv)
            ck, cv = kv_per_head(ck, ng, hd), kv_per_head(cv, ng, hd)
            width = ck.shape[1]
            kw = min(width, window + l) if sliding else width
            lo = jnp.clip(idx + l - kw, 0, width - kw)
            if kw < width:
                ck = jax.lax.dynamic_slice_in_dim(ck, lo, kw, axis=1)
                cv = jax.lax.dynamic_slice_in_dim(cv, lo, kw, axis=1)
            ctx = _grouped_attention(
                q, ck, cv, visible((lo + jnp.arange(kw))[None, :]), c.dtype)

        ctx = ctx * jax.nn.sigmoid(gate)
        return jnp.dot(ctx, _kernel(self, "o_proj", (nh * hd, hid))), new_entry


class AfmoeSwiGLU(nn.Module):
    config: AfmoeConfig
    width: int

    @nn.compact
    def __call__(self, x):
        hid = x.shape[-1]
        g = jnp.dot(x, _kernel(self, "gate_proj", (hid, self.width)))
        u = jnp.dot(x, _kernel(self, "up_proj", (hid, self.width)))
        return jnp.dot(jax.nn.silu(g) * u,
                       _kernel(self, "down_proj", (self.width, hid)))


class AfmoeExperts(nn.Module):
    """The expert layer of this chip: the shared expert on every token and
    the HELD experts' part of the routed sum."""

    config: AfmoeConfig

    @nn.compact
    def __call__(self, x):
        c = self.config
        b, l, hid = x.shape
        f, held = c.moe_intermediate_size, c.held
        h = x.reshape(b * l, hid)
        sel, w = route_sigmoid_topk(
            h, _kernel(self, "router", (hid, c.num_experts), jnp.float32),
            self.param("expert_bias", nn.initializers.zeros,
                       (c.num_experts,), jnp.float32),
            c.num_experts_per_tok, route_norm=c.route_norm,
            route_scale=c.route_scale)
        routed, counts = dropless_experts(
            h, sel, w, _kernel(self, "experts_gate", (held, hid, f)),
            _kernel(self, "experts_up", (held, hid, f)),
            _kernel(self, "experts_down", (held, f, hid)),
            first_expert=c.first_expert)
        shared = AfmoeSwiGLU(c, f, name="shared")(x)
        return shared + routed.reshape(b, l, hid), counts


class AfmoeBlock(nn.Module):
    config: AfmoeConfig
    layer_idx: int

    @nn.compact
    def __call__(self, x, *, cache: Optional[dict],
                 positions: Optional[jax.Array] = None):
        c = self.config
        hid, eps = c.hidden_size, c.rms_norm_eps
        a, new_entry = AfmoeAttention(c, self.layer_idx, name="attn")(
            rms_norm(x, _gain(self, "input_norm", hid), eps),
            cache=cache, positions=positions)
        x = x + rms_norm(a, _gain(self, "post_attn_norm", hid), eps)
        h = rms_norm(x, _gain(self, "pre_mlp_norm", hid), eps)
        counts = None
        if self.layer_idx < c.num_dense_layers:
            m = AfmoeSwiGLU(c, c.intermediate_size, name="mlp")(h)
        else:
            m, counts = AfmoeExperts(c, name="moe")(h)
        x = x + rms_norm(m, _gain(self, "post_mlp_norm", hid), eps)
        return x, new_entry, counts


class AfmoeLMHeadModel(nn.Module):
    """``__call__(input_ids, cache=None, positions=None)`` -> ``(logits
    float32, cache)``, with :class:`~sparkdl_tpu.models.gpt.GPTLMHeadModel`'s
    cache contracts: no cache (the whole causal forward, cache None back);
    a dense cache ``{"k", "v", "idx"}`` of ``init_afmoe_cache`` (scalar
    ``idx``; the updated cache back); a paged cache ``{"k", "v", "table",
    "idx"}`` over the engine's block pool (this call's new columns
    ``[layers, S, L, *kv_tail]`` back, as the pool stores a token). A
    cached call's cache also holds ``expert_counts`` ``[expert_layers,
    experts_held]``.
    ``positions`` ([B, L]) override the rotary positions of this call's
    tokens only; masks always count from ``cache["idx"]``."""

    config: AfmoeConfig

    @nn.compact
    def __call__(self, input_ids, *, cache: Optional[dict] = None,
                 positions: Optional[jax.Array] = None):
        c = self.config
        embed = self.param("embed_tokens", nn.initializers.normal(0.02),
                           (c.vocab_size, c.hidden_size), c.dtype)
        x = embed[input_ids]
        if c.mup_enabled:
            x = x * jnp.asarray(math.sqrt(c.hidden_size), c.dtype)
        new_ks, new_vs, counts = [], [], []
        for i in range(c.num_layers):
            x, entry, n = AfmoeBlock(c, i, name=f"layers_{i}")(
                x, cache=cache, positions=positions)
            if entry is not None:
                new_ks.append(entry[0])
                new_vs.append(entry[1])
            if n is not None:
                counts.append(n)
        x = rms_norm(x, _gain(self, "norm", c.hidden_size), c.rms_norm_eps)
        logits = jnp.dot(x, _kernel(self, "lm_head",
                                    (c.hidden_size, c.vocab_size)),
                         preferred_element_type=jnp.float32)
        if cache is None:
            return logits, None
        out = {"k": jnp.stack(new_ks), "v": jnp.stack(new_vs),
               "idx": cache["idx"] + input_ids.shape[1]}
        if counts:
            out["expert_counts"] = jnp.stack(counts)
        return logits, out


def init_afmoe_cache(config: AfmoeConfig, batch: int, max_len: int) -> dict:
    """A zeroed dense cache ``[layers, B, max_len, kv_heads, head_dim]``
    with a scalar ``idx``: prefill and lockstep decode outside the engine."""
    shape = (config.num_layers, batch, max_len, config.num_kv_heads,
             config.head_dim)
    return {"k": jnp.zeros(shape, config.dtype),
            "v": jnp.zeros(shape, config.dtype),
            "idx": jnp.zeros((), jnp.int32)}
