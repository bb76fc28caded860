"""The ``mimo_v2_flash`` decoder family (Xiaomi MiMo-V2-Flash): sparse experts
under window and full attention with heads of unequal key and value size.

A layer, as the published ``config.json`` gives it
(``benchmark/reference_mimo_v2_flash.py`` is the plain float32 statement of
the same equations; what the config does not settle is listed under
``assumed`` in ``benchmark/configs/mimo-v2-flash-serve.json``):

- an RMS norm before attention and before the MLP, in float32; no biases, no
  norm of q or k;
- the KIND of layer ``l`` is ``hybrid_layer_pattern[l]``: 0 full (``num_heads``
  query heads over ``num_kv_heads`` K/V heads, rotary base ``rope_theta``, no
  sink), 1 window (over ``swa_num_kv_heads`` K/V heads, base
  ``swa_rope_theta``, the last ``sliding_window`` positions, a learned sink a
  head). Keys and queries are ``head_dim`` wide, values ``v_head_dim``, and
  ``v`` is scaled by ``attention_value_scale``;
- rotation on the first ``int(head_dim * partial_rotary_factor)`` values of
  every q and k head, half-split pairs, by absolute position;
- ``a_ij = q_i . k_j / sqrt(head_dim)``; a window layer's softmax has one
  more term in its denominator, ``exp(sink_h)``: the sink takes weight and
  gives no value;
- the MLP of layer ``l`` is SwiGLU where ``moe_layer_freq[l]`` is 0 and an
  expert layer elsewhere: sigmoid scores in float32, the top ``k`` by score
  plus bias, weights the unbiased scores normalised to one, NO shared expert
  and NO token dropped (``parallel/moe_dropless.py``). The layer is told
  which experts this chip HOLDS (``first_expert``, ``experts_held``): it
  routes over all of them and computes its own experts' part;
- an untied head.

**What a layer keeps.** A full layer keeps K and V a token, in the engine's
block pool (``kv_pool``: K on one axis of ``kv_heads * head_dim``, V on one of
``kv_heads * v_head_dim``). A window layer keeps NO block: its last
``sliding_window`` columns a sequence lie in a RING by slot, ``win_k``
``[window layers, slots, window, swa_kv_heads * head_dim]`` and ``win_v``
(``... * v_head_dim``), the column of position ``p`` at ``p % window``, so a
window layer's bytes a slot do not grow with the context. The rings ride the
engine's arrays by slot (``ServingFamily.state_arrays``), as Olmo-Hybrid's
recurrent state does.

The module keeps :class:`~sparkdl_tpu.models.gpt.GPTLMHeadModel`'s cache
contracts, with the rings beside the K/V:

- none: the whole causal forward;
- dense (the engine's private prefill cache) ``{"k", "v", "idx", "win_k",
  "win_v"}`` and optionally ``"n"``: K/V of the FULL layers only ``[full
  layers, 1, W, *tail]`` written at the scalar ``idx``; the rings ``[window
  layers, B, window, ...]`` as the calls before left them; ``n`` the count of
  real tokens in this call (all of them if absent). A window layer attends
  over the ring's older columns and this call's own (a chunk may be wider
  than the window), then leaves the last ``window`` REAL columns in the ring:
  no pad column ever enters it;
- paged ``{"k", "v", "table", "idx", "win_k", "win_v", "live"}`` over the
  engine's pool, one token a row: the full layers read their K/V through the
  table, the window layers their slot's ring; a row that is not ``live``
  keeps its ring bit for bit. This call's new K/V columns of the full layers
  and the whole updated rings come back.

A cached call also hands back ``expert_counts`` ``[expert_layers,
experts_held]``: rows each held expert was given.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from sparkdl_tpu.models.afmoe import AfmoeSwiGLU, _gain, _kernel, rms_norm
from sparkdl_tpu.models.family import ServingFamily
from sparkdl_tpu.models.kv_pool import (
    kv_per_head,
    kv_stored,
    kv_tails,
    layer_rows,
)
from sparkdl_tpu.ops import paged_decode
from sparkdl_tpu.parallel.moe_dropless import (
    dropless_experts,
    route_sigmoid_topk,
)

_NEG_INF = -1e30
#: ``hybrid_layer_pattern``'s two kinds of layer
FULL, WINDOW = 0, 1


@dataclasses.dataclass(frozen=True)
class MimoV2FlashConfig:
    vocab_size: int = 152576
    hidden_size: int = 4096
    intermediate_size: int = 16384       #: a dense layer's SwiGLU width
    moe_intermediate_size: int = 2048    #: an expert's
    num_heads: int = 64                  #: query heads, every kind of layer
    num_kv_heads: int = 4                #: K/V heads of a full layer
    swa_num_kv_heads: int = 8            #: K/V heads of a window layer
    head_dim: int = 192                  #: a query's and a key's
    v_head_dim: int = 128
    #: layer kinds (0 full, 1 window) and MLP kinds (0 dense, 1 experts)
    hybrid_layer_pattern: "tuple[int, ...]" = (
        (FULL,) + (WINDOW,) * 4 + ((FULL,) + (WINDOW,) * 5) * 7 + (FULL,))
    moe_layer_freq: "tuple[int, ...]" = (0,) + (1,) * 47
    sliding_window: int = 128
    rope_theta: float = 5000000.0
    swa_rope_theta: float = 10000.0
    partial_rotary_factor: float = 0.334
    attention_value_scale: float = 0.707
    num_experts: int = 256
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    rms_norm_eps: float = 1e-5
    #: the experts THIS chip holds of every expert layer (routing is over
    #: all ``num_experts``); None holds them all
    first_expert: int = 0
    experts_held: "int | None" = None
    dtype: Any = jnp.float32

    def __post_init__(self):
        bad = set(self.hybrid_layer_pattern) - {FULL, WINDOW}
        if bad:
            raise ValueError(f"unknown layer kinds {sorted(bad)}")
        if len(self.moe_layer_freq) != len(self.hybrid_layer_pattern):
            raise ValueError(
                "hybrid_layer_pattern and moe_layer_freq disagree in length")
        for heads in (self.num_kv_heads, self.swa_num_kv_heads):
            if self.num_heads % heads:
                raise ValueError(
                    "num_heads must be a multiple of both counts of K/V heads")
        if self.rotary_dim % 2 or not 0 < self.rotary_dim <= self.head_dim:
            raise ValueError(f"cannot rotate {self.rotary_dim} of a head's "
                             f"{self.head_dim} values in pairs")
        held = self.held
        if not (0 <= self.first_expert
                and self.first_expert + held <= self.num_experts):
            raise ValueError(
                f"experts [{self.first_expert}, {self.first_expert + held}) "
                f"are not among the {self.num_experts}")

    @property
    def num_layers(self) -> int:
        return len(self.hybrid_layer_pattern)

    @property
    def held(self) -> int:
        return (self.num_experts if self.experts_held is None
                else self.experts_held)

    @property
    def rotary_dim(self) -> int:
        """Values of a q or k head that are rotated: the first ones."""
        return int(self.head_dim * self.partial_rotary_factor)

    def layers_of(self, kind: int) -> int:
        return sum(t == kind for t in self.hybrid_layer_pattern)

    def index_in_kind(self, layer: int) -> int:
        """``layer``'s place among the layers of its own kind: its row of
        the K/V pool (full) or of the rings (window)."""
        kind = self.hybrid_layer_pattern[layer]
        return sum(t == kind for t in self.hybrid_layer_pattern[:layer])

    def kv_heads_of(self, kind: int) -> int:
        return self.swa_num_kv_heads if kind == WINDOW else self.num_kv_heads

    @classmethod
    def tiny(cls, **kw) -> "MimoV2FlashConfig":
        """Test-sized: a leading dense full layer and one whole period of
        the published pattern (window x 5, full), all six with experts."""
        defaults = dict(
            vocab_size=512, hidden_size=64, intermediate_size=96,
            moe_intermediate_size=32, num_heads=8, num_kv_heads=2,
            swa_num_kv_heads=4, head_dim=24, v_head_dim=16,
            hybrid_layer_pattern=(FULL,) + (WINDOW,) * 5 + (FULL,),
            moe_layer_freq=(0,) + (1,) * 6, sliding_window=16,
            num_experts=8, num_experts_per_tok=2,
        )
        defaults.update(kw)
        return cls(**defaults)

    def serving_family(self) -> ServingFamily:
        w = self.sliding_window
        # a ring's column: a window layer's K heads side by side on one
        # axis, and its V heads on another
        ring_k = (self.swa_num_kv_heads * self.head_dim,)
        ring_v = (self.swa_num_kv_heads * self.v_head_dim,)
        return ServingFamily(
            module=MimoV2FlashLMHeadModel(self), layers=self.num_layers,
            kv_heads=self.num_kv_heads, head_dim=self.head_dim,
            v_head_dim=self.v_head_dim, dtype=self.dtype, max_positions=None,
            expert_layers=sum(self.moe_layer_freq), experts=self.held,
            experts_per_token=self.num_experts_per_tok, paged_only=True,
            kv_layers=self.layers_of(FULL),
            decode_reads_in_place=paged_decode.reads_in_place(
                *kv_tails(self.num_kv_heads, self.head_dim, self.v_head_dim),
                self.num_kv_heads, self.head_dim, self.v_head_dim),
            state_layers=self.layers_of(WINDOW), ring_columns=w,
            state_arrays=(("win_k", (w,) + ring_k, self.dtype),
                          ("win_v", (w,) + ring_v, self.dtype)))


def config_from_hf_mimo_v2_flash(hf: dict, **kw) -> MimoV2FlashConfig:
    """MimoV2FlashConfig from the keys of a ``mimo_v2_flash`` ``config.json``.
    ``n_routed_experts`` counts the experts the router scores; which of them
    this chip holds is ``first_expert`` / ``experts_held`` (keywords).
    Variants this forward does not compute are refused, not approximated."""
    if hf.get("model_type", "mimo_v2_flash") != "mimo_v2_flash":
        raise ValueError(
            f"not a mimo_v2_flash config: {hf.get('model_type')!r}")
    if (hf.get("n_group") or 1, hf.get("topk_group") or 1) != (1, 1):
        raise ValueError("group-limited routing (n_group, topk_group > 1) "
                         "is not implemented")
    if hf.get("n_shared_experts"):
        raise ValueError("a shared expert is not implemented: the family "
                         "publishes none")
    if hf.get("add_full_attention_sink_bias", False):
        raise ValueError("a sink in full-attention layers is not implemented")
    if not hf.get("add_swa_attention_sink_bias", True):
        raise ValueError("window layers without their sink are not "
                         "implemented")
    scaling = hf.get("rope_scaling")
    if scaling is not None and (scaling.get("rope_type")
                                or scaling.get("type")) != "default":
        raise ValueError("rope_scaling is not implemented")
    if hf.get("scoring_func", "sigmoid") != "sigmoid":
        raise ValueError("only sigmoid router scores are implemented")
    if hf.get("topk_method", "noaux_tc") != "noaux_tc":
        raise ValueError("only noaux_tc selection (top k by score plus "
                         "bias) is implemented")
    if hf.get("hidden_act", "silu") != "silu":
        raise ValueError("only silu gated MLPs are implemented")
    if hf.get("attention_bias", False):
        raise ValueError("attention biases are not implemented")
    if hf.get("tie_word_embeddings", False):
        raise ValueError("a tied head is not implemented")
    heads = int(hf["num_attention_heads"])
    for key, want in (("swa_num_attention_heads", heads),
                      ("swa_head_dim", int(hf["head_dim"])),
                      ("swa_v_head_dim", int(hf["v_head_dim"]))):
        if int(hf.get(key, want)) != want:
            raise ValueError(f"{key} other than the full layers' is not "
                             "implemented")
    window = int(hf["sliding_window"])
    if int(hf.get("sliding_window_size", window)) != window:
        raise ValueError("sliding_window and sliding_window_size disagree")
    pattern = tuple(int(t) for t in hf["hybrid_layer_pattern"])
    if len(pattern) != int(hf["num_hidden_layers"]):
        raise ValueError(
            "hybrid_layer_pattern and num_hidden_layers disagree")
    scale = hf.get("routed_scaling_factor")
    return MimoV2FlashConfig(
        vocab_size=int(hf["vocab_size"]), hidden_size=int(hf["hidden_size"]),
        intermediate_size=int(hf["intermediate_size"]),
        moe_intermediate_size=int(hf["moe_intermediate_size"]),
        num_heads=heads, num_kv_heads=int(hf["num_key_value_heads"]),
        swa_num_kv_heads=int(hf["swa_num_key_value_heads"]),
        head_dim=int(hf["head_dim"]), v_head_dim=int(hf["v_head_dim"]),
        hybrid_layer_pattern=pattern,
        moe_layer_freq=tuple(int(t) for t in hf["moe_layer_freq"]),
        sliding_window=window, rope_theta=float(hf["rope_theta"]),
        swa_rope_theta=float(hf["swa_rope_theta"]),
        partial_rotary_factor=float(hf["partial_rotary_factor"]),
        attention_value_scale=float(hf.get("attention_value_scale", 1.0)),
        num_experts=int(hf["n_routed_experts"]),
        num_experts_per_tok=int(hf["num_experts_per_tok"]),
        norm_topk_prob=bool(hf.get("norm_topk_prob", True)),
        routed_scaling_factor=1.0 if scale is None else float(scale),
        rms_norm_eps=float(hf.get("layernorm_epsilon", 1e-5)), **kw)


# -- attention --------------------------------------------------------------------

def partial_rope(x: jax.Array, positions: jax.Array, base: float,
                 rotary_dim: int) -> jax.Array:
    """Rotary on the FIRST ``rotary_dim`` values of every head, half-split
    pairs (value ``i`` with value ``i + rotary_dim / 2``); the rest pass.
    x ``[B, L, H, D]``; positions ``[B, L]``."""
    half = rotary_dim // 2
    freqs = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[:, :, None].astype(jnp.float32) * freqs
    cos = jnp.cos(ang)[:, :, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[:, :, None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:rotary_dim]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos, x[..., rotary_dim:]], -1)


def sink_attention(q, k, v, mask, sink, dtype):
    """Softmax attention with each K/V head shared by a group of query
    heads, keys and values of unequal size, and (``sink`` ``[H]`` float32,
    or None) one more term a head in the softmax's denominator that gives
    no value. q ``[B, L, H, Dk]``; k ``[B, K, G, Dk]``; v ``[B, K, G, Dv]``;
    mask ``[B|1, L, K]`` bool. Returns ``[B, L, H * Dv]``."""
    b, l, h, d = q.shape
    g = k.shape[2]
    q = q.reshape(b, l, g, h // g, d)
    s = jnp.einsum("blgrd,bkgd->bgrlk", q, k,
                   preferred_element_type=jnp.float32) / math.sqrt(d)
    s = jnp.where(mask[:, None, None], s, _NEG_INF)
    top = s.max(-1, keepdims=True)
    if sink is not None:
        sink = sink.astype(jnp.float32).reshape(1, g, h // g, 1, 1)
        top = jnp.maximum(top, sink)
    e = jnp.exp(s - top)
    total = e.sum(-1, keepdims=True)
    if sink is not None:
        total = total + jnp.exp(sink - top)
    p = (e / total).astype(dtype)
    return jnp.einsum("bgrlk,bkgd->blgrd", p, v).reshape(b, l, -1)


def merged_sink_attention(q, k_old, v_old, k_new, v_new, seen, sink, groups):
    """One query a row over K and V that keep their heads side by side on
    one axis, every row with its own visible columns: the paged step of
    both kinds of layer.

    ``q`` ``[S, H, Dk]``; ``k_old`` ``[S, W, groups * Dk]`` and ``v_old``
    ``[S, W, groups * Dv]``, the rows as the pool or the ring holds them, of
    which row ``s`` sees ``seen[s]`` ``[S, W]``; ``k_new`` / ``v_new`` ``[S,
    groups * D]``, this call's own column, which every row sees; ``sink``
    ``[H]`` or None. Returns ``[S, H * Dv]``.

    Neither K nor V is reshaped to heads (on the chip a head of 192 is a
    padded copy of every row; :func:`~sparkdl_tpu.models.gpt.
    merged_axis_attention` has the same reason): the queries are laid
    block-diagonally, ``Qbd[s, h, g*Dk + d] = q[s, h, d]`` for ``h``'s own
    K/V head ``g`` and zero elsewhere, so the columns of other heads add
    exact zeros to ONE product over the merged axis, and of ``p @ V`` each
    head keeps its own ``Dv`` columns. That is ``groups`` times the useful
    products, on a step that is bound by bytes. The new column is not
    written into the rows: its score and value join beside the old ones.
    Float32 scores and softmax, ``p`` cast to the operands' dtype before the
    second product.
    """
    s, h, dk = q.shape
    dv = v_old.shape[-1] // groups
    mine = jnp.arange(h) // (h // groups)                       # [H]
    own_k = jnp.arange(groups * dk)[None, :] // dk == mine[:, None]
    own_v = jnp.arange(groups * dv)[None, :] // dv == mine[:, None]
    qbd = jnp.where(own_k, jnp.tile(q, (1, 1, groups)), 0)      # [S, H, G*Dk]
    scale = 1.0 / math.sqrt(dk)
    s_old = jnp.einsum("shc,swc->shw", qbd, k_old,
                       preferred_element_type=jnp.float32) * scale
    s_old = jnp.where(seen[:, None, :], s_old, _NEG_INF)
    s_new = jnp.einsum("shc,sc->sh", qbd, k_new,
                       preferred_element_type=jnp.float32) * scale
    top = jnp.maximum(s_old.max(-1), s_new)
    if sink is not None:
        sink = sink.astype(jnp.float32)[None, :]
        top = jnp.maximum(top, sink)
    e_old, e_new = jnp.exp(s_old - top[..., None]), jnp.exp(s_new - top)
    total = e_old.sum(-1) + e_new
    if sink is not None:
        total = total + jnp.exp(sink - top)
    p_old = (e_old / total[..., None]).astype(q.dtype)
    p_new = (e_new / total).astype(q.dtype)
    r = (jnp.einsum("shw,swc->shc", p_old, v_old,
                    preferred_element_type=jnp.float32)
         + p_new[..., None].astype(jnp.float32)
         * v_new[:, None, :].astype(jnp.float32))
    out = jnp.where(own_v, r, 0).reshape(s, h, groups, dv).sum(2)
    return out.astype(q.dtype).reshape(s, h * dv)


def ring_positions(idx, window: int):
    """The position each of a ring's ``window`` slots holds when ``idx``
    positions have been written: the latest ``p < idx`` with ``p % window ==
    slot``; negative where the slot was never written. ``idx`` ``[...]`` ->
    ``[..., window]``."""
    slot = jnp.arange(window)
    last = jnp.asarray(idx)[..., None] - 1
    return last - (last - slot) % window


class MimoAttention(nn.Module):
    """One layer's attention. ``at`` is its row among the layers of its
    kind (of the K/V arrays if full, of the rings if window). Returns ``(y,
    entry)``: ``entry`` is None without a cache, the layer's new K/V (full:
    this call's columns of a paged cache, the updated rows of a dense one)
    or its updated ring (window: ``(ring_k, ring_v)`` ``[B, window, ...]``)."""

    config: MimoV2FlashConfig
    kind: int
    at: int

    @nn.compact
    def __call__(self, x, *, cache: Optional[dict],
                 positions: Optional[jax.Array] = None):
        c = self.config
        b, l, hid = x.shape
        window_layer = self.kind == WINDOW
        nh, ng = c.num_heads, c.kv_heads_of(self.kind)
        dk, dv, w = c.head_dim, c.v_head_dim, c.sliding_window

        q = jnp.dot(x, _kernel(self, "q_proj", (hid, nh * dk)))
        k = jnp.dot(x, _kernel(self, "k_proj", (hid, ng * dk)))
        v = jnp.dot(x, _kernel(self, "v_proj", (hid, ng * dv)))
        v = v * jnp.asarray(c.attention_value_scale, v.dtype)
        sink = (self.param("sink", nn.initializers.zeros, (nh,), jnp.float32)
                if window_layer else None)
        q, k = q.reshape(b, l, nh, dk), k.reshape(b, l, ng, dk)
        v = v.reshape(b, l, ng, dv)

        idx = cache["idx"] if cache is not None else jnp.zeros((), jnp.int32)
        # [1|B, L] positions of this call's tokens: masks always count from
        # the cache's depth; rotary takes the caller's ``positions`` where
        # it gives them (the engine clamps a padded chunk's tail)
        q_pos = jnp.reshape(idx, (-1, 1)) + jnp.arange(l)[None, :]
        rope_pos = jnp.broadcast_to(
            q_pos if positions is None else positions, (b, l))
        base = c.swa_rope_theta if window_layer else c.rope_theta
        q = partial_rope(q, rope_pos, base, c.rotary_dim)
        k = partial_rope(k, rope_pos, base, c.rotary_dim).astype(c.dtype)
        v = v.astype(c.dtype)

        def visible(k_pos):
            # [1|B, L, K]: key j is seen by query i iff 0 <= i - j (full)
            # and i - j < window (window); a ring slot never written holds
            # a negative position
            gap = q_pos[:, :, None] - k_pos[:, None, :]
            seen = (gap >= 0) & (k_pos[:, None, :] >= 0)
            return seen & (gap < w) if window_layer else seen

        out_proj = _kernel(self, "o_proj", (nh * dv, hid))
        if cache is None:
            ctx = sink_attention(q, k, v, visible(jnp.arange(l)[None, :]),
                                 sink, c.dtype)
            return jnp.dot(ctx, out_proj), None

        merged = lambda a: a.reshape(*a.shape[:-2], -1)  # noqa: E731
        if "table" in cache:
            # one query a row, every row at its own depth; this call's
            # column joins the softmax beside the old ones
            k_new, v_new = merged(k[:, 0]), merged(v[:, 0])
            if window_layer:
                # the slot's ring: every slot but the one this column will
                # overwrite holds a position inside the window
                pos = ring_positions(idx, w)                     # [B, w]
                ctx = merged_sink_attention(
                    q[:, 0], cache["win_k"][self.at], cache["win_v"][self.at],
                    k_new, v_new, (pos >= 0) & (pos > (idx - w)[:, None]),
                    sink, ng)[:, None]
            elif paged_decode.reads_in_place(
                    cache["k"].shape[3:], cache["v"].shape[3:], ng, dk, dv):
                # K and V each on one unpadded axis of whole lane tiles: the
                # old columns are read where the pool keeps them, each row's
                # own blocks and no more (ops/paged_decode.py; a full layer
                # has no sink)
                ctx = paged_decode.paged_decode_attention(
                    q, cache["k"], cache["v"], self.at, cache["table"], idx,
                    k_new[:, None], v_new[:, None])
            else:
                # the rows come through the table as the pool stores them
                k_old, v_old = (
                    a.reshape(b, a.shape[1], -1) if a.ndim > 3 else a
                    for a in layer_rows(cache, self.at, cache["table"],
                                        c.dtype))
                seen = jnp.arange(k_old.shape[1])[None, :] < idx[:, None]
                ctx = merged_sink_attention(
                    q[:, 0], k_old[..., :ng * dk], v_old[..., :ng * dv],
                    k_new, v_new, seen, None, ng)[:, None]
            if window_layer:
                # the one column a row written at position % window; a row
                # that is not live writes nothing (an index past the ring
                # is dropped)
                live = cache.get("live")
                slot = idx % w
                if live is not None:
                    slot = jnp.where(live, slot, w)
                rows = jnp.arange(b)
                entry = (
                    cache["win_k"].at[self.at, rows, slot].set(
                        k_new, mode="drop"),
                    cache["win_v"].at[self.at, rows, slot].set(
                        v_new, mode="drop"))
            else:
                entry = (kv_stored(k, cache["k"].shape[3:]),
                         kv_stored(v, cache["v"].shape[3:]))
            return jnp.dot(ctx, out_proj), entry

        if jnp.ndim(idx) != 0:
            raise ValueError(
                "the mimo_v2_flash family's dense cache takes a scalar idx; "
                "per-slot decode is the paged cache's")
        if window_layer:
            # the ring's columns, then this call's: a chunk wider than the
            # window attends over both and leaves its last REAL columns
            ring_k, ring_v = cache["win_k"][self.at], cache["win_v"][self.at]
            n = cache.get("n", l)
            k_pos = jnp.concatenate(
                [jnp.broadcast_to(ring_positions(idx, w), (1, w)),
                 q_pos[:1]], axis=1)
            ck = jnp.concatenate(
                [kv_per_head(ring_k.astype(c.dtype), ng, dk), k], axis=1)
            cv = jnp.concatenate(
                [kv_per_head(ring_v.astype(c.dtype), ng, dv), v], axis=1)
            ctx = sink_attention(q, ck, cv, visible(k_pos), sink, c.dtype)
            # slot j of the new ring holds the latest position p < idx + n
            # with p % window == j: this call's column where p >= idx, what
            # the ring held elsewhere. Columns past n are pad and never
            # enter
            pos = ring_positions(idx + n, w)                      # [w]
            mine = (pos >= idx)[None, :, None]
            src = jnp.clip(pos - idx, 0, l - 1)
            entry = (
                jnp.where(mine, merged(k)[:, src], ring_k).astype(
                    ring_k.dtype),
                jnp.where(mine, merged(v)[:, src], ring_v).astype(
                    ring_v.dtype))
        else:
            layer_k, layer_v = cache["k"][self.at], cache["v"][self.at]
            ck = jax.lax.dynamic_update_slice(
                layer_k, kv_stored(k, layer_k.shape[2:]),
                (0, idx) + (0,) * (layer_k.ndim - 2))
            cv = jax.lax.dynamic_update_slice(
                layer_v, kv_stored(v, layer_v.shape[2:]),
                (0, idx) + (0,) * (layer_v.ndim - 2))
            entry = (ck, cv)
            ctx = sink_attention(
                q, kv_per_head(ck, ng, dk), kv_per_head(cv, ng, dv),
                visible(jnp.arange(ck.shape[1])[None, :]), None, c.dtype)
        return jnp.dot(ctx, out_proj), entry


class MimoExperts(nn.Module):
    """The expert layer of this chip: the HELD experts' part of the routed
    sum, and nothing else (the family has no shared expert)."""

    config: MimoV2FlashConfig

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
            c.num_experts_per_tok, route_norm=c.norm_topk_prob,
            route_scale=c.routed_scaling_factor)
        routed, counts = dropless_experts(
            h, sel, w, _kernel(self, "experts_gate", (held, hid, f)),
            _kernel(self, "experts_up", (held, hid, f)),
            _kernel(self, "experts_down", (held, f, hid)),
            first_expert=c.first_expert)
        return routed.reshape(b, l, hid), counts


class MimoBlock(nn.Module):
    config: MimoV2FlashConfig
    layer_idx: int

    @nn.compact
    def __call__(self, x, *, cache: Optional[dict],
                 positions: Optional[jax.Array] = None):
        c = self.config
        hid, eps = c.hidden_size, c.rms_norm_eps
        kind = c.hybrid_layer_pattern[self.layer_idx]
        a, entry = MimoAttention(
            c, kind, c.index_in_kind(self.layer_idx), name="attn")(
            rms_norm(x, _gain(self, "input_norm", hid), eps),
            cache=cache, positions=positions)
        x = x + a
        h = rms_norm(x, _gain(self, "pre_mlp_norm", hid), eps)
        counts = None
        if c.moe_layer_freq[self.layer_idx]:
            m, counts = MimoExperts(c, name="moe")(h)
        else:
            m = AfmoeSwiGLU(c, c.intermediate_size, name="mlp")(h)
        return x + m, entry, counts


class MimoV2FlashLMHeadModel(nn.Module):
    """``__call__(input_ids, cache=None, positions=None)`` -> ``(logits
    float32, cache)`` under the three cache contracts of the module
    docstring. ``positions`` ([B, L]) override the rotary positions of this
    call's tokens only; masks always count from ``cache["idx"]``."""

    config: MimoV2FlashConfig

    @nn.compact
    def __call__(self, input_ids, *, cache: Optional[dict] = None,
                 positions: Optional[jax.Array] = None):
        c = self.config
        if cache is not None and "table" in cache and input_ids.shape[1] != 1:
            raise ValueError(
                "the mimo_v2_flash family's paged cache takes one token a "
                "row: a wider paged call (speculative verify) would need "
                "the rings rolled back")
        embed = self.param("embed_tokens", nn.initializers.normal(0.02),
                           (c.vocab_size, c.hidden_size), c.dtype)
        x = embed[input_ids]
        new_ks, new_vs, counts = [], [], []
        win_k = cache["win_k"] if cache is not None else None
        win_v = cache["win_v"] if cache is not None else None
        paged = cache is not None and "table" in cache
        for i in range(c.num_layers):
            x, entry, n = MimoBlock(c, i, name=f"layers_{i}")(
                x, cache=(None if cache is None
                          else dict(cache, win_k=win_k, win_v=win_v)),
                positions=positions)
            if n is not None:
                counts.append(n)
            if entry is None:
                continue
            if c.hybrid_layer_pattern[i] == FULL:
                new_ks.append(entry[0])
                new_vs.append(entry[1])
            elif paged:
                # the step wrote one column a live row into the arrays
                # themselves: they ride the caller's donated buffers
                win_k, win_v = entry
            else:
                at = c.index_in_kind(i)
                win_k = win_k.at[at].set(entry[0])
                win_v = win_v.at[at].set(entry[1])
        x = rms_norm(x, _gain(self, "norm", c.hidden_size), c.rms_norm_eps)
        logits = jnp.dot(x, _kernel(self, "lm_head",
                                    (c.hidden_size, c.vocab_size)),
                         preferred_element_type=jnp.float32)
        if cache is None:
            return logits, None
        out = {"k": jnp.stack(new_ks), "v": jnp.stack(new_vs),
               "idx": cache["idx"] + input_ids.shape[1],
               "win_k": win_k, "win_v": win_v}
        if counts:
            out["expert_counts"] = jnp.stack(counts)
        return logits, out


def init_mimo_v2_flash_cache(config: MimoV2FlashConfig, batch: int,
                             max_len: int) -> dict:
    """A zeroed dense cache with a scalar ``idx``: K/V of the full layers
    ``[full layers, B, max_len, *tail]`` as a pool stores a token, and the
    window layers' empty rings (prefill and lockstep decode outside the
    engine)."""
    fam = config.serving_family()
    head = (fam.kv_layers, batch, max_len)
    out = {"k": jnp.zeros(head + fam.kv_tail, config.dtype),
           "v": jnp.zeros(head + fam.v_tail, config.dtype),
           "idx": jnp.zeros((), jnp.int32)}
    for name, tail, dtype in fam.state_arrays:
        out[name] = jnp.zeros((fam.state_layers, batch) + tail, dtype)
    return out
