"""The ``lfm2_moe`` decoder family (LiquidAI LFM2-MoE): gated short
convolutions beside grouped-query attention, under sparse experts.

Three of every four layers keep no K/V and no recurrence: a gated short
convolution carries, per sequence, the LAST ``conv_L_cache - 1`` inputs of a
depthwise causal convolution and nothing else, whatever the length of the
context. Every fourth layer is grouped-query softmax attention over paged
K/V (``benchmark/reference_lfm2_moe.py`` is the plain float32 statement of
the same equations; what the published ``config.json`` does not settle is
listed under ``assumed`` in ``benchmark/configs/lfm2-24b-a2b-serve.json``):

- a layer ``l``: ``r = x + op_l(rms(x; operator_norm))``; ``y = r +
  ff_l(rms(r; ffn_norm))``; RMS norms in float32; after the last layer one
  more (``embedding_norm``), then the head, TIED to the embedding;
- ``op_l`` where ``layer_types[l]`` is ``conv``: ``[B, C, u] = split3(h
  W_in)`` (no bias); ``z = B * u``; ``c_t = sum_j w[j] * z_{t-(taps-1)+j}``
  with ``z`` zero before the sequence's first token (``taps`` =
  ``conv_L_cache``, one weight a channel a tap, no activation); ``out = (C *
  c) W_out``;
- ``op_l`` where it is ``full_attention``: ``num_heads`` query heads over
  ``num_kv_heads`` K/V heads, no biases; an RMS norm of every q and k HEAD
  (one gain of ``head_dim`` each) BEFORE the rotation; the rotation over the
  whole head, half-split pairs, base ``rope_theta``, by absolute position;
  causal softmax in float32 scaled by ``1/sqrt(head_dim)``;
- ``ff_l`` is SwiGLU of ``intermediate_size`` in the first
  ``num_dense_layers`` layers and an expert layer elsewhere: sigmoid scores
  in float32, the top ``k`` by score plus ``expert_bias``, weights the
  unbiased scores divided by their sum + :data:`ROUTE_NORM_EPS`, NO shared
  expert and NO token dropped (``parallel/moe_dropless.py``). The layer is
  told which experts this chip HOLDS (``first_expert``, ``experts_held``):
  it routes over all of them and computes its own experts' part.

**What a layer keeps.** An attention layer keeps K and V a token in the
engine's block pool (``kv_pool``: 8 heads of 64 side by side on one axis of
512). A convolution keeps NO block: its tail ``z_{t-2}, z_{t-1}`` lies by
slot, ``conv`` ``[conv layers, slots, taps - 1, hidden]`` in the compute
dtype, and rides the engine's arrays by slot
(``ServingFamily.state_arrays``, ``tail_columns``).

Two forms of the convolution, equal up to the order of three float32
additions: :func:`short_conv_step` shifts a tail by one token (decode);
:func:`short_conv_chunk` convolves a whole chunk after the tail and leaves
the tail AT THE CHUNK'S LAST REAL TOKEN (the engine pads a chunk to a
power-of-two width; a pad token's input never enters a tail).

The module keeps :class:`~sparkdl_tpu.models.gpt.GPTLMHeadModel`'s cache
contracts, with the tails beside the K/V:

- none: the whole causal forward from zero tails;
- dense (the engine's private prefill cache) ``{"k", "v", "idx", "conv"}``
  and optionally ``"n"``: K/V of the ATTENTION layers only ``[attention
  layers, 1, W, 512]`` written at the scalar ``idx``; ``conv`` ``[conv
  layers, B, taps - 1, hidden]`` as the calls before left it; ``n`` the
  count of real tokens in this call (all of them if absent);
- paged ``{"k", "v", "table", "idx", "conv", "live"}`` over the engine's
  pool, one token a row: the attention layers read their K/V through the
  table; ``conv`` is indexed by SLOT, and a row that is not ``live`` keeps
  its tail bit for bit. This call's new K/V columns and the whole updated
  ``conv`` come back.

A cached call also hands back ``expert_counts`` ``[expert_layers,
experts_held]``: rows each held expert was given.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from sparkdl_tpu.models.afmoe import (
    AfmoeSwiGLU,
    _gain,
    _grouped_attention,
    _kernel,
    rms_norm,
)
from sparkdl_tpu.models.family import ServingFamily
from sparkdl_tpu.models.gpt import apply_rope
from sparkdl_tpu.models.kv_pool import (
    kv_per_head,
    kv_stored,
    kv_tail,
    layer_rows,
)
from sparkdl_tpu.models.mimo_v2_flash import merged_sink_attention
from sparkdl_tpu.ops import paged_decode
from sparkdl_tpu.parallel.moe_dropless import (
    dropless_experts,
    route_sigmoid_topk,
)

CONV, FULL = "conv", "full_attention"
#: added to the sum of a token's selected scores before they are divided by
#: it (the family's modelling code; afmoe's and MiMo's add 1e-20)
ROUTE_NORM_EPS = 1e-6
#: the named scopes around the two forms of the convolution: every operation
#: of them carries one in its ``op_name`` in the compiled text (this
#: installation's device TRACE holds no metadata: the benchmark's readers
#: find the convolution by shape, ``benchmark/readers_lfm2_moe.py``)
STEP_SCOPE, CHUNK_SCOPE = "short_conv_step", "short_conv_chunk"


@dataclasses.dataclass(frozen=True)
class Lfm2MoeConfig:
    vocab_size: int = 65536
    hidden_size: int = 2048
    intermediate_size: int = 11776       #: a dense layer's SwiGLU width
    moe_intermediate_size: int = 1536    #: an expert's
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int = 64
    layer_types: "tuple[str, ...]" = (
        (CONV, CONV) + (FULL, CONV, CONV, CONV) * 9 + (FULL, CONV))
    num_dense_layers: int = 2
    conv_L_cache: int = 3                #: taps of the short convolution
    rope_theta: float = 1000000.0
    num_experts: int = 64
    num_experts_per_tok: int = 4
    norm_topk_prob: bool = True
    use_expert_bias: bool = True
    routed_scaling_factor: float = 1.0
    norm_eps: float = 1e-5
    #: the experts THIS chip holds of every expert layer (routing is over
    #: all ``num_experts``); None holds them all
    first_expert: int = 0
    experts_held: "int | None" = None
    dtype: Any = jnp.float32

    def __post_init__(self):
        bad = set(self.layer_types) - {CONV, FULL}
        if bad:
            raise ValueError(f"unknown layer types {sorted(bad)}")
        if self.conv_L_cache < 2:
            raise ValueError("conv_L_cache must be at least 2: a "
                             "convolution of one tap keeps no tail")
        if self.num_heads % self.num_kv_heads:
            raise ValueError("num_heads must be a multiple of num_kv_heads")
        if self.head_dim % 2:
            raise ValueError("a head of odd size cannot be rotated in pairs")
        if not 0 <= self.num_dense_layers <= self.num_layers:
            raise ValueError("num_dense_layers exceeds the layers")
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

    @property
    def expert_layers(self) -> int:
        return self.num_layers - self.num_dense_layers

    def layers_of(self, kind: str) -> int:
        return sum(t == kind for t in self.layer_types)

    def index_in_kind(self, layer: int) -> int:
        """``layer``'s place among the layers of its own kind: its row of
        the K/V pool (attention) or of the tails (convolution)."""
        kind = self.layer_types[layer]
        return sum(t == kind for t in self.layer_types[:layer])

    @classmethod
    def tiny(cls, **kw) -> "Lfm2MoeConfig":
        """Test-sized: a leading dense convolution, then one whole period of
        the published pattern (attention, convolution x 3) with experts."""
        defaults = dict(
            vocab_size=512, hidden_size=64, intermediate_size=96,
            moe_intermediate_size=32, num_heads=8, num_kv_heads=2,
            head_dim=8, layer_types=(CONV, FULL, CONV, CONV, CONV),
            num_dense_layers=1, num_experts=8, num_experts_per_tok=2,
        )
        defaults.update(kw)
        return cls(**defaults)

    def serving_family(self) -> ServingFamily:
        tail = kv_tail(self.num_kv_heads, self.head_dim)
        taps = self.conv_L_cache
        return ServingFamily(
            module=Lfm2MoeLMHeadModel(self), layers=self.num_layers,
            kv_heads=self.num_kv_heads, head_dim=self.head_dim,
            dtype=self.dtype, max_positions=None,
            expert_layers=self.expert_layers, experts=self.held,
            experts_per_token=self.num_experts_per_tok, paged_only=True,
            kv_layers=self.layers_of(FULL),
            decode_reads_in_place=paged_decode.reads_in_place(
                tail, tail, self.num_kv_heads, self.head_dim),
            state_layers=self.layers_of(CONV), tail_columns=taps - 1,
            state_arrays=(
                ("conv", (taps - 1, self.hidden_size), self.dtype),))


def config_from_hf_lfm2_moe(hf: dict, **kw) -> Lfm2MoeConfig:
    """Lfm2MoeConfig from the keys of an ``lfm2_moe`` ``config.json``.
    ``num_experts`` counts the experts the router scores; which of them this
    chip holds is ``first_expert`` / ``experts_held`` (keywords). Variants
    this forward does not compute are refused by name, not approximated."""
    if hf.get("model_type", "lfm2_moe") != "lfm2_moe":
        raise ValueError(f"not an lfm2_moe config: {hf.get('model_type')!r}")
    rope = dict(hf.get("rope_parameters") or {})
    for said in (rope, hf.get("rope_scaling") or {}):
        kind = said.get("rope_type") or said.get("type") or "default"
        if kind != "default":
            raise ValueError(f"rope_type {kind!r} is not implemented: only "
                             "the default rotation")
    theta = rope.get("rope_theta", hf.get("rope_theta"))
    if theta is None:
        raise ValueError("no rope_theta (neither under rope_parameters nor "
                         "at the top level)")
    layer_types = tuple(hf["layer_types"])
    bad = sorted(set(layer_types) - {CONV, FULL})
    if bad:
        raise ValueError(f"layer_types {bad} are not implemented: only "
                         f"{CONV!r} and {FULL!r}")
    if len(layer_types) != int(hf["num_hidden_layers"]):
        raise ValueError("layer_types and num_hidden_layers disagree")
    if hf.get("conv_bias", False):
        raise ValueError("conv_bias is not implemented: the family publishes "
                         "its convolutions without one")
    if hf.get("scoring_func", "sigmoid") != "sigmoid":
        raise ValueError("only sigmoid router scores are implemented")
    for key in ("n_group", "topk_group"):
        if (hf.get(key) or 1) != 1:
            raise ValueError(f"group-limited routing ({key} > 1) is not "
                             "implemented")
    for key in ("n_shared_experts", "num_shared_experts"):
        if hf.get(key):
            raise ValueError(f"a shared expert ({key}) is not implemented: "
                             "the family publishes none")
    if hf.get("hidden_act", "silu") != "silu":
        raise ValueError("only silu gated MLPs are implemented")
    if hf.get("attention_bias", False):
        raise ValueError("attention biases are not implemented")
    if not hf.get("tie_word_embeddings", True):
        raise ValueError("an untied head is not implemented: the family's "
                         "published configurations tie it")
    heads, hidden = int(hf["num_attention_heads"]), int(hf["hidden_size"])
    head_dim = hf.get("head_dim")
    if head_dim is None:
        if hidden % heads:
            raise ValueError("hidden_size is no multiple of the heads")
        head_dim = hidden // heads
    scale = hf.get("routed_scaling_factor")
    return Lfm2MoeConfig(
        vocab_size=int(hf["vocab_size"]), hidden_size=hidden,
        intermediate_size=int(hf["intermediate_size"]),
        moe_intermediate_size=int(hf["moe_intermediate_size"]),
        num_heads=heads,
        num_kv_heads=int(hf.get("num_key_value_heads", heads)),
        head_dim=int(head_dim), layer_types=layer_types,
        num_dense_layers=int(hf["num_dense_layers"]),
        conv_L_cache=int(hf["conv_L_cache"]), rope_theta=float(theta),
        num_experts=int(hf["num_experts"]),
        num_experts_per_tok=int(hf["num_experts_per_tok"]),
        norm_topk_prob=bool(hf.get("norm_topk_prob", True)),
        use_expert_bias=bool(hf.get("use_expert_bias", True)),
        routed_scaling_factor=1.0 if scale is None else float(scale),
        norm_eps=float(hf.get("norm_eps", 1e-5)), **kw)


# -- the gated short convolution ----------------------------------------------

def short_conv_step(z, tail, taps_w, live=None):
    """One token a row. ``z`` ``[B, C]``, this token's input; ``tail`` ``[B,
    taps - 1, C]``, the inputs before it, oldest first; ``taps_w`` ``[taps,
    C]`` -> ``(c [B, C] float32, tail)``: three multiply-adds a channel, and
    the tail shifted by one. A row that is not ``live`` (``[B]`` bool) keeps
    its tail bit for bit."""
    with jax.named_scope(STEP_SCOPE):
        f32 = jnp.float32
        w = taps_w.astype(f32)
        c = (jnp.sum(tail.astype(f32) * w[None, :-1], axis=1)
             + z.astype(f32) * w[-1])
        new = jnp.concatenate(
            [tail[:, 1:], z[:, None].astype(tail.dtype)], axis=1)
        if live is not None:
            new = jnp.where(live[:, None, None], new, tail)
        return c, new


def short_conv_chunk(z, tail, taps_w, n=None):
    """A whole chunk. ``z`` ``[B, L, C]``; ``tail`` ``[B, taps - 1, C]``, the
    inputs before the chunk; ``taps_w`` ``[taps, C]`` -> ``(c [B, L, C]
    float32, tail)``, the tail handed on ending at token ``n`` (a scalar:
    the count of REAL tokens among the ``L``; None: all of them), so that no
    pad token's input enters it."""
    with jax.named_scope(CHUNK_SCOPE):
        f32 = jnp.float32
        taps, length = taps_w.shape[0], z.shape[1]
        # ext[t + j] is the input (taps - 1) - j tokens before token t
        ext = jnp.concatenate([tail.astype(z.dtype), z], axis=1)
        w = taps_w.astype(f32)
        c = sum(ext[:, j:j + length].astype(f32) * w[j] for j in range(taps))
        new = (ext[:, length:] if n is None else
               jax.lax.dynamic_slice_in_dim(ext, n, taps - 1, axis=1))
        return c, new.astype(tail.dtype)


class Lfm2ShortConv(nn.Module):
    """A gated short convolution. ``tail`` is this layer's ``[B, taps - 1,
    hidden]`` (zeros where None), ``n`` the count of real tokens among the
    ``L`` (a scalar; None: all), ``live`` the rows whose tail may move
    (``[B]`` bool; None: all). Returns ``(y, tail)``."""

    config: Lfm2MoeConfig

    @nn.compact
    def __call__(self, x, *, tail=None, n=None, live=None, step=False):
        c = self.config
        b, l, hid = x.shape
        taps = c.conv_L_cache
        bcu = jnp.dot(x, _kernel(self, "in_proj", (hid, 3 * hid)))
        gate_b, gate_c, u = jnp.split(bcu, 3, axis=-1)
        taps_w = _kernel(self, "conv", (taps, hid))
        z = gate_b * u
        if tail is None:
            tail = jnp.zeros((b, taps - 1, hid), z.dtype)
        if step:
            mixed, new_tail = short_conv_step(z[:, 0], tail, taps_w, live)
            mixed = mixed[:, None]
        else:
            mixed, new_tail = short_conv_chunk(z, tail, taps_w, n)
        y = gate_c * mixed.astype(c.dtype)
        return jnp.dot(y, _kernel(self, "out_proj", (hid, hid))), new_tail


# -- attention --------------------------------------------------------------------

class Lfm2Attention(nn.Module):
    """A grouped-query attention layer; ``kv_index`` is its row of the K/V
    arrays (its place among the ATTENTION layers)."""

    config: Lfm2MoeConfig
    kv_index: int

    @nn.compact
    def __call__(self, x, *, cache: Optional[dict],
                 positions: Optional[jax.Array] = None):
        c = self.config
        b, l, hid = x.shape
        nh, ng, hd = c.num_heads, c.num_kv_heads, c.head_dim
        q = jnp.dot(x, _kernel(self, "q_proj", (hid, nh * hd)))
        k = jnp.dot(x, _kernel(self, "k_proj", (hid, ng * hd)))
        v = jnp.dot(x, _kernel(self, "v_proj", (hid, ng * hd)))
        # a head at a time, one gain of head_dim for all of them, BEFORE the
        # rotation
        q = rms_norm(q.reshape(b, l, nh, hd),
                     _gain(self, "q_layernorm", hd), c.norm_eps)
        k = rms_norm(k.reshape(b, l, ng, hd),
                     _gain(self, "k_layernorm", hd), c.norm_eps)
        v = v.reshape(b, l, ng, hd).astype(c.dtype)

        idx = cache["idx"] if cache is not None else jnp.zeros((), jnp.int32)
        # [1|B, L] positions of this call's tokens: masks always count from
        # the cache's depth; the rotation takes the caller's ``positions``
        # where it gives them (the engine clamps a padded chunk's tail)
        q_pos = jnp.reshape(idx, (-1, 1)) + jnp.arange(l)[None, :]
        rope_pos = jnp.broadcast_to(
            q_pos if positions is None else positions, (b, l))
        q = apply_rope(q, rope_pos, c.rope_theta)
        k = apply_rope(k, rope_pos, c.rope_theta).astype(c.dtype)

        def visible(k_pos):
            return q_pos[:, :, None] >= k_pos[:, None, :]

        out_proj = _kernel(self, "out_proj", (nh * hd, hid))
        if cache is None:
            ctx = _grouped_attention(
                q, k, v, visible(jnp.arange(l)[None, :]), c.dtype)
            return jnp.dot(ctx, out_proj), None

        if "table" in cache:
            # one query a row, every row at its own depth. This call's
            # column joins the softmax beside the old ones and is written
            # into nothing here (the step's one column write is the
            # engine's)
            tail = cache["k"].shape[3:]
            k_new = k[:, 0].reshape(b, ng * hd)
            v_new = v[:, 0].reshape(b, ng * hd)
            if paged_decode.reads_in_place(tail, cache["v"].shape[3:], ng,
                                           hd):
                # (8 heads of 64 on one unpadded axis of 512: whole lane
                # tiles, which the rule takes; a tiny configuration's axis
                # of 16 it does not)
                ctx = paged_decode.paged_decode_attention(
                    q, cache["k"], cache["v"], self.kv_index,
                    cache["table"], idx, k_new[:, None], v_new[:, None])
            else:
                # the rows come through the table as the pool stores them,
                # every slot's at the deepest row's bucket, and are never
                # reshaped to heads (a head of 64 is a padded copy of every
                # row): ONE product over the merged axis for each group of
                # query heads' own K/V head
                k_old, v_old = (
                    a.reshape(b, a.shape[1], -1) if a.ndim > 3 else a
                    for a in layer_rows(cache, self.kv_index,
                                        cache["table"], c.dtype))
                seen = jnp.arange(k_old.shape[1])[None, :] < idx[:, None]
                ctx = merged_sink_attention(
                    q[:, 0], k_old[..., :ng * hd], v_old[..., :ng * hd],
                    k_new, v_new, seen, None, ng)[:, None]
            entry = (kv_stored(k, tail), kv_stored(v, cache["v"].shape[3:]))
            return jnp.dot(ctx, out_proj), entry

        if jnp.ndim(idx) != 0:
            raise ValueError(
                "the lfm2_moe family's dense cache takes a scalar idx; "
                "per-slot decode is the paged cache's")
        layer_k, layer_v = cache["k"][self.kv_index], cache["v"][self.kv_index]
        at = (0, idx) + (0,) * (layer_k.ndim - 2)
        ck = jax.lax.dynamic_update_slice(
            layer_k, kv_stored(k, layer_k.shape[2:]), at)
        cv = jax.lax.dynamic_update_slice(
            layer_v, kv_stored(v, layer_v.shape[2:]), at)
        ctx = _grouped_attention(
            q, kv_per_head(ck, ng, hd), kv_per_head(cv, ng, hd),
            visible(jnp.arange(ck.shape[1])[None, :]), c.dtype)
        return jnp.dot(ctx, out_proj), (ck, cv)


# -- layers -----------------------------------------------------------------------

class Lfm2Experts(nn.Module):
    """The expert layer of this chip: the HELD experts' part of the routed
    sum, and nothing else (the family has no shared expert)."""

    config: Lfm2MoeConfig

    @nn.compact
    def __call__(self, x):
        c = self.config
        b, l, hid = x.shape
        f, held = c.moe_intermediate_size, c.held
        h = x.reshape(b * l, hid)
        bias = self.param("expert_bias", nn.initializers.zeros,
                          (c.num_experts,), jnp.float32)
        sel, w = route_sigmoid_topk(
            h, _kernel(self, "router", (hid, c.num_experts), jnp.float32),
            bias if c.use_expert_bias else jnp.zeros_like(bias),
            c.num_experts_per_tok, route_norm=c.norm_topk_prob,
            route_scale=c.routed_scaling_factor, norm_eps=ROUTE_NORM_EPS)
        routed, counts = dropless_experts(
            h, sel, w, _kernel(self, "experts_gate", (held, hid, f)),
            _kernel(self, "experts_up", (held, hid, f)),
            _kernel(self, "experts_down", (held, f, hid)),
            first_expert=c.first_expert)
        return routed.reshape(b, l, hid), counts


class Lfm2Block(nn.Module):
    config: Lfm2MoeConfig
    layer_idx: int

    @nn.compact
    def __call__(self, x, *, cache: Optional[dict],
                 positions: Optional[jax.Array] = None):
        c = self.config
        hid, eps = c.hidden_size, c.norm_eps
        at = c.index_in_kind(self.layer_idx)
        h = rms_norm(x, _gain(self, "operator_norm", hid), eps)
        if c.layer_types[self.layer_idx] == FULL:
            a, entry = Lfm2Attention(c, at, name="self_attn")(
                h, cache=cache, positions=positions)
        else:
            cache = cache or {}
            a, entry = Lfm2ShortConv(c, name="conv")(
                h, tail=None if "conv" not in cache else cache["conv"][at],
                n=cache.get("n"), live=cache.get("live"),
                step="table" in cache)
        x = x + a
        h = rms_norm(x, _gain(self, "ffn_norm", hid), eps)
        counts = None
        if self.layer_idx < c.num_dense_layers:
            m = AfmoeSwiGLU(c, c.intermediate_size, name="feed_forward")(h)
        else:
            m, counts = Lfm2Experts(c, name="moe")(h)
        return x + m, entry, counts


class Lfm2MoeLMHeadModel(nn.Module):
    """``__call__(input_ids, cache=None, positions=None)`` -> ``(logits
    float32, cache)`` under the three cache contracts of the module
    docstring. ``positions`` ([B, L]) override the rotary positions of this
    call's tokens only; masks always count from ``cache["idx"]``."""

    config: Lfm2MoeConfig

    @nn.compact
    def __call__(self, input_ids, *, cache: Optional[dict] = None,
                 positions: Optional[jax.Array] = None):
        c = self.config
        if cache is not None and "table" in cache and input_ids.shape[1] != 1:
            raise ValueError(
                "the lfm2_moe family's paged cache takes one token a row: a "
                "wider paged call (speculative verify) would need the "
                "convolutions' tails rolled back")
        embed = self.param("embed_tokens", nn.initializers.normal(0.02),
                           (c.vocab_size, c.hidden_size), c.dtype)
        x = embed[input_ids]
        new_ks, new_vs, counts = [], [], []
        conv = cache["conv"] if cache is not None else None
        for i in range(c.num_layers):
            x, entry, n = Lfm2Block(c, i, name=f"layers_{i}")(
                x, cache=None if cache is None else dict(cache, conv=conv),
                positions=positions)
            if n is not None:
                counts.append(n)
            if cache is None:
                continue
            if c.layer_types[i] == FULL:
                new_ks.append(entry[0])
                new_vs.append(entry[1])
            else:
                # each layer's row written back where it was read: the array
                # rides the caller's donated buffer in place
                conv = conv.at[c.index_in_kind(i)].set(entry)
        x = rms_norm(x, _gain(self, "embedding_norm", c.hidden_size),
                     c.norm_eps)
        # the head is the embedding's transpose
        logits = jnp.einsum("blh,vh->blv", x, embed,
                            preferred_element_type=jnp.float32)
        if cache is None:
            return logits, None
        out = {"k": jnp.stack(new_ks), "v": jnp.stack(new_vs),
               "idx": cache["idx"] + input_ids.shape[1], "conv": conv}
        if counts:
            out["expert_counts"] = jnp.stack(counts)
        return logits, out


def init_lfm2_moe_cache(config: Lfm2MoeConfig, batch: int,
                        max_len: int) -> dict:
    """A zeroed dense cache with a scalar ``idx``: K/V of the attention
    layers ``[attention layers, B, max_len, *tail]`` as a pool stores a
    token, and the convolutions' zero tails (prefill and lockstep decode
    outside the engine)."""
    fam = config.serving_family()
    head = (fam.kv_layers, batch, max_len)
    out = {"k": jnp.zeros(head + fam.kv_tail, config.dtype),
           "v": jnp.zeros(head + fam.v_tail, config.dtype),
           "idx": jnp.zeros((), jnp.int32)}
    for name, tail, dtype in fam.state_arrays:
        out[name] = jnp.zeros((fam.state_layers, batch) + tail, dtype)
    return out
