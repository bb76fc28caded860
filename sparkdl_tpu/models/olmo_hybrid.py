"""The ``olmo_hybrid`` decoder family (AllenAI Olmo Hybrid): gated-delta-rule
linear attention beside full attention.

Three of every four layers keep no K/V. A linear-attention layer carries,
per sequence and per head, ONE float32 state ``S`` of ``[d_k, d_v]`` and the
last ``kernel - 1`` inputs of its short convolutions, whatever the length of
the context; every fourth layer is full softmax attention over paged K/V
(``benchmark/reference_olmo_hybrid.py`` is the plain float32 statement of
the same equations, token by token):

- linear layer, per token ``t`` and head: ``q~, k~, v~`` (no bias) pass a
  causal depthwise convolution of ``kernel`` taps and SiLU; ``q`` and ``k``
  are L2-normalised (``q`` also scaled by ``1/sqrt(d_k)``);
  ``beta = sigmoid(W_b x)`` (doubled where ``linear_allow_neg_eigval``),
  ``g = -exp(A_log) softplus(W_a x + dt_bias)``, ``alpha = exp(g)``;
  ``S_t = alpha S_{t-1} + beta k (v - alpha S_{t-1}^T k)^T``;
  ``o_t = S_t^T q``; ``y = W_o [rms_norm(o) * silu(W_g x)]``;
- full layer: q, k, v, o without bias, an RMS norm of q and of k over the
  WHOLE projection before the heads are split, causal softmax, no rotation
  (the config gives no rope base: positions reach a full layer through the
  recurrence under it);
- a block is ``x + norm(mixer(x))`` then ``x + norm(mlp(x))`` with the norm
  on each branch's OUTPUT, a SwiGLU MLP, a last RMS norm, an untied head.

Two forms of the recurrence, equal up to rounding (``tests/models/
test_olmo_hybrid.py`` holds both to the token-by-token one in float64):
:func:`gated_delta_step` advances a state by one token (decode);
:func:`gated_delta_chunked` advances it by a whole chunk in sub-chunks of
:data:`SUB_CHUNK` tokens, the WY / UT form: inside a sub-chunk the
interactions are one triangular system a head, solved by forward
substitution by blocks in ``ops/delta_solve`` (by ``solve_triangular``, the
definition, where that module's rule refuses the shapes), and a few matrix
products, between sub-chunks the state is carried; decays are accumulated
in log space, so no factor ever exceeds one. A token past the chunk's real
count (the engine pads a chunk to a power-of-two width) has ``beta = 0`` and
``g = 0``: it leaves the state untouched bit for bit, and the convolutions'
tails are taken at the last REAL token.

The module keeps :class:`~sparkdl_tpu.models.gpt.GPTLMHeadModel`'s cache
contracts, with the state beside the K/V:

- none: the whole causal forward from a zero state;
- dense (the engine's private prefill cache) ``{"k", "v", "idx", "state",
  "conv"}`` and optionally ``"n"``: K/V of the FULL layers only ``[full
  layers, 1, W, heads, head_dim]`` written at the scalar ``idx``; the
  running ``state`` ``[linear layers, B, heads, d_k, d_v]`` float32 and
  ``conv`` tails ``[linear layers, B, kernel - 1, channels]``; ``n`` the
  count of real tokens in this call (all of them if absent);
- paged ``{"k", "v", "table", "idx", "state", "conv", "live"}`` over the
  engine's pool, one token a row: the full layers read their K/V through
  the table; ``state`` and ``conv`` are indexed by SLOT, and a row that is
  not ``live`` keeps both bit for bit. This call's new K/V columns and the
  whole updated ``state`` and ``conv`` arrays come back.
"""

from __future__ import annotations

import dataclasses
import math
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
from sparkdl_tpu.models.gpt import merged_axis_attention
from sparkdl_tpu.models.kv_pool import (
    kv_per_head,
    kv_stored,
    kv_tail,
    layer_rows,
)
from sparkdl_tpu.ops import delta_solve, paged_decode

LINEAR, FULL = "linear_attention", "full_attention"
#: tokens a sub-chunk of the chunkwise recurrence: one triangular system of
#: this order a head
SUB_CHUNK = 64
#: under the square root of the q and k L2 norms
L2_EPS = 1e-6
#: the named scopes around the two forms of the recurrence: every operation
#: of them carries one in its ``op_name`` in the compiled text (this
#: installation's device TRACE does not hold metadata: the benchmark's
#: readers find the recurrence by shape, ``benchmark/readers_olmo_hybrid.py``)
SCAN_SCOPE, STEP_SCOPE = "gated_delta_scan", "gated_delta_step"


@dataclasses.dataclass(frozen=True)
class OlmoHybridConfig:
    vocab_size: int = 100352
    hidden_size: int = 3840
    intermediate_size: int = 11008
    num_heads: int = 30
    head_dim: int = 128
    layer_types: "tuple[str, ...]" = (LINEAR, LINEAR, LINEAR, FULL) * 8
    linear_num_heads: int = 30
    linear_key_head_dim: int = 96
    linear_value_head_dim: int = 192
    linear_conv_kernel_dim: int = 4
    linear_allow_neg_eigval: bool = True
    rms_norm_eps: float = 1e-6
    dtype: Any = jnp.float32

    def __post_init__(self):
        bad = set(self.layer_types) - {LINEAR, FULL}
        if bad:
            raise ValueError(f"unknown layer types {sorted(bad)}")
        if self.linear_conv_kernel_dim < 2:
            raise ValueError("linear_conv_kernel_dim must be at least 2")

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @property
    def conv_channels(self) -> int:
        """q~, k~ and v~ side by side: what the convolutions run over."""
        return self.linear_num_heads * (2 * self.linear_key_head_dim
                                        + self.linear_value_head_dim)

    def layers_of(self, kind: str) -> int:
        return sum(t == kind for t in self.layer_types)

    def index_in_kind(self, layer: int) -> int:
        """``layer``'s place among the layers of its own kind: its row of
        the K/V pool (full) or of the state arrays (linear)."""
        kind = self.layer_types[layer]
        return sum(t == kind for t in self.layer_types[:layer])

    @classmethod
    def tiny(cls, **kw) -> "OlmoHybridConfig":
        """Test-sized: one whole period of the published pattern."""
        defaults = dict(
            vocab_size=512, hidden_size=64, intermediate_size=96,
            num_heads=4, head_dim=16,
            layer_types=(LINEAR, LINEAR, LINEAR, FULL),
            linear_num_heads=4, linear_key_head_dim=8,
            linear_value_head_dim=16,
        )
        defaults.update(kw)
        return cls(**defaults)

    def serving_family(self) -> ServingFamily:
        h = self.linear_num_heads
        tail = kv_tail(self.num_heads, self.head_dim)
        return ServingFamily(
            module=OlmoHybridLMHeadModel(self), layers=self.num_layers,
            kv_heads=self.num_heads, head_dim=self.head_dim,
            dtype=self.dtype, max_positions=None, paged_only=True,
            kv_layers=self.layers_of(FULL),
            decode_reads_in_place=paged_decode.reads_in_place(
                tail, tail, self.num_heads, self.head_dim),
            scan_solved_in_kernel=delta_solve.solves_in_kernel(
                SUB_CHUNK, self.linear_key_head_dim
                + self.linear_value_head_dim, jnp.float32),
            state_layers=self.layers_of(LINEAR),
            state_arrays=(
                ("state", (h, self.linear_key_head_dim,
                           self.linear_value_head_dim), jnp.float32),
                ("conv", (self.linear_conv_kernel_dim - 1,
                          self.conv_channels), self.dtype)))


def config_from_hf_olmo_hybrid(hf: dict, **kw) -> OlmoHybridConfig:
    """OlmoHybridConfig from the keys of an ``olmo_hybrid`` ``config.json``.
    Variants this forward does not compute are refused, not approximated."""
    if hf.get("model_type", "olmo_hybrid") != "olmo_hybrid":
        raise ValueError(f"not an olmo_hybrid config: {hf.get('model_type')!r}")
    rope = hf.get("rope_parameters") or {}
    if rope.get("rope_theta") is not None or hf.get("rope_theta") is not None:
        raise ValueError("a rope base is not implemented: this forward "
                         "rotates nothing")
    if hf.get("attention_bias", False):
        raise ValueError("attention biases are not implemented")
    if hf.get("tie_word_embeddings", False):
        raise ValueError("a tied head is not implemented")
    if hf.get("hidden_act", "silu") != "silu":
        raise ValueError("only silu gated MLPs are implemented")
    heads = int(hf["num_attention_heads"])
    if int(hf.get("num_key_value_heads", heads)) != heads:
        raise ValueError("grouped K/V heads are not implemented")
    if int(hf["hidden_size"]) % heads:
        raise ValueError("hidden_size is no multiple of the heads")
    if int(hf["linear_num_key_heads"]) != int(hf["linear_num_value_heads"]):
        raise ValueError("linear key and value heads must be as many")
    layer_types = tuple(hf["layer_types"])
    if len(layer_types) != int(hf["num_hidden_layers"]):
        raise ValueError("layer_types and num_hidden_layers disagree")
    return OlmoHybridConfig(
        vocab_size=int(hf["vocab_size"]), hidden_size=int(hf["hidden_size"]),
        intermediate_size=int(hf["intermediate_size"]), num_heads=heads,
        head_dim=int(hf.get("head_dim") or int(hf["hidden_size"]) // heads),
        layer_types=layer_types,
        linear_num_heads=int(hf["linear_num_value_heads"]),
        linear_key_head_dim=int(hf["linear_key_head_dim"]),
        linear_value_head_dim=int(hf["linear_value_head_dim"]),
        linear_conv_kernel_dim=int(hf["linear_conv_kernel_dim"]),
        linear_allow_neg_eigval=bool(hf.get("linear_allow_neg_eigval",
                                            False)),
        rms_norm_eps=float(hf.get("rms_norm_eps", 1e-6)), **kw)


# -- the gated delta rule -------------------------------------------------------

_HIGHEST = jax.lax.Precision.HIGHEST


def gated_delta_step(q, k, v, g, beta, state):
    """One token. q, k ``[B, H, d_k]``; v ``[B, H, d_v]``; g, beta ``[B,
    H]``; state ``[B, H, d_k, d_v]`` float32 -> ``(o [B, H, d_v], state)``.
    Sums over ``d_k`` in float32, no matrix unit: what a row reads and
    writes is its state, once each."""
    with jax.named_scope(STEP_SCOPE):
        q, k, v, g, beta = (x.astype(jnp.float32) for x in (q, k, v, g, beta))
        state = state * jnp.exp(g)[..., None, None]
        seen = jnp.sum(state * k[..., None], axis=-2)
        state = state + (beta[..., None] * k)[..., None] * (
            v - seen)[..., None, :]
        return jnp.sum(state * q[..., None], axis=-2), state


def gated_delta_recurrent(q, k, v, g, beta, state):
    """:func:`gated_delta_step` over the tokens of ``[B, L, H, ...]``, one
    after the other: the definition the other forms are held to."""
    def body(state, x):
        o, state = gated_delta_step(*x, state)
        return state, o

    state, o = jax.lax.scan(
        body, state, tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), state


def gated_delta_chunked(q, k, v, g, beta, state, sub: int = SUB_CHUNK):
    """A whole chunk. q, k ``[B, L, H, d_k]``; v ``[B, L, H, d_v]``; g, beta
    ``[B, L, H]``; state ``[B, H, d_k, d_v]`` float32 -> ``(o [B, L, H,
    d_v], state)``, float32 throughout. ``L`` is padded here to whole
    sub-chunks with tokens of ``beta = 0``, ``g = 0``, which change nothing.

    Inside a sub-chunk, with ``c_i`` the running sum of ``g`` and ``A`` the
    strictly lower triangle of ``beta_i (k_i . k_j) exp(c_i - c_j)``: ``T =
    (I + A)^-1`` gives every token's corrected value (``u = T beta v``, ``w
    = T beta k exp(c)``) by forward substitution by blocks in
    ``ops/delta_solve`` where its rule takes the shapes and by
    ``solve_triangular`` where not; never by a product of powers of ``A``,
    which cancel catastrophically where keys repeat. With the state ``S`` at
    the sub-chunk's start: ``v_new = u - w S``; ``o = (q exp(c)) S + tril(q
    k^T exp(c_i - c_j)) v_new``; ``S <- exp(c_last) S + (k exp(c_last -
    c))^T v_new``.
    """
    with jax.named_scope(SCAN_SCOPE):
        f32 = jnp.float32
        q, k, v, g, beta = (x.astype(f32) for x in (q, k, v, g, beta))
        b, length, h, dk = q.shape
        pad = -length % sub
        if pad:
            q, k, v, g, beta = (
                jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
                for x in (q, k, v, g, beta))
        n = (length + pad) // sub

        def split(x):  # [B, L, H, ...] -> [B, H, n, sub, ...]
            x = x.reshape((b, n, sub) + x.shape[2:])
            return jnp.moveaxis(x, 3, 1)

        q, k, v, g, beta = map(split, (q, k, v, g, beta))
        c = jnp.cumsum(g, axis=-1)                       # [B, H, n, sub]
        rel = c[..., :, None] - c[..., None, :]          # c_i - c_j
        lower = jnp.tril(jnp.ones((sub, sub), bool))
        decay = jnp.where(lower, jnp.exp(jnp.where(lower, rel, 0.0)), 0.0)
        kb = k * beta[..., None]
        kk = jnp.einsum("...id,...jd->...ij", kb, k, precision=_HIGHEST)
        a = jnp.where(jnp.tril(jnp.ones((sub, sub), bool), -1),
                      kk * decay, 0.0)
        rhs = jnp.concatenate(
            [kb * jnp.exp(c)[..., None], v * beta[..., None]], axis=-1)
        if delta_solve.solves_in_kernel(sub, rhs.shape[-1], f32):
            solved = delta_solve.delta_solve(a, rhs)
        else:
            solved = jax.scipy.linalg.solve_triangular(
                a + jnp.eye(sub, dtype=f32), rhs, lower=True,
                unit_diagonal=True)
        w, u = solved[..., :dk], solved[..., dk:]
        qk = jnp.einsum("...id,...jd->...ij", q, k,
                        precision=_HIGHEST) * decay
        q_in = q * jnp.exp(c)[..., None]
        k_out = k * jnp.exp(c[..., -1:] - c)[..., None]
        last = jnp.exp(c[..., -1])                       # [B, H, n]

        def body(state, x):
            w, u, qk, q_in, k_out, last = x
            v_new = u - jnp.einsum("bhik,bhkv->bhiv", w, state,
                                   precision=_HIGHEST)
            o = (jnp.einsum("bhik,bhkv->bhiv", q_in, state,
                            precision=_HIGHEST)
                 + jnp.einsum("bhij,bhjv->bhiv", qk, v_new,
                              precision=_HIGHEST))
            state = (state * last[..., None, None]
                     + jnp.einsum("bhik,bhiv->bhkv", k_out, v_new,
                                  precision=_HIGHEST))
            return state, o

        state, o = jax.lax.scan(
            body, state.astype(f32),
            tuple(jnp.moveaxis(x, 2, 0)
                  for x in (w, u, qk, q_in, k_out, last)))
        # [n, B, H, sub, d_v] -> [B, L, H, d_v]
        o = jnp.moveaxis(o, 0, 2).reshape(b, h, n * sub, -1)
        return jnp.moveaxis(o, 1, 2)[:, :length], state


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


# -- layers -----------------------------------------------------------------------

class GatedDeltaNet(nn.Module):
    """A linear-attention layer. ``recur`` is this layer's ``(state [B, H,
    d_k, d_v], conv [B, kernel - 1, channels])`` (zeros where None), ``n``
    the count of real tokens among the ``L`` (a scalar; None: all), ``live``
    the rows whose state may move (``[B]`` bool; None: all). Returns ``(y,
    (state, conv))``."""

    config: OlmoHybridConfig

    @nn.compact
    def __call__(self, x, *, recur=None, n=None, live=None):
        c = self.config
        b, l, hid = x.shape
        h, dk, dv = (c.linear_num_heads, c.linear_key_head_dim,
                     c.linear_value_head_dim)
        taps = c.linear_conv_kernel_dim
        f32 = jnp.float32

        u = jnp.concatenate([
            jnp.dot(x, _kernel(self, "q_proj", (hid, h * dk))),
            jnp.dot(x, _kernel(self, "k_proj", (hid, h * dk))),
            jnp.dot(x, _kernel(self, "v_proj", (hid, h * dv)))], axis=-1)
        conv_w = jnp.concatenate([
            self.param(name, nn.initializers.normal(0.2), (taps, width), f32)
            for name, width in (("conv_q", h * dk), ("conv_k", h * dk),
                                ("conv_v", h * dv))], axis=-1)
        if recur is None:
            state = jnp.zeros((b, h, dk, dv), f32)
            tail = jnp.zeros((b, taps - 1, u.shape[-1]), u.dtype)
        else:
            state, tail = recur
        # the convolution sees the last taps-1 inputs before this call, then
        # this call's: ext[t + j] is the input j - (taps-1) tokens from t
        ext = jnp.concatenate([tail.astype(u.dtype), u], axis=1)
        mixed = sum(ext[:, j:j + l].astype(f32) * conv_w[j]
                    for j in range(taps))
        mixed = jax.nn.silu(mixed)
        # the tail handed on ends at the last REAL token
        new_tail = (ext[:, l:] if n is None else
                    jax.lax.dynamic_slice_in_dim(ext, n, taps - 1, axis=1))
        q = _l2norm(mixed[..., :h * dk].reshape(b, l, h, dk)) / math.sqrt(dk)
        k = _l2norm(mixed[..., h * dk:2 * h * dk].reshape(b, l, h, dk))
        v = mixed[..., 2 * h * dk:].reshape(b, l, h, dv)

        beta = jax.nn.sigmoid(jnp.dot(
            x, _kernel(self, "b_proj", (hid, h)),
            preferred_element_type=f32))
        if c.linear_allow_neg_eigval:
            beta = beta * 2.0
        a_log = self.param("A_log", nn.initializers.zeros, (h,), f32)
        dt_bias = self.param("dt_bias", nn.initializers.zeros, (h,), f32)
        g = -jnp.exp(a_log) * jax.nn.softplus(jnp.dot(
            x, _kernel(self, "a_proj", (hid, h)),
            preferred_element_type=f32) + dt_bias)
        if n is not None:
            # a pad token moves nothing: no write (beta 0), no decay (g 0)
            real = (jnp.arange(l) < n)[None, :, None]
            beta, g = jnp.where(real, beta, 0.0), jnp.where(real, g, 0.0)

        if l == 1:
            o, new_state = gated_delta_step(
                q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], state)
            o = o[:, None]
        else:
            o, new_state = gated_delta_chunked(q, k, v, g, beta, state)
        if live is not None:
            new_state = jnp.where(live[:, None, None, None], new_state, state)
            new_tail = jnp.where(live[:, None, None], new_tail,
                                 tail.astype(new_tail.dtype))

        gate = jnp.dot(x, _kernel(self, "g_proj", (hid, h * dv)))
        o = rms_norm(o, _gain(self, "o_norm", dv), c.rms_norm_eps)
        o = (o * jax.nn.silu(gate.astype(f32)).reshape(b, l, h, dv)).astype(
            c.dtype).reshape(b, l, h * dv)
        y = jnp.dot(o, _kernel(self, "o_proj", (h * dv, hid)))
        return y, (new_state, new_tail.astype(tail.dtype))


class OlmoHybridAttention(nn.Module):
    """A full-attention layer; ``kv_index`` is its row of the K/V arrays
    (its place among the FULL layers)."""

    config: OlmoHybridConfig
    kv_index: int

    @nn.compact
    def __call__(self, x, *, cache: Optional[dict]):
        c = self.config
        b, l, hid = x.shape
        nh, hd = c.num_heads, c.head_dim
        eps = c.rms_norm_eps
        q = jnp.dot(x, _kernel(self, "q_proj", (hid, nh * hd)))
        k = jnp.dot(x, _kernel(self, "k_proj", (hid, nh * hd)))
        v = jnp.dot(x, _kernel(self, "v_proj", (hid, nh * hd)))
        # over the WHOLE projection, before the heads are split
        q = rms_norm(q, _gain(self, "q_norm", nh * hd), eps).reshape(
            b, l, nh, hd)
        k = rms_norm(k, _gain(self, "k_norm", nh * hd), eps).reshape(
            b, l, nh, hd)
        v = v.reshape(b, l, nh, hd)

        idx = cache["idx"] if cache is not None else jnp.zeros((), jnp.int32)
        q_pos = jnp.reshape(idx, (-1, 1)) + jnp.arange(l)[None, :]

        def visible(k_pos):
            return q_pos[:, :, None] >= k_pos[:, None, :]

        new_entry = None
        if cache is None:
            ctx = _grouped_attention(
                q, k, v, visible(jnp.arange(l)[None, :]), c.dtype)
        elif "table" in cache:
            # one query a row, every row at its own depth. This call's
            # column joins the softmax beside the old ones and is written
            # into nothing here (the step's one column write is the
            # engine's)
            tail = cache["k"].shape[3:]
            merged = (math.prod(tail),)
            k_new = kv_stored(k.astype(c.dtype), merged)
            v_new = kv_stored(v.astype(c.dtype), merged)
            if paged_decode.reads_in_place(tail, cache["v"].shape[3:], nh,
                                           hd):
                # heads of whole lane tiles on one unpadded axis: the old
                # columns are read where the pool keeps them, each row's
                # own blocks and no more (ops/paged_decode.py)
                ctx = paged_decode.paged_decode_attention(
                    q, cache["k"], cache["v"], self.kv_index,
                    cache["table"], idx, k_new, v_new)
            else:
                # the rows come through the table as the pool stores them
                # and are never reshaped to heads (models/gpt.py,
                # merged_axis_attention)
                k_old, v_old = (
                    a.reshape(b, a.shape[1], -1) if a.ndim > 3 else a
                    for a in layer_rows(cache, self.kv_index,
                                        cache["table"], c.dtype))
                ctx = merged_axis_attention(q, k_old, v_old, k_new, v_new, idx)
            ctx = ctx.reshape(b, l, nh * hd)
            new_entry = (k_new.reshape(b, l, *tail),
                         v_new.reshape(b, l, *tail))
        else:
            if jnp.ndim(idx) != 0:
                raise ValueError(
                    "the olmo_hybrid family's dense cache takes a scalar "
                    "idx; per-slot decode is the paged cache's")
            layer_k = cache["k"][self.kv_index]
            at = (0, idx) + (0,) * (layer_k.ndim - 2)
            tail = layer_k.shape[2:]
            ck = jax.lax.dynamic_update_slice(
                layer_k, kv_stored(k.astype(c.dtype), tail), at)
            cv = jax.lax.dynamic_update_slice(
                cache["v"][self.kv_index],
                kv_stored(v.astype(c.dtype), tail), at)
            new_entry = (ck, cv)
            ck, cv = kv_per_head(ck, nh, hd), kv_per_head(cv, nh, hd)
            ctx = _grouped_attention(
                q, ck, cv, visible(jnp.arange(ck.shape[1])[None, :]),
                c.dtype)
        return jnp.dot(ctx, _kernel(self, "o_proj", (nh * hd, hid))), new_entry


class OlmoHybridBlock(nn.Module):
    config: OlmoHybridConfig
    layer_idx: int

    @nn.compact
    def __call__(self, x, *, cache: Optional[dict]):
        c = self.config
        hid, eps = c.hidden_size, c.rms_norm_eps
        at = c.index_in_kind(self.layer_idx)
        if c.layer_types[self.layer_idx] == FULL:
            a, entry = OlmoHybridAttention(c, at, name="attn")(x, cache=cache)
        else:
            recur = (None if cache is None
                     else (cache["state"][at], cache["conv"][at]))
            a, entry = GatedDeltaNet(c, name="linear_attn")(
                x, recur=recur, n=(cache or {}).get("n"),
                live=(cache or {}).get("live"))
        x = x + rms_norm(a, _gain(self, "post_attn_norm", hid), eps)
        m = AfmoeSwiGLU(c, c.intermediate_size, name="mlp")(x)
        x = x + rms_norm(m, _gain(self, "post_mlp_norm", hid), eps)
        return x, entry


class OlmoHybridLMHeadModel(nn.Module):
    """``__call__(input_ids, cache=None, positions=None)`` -> ``(logits
    float32, cache)`` under the three cache contracts of the module
    docstring. ``positions`` is accepted and unused: nothing here rotates,
    and masks count from ``cache["idx"]``."""

    config: OlmoHybridConfig

    @nn.compact
    def __call__(self, input_ids, *, cache: Optional[dict] = None,
                 positions: Optional[jax.Array] = None):
        del positions
        c = self.config
        if cache is not None and "table" in cache and input_ids.shape[1] != 1:
            raise ValueError(
                "the olmo_hybrid family's paged cache takes one token a "
                "row: a wider paged call (speculative verify) would need "
                "the recurrent state rolled back")
        embed = self.param("embed_tokens", nn.initializers.normal(0.02),
                           (c.vocab_size, c.hidden_size), c.dtype)
        x = embed[input_ids]
        new_ks, new_vs = [], []
        state = cache["state"] if cache is not None else None
        conv = cache["conv"] if cache is not None else None
        for i in range(c.num_layers):
            x, entry = OlmoHybridBlock(c, i, name=f"layers_{i}")(
                x, cache=(None if cache is None
                          else dict(cache, state=state, conv=conv)))
            if c.layer_types[i] == FULL:
                if entry is not None:
                    new_ks.append(entry[0])
                    new_vs.append(entry[1])
            elif cache is not None:
                # each layer's row written back where it was read: the
                # arrays ride the caller's donated buffers in place
                at = c.index_in_kind(i)
                state = state.at[at].set(entry[0])
                conv = conv.at[at].set(entry[1])
        x = rms_norm(x, _gain(self, "norm", c.hidden_size), c.rms_norm_eps)
        logits = jnp.dot(x, _kernel(self, "lm_head",
                                    (c.hidden_size, c.vocab_size)),
                         preferred_element_type=jnp.float32)
        if cache is None:
            return logits, None
        return logits, {"k": jnp.stack(new_ks), "v": jnp.stack(new_vs),
                        "idx": cache["idx"] + input_ids.shape[1],
                        "state": state, "conv": conv}


def init_olmo_hybrid_cache(config: OlmoHybridConfig, batch: int,
                           max_len: int) -> dict:
    """A zeroed dense cache with a scalar ``idx``: K/V ``[full layers, B,
    max_len, heads, head_dim]``, and the linear layers' zero state and
    convolution tails (prefill and lockstep decode outside the engine)."""
    fam = config.serving_family()
    shape = (fam.kv_layers, batch, max_len, config.num_heads,
             config.head_dim)
    out = {"k": jnp.zeros(shape, config.dtype),
           "v": jnp.zeros(shape, config.dtype),
           "idx": jnp.zeros((), jnp.int32)}
    for name, tail, dtype in fam.state_arrays:
        out[name] = jnp.zeros((fam.state_layers, batch) + tail, dtype)
    return out
