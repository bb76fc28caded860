"""The ``glm_moe_dsa`` decoder family (zai-org GLM-5.2): latent attention
under a learned sparse selection, sparse experts beside one shared expert.

A layer, as the published ``config.json`` gives it
(``benchmark/reference_glm_moe_dsa.py`` is the plain float32 statement of the
same equations; what the config does not settle is listed under ``assumed``
in ``benchmark/configs/glm-5.2-serve.json``). Pre-norm residual blocks, RMS
norms in float32, no biases but the indexer key's. For token ``t`` with normed
input ``x_t``:

- **latent attention** (every layer): ``c^q_t = RMSNorm(x_t W_dq)``; ``q_t =
  c^q_t W_uq``, ``num_heads`` heads of ``[q^nope | q^rope]``; ``[c^kv_t |
  k^r_t] = x_t W_dkv``, ``c^kv_t <- RMSNorm(c^kv_t)``; rotary on INTERLEAVED
  pairs of ``q^rope`` and of ``k^r`` (one rotary key shared by every head).
  Per head ``k^nope_s = c^kv_s W_uk``, ``v_s = c^kv_s W_uv``; a score is
  ``(q^nope . k^nope + q^rope . k^r) / sqrt(qk_head_dim)``; the softmax runs
  over the SELECTED positions ``S_t`` alone. What a token keeps is ``[c^kv_s
  | k^r_s]``: ONE column of ``kv_lora_rank + qk_rope_head_dim`` values a
  layer, K and V both;
- **the indexer** (layers whose ``indexer_types`` entry is ``full``): ``q^I_t
  = c^q_t W_qI`` (``index_n_heads`` heads of ``index_head_dim``), ``k^I_s =
  LayerNorm(x_s W_kI)`` (one key a token), rotary on the first
  ``qk_rope_head_dim`` values of both, ``w_t = x_t W_w``; ``I_ts = sum_h w_th
  ReLU(q^I_th . k^I_s)`` for ``s <= t``; ``S_t`` the ``index_topk`` positions
  of largest ``I_ts`` (all of them while there are no more; ties to the lower
  position). A ``shared`` layer has no indexer and uses the ``S_t`` of the
  nearest ``full`` layer before it: the selection is handed from layer to
  layer INSIDE one call and kept nowhere. The indexer keeps ``k^I_s`` a
  token, in ``full`` layers only;
- the MLP is SwiGLU where ``mlp_layer_types[l]`` is ``dense`` and an expert
  layer where it is ``sparse``: sigmoid scores in float32, the top ``k`` by
  score plus bias, weights the unbiased scores over their sum, times
  ``routed_scaling_factor``; ONE shared expert on every token; no token
  dropped (``parallel/moe_dropless.py``). The layer is told which experts this
  chip HOLDS (``first_expert``, ``experts_held``): it routes over all of them
  and computes its own experts' part;
- an untied head.

**What a layer keeps**, by block in the engine's pool under the family's own
names (``ServingFamily.block_arrays``): ``latent`` ``[layers, blocks, 16,
latent_width padded to whole lane tiles]`` and ``index_k`` ``[full layers,
blocks, 16, index_head_dim]``. There is no V beside ``latent``.

**Two forms of one attention.** A call over many tokens (no cache; the
engine's private prefill cache) is EXPANDED: per-head K and V are made from
the rows' ``c^kv``, every query scores every column of its row through the
indexer, and attends under the mask of its own ``S_t`` (gathering each
query's own 2,048 columns would make K and V once a query). The one-token
step is ABSORBED: ``q~ = q^nope W_uk^T`` scores the stored columns as they
lie, ``(sum_s p_s c^kv_s) W_uv`` is the output, and only the columns of
``S_t`` are attended: ``min(depth, index_topk)`` columns a row a layer,
whatever the depth. How the step READS them follows the width of its tables
(``ops/sparse_attention.attends_in_place``): while a table is no wider than
a few selections, ``S_t`` is a mask and the rows' live blocks are read where
the pool keeps them, each row to its own depth (the paged one-query kernel
over ``latent`` alone), every column outside ``S_t`` under a weight of
exactly zero; past that width ``S_t`` is 2,048 positions a row and those
columns are read from the pool one by one. Its indexer reads the row's
``index_k`` through the table in both.

The module keeps :class:`~sparkdl_tpu.models.gpt.GPTLMHeadModel`'s cache
contracts under those names: none; dense ``{"latent", "index_k", "idx"}``
with a scalar ``idx`` (the updated rows back); paged ``{"latent", "index_k",
"table", "idx"}`` over the engine's pool, one token a row (this call's new
columns back). A cached call also hands back ``expert_counts``
``[expert_layers, experts_held]``.
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
from sparkdl_tpu.models.kv_pool import LANE_TILE, layer_rows
from sparkdl_tpu.ops.sparse_attention import (
    absorbed_attention,
    attend_in_place,
    attends_in_place,
    index_scores,
    pick_columns,
    select_mask,
    selected_columns,
)
from sparkdl_tpu.parallel.moe_dropless import (
    dropless_experts,
    route_sigmoid_topk,
)

_NEG_INF = -1e30
FULL, SHARED = "full", "shared"
DENSE, SPARSE = "dense", "sparse"
#: query heads a pass of the expanded attention makes K and V for: what
#: bounds a chunk's temporaries at 16 k columns (scores ``[heads, 256,
#: columns]`` in float32)
HEAD_GROUP = 16
#: the indexer key's LayerNorm
INDEX_NORM_EPS = 1e-6


@dataclasses.dataclass(frozen=True)
class GlmMoeDsaConfig:
    vocab_size: int = 154880
    hidden_size: int = 6144
    intermediate_size: int = 12288       #: a dense layer's SwiGLU width
    moe_intermediate_size: int = 2048    #: an expert's, and the shared one's
    num_heads: int = 64
    q_lora_rank: int = 2048
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    index_n_heads: int = 32
    index_head_dim: int = 128
    index_topk: int = 2048
    #: per layer: ``full`` (its own indexer) or ``shared`` (the selection of
    #: the nearest ``full`` layer before it), ``dense`` or ``sparse`` MLP
    indexer_types: "tuple[str, ...]" = (
        (FULL,) * 3 + (SHARED, SHARED, SHARED, FULL) * 18 + (SHARED,) * 3)
    mlp_layer_types: "tuple[str, ...]" = (DENSE,) * 3 + (SPARSE,) * 75
    rope_theta: float = 8000000.0
    num_experts: int = 256
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    rms_norm_eps: float = 1e-5
    #: the experts THIS chip holds of every expert layer (routing is over
    #: all ``num_experts``); None holds them all
    first_expert: int = 0
    experts_held: "int | None" = None
    dtype: Any = jnp.float32

    def __post_init__(self):
        if set(self.indexer_types) - {FULL, SHARED}:
            raise ValueError(f"unknown indexer types "
                             f"{sorted(set(self.indexer_types))}")
        if set(self.mlp_layer_types) - {DENSE, SPARSE}:
            raise ValueError(f"unknown MLP types "
                             f"{sorted(set(self.mlp_layer_types))}")
        if len(self.mlp_layer_types) != len(self.indexer_types):
            raise ValueError(
                "indexer_types and mlp_layer_types disagree in length")
        if self.indexer_types[0] != FULL:
            raise ValueError("the first layer has no selection to share: "
                             "indexer_types must start with 'full'")
        if self.qk_rope_head_dim % 2 or not (
                0 < self.qk_rope_head_dim <= self.index_head_dim):
            raise ValueError(
                f"cannot rotate {self.qk_rope_head_dim} values in pairs of "
                f"an index head's {self.index_head_dim}")
        held = self.held
        if not (0 <= self.first_expert
                and self.first_expert + held <= self.num_experts):
            raise ValueError(
                f"experts [{self.first_expert}, {self.first_expert + held}) "
                f"are not among the {self.num_experts}")

    @property
    def num_layers(self) -> int:
        return len(self.indexer_types)

    @property
    def held(self) -> int:
        return (self.num_experts if self.experts_held is None
                else self.experts_held)

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_width(self) -> int:
        """Values a token keeps a layer: ``[c^kv | k^r]``."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_tail(self) -> "tuple[int]":
        """A ``latent`` column as the pool stores it: padded with zeros to
        whole lane tiles (576 -> 640), for ``kv_pool.kv_tail``'s reason: an
        unpadded minor axis would lose the lanes to the block axis."""
        return (-(-self.latent_width // LANE_TILE) * LANE_TILE,)

    @property
    def full_layers(self) -> int:
        return sum(t == FULL for t in self.indexer_types)

    def index_row(self, layer: int) -> int:
        """A ``full`` layer's row of ``index_k``: its place among them."""
        return sum(t == FULL for t in self.indexer_types[:layer])

    @classmethod
    def tiny(cls, **kw) -> "GlmMoeDsaConfig":
        """Test-sized: the last leading dense layer and one whole period of
        the published pattern (shared x 3, full), all four with experts."""
        defaults = dict(
            vocab_size=512, hidden_size=64, intermediate_size=96,
            moe_intermediate_size=32, num_heads=4, q_lora_rank=32,
            kv_lora_rank=16, qk_nope_head_dim=12, qk_rope_head_dim=8,
            v_head_dim=16, index_n_heads=4, index_head_dim=16, index_topk=16,
            indexer_types=(FULL, SHARED, SHARED, SHARED, FULL),
            mlp_layer_types=(DENSE,) + (SPARSE,) * 4,
            num_experts=8, num_experts_per_tok=2,
        )
        defaults.update(kw)
        return cls(**defaults)

    def serving_family(self) -> ServingFamily:
        return ServingFamily(
            module=GlmMoeDsaLMHeadModel(self), layers=self.num_layers,
            # (no K/V heads: what a token keeps is ``block_arrays``)
            kv_heads=1, head_dim=self.latent_width, dtype=self.dtype,
            max_positions=None,
            expert_layers=sum(t == SPARSE for t in self.mlp_layer_types),
            experts=self.held, experts_per_token=self.num_experts_per_tok,
            paged_only=True,
            block_arrays=(
                ("latent", self.num_layers, self.latent_tail),
                ("index_k", self.full_layers, (self.index_head_dim,))),
            selected_columns=self.index_topk)


def config_from_hf_glm_moe_dsa(hf: dict, **kw) -> GlmMoeDsaConfig:
    """GlmMoeDsaConfig from the keys of a ``glm_moe_dsa`` ``config.json``.
    ``n_routed_experts`` counts the experts the router scores; which of them
    this chip holds is ``first_expert`` / ``experts_held`` (keywords).
    Variants this forward does not compute are refused, not approximated."""
    if hf.get("model_type", "glm_moe_dsa") != "glm_moe_dsa":
        raise ValueError(f"not a glm_moe_dsa config: {hf.get('model_type')!r}")
    if (hf.get("n_group") or 1, hf.get("topk_group") or 1) != (1, 1):
        raise ValueError("group-limited routing (n_group, topk_group > 1) "
                         "is not implemented")
    if int(hf.get("n_shared_experts") or 0) != 1:
        raise ValueError("exactly one shared expert is implemented")
    rope = hf.get("rope_parameters") or {}
    if rope.get("rope_type", "default") != "default":
        raise ValueError("rope scaling (a rope_type other than 'default') "
                         "is not implemented")
    if not (hf.get("rope_interleave", True)
            and hf.get("indexer_rope_interleave", True)):
        raise ValueError("only interleaved rotary pairs are implemented")
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
    if hf.get("index_topk_pattern") is not None:
        raise ValueError("index_topk_pattern is not implemented: the "
                         "selection's size is index_topk in every layer")
    layers = int(hf["num_hidden_layers"])
    kinds = tuple(hf["indexer_types"])
    mlps = tuple(hf["mlp_layer_types"])
    if not len(kinds) == len(mlps) == layers:
        raise ValueError("indexer_types, mlp_layer_types and "
                         "num_hidden_layers disagree")
    if kinds and kinds[0] != FULL:
        raise ValueError("indexer_types starts with 'shared': the first "
                         "layer has no selection to share")
    nope, rot = int(hf["qk_nope_head_dim"]), int(hf["qk_rope_head_dim"])
    if int(hf.get("qk_head_dim", nope + rot)) != nope + rot:
        raise ValueError("qk_head_dim is not qk_nope_head_dim + "
                         "qk_rope_head_dim")
    scale = hf.get("routed_scaling_factor")
    return GlmMoeDsaConfig(
        vocab_size=int(hf["vocab_size"]), hidden_size=int(hf["hidden_size"]),
        intermediate_size=int(hf["intermediate_size"]),
        moe_intermediate_size=int(hf["moe_intermediate_size"]),
        num_heads=int(hf["num_attention_heads"]),
        q_lora_rank=int(hf["q_lora_rank"]),
        kv_lora_rank=int(hf["kv_lora_rank"]),
        qk_nope_head_dim=nope, qk_rope_head_dim=rot,
        v_head_dim=int(hf["v_head_dim"]),
        index_n_heads=int(hf["index_n_heads"]),
        index_head_dim=int(hf["index_head_dim"]),
        index_topk=int(hf["index_topk"]),
        indexer_types=kinds, mlp_layer_types=mlps,
        rope_theta=float(rope.get("rope_theta", 10000.0)),
        num_experts=int(hf["n_routed_experts"]),
        num_experts_per_tok=int(hf["num_experts_per_tok"]),
        norm_topk_prob=bool(hf.get("norm_topk_prob", True)),
        routed_scaling_factor=1.0 if scale is None else float(scale),
        rms_norm_eps=float(hf.get("rms_norm_eps", 1e-5)), **kw)


# -- the pieces -------------------------------------------------------------------

def rope_interleaved(x: jax.Array, positions: jax.Array, base: float,
                     rotary_dim: int) -> jax.Array:
    """Rotary on the FIRST ``rotary_dim`` values of every head, INTERLEAVED
    pairs (value ``2i`` with value ``2i + 1``), in float32; the rest pass.
    x ``[B, L, H, D]``; positions ``[B, L]``."""
    half = rotary_dim // 2
    freqs = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[:, :, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    pairs = x[..., :rotary_dim].astype(jnp.float32).reshape(
        *x.shape[:-1], half, 2)
    x1, x2 = pairs[..., 0], pairs[..., 1]
    turned = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                       -1).reshape(*x.shape[:-1], rotary_dim)
    return jnp.concatenate([turned.astype(x.dtype), x[..., rotary_dim:]], -1)


def layer_norm(x: jax.Array, gain: jax.Array, bias: jax.Array,
               eps: float) -> jax.Array:
    """LayerNorm over the last axis in float32, in ``x``'s dtype."""
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean((x32 - mean) ** 2, axis=-1, keepdims=True)
    return ((x32 - mean) * jax.lax.rsqrt(var + eps) * gain.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(x.dtype)


def expanded_attention(q_nope, q_rope, latent, w_uk, w_uv, mask, scale,
                       dtype):
    """The expanded form, :data:`HEAD_GROUP` heads a pass: per-head K and V
    from the rows' ``c^kv``, a softmax over the columns ``mask`` allows.
    q_nope ``[B, L, H, Dn]``; q_rope ``[B, L, H, Dr]``; latent ``[B, W,
    >= C + Dr]`` (``[c^kv | k^r | pad]``); w_uk ``[C, H, Dn]``; w_uv ``[C, H,
    Dv]``; mask ``[B, L, W]``. Returns ``[B, L, H * Dv]``."""
    with jax.named_scope("dsa_expanded_attention"):
        b, l, h, _ = q_nope.shape
        c, dr = w_uk.shape[0], q_rope.shape[-1]
        ckv, k_r = latent[..., :c], latent[..., c:c + dr]
        g = math.gcd(h, HEAD_GROUP)

        def group(args):
            qn, qr, uk, uv = args
            k = jnp.einsum("bwc,cgn->bwgn", ckv, uk)
            v = jnp.einsum("bwc,cgv->bwgv", ckv, uv)
            s = (jnp.einsum("blgn,bwgn->bglw", qn, k,
                            preferred_element_type=jnp.float32)
                 + jnp.einsum("blgr,bwr->bglw", qr, k_r,
                              preferred_element_type=jnp.float32)) * scale
            s = jnp.where(mask[:, None], s, _NEG_INF)
            p = jax.nn.softmax(s, axis=-1).astype(dtype)
            return jnp.einsum("bglw,bwgv->blgv", p, v)

        if g == h:
            return group((q_nope, q_rope, w_uk, w_uv)).reshape(b, l, -1)

        def split(x, axis):
            shape = x.shape[:axis] + (h // g, g) + x.shape[axis + 1:]
            return jnp.moveaxis(x.reshape(shape), axis, 0)

        out = jax.lax.map(group, (split(q_nope, 2), split(q_rope, 2),
                                  split(w_uk, 1), split(w_uv, 1)))
        return jnp.moveaxis(out, 0, 2).reshape(b, l, -1)


class GlmIndexer(nn.Module):
    """A ``full`` layer's indexer: this call's queries, keys and head
    weights. Returns ``(q_i [B, L, H, D], k_i [B, L, D], w [B, L, H]
    float32)``."""

    config: GlmMoeDsaConfig

    @nn.compact
    def __call__(self, x, c_q, rope_pos):
        c = self.config
        b, l, hid = x.shape
        nh, d, rot = c.index_n_heads, c.index_head_dim, c.qk_rope_head_dim
        q_i = jnp.dot(c_q, _kernel(self, "wq_b", (c.q_lora_rank, nh * d)))
        k_i = layer_norm(
            jnp.dot(x, _kernel(self, "wk", (hid, d))),
            _gain(self, "k_norm", d),
            self.param("k_norm_bias", nn.initializers.zeros, (d,),
                       jnp.float32), INDEX_NORM_EPS)
        w = jnp.dot(x, _kernel(self, "weights_proj", (hid, nh)),
                    preferred_element_type=jnp.float32)
        q_i = rope_interleaved(q_i.reshape(b, l, nh, d), rope_pos,
                               c.rope_theta, rot)
        k_i = rope_interleaved(k_i[:, :, None, :], rope_pos, c.rope_theta,
                               rot)[:, :, 0]
        return q_i, k_i.astype(c.dtype), w


class GlmAttention(nn.Module):
    """One layer's latent attention and, in a ``full`` layer, its indexer.
    ``picked`` is the selection handed down by the nearest ``full`` layer
    before a ``shared`` one (a mask ``[B, L, W]`` in a call over many
    tokens; in a paged step a mask ``[S, W]`` where it attends in place,
    ``(positions, taken)`` where it does not, None while no table passes
    the selection's size). Returns ``(y,
    entry, picked)``: ``entry`` is None without a cache, else ``(latent,
    index_k | None)``: this call's columns of a paged cache, the updated
    rows of a dense one."""

    config: GlmMoeDsaConfig
    layer_idx: int

    @nn.compact
    def __call__(self, x, *, cache: Optional[dict], picked,
                 positions: Optional[jax.Array] = None):
        c = self.config
        b, l, hid = x.shape
        nh, dn, dr, dv = (c.num_heads, c.qk_nope_head_dim,
                          c.qk_rope_head_dim, c.v_head_dim)
        rank, topk = c.kv_lora_rank, c.index_topk
        full = c.indexer_types[self.layer_idx] == FULL
        scale = 1.0 / math.sqrt(c.qk_head_dim)

        c_q = rms_norm(
            jnp.dot(x, _kernel(self, "q_a_proj", (hid, c.q_lora_rank))),
            _gain(self, "q_a_norm", c.q_lora_rank), c.rms_norm_eps)
        q = jnp.dot(c_q, _kernel(self, "q_b_proj",
                                 (c.q_lora_rank, nh * c.qk_head_dim)))
        q = q.reshape(b, l, nh, c.qk_head_dim)
        kv_a = jnp.dot(x, _kernel(self, "kv_a_proj", (hid, c.latent_width)))
        c_kv = rms_norm(kv_a[..., :rank], _gain(self, "kv_a_norm", rank),
                        c.rms_norm_eps)
        kv_b = _kernel(self, "kv_b_proj", (rank, nh * (dn + dv))).reshape(
            rank, nh, dn + dv)
        w_uk, w_uv = kv_b[..., :dn], kv_b[..., dn:]
        out_proj = _kernel(self, "o_proj", (nh * dv, hid))

        idx = cache["idx"] if cache is not None else jnp.zeros((), jnp.int32)
        # [1|B, L] positions of this call's tokens: masks always count from
        # the cache's depth; rotary takes the caller's ``positions`` where
        # it gives them (the engine clamps a padded chunk's tail)
        q_pos = jnp.reshape(idx, (-1, 1)) + jnp.arange(l)[None, :]
        rope_pos = jnp.broadcast_to(
            q_pos if positions is None else positions, (b, l))
        q_nope = q[..., :dn]
        q_rope = rope_interleaved(q[..., dn:], rope_pos, c.rope_theta, dr)
        k_r = rope_interleaved(kv_a[:, :, None, rank:], rope_pos,
                               c.rope_theta, dr)[:, :, 0]
        # this call's columns as a pool stores a token: [c^kv | k^r | 0]
        tail = c.latent_tail[0]
        new = jnp.pad(jnp.concatenate([c_kv, k_r], -1).astype(c.dtype),
                      ((0, 0), (0, 0), (0, tail - c.latent_width)))
        q_i = k_i = w_i = None
        if full:
            q_i, k_i, w_i = GlmIndexer(c, name="indexer")(x, c_q, rope_pos)

        if cache is not None and "table" in cache:
            # one query a row, every row at its own depth
            table = cache["table"]
            width = table.shape[1] * cache["latent"].shape[2]
            # (the rule, on the table's width: every layer of a step asks
            # it of the same table, so a ``shared`` layer knows which form
            # of selection it was handed)
            in_place = attends_in_place(width, topk)
            if full:
                picked = None
                if width > topk:
                    picked = self._pick_step(cache, q_i, k_i, w_i, idx, width,
                                             as_mask=in_place)
                    self.sow("intermediates", "picked", picked)
            q_abs = jnp.einsum("shn,chn->shc", q_nope[:, 0], w_uk)
            q_full = jnp.pad(
                jnp.concatenate([q_abs, q_rope[:, 0]], -1).astype(c.dtype),
                ((0, 0), (0, 0), (0, tail - c.latent_width)))
            if in_place:
                mix = attend_in_place(cache, self.layer_idx, q_full, picked,
                                      idx, new[:, 0], scale)
            else:
                old, seen, new_seen = selected_columns(
                    cache, self.layer_idx, picked, idx)
                mix = absorbed_attention(q_full, old.astype(c.dtype), seen,
                                         new[:, 0], new_seen, scale)
            ctx = jnp.einsum("shc,chv->shv", mix[..., :rank].astype(c.dtype),
                             w_uv).reshape(b, 1, nh * dv)
            entry = (new, None if k_i is None else k_i)
            return jnp.dot(ctx, out_proj), entry, picked

        if cache is None:
            rows, index_rows = new, k_i
        else:
            if jnp.ndim(idx) != 0:
                raise ValueError(
                    "the glm_moe_dsa family's dense cache takes a scalar "
                    "idx; per-slot decode is the paged cache's")
            rows = jax.lax.dynamic_update_slice(
                cache["latent"][self.layer_idx], new, (0, idx, 0))
            index_rows = None if not full else jax.lax.dynamic_update_slice(
                cache["index_k"][c.index_row(self.layer_idx)], k_i,
                (0, idx, 0))
        if full:
            # every query scores every column of its row, its own included,
            # and keeps its own ``index_topk`` of those before it
            before = (jnp.arange(rows.shape[1])[None, None, :]
                      <= q_pos[:, :, None])
            picked = before
            if rows.shape[1] > topk:
                picked = select_mask(
                    jnp.where(before, index_scores(q_i, index_rows, w_i),
                              -jnp.inf), topk)
            # (read by the tests alone: nothing collects it in serving)
            self.sow("intermediates", "picked", picked)
        ctx = expanded_attention(q_nope, q_rope, rows, w_uk, w_uv, picked,
                                 scale, c.dtype)
        entry = None if cache is None else (rows, index_rows)
        return jnp.dot(ctx, out_proj), entry, picked

    def _pick_step(self, cache, q_i, k_i, w_i, idx, width, as_mask):
        """A step's selection: each row's ``index_topk`` positions of
        largest ``I`` among its ``idx`` stored columns (its ``index_k``
        through the table) and this call's own, which sits at position
        ``idx``. ``(positions [S, K], taken [S, K])``, or where the step
        attends in place (``as_mask``) the same set as a mask ``[S, W]``."""
        c = self.config
        keys, = layer_rows(cache, c.index_row(self.layer_idx),
                           cache["table"], c.dtype, names=("index_k",))
        scores = index_scores(q_i, keys, w_i)[:, 0]                # [S, W]
        own = index_scores(q_i, k_i, w_i)[:, 0, 0]                 # [S]
        cols = jnp.arange(width)[None, :]
        scores = jnp.where(cols == idx[:, None], own[:, None],
                           jnp.where(cols < idx[:, None], scores, -jnp.inf))
        if as_mask:
            return select_mask(scores, c.index_topk)
        return pick_columns(scores, c.index_topk)


class GlmExperts(nn.Module):
    """The expert layer of this chip: the shared expert on every token and
    the HELD experts' part of the routed sum."""

    config: GlmMoeDsaConfig

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
        shared = AfmoeSwiGLU(c, f, name="shared")(x)
        return shared + routed.reshape(b, l, hid), counts


class GlmBlock(nn.Module):
    config: GlmMoeDsaConfig
    layer_idx: int

    @nn.compact
    def __call__(self, x, *, cache: Optional[dict], picked,
                 positions: Optional[jax.Array] = None):
        c = self.config
        hid, eps = c.hidden_size, c.rms_norm_eps
        a, entry, picked = GlmAttention(c, self.layer_idx, name="attn")(
            rms_norm(x, _gain(self, "input_norm", hid), eps),
            cache=cache, picked=picked, positions=positions)
        x = x + a
        h = rms_norm(x, _gain(self, "pre_mlp_norm", hid), eps)
        counts = None
        if c.mlp_layer_types[self.layer_idx] == SPARSE:
            m, counts = GlmExperts(c, name="moe")(h)
        else:
            m = AfmoeSwiGLU(c, c.intermediate_size, name="mlp")(h)
        return x + m, entry, picked, counts


class GlmMoeDsaLMHeadModel(nn.Module):
    """``__call__(input_ids, cache=None, positions=None)`` -> ``(logits
    float32, cache)`` under the three cache contracts of the module
    docstring. ``positions`` ([B, L]) override the rotary positions of this
    call's tokens only; masks always count from ``cache["idx"]``."""

    config: GlmMoeDsaConfig

    @nn.compact
    def __call__(self, input_ids, *, cache: Optional[dict] = None,
                 positions: Optional[jax.Array] = None):
        c = self.config
        if cache is not None and "table" in cache and input_ids.shape[1] != 1:
            raise ValueError(
                "the glm_moe_dsa family's paged cache takes one token a "
                "row: a wider paged call (speculative verify) would need "
                "a selection a drafted token")
        embed = self.param("embed_tokens", nn.initializers.normal(0.02),
                           (c.vocab_size, c.hidden_size), c.dtype)
        x = embed[input_ids]
        latents, index_ks, counts = [], [], []
        # the selection lives for this call: a ``full`` layer makes it, the
        # ``shared`` layers after it use it, the next ``full`` one replaces it
        picked = None
        for i in range(c.num_layers):
            x, entry, picked, n = GlmBlock(c, i, name=f"layers_{i}")(
                x, cache=cache, picked=picked, positions=positions)
            if n is not None:
                counts.append(n)
            if entry is not None:
                latents.append(entry[0])
                if entry[1] is not None:
                    index_ks.append(entry[1])
        x = rms_norm(x, _gain(self, "norm", c.hidden_size), c.rms_norm_eps)
        logits = jnp.dot(x, _kernel(self, "lm_head",
                                    (c.hidden_size, c.vocab_size)),
                         preferred_element_type=jnp.float32)
        if cache is None:
            return logits, None
        out = {"latent": jnp.stack(latents), "index_k": jnp.stack(index_ks),
               "idx": cache["idx"] + input_ids.shape[1]}
        if counts:
            out["expert_counts"] = jnp.stack(counts)
        return logits, out


def init_glm_moe_dsa_cache(config: GlmMoeDsaConfig, batch: int,
                           max_len: int) -> dict:
    """A zeroed dense cache with a scalar ``idx``: ``latent`` ``[layers, B,
    max_len, *latent_tail]`` and ``index_k`` ``[full layers, B, max_len,
    index_head_dim]`` as a pool stores a token (prefill and chunked prefill
    outside the engine)."""
    out = {"idx": jnp.zeros((), jnp.int32)}
    for name, layers, tail in config.serving_family().pool_arrays:
        out[name] = jnp.zeros((layers, batch, max_len) + tail, config.dtype)
    return out
