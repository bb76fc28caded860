"""The plain reference of the ``glm_moe_dsa`` family (zai-org GLM-5.2) and its seeded weights.

Nothing here imports ``sparkdl_tpu``. The forward is written out in
straightforward ``jax.numpy`` after the published ``config.json``, in float32
at ``highest`` matmul precision, in the EXPANDED form only: per-head K and V
are made from every token's ``c^kv`` (the absorbed product ``q^nope W_uk^T``
is never formed), every column is scored by the indexer and a plain
``top_k`` takes the selection, a block of queries at a time over every key
before them; no cache, no kernels, no batching of requests, no grouped
product.

A layer ``l`` (``x`` ``[T, hidden]``; RMS norm before attention and before
the MLP, eps ``rms_norm_eps``; no biases but the indexer key's):

- ``c^q = RMSNorm(h W_dq)``; ``q = c^q W_uq`` ``[T, H, qk_nope + qk_rope]``;
  ``[c^kv | k^r] = h W_dkv``, ``c^kv <- RMSNorm(c^kv)``; rotary on
  INTERLEAVED pairs (2i, 2i + 1) of ``q^rope`` and of ``k^r`` (one rotary
  key for every head), base ``rope_parameters.rope_theta``, no scaling;
  ``k^nope = c^kv W_uk`` ``[T, H, qk_nope]``, ``v = c^kv W_uv`` ``[T, H,
  v_head_dim]`` (``kv_b_proj`` holds ``[W_uk | W_uv]`` a head);
- ``a_ts = (q^nope_t . k^nope_s + q^rope_t . k^r_s) / sqrt(qk_nope +
  qk_rope)``; ``p = softmax`` over ``s in S_t`` ALONE; ``o = p v`` ->
  ``[T, H * v_head_dim] Wo``;
- ``indexer_types[l] == "full"``: ``q^I = c^q W_qI`` ``[T, index_n_heads,
  index_head_dim]``, ``k^I = LayerNorm(h W_kI)`` (gain and bias, eps 1e-6),
  rotary on the first ``qk_rope_head_dim`` values of both (interleaved
  pairs), ``w = h W_w``; ``I_ts = sum_h w_th ReLU(q^I_th . k^I_s)``, ``s <=
  t``; ``S_t`` = ``top_k(I_t, index_topk)`` (every ``s <= t`` while there are
  no more; ``top_k`` gives a tie to the lower position). ``"shared"``: no
  indexer, the ``S_t`` of the nearest ``full`` layer before it;
- MLP: SwiGLU of ``intermediate_size`` where ``mlp_layer_types[l]`` is
  ``dense``; else ``s = sigmoid(h Wr)`` over all ``n_routed_experts``, the top
  ``num_experts_per_tok`` by ``s + b``, weights ``s[sel] / (sum + 1e-20)``
  times ``routed_scaling_factor``, ``y = SwiGLU_shared(h) + sum_e w_e
  SwiGLU_e(h)`` over the selected experts THIS SHARE HOLDS (``first_expert``,
  ``experts_held``: the reference is given the same share as the program;
  what the absent experts would add is left out of both). An untied head.

**Departures from the published description**, each set by the issue that
added the family and listed under ``assumed`` in the configuration's file:

- norm placement (pre-norm, one last norm) is the family's convention: the
  config names none;
- the indexer key's LayerNorm (with bias, eps 1e-6) and WHICH 64 of an index
  head's 128 values rotate (the first) follow the DeepSeek-V3.2 inference
  code, which this family's indexer follows; the config names neither;
- that code's Hadamard rotation of ``q^I`` and ``k^I`` and their FP8 storage
  are left out: an orthogonal rotation of both changes no dot product;
- no positive scale is applied to ``w`` or to ``I`` (the inference code's
  ``index_n_heads ** -0.5`` and softmax scale): it changes no selection;
- the one multi-token-prediction layer (``num_nextn_predict_layers`` 1) is
  left out: the language model alone is served, one token a step.

**The share.** ``hf`` is the published keys with ``n_routed_experts`` the
ROUTER's width, and beside them ``experts_held`` and ``first_expert`` (absent:
all, from 0): the experts whose kernels exist here.

**One layer at a time.** The weights are a pure function of ``(seed,
layer)`` (``layer_weights``) and of ``seed`` alone for the embedding, the
last norm and the head (``top_weights``), in the types they are served in
(:func:`layer_leaves` has the kinds and why ``W_uq`` is drawn wider). The
reference makes a layer, applies it to every checked sequence, and lets it
go; a ``full`` layer hands its selection (a mask a sequence) to the layers
after it; experts are applied to their OWN tokens only.

**Controls** (``control=``), the reference put in the program's place with
one thing wrong, judged by the float32 reference at the served positions:
``"int8"`` and ``"float8"`` round every matmul operand; ``"bfloat16"``
rounds them to the precision the configuration STATES (not a fault);
``"all_columns"`` attends every column before the query in place of
``S_t``; ``"shared_last"`` gives a ``shared`` layer the LAST ``index_topk``
columns in place of the handed-down ``S_t``; ``"no_relu"`` leaves the ReLU
out of ``I``; ``"weakest_held_dropped"`` leaves out each token's weakest
selected expert among those held.
"""

from __future__ import annotations

import functools
import json
import math

import numpy as np

from benchmark.reference import seed_key
from benchmark.reference_afmoe import _ROUND, _rms, _sizes_up, _swiglu

FULL, SHARED = "full", "shared"
DENSE, SPARSE = "dense", "sparse"
CONTROLS = ("f32", "bfloat16", "int8", "float8", "all_columns",
            "shared_last", "no_relu", "weakest_held_dropped")
#: queries a block of the reference's attention, rows a block of its head
Q_BLOCK, HEAD_BLOCK = 256, 256
#: the standard deviation ``W_uq`` is drawn with, where every other kernel
#: has 0.02 (:func:`layer_leaves`)
Q_KERNEL_STD = 0.076
INDEX_NORM_EPS = 1e-6


def glm_sizes(hf: dict) -> dict:
    """The sizes a ``glm_moe_dsa`` ``config.json`` (and the share) fixes."""
    kinds, mlps = list(hf["indexer_types"]), list(hf["mlp_layer_types"])
    if not len(kinds) == len(mlps) == int(hf["num_hidden_layers"]):
        raise ValueError("indexer_types, mlp_layer_types and "
                         "num_hidden_layers disagree")
    if kinds[0] != FULL:
        raise ValueError("indexer_types starts with 'shared'")
    experts = int(hf["n_routed_experts"])
    held = int(hf.get("experts_held") or experts)
    first = int(hf.get("first_expert", 0))
    if not 0 <= first <= first + held <= experts:
        raise ValueError(f"experts [{first}, {first + held}) are not among "
                         f"the router's {experts}")
    scale = hf.get("routed_scaling_factor")
    return {
        "hidden": int(hf["hidden_size"]), "layers": len(kinds),
        "kinds": kinds, "mlps": mlps,
        "heads": int(hf["num_attention_heads"]),
        "q_rank": int(hf["q_lora_rank"]), "kv_rank": int(hf["kv_lora_rank"]),
        "nope": int(hf["qk_nope_head_dim"]),
        "rope": int(hf["qk_rope_head_dim"]),
        "v_head_dim": int(hf["v_head_dim"]),
        "index_heads": int(hf["index_n_heads"]),
        "index_dim": int(hf["index_head_dim"]),
        "index_topk": int(hf["index_topk"]),
        "theta": float(hf["rope_parameters"]["rope_theta"]),
        "inner": int(hf["intermediate_size"]),
        "expert_inner": int(hf["moe_intermediate_size"]),
        "experts": experts, "held": held, "first": first,
        "top_k": int(hf["num_experts_per_tok"]),
        "norm_topk": bool(hf.get("norm_topk_prob", True)),
        "route_scale": 1.0 if scale is None else float(scale),
        "vocab": int(hf["vocab_size"]),
        "eps": float(hf.get("rms_norm_eps", 1e-5)),
    }


# -- seeded weights --------------------------------------------------------------

def layer_leaves(hf: dict, layer: int) -> "dict[str, tuple]":
    """name -> (shape, kind) of one layer's weights. Kinds: ``kernel``
    (normal 0.02, the dense dtype), ``kernel_q`` (normal
    :data:`Q_KERNEL_STD`, the dense dtype: ``W_uq`` alone), ``gain`` (1 +
    0.05 normal, float32), ``router`` and ``bias`` (normal 0.02, float32).

    Why ``W_uq`` is wider. At the published widths and kernels of 0.02 a
    value of ``q`` has a variance of ``2048 x 0.02^2 = 0.82``, of ``k^nope``
    ``512 x 0.02^2 = 0.20``, of ``k^r`` ``6144 x 0.02^2 = 2.46`` (it is not
    normed), so a score has a spread of ``sqrt(0.82 x (192 x 0.20 + 64 x
    2.46)) / 16 = 0.79`` over a row's columns: a softmax that flat weighs
    2,048 random columns and 8,000 all but alike, both outputs vanish under
    the residual stream, and WHICH columns were selected would be lost in
    bfloat16's rounding. Drawn at 0.076 the score's spread is 3.0: a few
    dozen columns of a row carry most of a head's weight, and whether the
    indexer kept them moves the logits. The indexer's own kernels stay at
    0.02: ``I`` is a sum over 32 heads of ``w_h`` (either sign, spread 1.6)
    times a rectified product of spread 10, its values at the 2,048th place
    of 8,000 lie some 0.01-0.02 apart and no two are equal."""
    s = glm_sizes(hf)
    h, nh, qr, kr = s["hidden"], s["heads"], s["q_rank"], s["kv_rank"]
    dn, dr, dv = s["nope"], s["rope"], s["v_head_dim"]
    out = {
        "input_norm": ((h,), "gain"), "pre_mlp_norm": ((h,), "gain"),
        "attn.q_a_proj": ((h, qr), "kernel"),
        "attn.q_a_norm": ((qr,), "gain"),
        "attn.q_b_proj": ((qr, nh * (dn + dr)), "kernel_q"),
        "attn.kv_a_proj": ((h, kr + dr), "kernel"),
        "attn.kv_a_norm": ((kr,), "gain"),
        "attn.kv_b_proj": ((kr, nh * (dn + dv)), "kernel"),
        "attn.o_proj": ((nh * dv, h), "kernel"),
    }
    if s["kinds"][layer] == FULL:
        ih, d = s["index_heads"], s["index_dim"]
        out.update({
            "attn.indexer.wq_b": ((qr, ih * d), "kernel"),
            "attn.indexer.wk": ((h, d), "kernel"),
            "attn.indexer.k_norm": ((d,), "gain"),
            "attn.indexer.k_norm_bias": ((d,), "bias"),
            "attn.indexer.weights_proj": ((h, ih), "kernel")})
    if s["mlps"][layer] == SPARSE:
        f, e, held = s["expert_inner"], s["experts"], s["held"]
        out.update({
            "moe.router": ((h, e), "router"),
            "moe.expert_bias": ((e,), "bias"),
            "moe.experts_gate": ((held, h, f), "kernel"),
            "moe.experts_up": ((held, h, f), "kernel"),
            "moe.experts_down": ((held, f, h), "kernel"),
            "moe.shared.gate_proj": ((h, f), "kernel"),
            "moe.shared.up_proj": ((h, f), "kernel"),
            "moe.shared.down_proj": ((f, h), "kernel")})
    else:
        f = s["inner"]
        out.update({"mlp.gate_proj": ((h, f), "kernel"),
                    "mlp.up_proj": ((h, f), "kernel"),
                    "mlp.down_proj": ((f, h), "kernel")})
    return out


def top_leaves(hf: dict) -> "dict[str, tuple]":
    s = glm_sizes(hf)
    return {"embed_tokens": ((s["vocab"], s["hidden"]), "kernel"),
            "norm": ((s["hidden"],), "gain"),
            "lm_head": ((s["hidden"], s["vocab"]), "kernel")}


@functools.lru_cache(maxsize=None)
def _maker(leaves_json: str, dense_dtype: str):
    """The jitted ``key -> {name: array}`` of a table of leaves (one program
    a kind of layer)."""
    import jax
    import jax.numpy as jnp

    leaves = json.loads(leaves_json)
    dense = jnp.dtype(dense_dtype)

    def make(key):
        out = {}
        for i, (name, (shape, kind)) in enumerate(leaves.items()):
            x = jax.random.normal(jax.random.fold_in(key, i), tuple(shape),
                                  jnp.float32)
            out[name] = (1.0 + 0.05 * x if kind == "gain"
                         else (0.02 * x).astype(dense) if kind == "kernel"
                         else (Q_KERNEL_STD * x).astype(dense)
                         if kind == "kernel_q" else 0.02 * x)
        return out

    made = jax.jit(make)

    def under_no_precision(key):
        # nothing in ``make`` is a product, and jit keys its programs by the
        # matmul precision in force: made under ONE setting, whoever calls,
        # or the check's "highest" compiles every maker a second time
        with jax.default_matmul_precision(None):
            return made(key)

    return under_no_precision


def layer_weights(seed: int, layer: int, hf: dict,
                  dense_dtype: str = "bfloat16") -> dict:
    """One layer's seeded weights on the device, from ``(seed, layer)``."""
    import jax

    key = jax.random.fold_in(seed_key(seed), 1 + layer)
    return _maker(json.dumps(layer_leaves(hf, layer)), dense_dtype)(key)


def top_weights(seed: int, hf: dict, dense_dtype: str = "bfloat16") -> dict:
    """The embedding, the last norm and the untied head, from ``seed``."""
    import jax

    return _maker(json.dumps(top_leaves(hf)), dense_dtype)(
        jax.random.fold_in(seed_key(seed), 0))


def _tables(hf: dict) -> "list[dict]":
    return [top_leaves(hf)] + [layer_leaves(hf, i)
                               for i in range(glm_sizes(hf)["layers"])]


def seeded_parameters(hf: dict) -> int:
    """The count of every seeded value, from the tables above."""
    return sum(int(np.prod(shape)) for t in _tables(hf)
               for shape, _ in t.values())


def seeded_weight_bytes(hf: dict, dense_dtype: str = "bfloat16") -> int:
    """Bytes of every seeded array, counted from the tables above."""
    dense = np.dtype("float32").itemsize if dense_dtype == "float32" else 2
    return sum(int(np.prod(shape))
               * (dense if kind.startswith("kernel") else 4)
               for t in _tables(hf) for shape, kind in t.values())


# -- the forward -------------------------------------------------------------------

def _rope(x, pos, theta, rot):
    """Rotary on the first ``rot`` values of every head, INTERLEAVED pairs
    (2i, 2i + 1); the rest pass. x [L, H, D]; pos [L]."""
    import jax.numpy as jnp

    half = rot // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0:rot:2], x[..., 1:rot:2]
    turned = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return jnp.concatenate(
        [turned.reshape(*x.shape[:-1], rot), x[..., rot:]], -1)


def _layer_norm(x, gain, bias, eps):
    import jax.numpy as jnp

    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * gain + bias


@functools.lru_cache(maxsize=None)
def _attention_layer(hf_json: str, kind: str, control: str):
    """``full``: ``(weights, x [rows, L, hidden]) -> (x, h, picked [rows, L,
    L])``; ``shared``: ``(weights, x, picked) -> (x, h)``: the residual
    stream after the attention half of a layer, the normed input of its MLP
    and, from a ``full`` layer, its selection (a mask of each query's
    ``S_t``, which the ``shared`` layers after it are handed), one sequence
    at a time. One jitted program per kind of layer."""
    import jax
    import jax.numpy as jnp

    s = glm_sizes(json.loads(hf_json))
    q8 = _ROUND.get(control, _ROUND["f32"])
    nh, eps, rank = s["heads"], s["eps"], s["kv_rank"]
    dn, dr, dv = s["nope"], s["rope"], s["v_head_dim"]
    ih, idim, topk, theta = (s["index_heads"], s["index_dim"],
                             s["index_topk"], s["theta"])
    full = kind == FULL

    def apply(w, x, picked):
        length = x.shape[0]
        f32 = {k: v.astype(jnp.float32) for k, v in w.items()}
        pos = jnp.arange(length)
        a = _rms(x, f32["input_norm"], eps)
        qa = q8(a, -1)
        c_q = _rms(qa @ q8(f32["attn.q_a_proj"], 0), f32["attn.q_a_norm"],
                   eps)
        cq8 = q8(c_q, -1)
        q = (cq8 @ q8(f32["attn.q_b_proj"], 0)).reshape(length, nh, dn + dr)
        kv_a = qa @ q8(f32["attn.kv_a_proj"], 0)
        c_kv = _rms(kv_a[:, :rank], f32["attn.kv_a_norm"], eps)
        k_r = _rope(kv_a[:, None, rank:], pos, theta, dr)[:, 0]
        q_nope, q_rope = q[..., :dn], _rope(q[..., dn:], pos, theta, dr)
        kv_b = f32["attn.kv_b_proj"].reshape(rank, nh, dn + dv)
        ck8 = q8(c_kv, -1)
        # EXPANDED: every token's own K and V a head
        k_nope = jnp.einsum("lc,chn->lhn", ck8, q8(kv_b[..., :dn], 0))
        v = jnp.einsum("lc,chv->lhv", ck8, q8(kv_b[..., dn:], 0))
        kn8, kr8, v8 = q8(k_nope, -1), q8(k_r, -1), q8(v, 0)
        if full:
            q_i = _rope((cq8 @ q8(f32["attn.indexer.wq_b"], 0)).reshape(
                length, ih, idim), pos, theta, dr)
            k_i = _layer_norm(qa @ q8(f32["attn.indexer.wk"], 0),
                              f32["attn.indexer.k_norm"],
                              f32["attn.indexer.k_norm_bias"], INDEX_NORM_EPS)
            k_i = q8(_rope(k_i[:, None, :], pos, theta, dr)[:, 0], -1)
            wt = qa @ q8(f32["attn.indexer.weights_proj"], 0)
        qb = min(Q_BLOCK, length)
        if length % qb:
            raise ValueError(f"length {length} is no multiple of {qb}")

        def block(i):
            # queries [i*qb, (i+1)*qb) against every key before them
            take = lambda z: jax.lax.dynamic_slice_in_dim(  # noqa: E731
                z, i * qb, qb, 0)
            gap = (i * qb + jnp.arange(qb))[:, None] - pos[None, :]
            before = gap >= 0
            if full:
                si = jnp.einsum("qhd,kd->qhk", q8(take(q_i), -1), k_i)
                if control != "no_relu":
                    si = jax.nn.relu(si)
                score = jnp.where(before,
                                  (si * take(wt)[:, :, None]).sum(1),
                                  -jnp.inf)
                best, at = jax.lax.top_k(score, min(topk, length))
                mine = jnp.zeros((qb, length), bool).at[
                    jnp.arange(qb)[:, None], at].set(best > -jnp.inf)
            else:
                mine = take(picked)
            seen = mine
            if control == "all_columns":
                seen = before
            elif control == "shared_last" and not full:
                seen = before & (gap < topk)
            sc = (jnp.einsum("qhn,khn->hqk", q8(take(q_nope), -1), kn8)
                  + jnp.einsum("qhr,kr->hqk", q8(take(q_rope), -1), kr8)
                  ) / math.sqrt(dn + dr)
            sc = jnp.where(seen[None], sc, -1e30)
            p = jax.nn.softmax(sc, axis=-1)
            ctx = jnp.einsum("hqk,khv->qhv", q8(p, -1), v8).reshape(
                qb, nh * dv)
            return (ctx, mine) if full else ctx

        out = jax.lax.map(block, jnp.arange(length // qb))
        ctx = out[0] if full else out
        x = x + q8(ctx.reshape(length, nh * dv), -1) @ q8(
            f32["attn.o_proj"], 0)
        h = _rms(x, f32["pre_mlp_norm"], eps)
        # (a shared layer hands nothing on: the caller keeps what it gave)
        return (x, h, out[1].reshape(length, length)) if full else (x, h)

    if full:
        return jax.jit(lambda w, x: jax.lax.map(
            lambda r: apply(w, r, None), x))
    return jax.jit(lambda w, x, picked: jax.lax.map(
        lambda rp: apply(w, *rp), (x, picked)))


@functools.lru_cache(maxsize=None)
def _mlp_programs(hf_json: str, control: str):
    """The jitted pieces of the MLP halves: ``route`` (scores, selection,
    weights), ``swiglu`` (a dense MLP, the shared expert) and ``one`` (one
    held expert on its own rows, added into the running sum)."""
    import jax
    import jax.numpy as jnp

    s = glm_sizes(json.loads(hf_json))
    q8 = _ROUND.get(control, _ROUND["f32"])
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    first, held = s["first"], s["held"]

    @jax.jit
    def route(h, router, bias):
        sc = jax.nn.sigmoid(q8(h, -1) @ q8(f32(router), 0))
        _, sel = jax.lax.top_k(sc + bias, s["top_k"])
        wt = jnp.take_along_axis(sc, sel, axis=-1)
        if s["norm_topk"]:
            wt = wt / (wt.sum(-1, keepdims=True) + 1e-20)
        wt = wt * s["route_scale"]
        if control == "weakest_held_dropped":
            here = (sel >= first) & (sel < first + held)
            weakest = jnp.where(here, wt, jnp.inf).min(-1, keepdims=True)
            wt = jnp.where(here & (wt == weakest), 0.0, wt)
        return sel, wt

    @jax.jit
    def swiglu(h, gate, up, down):
        return _swiglu(h, f32(gate), f32(up), f32(down), q8)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def one(out, hpad, idx, wts, e, gate, up, down):
        # held expert ``e`` of the stacked kernels on the rows ``idx`` alone
        mine = [jax.lax.dynamic_index_in_dim(f32_e, e, keepdims=False)
                for f32_e in (gate, up, down)]
        y = _swiglu(hpad[idx], *map(f32, mine), q8)
        return out.at[idx].add(wts[:, None] * y)

    return route, swiglu, one


def routed_part(hf: dict, w: dict, h, control: str = "f32"):
    """The HELD routed experts' part of an expert layer over ``h`` [T,
    hidden] (every sequence's tokens): each held expert on its OWN tokens;
    selected experts held elsewhere add nothing; no shared expert. Returns
    ``(m [T, hidden], sel [T, k] on the host)``."""
    import jax.numpy as jnp

    s = glm_sizes(hf)
    route, _, one = _mlp_programs(json.dumps(hf, sort_keys=True), control)
    t = h.shape[0]
    sel, wt = route(h, w["moe.router"], w["moe.expert_bias"])
    sel_h, wt_h = np.asarray(sel), np.asarray(wt)
    hpad = jnp.concatenate([h, jnp.zeros((1, h.shape[1]), h.dtype)])
    out = jnp.zeros_like(hpad)
    for e in range(s["held"]):
        rows, slot = np.nonzero(sel_h == s["first"] + e)
        if not rows.size:
            continue
        n = _sizes_up(rows.size)
        idx = np.full((n,), t, np.int32)       # pad rows: the spare row
        idx[:rows.size] = rows
        wts = np.zeros((n,), np.float32)
        wts[:rows.size] = wt_h[rows, slot]
        out = one(out, hpad, jnp.asarray(idx), jnp.asarray(wts),
                  jnp.asarray(e, jnp.int32), w["moe.experts_gate"],
                  w["moe.experts_up"], w["moe.experts_down"])
    return out[:t], sel_h


def shared_part(hf: dict, w: dict, h, control: str = "f32"):
    """The shared expert of an expert layer over ``h`` [T, hidden]: what
    every chip of the deployment computes alike."""
    return _mlp_programs(json.dumps(hf, sort_keys=True), control)[1](
        h, w["moe.shared.gate_proj"], w["moe.shared.up_proj"],
        w["moe.shared.down_proj"])


def glm_hidden(seed: int, hf: dict, seqs, dense_dtype: str = "bfloat16",
               control: str = "f32", keep_picked: bool = False):
    """The residual stream after the last layer for each row of ``seqs``
    ``[rows, length]`` (right-padded; ``length`` a multiple of
    ``min(Q_BLOCK, length)``), one layer made and let go at a time, and what
    the layers selected: ``{"experts": [layers][rows * length, k] | None,
    "picked": [layers] masks [rows, length, length] of each query's S_t
    (only if ``keep_picked``: at 14 k tokens one is 0.2 GB a row)}``. Call
    under ``jax.default_matmul_precision("highest")``."""
    import jax.numpy as jnp

    if control not in CONTROLS:
        raise ValueError(f"unknown control {control!r} (one of {CONTROLS})")
    s = glm_sizes(hf)
    hf_json = json.dumps(hf, sort_keys=True)
    seqs = jnp.asarray(seqs)
    rows, length = seqs.shape
    x = top_weights(seed, hf, dense_dtype)["embed_tokens"][seqs].astype(
        jnp.float32)
    sels, picks, picked = [], [], None
    for layer in range(s["layers"]):
        w = layer_weights(seed, layer, hf, dense_dtype)
        attn = {n: w[n] for n in w if not n.startswith(("mlp.", "moe."))}
        program = _attention_layer(hf_json, s["kinds"][layer], control)
        if s["kinds"][layer] == FULL:
            x, h, picked = program(attn, x)
        else:
            x, h = program(attn, x, picked)
        if keep_picked:
            picks.append(np.asarray(picked))
        h2 = h.reshape(rows * length, -1)
        if s["mlps"][layer] == SPARSE:
            m, sel = routed_part(hf, w, h2, control)
            m = m + shared_part(hf, w, h2, control)
            sels.append(sel)
        else:
            m = _mlp_programs(hf_json, control)[1](
                h2, w["mlp.gate_proj"], w["mlp.up_proj"], w["mlp.down_proj"])
            sels.append(None)
        x = x + m.reshape(rows, length, -1)
        del w, attn
    return x, {"experts": sels, "picked": picks}


@functools.lru_cache(maxsize=None)
def _head_program(eps: float, control: str):
    import jax
    import jax.numpy as jnp

    q8 = _ROUND.get(control, _ROUND["f32"])
    return jax.jit(lambda x, g, head: q8(_rms(x, g, eps), -1) @ q8(
        head.astype(jnp.float32), 0))


def glm_logits_at(top: dict, hf: dict, x_rows, control: str = "f32"):
    """Logits ``[n, vocab]`` of residual-stream rows ``[n, hidden]``: the
    last norm and the untied head of ``top`` (:func:`top_weights`)."""
    return _head_program(glm_sizes(hf)["eps"], control)(
        x_rows, top["norm"], top["lm_head"])


def glm_logits(seed: int, hf: dict, ids, dense_dtype: str = "bfloat16",
               control: str = "f32"):
    """Logits ``[length, vocab]`` of one short sequence (the tests')."""
    import jax

    with jax.default_matmul_precision("highest"):
        x, _ = glm_hidden(seed, hf, np.asarray(ids)[None], dense_dtype,
                          control)
        return glm_logits_at(top_weights(seed, hf, dense_dtype), hf, x[0],
                             control)


def glm_token_gaps(seed: int, hf: dict, seqs, spans,
                   dense_dtype: str = "bfloat16", control: str = "f32",
                   reference_hidden=None):
    """The float32 reference's verdict on the tokens served at ``spans``:
    for row ``r`` and each position ``t`` of ``spans[r] = (a, b)``, how far
    the reference's logit of token ``seqs[r, t+1]`` lies below the
    reference's best at ``t`` (0 where the served token IS the best). With
    a ``control`` the token judged at each position is the one THAT forward
    puts first (a control need not decode). ``reference_hidden``: the
    float32 forward's :func:`glm_hidden` over these ``seqs``, where the
    caller has it already (a probe judges the controls at the same
    positions). Returns ``(gaps [n], std of the reference's logits at those
    positions)``."""
    import jax
    import jax.numpy as jnp

    seqs = np.asarray(seqs)
    at = [(r, t) for r, (a, b) in enumerate(spans) for t in range(a, b)]
    rows_i = np.array([r for r, _ in at])
    cols_i = np.array([t for _, t in at])
    chosen = seqs[rows_i, cols_i + 1]

    def blocks(x, fn, control):
        top = top_weights(seed, hf, dense_dtype)
        out = []
        for i in range(0, len(at), HEAD_BLOCK):
            j = min(i + HEAD_BLOCK, len(at))
            out.append(fn(glm_logits_at(
                top, hf, x[rows_i[i:j], cols_i[i:j]], control), i, j))
        return out

    with jax.default_matmul_precision("highest"):
        if control != "f32":
            x, _ = glm_hidden(seed, hf, seqs, dense_dtype, control)
            chosen = np.concatenate(blocks(
                x, lambda lg, i, j: np.asarray(jnp.argmax(lg, -1)), control))
            del x
        x = reference_hidden
        if x is None:
            x, _ = glm_hidden(seed, hf, seqs, dense_dtype)

        def judge(lg, i, j):
            picked = jnp.take_along_axis(
                lg, jnp.asarray(chosen[i:j])[:, None], -1)[:, 0]
            return (np.asarray(lg.max(-1) - picked),
                    float(lg.sum()), float((lg * lg).sum()), lg.size)

        parts = blocks(x, judge, "f32")
    gaps = np.concatenate([p[0] for p in parts])
    n = sum(p[3] for p in parts)
    mean = sum(p[1] for p in parts) / n
    std = math.sqrt(max(sum(p[2] for p in parts) / n - mean * mean, 0.0))
    return gaps, std
