"""The plain reference of the ``lfm2_moe`` family (LiquidAI LFM2-24B-A2B) and its seeded weights.

Nothing here imports ``sparkdl_tpu``. The forward is written out in
straightforward ``jax.numpy`` after the published ``config.json`` and the
family's public modelling code, in float32 at ``highest`` matmul precision:
the short convolution as ONE causal depthwise convolution over the whole
sequence (``lax.conv_general_dilated``: no tail, no step, no chunk), plain
masked attention a block of queries at a time over EVERY key before them (no
cache, no table), no batching of requests, no grouped product.

A layer ``l`` (``x`` ``[T, hidden]``; ``r = x + op_l(rms(x;
operator_norm))``; ``y = r + ff_l(rms(r; ffn_norm))``; no biases):

- ``op_l``, ``layer_types[l] == "conv"``: ``[B, C, u] = split3(h W_in)``;
  ``z = B * u``; ``c_t = sum_{j < taps} w[j] z_{t - (taps-1) + j}``, ``z``
  zero before token 0 (``taps`` = ``conv_L_cache``; one weight a channel a
  tap; no activation); ``out = (C * c) W_out``;
- ``op_l``, ``"full_attention"``: ``q = h Wq`` ``[T, H, d]``, ``k = h Wk``,
  ``v = h Wv`` ``[T, G, d]``; ``q = rms_d(q; q_layernorm)``, ``k = rms_d(k;
  k_layernorm)`` a head, BEFORE the rotation; the rotation over the whole
  head, half-split pairs, base ``rope_theta``, by absolute position; ``a_ij
  = q_i . k_j / sqrt(d)``, ``j <= i``; ``p = softmax_j(a)``; ``o = p v`` ->
  ``[T, H * d] Wo``; each K/V head serves ``H / G`` query heads;
- ``ff_l``: SwiGLU of ``intermediate_size`` for ``l < num_dense_layers``;
  else ``s = sigmoid(h Wr)`` over all ``num_experts``, the top
  ``num_experts_per_tok`` by ``s + expert_bias`` (``use_expert_bias``),
  weights ``s[sel] / (sum + 1e-6)`` (``norm_topk_prob``) times
  ``routed_scaling_factor``, ``y = sum_e w_e SwiGLU_e(h)`` over the selected
  experts THIS SHARE HOLDS (``first_expert``, ``experts_held``: absent, all
  of them). No shared expert.
- after the last layer ``rms(x; embedding_norm)`` and the head, which is the
  embedding's transpose.

**One layer at a time.** The weights are a pure function of ``(seed,
layer)`` (``layer_weights``) and of ``seed`` alone for the embedding and the
last norm (``top_weights``), in the types they are served in: kernels normal
0.02 at the published hidden size (:func:`kernel_std`: ``0.02 * sqrt(2048 /
hidden_size)``, so that a product has the same gain at the rehearsal's hidden
64, where 0.02 would leave every layer a hundredth of the embedding and the
tied head would hand each token back), norm gains 1 + 0.05 normal, router
kernels as the kernels and ``expert_bias`` normal 0.05 in float32 (not zero,
and wide enough to move the selection of most tokens: the 4th and 5th of 64
scores lie some 0.02 apart), convolution taps normal 0.5 (every tap carries
a third of the sum, so a dropped tail shows). The reference makes a layer,
applies it to every checked sequence, and lets it go (an expert layer is 2.4
GB in float32: its experts are cast one at a time); experts are applied to
their OWN tokens only (indices found on the host, padded to a few sizes).

**Controls** (``control=``), the reference put in the program's place with
one thing wrong, judged by the float32 reference at the served positions:
``"int8"`` and ``"float8"`` round every matmul operand; ``"bfloat16"``
rounds them to the precision the configuration STATES (not a fault: it says
how far a sound program may lie from float32); ``"weakest_dropped"`` leaves
out each token's weakest selected expert; ``"bias_left_out"`` selects by the
scores alone; ``"tail_zeroed_N"`` zeroes the convolution's history at every
``N``-token boundary (the fault a chunk carry would have: ``N`` = 256 is the
engine's chunk); ``"gate_left_out"`` drops the gate ``C``;
``"qk_norm_left_out"`` does not norm q and k.
"""

from __future__ import annotations

import functools
import json
import math

import numpy as np

from benchmark.reference import seed_key
from benchmark.reference_afmoe import _ROUND, _rms, _rope, _sizes_up, _swiglu

CONV, FULL = "conv", "full_attention"
CONTROLS = ("f32", "bfloat16", "int8", "float8", "weakest_dropped",
            "bias_left_out", "tail_zeroed_256", "gate_left_out",
            "qk_norm_left_out")
#: queries a block of the reference's attention, rows a block of its head
Q_BLOCK, HEAD_BLOCK = 256, 256
#: added to the sum of a token's selected scores (the modelling code's)
ROUTE_NORM_EPS = 1e-6


def _rounding(control: str):
    return _ROUND.get(control, _ROUND["f32"])


def _known(control: str) -> bool:
    head, _, n = control.rpartition("_")
    return control in CONTROLS or (head == "tail_zeroed" and n.isdigit()
                                   and int(n) > 0)


def lfm2_sizes(hf: dict) -> dict:
    """The sizes an ``lfm2_moe`` ``config.json`` (and the share) fixes."""
    kinds = list(hf["layer_types"])
    if len(kinds) != int(hf["num_hidden_layers"]):
        raise ValueError("layer_types and num_hidden_layers disagree")
    if set(kinds) - {CONV, FULL}:
        raise ValueError(f"unknown layer types {sorted(set(kinds))}")
    experts = int(hf["num_experts"])
    held = int(hf.get("experts_held") or experts)
    first = int(hf.get("first_expert", 0))
    if not 0 <= first <= first + held <= experts:
        raise ValueError(f"experts [{first}, {first + held}) are not among "
                         f"the router's {experts}")
    heads, hidden = int(hf["num_attention_heads"]), int(hf["hidden_size"])
    scale = hf.get("routed_scaling_factor")
    return {
        "hidden": hidden, "layers": len(kinds), "kinds": kinds,
        "dense_layers": int(hf["num_dense_layers"]),
        "heads": heads, "kv_heads": int(hf["num_key_value_heads"]),
        "head_dim": int(hf.get("head_dim") or hidden // heads),
        "theta": float(hf["rope_parameters"]["rope_theta"]),
        "taps": int(hf["conv_L_cache"]),
        "inner": int(hf["intermediate_size"]),
        "expert_inner": int(hf["moe_intermediate_size"]),
        "experts": experts, "held": held, "first": first,
        "top_k": int(hf["num_experts_per_tok"]),
        "norm_topk": bool(hf.get("norm_topk_prob", True)),
        "use_bias": bool(hf.get("use_expert_bias", True)),
        "route_scale": 1.0 if scale is None else float(scale),
        "vocab": int(hf["vocab_size"]),
        "eps": float(hf.get("norm_eps", 1e-5)),
    }


# -- seeded weights --------------------------------------------------------------

def kernel_std(hf: dict) -> float:
    """The standard deviation of a seeded kernel: 0.02 at the published
    hidden size of 2048, and at any other the one that gives a product over
    ``hidden_size`` the same gain."""
    return 0.02 * math.sqrt(2048 / int(hf["hidden_size"]))


def layer_leaves(hf: dict, layer: int) -> "dict[str, tuple]":
    """name -> (shape, kind) of one layer's weights. Kinds: ``kernel``
    (normal :func:`kernel_std`, the dense dtype), ``tap`` (normal 0.5, the
    dense dtype), ``gain`` (1 + 0.05 normal, float32), ``router`` (normal
    :func:`kernel_std`, float32), ``bias`` (normal 0.05, float32)."""
    s = lfm2_sizes(hf)
    h, nh, ng, d = s["hidden"], s["heads"], s["kv_heads"], s["head_dim"]
    out = {"operator_norm": ((h,), "gain"), "ffn_norm": ((h,), "gain")}
    if s["kinds"][layer] == CONV:
        out.update({"conv.in_proj": ((h, 3 * h), "kernel"),
                    "conv.conv": ((s["taps"], h), "tap"),
                    "conv.out_proj": ((h, h), "kernel")})
    else:
        out.update({"self_attn.q_proj": ((h, nh * d), "kernel"),
                    "self_attn.k_proj": ((h, ng * d), "kernel"),
                    "self_attn.v_proj": ((h, ng * d), "kernel"),
                    "self_attn.out_proj": ((nh * d, h), "kernel"),
                    "self_attn.q_layernorm": ((d,), "gain"),
                    "self_attn.k_layernorm": ((d,), "gain")})
    if layer < s["dense_layers"]:
        f = s["inner"]
        out.update({"feed_forward.gate_proj": ((h, f), "kernel"),
                    "feed_forward.up_proj": ((h, f), "kernel"),
                    "feed_forward.down_proj": ((f, h), "kernel")})
    else:
        f, e, held = s["expert_inner"], s["experts"], s["held"]
        out.update({"moe.router": ((h, e), "router"),
                    "moe.expert_bias": ((e,), "bias"),
                    "moe.experts_gate": ((held, h, f), "kernel"),
                    "moe.experts_up": ((held, h, f), "kernel"),
                    "moe.experts_down": ((held, f, h), "kernel")})
    return out


def top_leaves(hf: dict) -> "dict[str, tuple]":
    s = lfm2_sizes(hf)
    return {"embed_tokens": ((s["vocab"], s["hidden"]), "kernel"),
            "embedding_norm": ((s["hidden"],), "gain")}


@functools.lru_cache(maxsize=None)
def _maker(leaves_json: str, dense_dtype: str, std: float):
    """The jitted ``key -> {name: array}`` of a table of leaves (one program
    a kind of layer), kernels of standard deviation ``std``."""
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
                         else (std * x).astype(dense) if kind == "kernel"
                         else (0.5 * x).astype(dense) if kind == "tap"
                         else 0.05 * x if kind == "bias" else std * x)
        return out

    return jax.jit(make)


def layer_weights(seed: int, layer: int, hf: dict,
                  dense_dtype: str = "bfloat16") -> dict:
    """One layer's seeded weights on the device, from ``(seed, layer)``."""
    import jax

    key = jax.random.fold_in(seed_key(seed), 1 + layer)
    return _maker(json.dumps(layer_leaves(hf, layer)), dense_dtype,
                  kernel_std(hf))(key)


def top_weights(seed: int, hf: dict, dense_dtype: str = "bfloat16") -> dict:
    """The embedding (and tied head) and the last norm, from ``seed``."""
    import jax

    return _maker(json.dumps(top_leaves(hf)), dense_dtype, kernel_std(hf))(
        jax.random.fold_in(seed_key(seed), 0))


def _count(hf: dict, size_of) -> int:
    tables = [top_leaves(hf)] + [layer_leaves(hf, i)
                                 for i in range(lfm2_sizes(hf)["layers"])]
    return sum(int(np.prod(shape)) * size_of(kind)
               for t in tables for shape, kind in t.values())


def seeded_weight_bytes(hf: dict, dense_dtype: str = "bfloat16") -> int:
    """Bytes of every seeded array, counted from the tables above."""
    dense = np.dtype("float32").itemsize if dense_dtype == "float32" else 2
    return _count(hf, lambda kind: dense if kind in ("kernel", "tap") else 4)


def seeded_parameters(hf: dict) -> int:
    """The count of seeded parameters (the tied head counted once)."""
    return _count(hf, lambda kind: 1)


# -- the forward -------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _operator_layer(hf_json: str, kind: str, control: str):
    """``(weights, x [rows, L, hidden]) -> (x, h)``: the residual stream
    after the operator half of a layer and the normed input of its MLP, one
    sequence at a time. One jitted program per kind of layer."""
    import jax
    import jax.numpy as jnp

    s = lfm2_sizes(json.loads(hf_json))
    q8 = _rounding(control)
    nh, ng, d, eps, taps = (s["heads"], s["kv_heads"], s["head_dim"],
                            s["eps"], s["taps"])
    every = (int(control.rpartition("_")[2])
             if control.startswith("tail_zeroed_") else None)

    def conv(f32, a):
        length, hid = a.shape
        bcu = q8(a, -1) @ q8(f32["conv.in_proj"], 0)
        gate_b, gate_c, u = jnp.split(bcu, 3, axis=-1)
        z = gate_b * u
        w = f32["conv.conv"]                                 # [taps, hidden]
        if every is None:
            # ONE causal depthwise convolution over the whole sequence:
            # out[t] = sum_j w[j] z[t - (taps - 1) + j], zeros before 0
            c = jax.lax.conv_general_dilated(
                z.T[None], w.T[:, None, :], window_strides=(1,),
                padding=[(taps - 1, 0)], feature_group_count=hid,
                precision=jax.lax.Precision.HIGHEST)[0].T
        else:
            # the fault: history is lost at every ``every``-token boundary
            t = jnp.arange(length)
            zp = jnp.pad(z, ((taps - 1, 0), (0, 0)))
            c = sum(jnp.where(((t - (taps - 1) + j) // every
                               == t // every)[:, None],
                              zp[j:j + length] * w[j], 0.0)
                    for j in range(taps))
        y = c if control == "gate_left_out" else gate_c * c
        return q8(y, -1) @ q8(f32["conv.out_proj"], 0)

    def attention(f32, a):
        length = a.shape[0]
        qa = q8(a, -1)
        q = (qa @ q8(f32["self_attn.q_proj"], 0)).reshape(length, nh, d)
        k = (qa @ q8(f32["self_attn.k_proj"], 0)).reshape(length, ng, d)
        v = (qa @ q8(f32["self_attn.v_proj"], 0)).reshape(length, ng, d)
        if control != "qk_norm_left_out":
            q = _rms(q, f32["self_attn.q_layernorm"], eps)
            k = _rms(k, f32["self_attn.k_layernorm"], eps)
        pos = jnp.arange(length)
        q, k = _rope(q, pos, s["theta"]), _rope(k, pos, s["theta"])
        qb = min(Q_BLOCK, length)
        if length % qb:
            raise ValueError(f"length {length} is no multiple of {qb}")
        kq, vq = q8(k, -1), q8(v, 0)

        def block(i):
            # queries [i*qb, (i+1)*qb) of every head against every key
            qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb, 0)
            qi = q8(qi, -1).reshape(qb, ng, nh // ng, d)
            sc = jnp.einsum("qgrd,kgd->grqk", qi, kq) / math.sqrt(d)
            seen = (i * qb + jnp.arange(qb))[:, None] >= pos[None, :]
            p = jax.nn.softmax(jnp.where(seen, sc, -1e30), axis=-1)
            return jnp.einsum("grqk,kgd->qgrd", q8(p, -1), vq).reshape(
                qb, nh * d)

        ctx = jax.lax.map(block, jnp.arange(length // qb)).reshape(
            length, nh * d)
        return q8(ctx, -1) @ q8(f32["self_attn.out_proj"], 0)

    def apply(w, x):
        f32 = {k: v.astype(jnp.float32) for k, v in w.items()}
        a = _rms(x, f32["operator_norm"], eps)
        x = x + (conv if kind == CONV else attention)(f32, a)
        return x, _rms(x, f32["ffn_norm"], eps)

    return jax.jit(lambda w, x: jax.lax.map(lambda r: apply(w, r), x))


@functools.lru_cache(maxsize=None)
def _mlp_programs(hf_json: str, control: str):
    """The jitted pieces of the MLP halves: ``route`` (scores, selection,
    weights), ``swiglu`` (a dense MLP) and ``one`` (one held expert on its
    own rows, added into the running sum)."""
    import jax
    import jax.numpy as jnp

    s = lfm2_sizes(json.loads(hf_json))
    q8 = _rounding(control)
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    biased = s["use_bias"] and control != "bias_left_out"

    @jax.jit
    def route(h, router, bias):
        sc = jax.nn.sigmoid(q8(h, -1) @ q8(f32(router), 0))
        _, sel = jax.lax.top_k(sc + bias if biased else sc, s["top_k"])
        wt = jnp.take_along_axis(sc, sel, axis=-1)
        if s["norm_topk"]:
            wt = wt / (wt.sum(-1, keepdims=True) + ROUTE_NORM_EPS)
        wt = wt * s["route_scale"]
        if control == "weakest_dropped":
            wt = jnp.where(wt == wt.min(-1, keepdims=True), 0.0, wt)
        return sel, wt

    @jax.jit
    def swiglu(h, gate, up, down):
        return _swiglu(h, f32(gate), f32(up), f32(down), q8)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def one(out, hpad, idx, wts, e, gate, up, down):
        # held expert ``e`` of the stacked kernels on the rows ``idx`` alone
        mine = [jax.lax.dynamic_index_in_dim(k, e, keepdims=False)
                for k in (gate, up, down)]
        y = _swiglu(hpad[idx], *map(f32, mine), q8)
        return out.at[idx].add(wts[:, None] * y)

    return route, swiglu, one


def _expert_layer(hf_json: str, w: dict, h, control: str):
    """The expert MLP over ``h`` [T, hidden] (every sequence's tokens): each
    HELD routed expert on its OWN tokens; selected experts held elsewhere
    add nothing. Returns ``(m [T, hidden], sel [T, k], wt [T, k])``, the
    last two on the host."""
    import jax.numpy as jnp

    s = lfm2_sizes(json.loads(hf_json))
    route, _, one = _mlp_programs(hf_json, control)
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
    return out[:t], sel_h, wt_h


def lfm2_hidden(seed: int, hf: dict, seqs, dense_dtype: str = "bfloat16",
                control: str = "f32"):
    """The residual stream after the last layer for each row of ``seqs``
    ``[rows, length]`` (right-padded; ``length`` a multiple of
    ``min(Q_BLOCK, length)``), one layer made and let go at a time, and the
    experts each layer selected with their weights (``[layers]`` of ``(sel,
    wt)`` ``[rows * length, k]``, None for a dense layer). Call under
    ``jax.default_matmul_precision("highest")``."""
    import jax.numpy as jnp

    if not _known(control):
        raise ValueError(f"unknown control {control!r} (one of {CONTROLS})")
    s = lfm2_sizes(hf)
    hf_json = json.dumps(hf, sort_keys=True)
    seqs = jnp.asarray(seqs)
    rows, length = seqs.shape
    x = top_weights(seed, hf, dense_dtype)["embed_tokens"][seqs].astype(
        jnp.float32)
    routed = []
    for layer in range(s["layers"]):
        w = layer_weights(seed, layer, hf, dense_dtype)
        names = [n for n in w if not n.startswith(("feed_forward.", "moe."))]
        x, h = _operator_layer(hf_json, s["kinds"][layer], control)(
            {n: w[n] for n in names}, x)
        h2 = h.reshape(rows * length, -1)
        if layer < s["dense_layers"]:
            m = _mlp_programs(hf_json, control)[1](
                h2, w["feed_forward.gate_proj"], w["feed_forward.up_proj"],
                w["feed_forward.down_proj"])
            routed.append(None)
        else:
            m, sel, wt = _expert_layer(hf_json, w, h2, control)
            routed.append((sel, wt))
        x = x + m.reshape(rows, length, -1)
        del w
    return x, routed


@functools.lru_cache(maxsize=None)
def _head_program(eps: float, control: str):
    import jax
    import jax.numpy as jnp

    q8 = _rounding(control)
    return jax.jit(lambda x, g, embed: q8(_rms(x, g, eps), -1) @ q8(
        embed.astype(jnp.float32).T, 0))


def lfm2_logits_at(top: dict, hf: dict, x_rows, control: str = "f32"):
    """Logits ``[n, vocab]`` of residual-stream rows ``[n, hidden]``: the
    last norm and the tied head of ``top`` (:func:`top_weights`)."""
    return _head_program(lfm2_sizes(hf)["eps"], control)(
        x_rows, top["embedding_norm"], top["embed_tokens"])


def lfm2_logits(seed: int, hf: dict, ids, dense_dtype: str = "bfloat16",
                control: str = "f32"):
    """Logits ``[length, vocab]`` of one short sequence (the tests')."""
    import jax

    with jax.default_matmul_precision("highest"):
        x, _ = lfm2_hidden(seed, hf, np.asarray(ids)[None], dense_dtype,
                           control)
        return lfm2_logits_at(top_weights(seed, hf, dense_dtype), hf, x[0],
                              control)


def lfm2_token_gaps(seed: int, hf: dict, seqs, spans,
                    dense_dtype: str = "bfloat16", control: str = "f32",
                    reference_hidden=None):
    """The float32 reference's verdict on the tokens served at ``spans``:
    for row ``r`` and each position ``t`` of ``spans[r] = (a, b)``, how far
    the reference's logit of token ``seqs[r, t+1]`` lies below the
    reference's best at ``t`` (0 where the served token IS the best). With
    a ``control`` the token judged at each position is the one THAT forward
    puts first (a control need not decode). ``reference_hidden``: the
    float32 forward's :func:`lfm2_hidden` over these ``seqs``, where the
    caller has it already (a probe judges every control at the same
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
            out.append(fn(lfm2_logits_at(
                top, hf, x[rows_i[i:j], cols_i[i:j]], control), i, j))
        return out

    with jax.default_matmul_precision("highest"):
        if control != "f32":
            x, _ = lfm2_hidden(seed, hf, seqs, dense_dtype, control)
            chosen = np.concatenate(blocks(
                x, lambda lg, i, j: np.asarray(jnp.argmax(lg, -1)), control))
            del x
        x = reference_hidden
        if x is None:
            x, _ = lfm2_hidden(seed, hf, seqs, dense_dtype)

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
