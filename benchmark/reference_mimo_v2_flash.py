"""The plain reference of the ``mimo_v2_flash`` family (Xiaomi MiMo-V2-Flash) and its seeded weights.

Nothing here imports ``sparkdl_tpu``. The forward is written out in
straightforward ``jax.numpy`` after the published ``config.json``, in float32
at ``highest`` matmul precision: plain masked attention a block of queries at
a time over EVERY key before them (no ring, no cache, no window-sized slice),
no batching of requests, no grouped product.

A layer ``l`` (``x`` ``[T, hidden]``; RMS norm before attention and before
the MLP; no biases; no norm of q or k):

- kind ``hybrid_layer_pattern[l]``: 0 full (``num_attention_heads`` query
  heads over ``num_key_value_heads`` K/V heads, rotary base ``rope_theta``,
  no sink), 1 window (over ``swa_num_key_value_heads``, base
  ``swa_rope_theta``, ``i - j < sliding_window``, a learned sink a head);
- ``q = h Wq`` ``[T, H, head_dim]``, ``k = h Wk`` ``[T, G, head_dim]``, ``v =
  attention_value_scale * h Wv`` ``[T, G, v_head_dim]``; rotation on the first
  ``int(head_dim * partial_rotary_factor)`` values of every q and k head,
  half-split pairs, by absolute position;
- ``a_ij = q_i . k_j / sqrt(head_dim)``, ``j <= i``; full: ``p =
  softmax_j(a)``; window: ``p_ij = exp(a_ij - m) / (sum_j exp(a_ij - m) +
  exp(s_h - m))``; ``o = p v`` -> ``[T, H * v_head_dim] Wo``;
- MLP: SwiGLU of ``intermediate_size`` where ``moe_layer_freq[l]`` is 0; else
  ``s = sigmoid(h Wr)`` over all ``n_routed_experts``, the top
  ``num_experts_per_tok`` by ``s + b``, weights ``s[sel] / (sum + 1e-20)``,
  ``y = sum_e w_e SwiGLU_e(h)`` over the selected experts THIS SHARE HOLDS
  (``first_expert``, ``experts_held``: the reference is given the same share
  as the program; what the absent experts would add is left out of both).
  No shared expert. An untied head.

**The share.** ``hf`` is the published keys with ``n_routed_experts`` the
ROUTER's width, and beside them ``experts_held`` and ``first_expert`` (absent:
all, from 0): the experts whose kernels exist here.

**One layer at a time.** The weights are a pure function of ``(seed,
layer)`` (``layer_weights``) and of ``seed`` alone for the embedding, the
last norm and the head (``top_weights``), in the types they are served in:
kernels normal 0.02, norm gains 1 + 0.05 normal, router kernels normal 0.02
and ``expert_bias`` normal 0.02 in float32, sinks 4 + normal 1.0 in float32
(so that both are exercised: :func:`layer_leaves` has the arithmetic). The
reference makes a layer, applies it to every checked sequence, and lets it
go; experts are applied to their OWN tokens only (indices found on the host,
padded to a few sizes).

**Controls** (``control=``), the reference put in the program's place with
one thing wrong, judged by the float32 reference at the served positions:
``"int8"`` and ``"float8"`` round every matmul operand; ``"bfloat16"``
rounds them to the precision the configuration STATES (not a fault: it says
how far a sound program may lie from float32); ``"weakest_held_dropped"``
leaves out each token's weakest selected expert among those held;
``"window_ignored"`` lets window layers see everything before them;
``"sink_left_out"`` drops the sink's term; ``"v_scale_left_out"`` does not
scale v; ``"whole_head_rotated"`` rotates all of a head's values;
``"window_127"`` sees one position fewer.
"""

from __future__ import annotations

import functools
import json
import math

import numpy as np

from benchmark.reference import seed_key
from benchmark.reference_afmoe import _ROUND, _rms, _sizes_up, _swiglu

FULL, WINDOW = 0, 1
CONTROLS = ("f32", "bfloat16", "int8", "float8", "weakest_held_dropped",
            "window_ignored", "sink_left_out", "v_scale_left_out",
            "whole_head_rotated", "window_127")
#: queries a block of the reference's attention, rows a block of its head
Q_BLOCK, HEAD_BLOCK = 256, 256


def mimo_sizes(hf: dict) -> dict:
    """The sizes a ``mimo_v2_flash`` ``config.json`` (and the share) fixes."""
    kinds = [int(t) for t in hf["hybrid_layer_pattern"]]
    moe = [int(t) for t in hf["moe_layer_freq"]]
    if not len(kinds) == len(moe) == int(hf["num_hidden_layers"]):
        raise ValueError("hybrid_layer_pattern, moe_layer_freq and "
                         "num_hidden_layers disagree")
    experts = int(hf["n_routed_experts"])
    held = int(hf.get("experts_held") or experts)
    first = int(hf.get("first_expert", 0))
    if not 0 <= first <= first + held <= experts:
        raise ValueError(f"experts [{first}, {first + held}) are not among "
                         f"the router's {experts}")
    scale = hf.get("routed_scaling_factor")
    d = int(hf["head_dim"])
    return {
        "hidden": int(hf["hidden_size"]), "layers": len(kinds),
        "kinds": kinds, "moe": moe,
        "heads": int(hf["num_attention_heads"]),
        "kv_heads": {FULL: int(hf["num_key_value_heads"]),
                     WINDOW: int(hf["swa_num_key_value_heads"])},
        "head_dim": d, "v_head_dim": int(hf["v_head_dim"]),
        "rotary": int(d * float(hf["partial_rotary_factor"])),
        "theta": {FULL: float(hf["rope_theta"]),
                  WINDOW: float(hf["swa_rope_theta"])},
        "v_scale": float(hf.get("attention_value_scale", 1.0)),
        "window": int(hf["sliding_window"]),
        "inner": int(hf["intermediate_size"]),
        "expert_inner": int(hf["moe_intermediate_size"]),
        "experts": experts, "held": held, "first": first,
        "top_k": int(hf["num_experts_per_tok"]),
        "norm_topk": bool(hf.get("norm_topk_prob", True)),
        "route_scale": 1.0 if scale is None else float(scale),
        "vocab": int(hf["vocab_size"]),
        "eps": float(hf.get("layernorm_epsilon", 1e-5)),
    }


# -- seeded weights --------------------------------------------------------------

def layer_leaves(hf: dict, layer: int) -> "dict[str, tuple]":
    """name -> (shape, kind) of one layer's weights. Kinds: ``kernel``
    (normal 0.02, the dense dtype), ``gain`` (1 + 0.05 normal, float32),
    ``router`` and ``bias`` (normal 0.02, float32), ``sink`` (4 + normal
    1.0, float32). At the published widths a score ``q . k / sqrt(192)`` of
    these kernels has a spread of 1.64 (``4096 x 0.02^2 = 1.64`` a value of
    q and of k), so a full window's 128 terms sum to some 300-500 and a
    sink of ``exp(4 +- 1)`` = 20-150 takes a tenth to a third of the
    weight: far over bfloat16's rounding, far under all of it. A sink of
    normal 1.0 alone (``exp`` of it 0.4-2.7) would take under a hundredth
    and leaving it out would be lost in the rounding."""
    s = mimo_sizes(hf)
    h, nh, dk, dv = s["hidden"], s["heads"], s["head_dim"], s["v_head_dim"]
    g = s["kv_heads"][s["kinds"][layer]]
    out = {
        "input_norm": ((h,), "gain"), "pre_mlp_norm": ((h,), "gain"),
        "attn.q_proj": ((h, nh * dk), "kernel"),
        "attn.k_proj": ((h, g * dk), "kernel"),
        "attn.v_proj": ((h, g * dv), "kernel"),
        "attn.o_proj": ((nh * dv, h), "kernel"),
    }
    if s["kinds"][layer] == WINDOW:
        out["attn.sink"] = ((nh,), "sink")
    if s["moe"][layer]:
        f, e, held = s["expert_inner"], s["experts"], s["held"]
        out.update({
            "moe.router": ((h, e), "router"),
            "moe.expert_bias": ((e,), "bias"),
            "moe.experts_gate": ((held, h, f), "kernel"),
            "moe.experts_up": ((held, h, f), "kernel"),
            "moe.experts_down": ((held, f, h), "kernel")})
    else:
        f = s["inner"]
        out.update({"mlp.gate_proj": ((h, f), "kernel"),
                    "mlp.up_proj": ((h, f), "kernel"),
                    "mlp.down_proj": ((f, h), "kernel")})
    return out


def top_leaves(hf: dict) -> "dict[str, tuple]":
    s = mimo_sizes(hf)
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
                         else 4.0 + x if kind == "sink" else 0.02 * x)
        return out

    return jax.jit(make)


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


def seeded_weight_bytes(hf: dict, dense_dtype: str = "bfloat16") -> int:
    """Bytes of every seeded array, counted from the tables above."""
    dense = np.dtype("float32").itemsize if dense_dtype == "float32" else 2
    tables = [top_leaves(hf)] + [layer_leaves(hf, i)
                                 for i in range(mimo_sizes(hf)["layers"])]
    return sum(int(np.prod(shape)) * (dense if kind == "kernel" else 4)
               for t in tables for shape, kind in t.values())


# -- the forward -------------------------------------------------------------------

def _rope(x, pos, theta, rot):
    """Rotary on the first ``rot`` values of every head, half-split pairs;
    the rest pass. x [L, H, D]; pos [L]."""
    import jax.numpy as jnp

    half = rot // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:rot]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos, x[..., rot:]], -1)


@functools.lru_cache(maxsize=None)
def _attention_layer(hf_json: str, kind: int, control: str):
    """``(weights, x [rows, L, hidden]) -> (x, h)``: the residual stream
    after the attention half of a layer and the normed input of its MLP,
    one sequence at a time. One jitted program per kind of layer."""
    import jax
    import jax.numpy as jnp

    s = mimo_sizes(json.loads(hf_json))
    q8 = _ROUND.get(control, _ROUND["f32"])
    nh, ng, eps = s["heads"], s["kv_heads"][kind], s["eps"]
    dk, dv = s["head_dim"], s["v_head_dim"]
    rot = dk if control == "whole_head_rotated" else s["rotary"]
    v_scale = 1.0 if control == "v_scale_left_out" else s["v_scale"]
    window = None
    if kind == WINDOW and control != "window_ignored":
        window = s["window"] - (control == "window_127")
    sunk = kind == WINDOW and control != "sink_left_out"

    def apply(w, x):
        length = x.shape[0]
        f32 = {k: v.astype(jnp.float32) for k, v in w.items()}
        a = _rms(x, f32["input_norm"], eps)
        qa = q8(a, -1)
        q = (qa @ q8(f32["attn.q_proj"], 0)).reshape(length, nh, dk)
        k = (qa @ q8(f32["attn.k_proj"], 0)).reshape(length, ng, dk)
        v = v_scale * (qa @ q8(f32["attn.v_proj"], 0)).reshape(length, ng, dv)
        pos = jnp.arange(length)
        q = _rope(q, pos, s["theta"][kind], rot)
        k = _rope(k, pos, s["theta"][kind], rot)
        qb = min(Q_BLOCK, length)
        if length % qb:
            raise ValueError(f"length {length} is no multiple of {qb}")
        kq, vq = q8(k, -1), q8(v, 0)

        def block(i):
            # queries [i*qb, (i+1)*qb) of every head against every key
            qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb, 0)
            qi = q8(qi, -1).reshape(qb, ng, nh // ng, dk)
            sc = jnp.einsum("qgrd,kgd->grqk", qi, kq) / math.sqrt(dk)
            gap = (i * qb + jnp.arange(qb))[:, None] - pos[None, :]
            seen = gap >= 0
            if window is not None:
                seen &= gap < window
            sc = jnp.where(seen, sc, -1e30)
            top = sc.max(-1, keepdims=True)
            if sunk:
                sink = f32["attn.sink"].reshape(ng, nh // ng, 1, 1)
                top = jnp.maximum(top, sink)
            e = jnp.exp(sc - top)
            total = e.sum(-1, keepdims=True)
            if sunk:
                total = total + jnp.exp(sink - top)
            return jnp.einsum("grqk,kgd->qgrd", q8(e / total, -1),
                              vq).reshape(qb, nh * dv)

        ctx = jax.lax.map(block, jnp.arange(length // qb)).reshape(
            length, nh * dv)
        x = x + q8(ctx, -1) @ q8(f32["attn.o_proj"], 0)
        return x, _rms(x, f32["pre_mlp_norm"], eps)

    return jax.jit(lambda w, x: jax.lax.map(lambda r: apply(w, r), x))


@functools.lru_cache(maxsize=None)
def _mlp_programs(hf_json: str, control: str):
    """The jitted pieces of the MLP halves: ``route`` (scores, selection,
    weights), ``swiglu`` (a dense MLP) and ``one`` (one held expert on its
    own rows, added into the running sum)."""
    import jax
    import jax.numpy as jnp

    s = mimo_sizes(json.loads(hf_json))
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


def _expert_layer(hf_json: str, w: dict, h, control: str):
    """The expert MLP over ``h`` [T, hidden] (every sequence's tokens): each
    HELD routed expert on its OWN tokens; selected experts held elsewhere
    add nothing. Returns ``(m [T, hidden], sel [T, k] on the host)``."""
    import jax.numpy as jnp

    s = mimo_sizes(json.loads(hf_json))
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
    return out[:t], sel_h


def mimo_hidden(seed: int, hf: dict, seqs, dense_dtype: str = "bfloat16",
                control: str = "f32"):
    """The residual stream after the last layer for each row of ``seqs``
    ``[rows, length]`` (right-padded; ``length`` a multiple of
    ``min(Q_BLOCK, length)``), one layer made and let go at a time, and the
    experts each layer selected (``[layers][rows * length, k]``, None for a
    dense layer). Call under ``jax.default_matmul_precision("highest")``."""
    import jax.numpy as jnp

    if control not in CONTROLS:
        raise ValueError(f"unknown control {control!r} (one of {CONTROLS})")
    s = mimo_sizes(hf)
    hf_json = json.dumps(hf, sort_keys=True)
    seqs = jnp.asarray(seqs)
    rows, length = seqs.shape
    x = top_weights(seed, hf, dense_dtype)["embed_tokens"][seqs].astype(
        jnp.float32)
    sels = []
    for layer in range(s["layers"]):
        w = layer_weights(seed, layer, hf, dense_dtype)
        names = [n for n in w if not n.startswith(("mlp.", "moe."))]
        x, h = _attention_layer(hf_json, s["kinds"][layer], control)(
            {n: w[n] for n in names}, x)
        h2 = h.reshape(rows * length, -1)
        if s["moe"][layer]:
            m, sel = _expert_layer(hf_json, w, h2, control)
            sels.append(sel)
        else:
            m = _mlp_programs(hf_json, control)[1](
                h2, w["mlp.gate_proj"], w["mlp.up_proj"], w["mlp.down_proj"])
            sels.append(None)
        x = x + m.reshape(rows, length, -1)
        del w
    return x, sels


@functools.lru_cache(maxsize=None)
def _head_program(eps: float, control: str):
    import jax
    import jax.numpy as jnp

    q8 = _ROUND.get(control, _ROUND["f32"])
    return jax.jit(lambda x, g, head: q8(_rms(x, g, eps), -1) @ q8(
        head.astype(jnp.float32), 0))


def mimo_logits_at(top: dict, hf: dict, x_rows, control: str = "f32"):
    """Logits ``[n, vocab]`` of residual-stream rows ``[n, hidden]``: the
    last norm and the untied head of ``top`` (:func:`top_weights`)."""
    return _head_program(mimo_sizes(hf)["eps"], control)(
        x_rows, top["norm"], top["lm_head"])


def mimo_logits(seed: int, hf: dict, ids, dense_dtype: str = "bfloat16",
                control: str = "f32"):
    """Logits ``[length, vocab]`` of one short sequence (the tests')."""
    import jax

    with jax.default_matmul_precision("highest"):
        x, _ = mimo_hidden(seed, hf, np.asarray(ids)[None], dense_dtype,
                           control)
        return mimo_logits_at(top_weights(seed, hf, dense_dtype), hf, x[0],
                              control)


def mimo_token_gaps(seed: int, hf: dict, seqs, spans,
                    dense_dtype: str = "bfloat16", control: str = "f32",
                    reference_hidden=None):
    """The float32 reference's verdict on the tokens served at ``spans``:
    for row ``r`` and each position ``t`` of ``spans[r] = (a, b)``, how far
    the reference's logit of token ``seqs[r, t+1]`` lies below the
    reference's best at ``t`` (0 where the served token IS the best). With
    a ``control`` the token judged at each position is the one THAT forward
    puts first (a control need not decode). ``reference_hidden``: the
    float32 forward's :func:`mimo_hidden` over these ``seqs``, where the
    caller has it already (a probe judges ten controls at the same
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
            out.append(fn(mimo_logits_at(
                top, hf, x[rows_i[i:j], cols_i[i:j]], control), i, j))
        return out

    with jax.default_matmul_precision("highest"):
        if control != "f32":
            x, _ = mimo_hidden(seed, hf, seqs, dense_dtype, control)
            chosen = np.concatenate(blocks(
                x, lambda lg, i, j: np.asarray(jnp.argmax(lg, -1)), control))
            del x
        x = reference_hidden
        if x is None:
            x, _ = mimo_hidden(seed, hf, seqs, dense_dtype)

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
