"""The plain reference of the ``afmoe`` family (Arcee Trinity) and its seeded weights.

Nothing here imports ``sparkdl_tpu``. The forward is written out in
straightforward ``jax.numpy`` after the published ``config.json`` and the
``afmoe`` modelling code of ``transformers``, in float32 at ``highest``
matmul precision: no cache, no batching of requests, no kernels.

**One layer at a time.** The weights are a pure function of ``(seed,
layer)`` (``layer_weights``) and of ``seed`` alone for the embedding, the
last norm and the head (``top_weights``), in the types they are served in.
Neither the program's set-up nor the reference ever holds the model whole
in float32 (17 GB at the benchmark's cut): the reference makes a layer,
applies it to every checked sequence, and lets it go. Attention is computed
a block of queries at a time; experts are applied to their OWN tokens only
(indices found on the host, padded to a few sizes), never all to all.

**Controls** (``control=``), the reference put in the program's place with
one thing wrong, judged by the float32 reference at the served positions:
``"int8"`` and ``"float8"`` round every matmul operand (as GPT-2's do);
``"bfloat16"`` rounds them to the precision the configuration STATES (not a
fault: it says how far a sound program may lie from float32, and how many
tokens it routes otherwise); ``"window_ignored"`` lets sliding layers see
everything before them; ``"weakest_dropped"`` leaves out each token's
weakest selected expert.
"""

from __future__ import annotations

import functools
import json
import math

import numpy as np

from benchmark.reference import _ROUNDING, seed_key

SLIDING = "sliding_attention"
CONTROLS = ("f32", "bfloat16", "int8", "float8", "window_ignored",
            "weakest_dropped")
#: queries a block of the reference's attention, rows a block of its head
Q_BLOCK, HEAD_BLOCK = 512, 256


def _round_bf16(x, axis):
    import jax.numpy as jnp

    del axis
    return x.astype(jnp.bfloat16).astype(jnp.float32)


_ROUND = {**_ROUNDING, "bfloat16": _round_bf16}


def afmoe_sizes(hf: dict) -> dict:
    """The sizes an ``afmoe`` ``config.json`` fixes."""
    kinds = list(hf["layer_types"])
    return {
        "hidden": int(hf["hidden_size"]), "layers": len(kinds),
        "kinds": kinds, "dense_layers": int(hf["num_dense_layers"]),
        "heads": int(hf["num_attention_heads"]),
        "kv_heads": int(hf["num_key_value_heads"]),
        "head_dim": int(hf["head_dim"]),
        "inner": int(hf["intermediate_size"]),
        "expert_inner": int(hf["moe_intermediate_size"]),
        "experts": int(hf["num_experts"]),
        "top_k": int(hf["num_experts_per_tok"]),
        "vocab": int(hf["vocab_size"]), "window": int(hf["sliding_window"]),
        "theta": float(hf.get("rope_theta", 10000.0)),
        "eps": float(hf.get("rms_norm_eps", 1e-5)),
        "route_norm": bool(hf.get("route_norm", True)),
        "route_scale": float(hf.get("route_scale", 1.0)),
        "mup": bool(hf.get("mup_enabled", False)),
    }


# -- seeded weights --------------------------------------------------------------

def layer_leaves(hf: dict, layer: int) -> "dict[str, tuple]":
    """name -> (shape, kind) of one layer's weights. Kinds: ``kernel``
    (normal 0.02, the dense dtype), ``gain`` (1 + 0.05 normal, float32),
    ``router`` (normal 0.02 in float32: scores of std 0.2 over 128 experts,
    so that the 8th and 9th are rarely a tie), ``bias`` (normal 0.02 in
    float32: the selection's ``expert_bias``, small beside the scores'
    spread and not zero, so that leaving it out changes the selection)."""
    s = afmoe_sizes(hf)
    h, d = s["hidden"], s["head_dim"]
    q, kv = s["heads"] * d, s["kv_heads"] * d
    out = {
        "input_norm": ((h,), "gain"), "post_attn_norm": ((h,), "gain"),
        "pre_mlp_norm": ((h,), "gain"), "post_mlp_norm": ((h,), "gain"),
        "attn.q_proj": ((h, q), "kernel"), "attn.k_proj": ((h, kv), "kernel"),
        "attn.v_proj": ((h, kv), "kernel"),
        "attn.gate_proj": ((h, q), "kernel"),
        "attn.o_proj": ((q, h), "kernel"),
        "attn.q_norm": ((d,), "gain"), "attn.k_norm": ((d,), "gain"),
    }
    if layer < s["dense_layers"]:
        f = s["inner"]
        out.update({"mlp.gate_proj": ((h, f), "kernel"),
                    "mlp.up_proj": ((h, f), "kernel"),
                    "mlp.down_proj": ((f, h), "kernel")})
    else:
        f, e = s["expert_inner"], s["experts"]
        out.update({
            "moe.router": ((h, e), "router"),
            "moe.expert_bias": ((e,), "bias"),
            "moe.experts_gate": ((e, h, f), "kernel"),
            "moe.experts_up": ((e, h, f), "kernel"),
            "moe.experts_down": ((e, f, h), "kernel"),
            "moe.shared.gate_proj": ((h, f), "kernel"),
            "moe.shared.up_proj": ((h, f), "kernel"),
            "moe.shared.down_proj": ((f, h), "kernel")})
    return out


def top_leaves(hf: dict) -> "dict[str, tuple]":
    s = afmoe_sizes(hf)
    return {"embed_tokens": ((s["vocab"], s["hidden"]), "kernel"),
            "norm": ((s["hidden"],), "gain"),
            "lm_head": ((s["hidden"], s["vocab"]), "kernel")}


@functools.lru_cache(maxsize=None)
def _maker(leaves_json: str, dense_dtype: str):
    """The jitted ``key -> {name: array}`` of a table of leaves (one program
    a kind of layer: every expert layer shares one)."""
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
                         else 0.02 * x)
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
                                 for i in range(afmoe_sizes(hf)["layers"])]
    return sum(int(np.prod(shape)) * (dense if kind == "kernel" else 4)
               for t in tables for shape, kind in t.values())


# -- the forward -------------------------------------------------------------------

def _rms(x, gain, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def _rope(x, pos, theta):
    """Rotary over the whole head. x [L, H, D]; pos [L]."""
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@functools.lru_cache(maxsize=None)
def _attention_layer(hf_json: str, sliding: bool, control: str):
    """``(weights, x [rows, L, hidden]) -> (x, h)``: the residual stream
    after the attention half of a layer and the normed input of its MLP,
    one sequence at a time. One jitted program per kind of layer."""
    import jax
    import jax.numpy as jnp

    s = afmoe_sizes(json.loads(hf_json))
    q8 = _ROUND.get(control, _ROUND["f32"])
    nh, ng, d, eps = s["heads"], s["kv_heads"], s["head_dim"], s["eps"]
    window = s["window"] if sliding else None

    def apply(w, x):
        length = x.shape[0]
        f32 = {k: v.astype(jnp.float32) for k, v in w.items()}
        a = _rms(x, f32["input_norm"], eps)
        qa = q8(a, -1)
        q = (qa @ q8(f32["attn.q_proj"], 0)).reshape(length, nh, d)
        k = (qa @ q8(f32["attn.k_proj"], 0)).reshape(length, ng, d)
        v = (qa @ q8(f32["attn.v_proj"], 0)).reshape(length, ng, d)
        g = qa @ q8(f32["attn.gate_proj"], 0)
        q = _rms(q, f32["attn.q_norm"], eps)
        k = _rms(k, f32["attn.k_norm"], eps)
        pos = jnp.arange(length)
        if sliding:
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
            gap = (i * qb + jnp.arange(qb))[:, None] - pos[None, :]
            seen = gap >= 0
            if window is not None:
                seen &= gap < window
            p = jax.nn.softmax(jnp.where(seen, sc, -1e30), axis=-1)
            return jnp.einsum("grqk,kgd->qgrd", q8(p, -1), vq).reshape(
                qb, nh * d)

        ctx = jax.lax.map(block, jnp.arange(length // qb)).reshape(
            length, nh * d)
        o = q8(ctx * jax.nn.sigmoid(g), -1) @ q8(f32["attn.o_proj"], 0)
        x = x + _rms(o, f32["post_attn_norm"], eps)
        return x, _rms(x, f32["pre_mlp_norm"], eps)

    return jax.jit(lambda w, x: jax.lax.map(lambda r: apply(w, r), x))


def _swiglu(h, gate, up, down, q8):
    import jax

    hq = q8(h, -1)
    mid = jax.nn.silu(hq @ q8(gate, 0)) * (hq @ q8(up, 0))
    return q8(mid, -1) @ q8(down, 0)


def _sizes_up(n: int) -> int:
    """Rows an expert's run is padded to: a power of two from 64."""
    return max(64, 1 << (n - 1).bit_length())


@functools.lru_cache(maxsize=None)
def _mlp_programs(hf_json: str, control: str):
    """The jitted pieces of the MLP halves: ``route`` (scores, selection,
    weights), ``swiglu`` (a dense MLP, the shared expert) and ``one`` (one
    expert on its own rows, added into the running sum)."""
    import jax
    import jax.numpy as jnp

    s = afmoe_sizes(json.loads(hf_json))
    q8 = _ROUND.get(control, _ROUND["f32"])
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731

    @jax.jit
    def route(h, router, bias):
        sc = jax.nn.sigmoid(q8(h, -1) @ q8(f32(router), 0))
        _, sel = jax.lax.top_k(sc + bias, s["top_k"])
        wt = jnp.take_along_axis(sc, sel, axis=-1)
        if s["route_norm"]:
            wt = wt / (wt.sum(-1, keepdims=True) + 1e-20)
        wt = wt * s["route_scale"]
        if control == "weakest_dropped":
            wt = jnp.where(wt == wt.min(-1, keepdims=True), 0.0, wt)
        return sel, wt

    @jax.jit
    def swiglu(h, gate, up, down):
        return _swiglu(h, f32(gate), f32(up), f32(down), q8)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def one(out, hpad, idx, wts, e, gate, up, down):
        # expert ``e`` of the stacked kernels on the rows ``idx`` alone
        mine = [jax.lax.dynamic_index_in_dim(f32_e, e, keepdims=False)
                for f32_e in (gate, up, down)]
        y = _swiglu(hpad[idx], *map(f32, mine), q8)
        return out.at[idx].add(wts[:, None] * y)

    return route, swiglu, one


def _expert_layer(hf_json: str, w: dict, h, control: str):
    """The expert MLP over ``h`` [T, hidden] (every sequence's tokens):
    the shared expert on all, each routed expert on its OWN tokens.
    Returns ``(m [T, hidden], sel [T, k] on the host)``."""
    import jax.numpy as jnp

    s = afmoe_sizes(json.loads(hf_json))
    route, shared, one = _mlp_programs(hf_json, control)
    t = h.shape[0]
    sel, wt = route(h, w["moe.router"], w["moe.expert_bias"])
    sel_h, wt_h = np.asarray(sel), np.asarray(wt)
    hpad = jnp.concatenate([h, jnp.zeros((1, h.shape[1]), h.dtype)])
    out = jnp.zeros_like(hpad)
    for e in range(s["experts"]):
        rows, slot = np.nonzero(sel_h == e)
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
    m = out[:t] + shared(h, w["moe.shared.gate_proj"],
                         w["moe.shared.up_proj"], w["moe.shared.down_proj"])
    return m, sel_h


def afmoe_hidden(seed: int, hf: dict, seqs, dense_dtype: str = "bfloat16",
                 control: str = "f32"):
    """The residual stream after the last layer for each row of ``seqs``
    ``[rows, length]`` (right-padded; ``length`` a multiple of
    ``min(Q_BLOCK, length)``), one layer made and let go at a time, and the
    experts each layer selected (``[layers][rows * length, k]``, None for a
    dense layer). Call under ``jax.default_matmul_precision("highest")``."""
    import jax
    import jax.numpy as jnp

    if control not in CONTROLS:
        raise ValueError(f"unknown control {control!r} (one of {CONTROLS})")
    s = afmoe_sizes(hf)
    hf_json = json.dumps(hf, sort_keys=True)
    seqs = jnp.asarray(seqs)
    rows, length = seqs.shape
    top = top_weights(seed, hf, dense_dtype)
    x = top["embed_tokens"][seqs].astype(jnp.float32)
    if s["mup"]:
        x = x * math.sqrt(s["hidden"])
    del top
    sels = []
    for layer in range(s["layers"]):
        w = layer_weights(seed, layer, hf, dense_dtype)
        sliding = (s["kinds"][layer] == SLIDING
                   and control != "window_ignored")
        names = [n for n in w if not n.startswith(("mlp.", "moe."))]
        x, h = _attention_layer(hf_json, sliding, control)(
            {n: w[n] for n in names}, x)
        h2 = h.reshape(rows * length, -1)
        if layer < s["dense_layers"]:
            m = _mlp_programs(hf_json, control)[1](
                h2, w["mlp.gate_proj"], w["mlp.up_proj"], w["mlp.down_proj"])
            sels.append(None)
        else:
            m, sel = _expert_layer(hf_json, w, h2, control)
            sels.append(sel)
        x = x + _rms(m.reshape(rows, length, -1),
                     w["post_mlp_norm"].astype(jnp.float32), s["eps"])
        del w
    return x, sels


@functools.lru_cache(maxsize=None)
def _head_program(eps: float, control: str):
    import jax
    import jax.numpy as jnp

    q8 = _ROUND.get(control, _ROUND["f32"])
    return jax.jit(lambda x, g, head: q8(_rms(x, g, eps), -1) @ q8(
        head.astype(jnp.float32), 0))


def afmoe_logits_at(top: dict, hf: dict, x_rows, control: str = "f32"):
    """Logits ``[n, vocab]`` of residual-stream rows ``[n, hidden]``: the
    last norm and the untied head of ``top`` (:func:`top_weights`)."""
    return _head_program(afmoe_sizes(hf)["eps"], control)(
        x_rows, top["norm"], top["lm_head"])


def afmoe_logits(seed: int, hf: dict, ids, dense_dtype: str = "bfloat16",
                 control: str = "f32"):
    """Logits ``[length, vocab]`` of one short sequence (the tests')."""
    import jax

    with jax.default_matmul_precision("highest"):
        x, _ = afmoe_hidden(seed, hf, np.asarray(ids)[None], dense_dtype,
                            control)
        return afmoe_logits_at(top_weights(seed, hf, dense_dtype), hf, x[0],
                               control)


def afmoe_token_gaps(seed: int, hf: dict, seqs, spans,
                     dense_dtype: str = "bfloat16", control: str = "f32"):
    """The float32 reference's verdict on the tokens served at ``spans``:
    for row ``r`` and each position ``t`` of ``spans[r] = (a, b)``, how far
    the reference's logit of token ``seqs[r, t+1]`` lies below the
    reference's best at ``t`` (0 where the served token IS the best). With
    a ``control`` the token judged at each position is the one THAT forward
    puts first (a control need not decode). Returns ``(gaps [n], std of the
    reference's logits at those positions, sels)``, ``sels`` the experts
    the forward that chose the tokens selected (:func:`afmoe_hidden`)."""
    import jax
    import jax.numpy as jnp

    seqs = np.asarray(seqs)
    at = [(r, t) for r, (a, b) in enumerate(spans) for t in range(a, b)]
    rows_i = np.array([r for r, _ in at])
    cols_i = np.array([t for _, t in at])
    chosen = seqs[rows_i, cols_i + 1]
    sels = None

    def blocks(x, fn, control):
        top = top_weights(seed, hf, dense_dtype)
        out = []
        for i in range(0, len(at), HEAD_BLOCK):
            j = min(i + HEAD_BLOCK, len(at))
            out.append(fn(afmoe_logits_at(
                top, hf, x[rows_i[i:j], cols_i[i:j]], control), i, j))
        return out

    with jax.default_matmul_precision("highest"):
        if control != "f32":
            x, sels = afmoe_hidden(seed, hf, seqs, dense_dtype, control)
            chosen = np.concatenate(blocks(
                x, lambda lg, i, j: np.asarray(jnp.argmax(lg, -1)), control))
            del x
        x, ref_sels = afmoe_hidden(seed, hf, seqs, dense_dtype)
        sels = ref_sels if sels is None else sels

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
    return gaps, std, sels
