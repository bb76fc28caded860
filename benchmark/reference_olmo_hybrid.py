"""The plain reference of the ``olmo_hybrid`` family (AllenAI Olmo Hybrid) and its seeded weights.

Nothing here imports ``sparkdl_tpu``. The forward is written out in
straightforward ``jax.numpy`` after the published ``config.json``, in
float32 at ``highest`` matmul precision: no cache, no batching of requests,
no kernels, no chunks. A linear-attention layer is the gated delta rule AS
IT IS DEFINED, one token after the other (a ``lax.scan`` over the tokens):

    S_t = alpha_t S_{t-1} + beta_t k_t (v_t - alpha_t S_{t-1}^T k_t)^T
    o_t = S_t^T q_t

with q, k, v from three causal depthwise convolutions (SiLU; q and k
L2-normalised, q scaled by 1/sqrt(d_k)), ``beta = 2 sigmoid(W_b x)`` where
``linear_allow_neg_eigval``, ``alpha = exp(-exp(A_log) softplus(W_a x +
dt_bias))``, and the output ``W_o [rms_norm(o) * silu(W_g x)]``. The program
computes the same thing in chunks (the WY form) and, while decoding, one
token at a time from a stored state; the two derivations check each other.
A full-attention layer norms q and k over the whole projection before the
heads are split and rotates nothing. A block is ``x + norm(mixer(x))`` then
``x + norm(mlp(x))``.

**One layer at a time**, as ``reference_afmoe.py``: the weights are a pure
function of ``(seed, layer)`` (``layer_weights``) and of ``seed`` alone for
the embedding, the last norm and the head (``top_weights``), in the types
they are served in; the reference makes a layer, applies it to every checked
sequence, and lets it go.

**Controls** (``control=``), the reference put in the program's place with
one thing wrong, judged by the float32 reference at the served positions:
``"bfloat16"``, ``"int8"`` and ``"float8"`` round every matmul operand
(``"bfloat16"`` is the precision the configuration STATES); ``"state_bf16"``
keeps the recurrent state in bfloat16 between tokens; ``"no_decay"`` drops
the decay (alpha = 1); ``"beta_single"`` does not double beta;
``"pad_unmasked"`` lets the pad tokens behind a prompt's last chunk (the
engine pads a chunk to a power-of-two width with token 0) move the state
and the convolutions' tails, as a chunk program that took no real count
would.
"""

from __future__ import annotations

import functools
import json
import math

import numpy as np

from benchmark.reference import _ROUNDING, seed_key

LINEAR = "linear_attention"
CONTROLS = ("f32", "bfloat16", "int8", "float8", "state_bf16", "no_decay",
            "beta_single", "pad_unmasked")
#: queries a block of the reference's attention, rows a block of its head
Q_BLOCK, HEAD_BLOCK = 512, 256
L2_EPS = 1e-6


def _to_bf16(x):
    """``x`` rounded to bfloat16's 8 bits of mantissa, still float32. As an
    explicit ``reduce_precision``: a cast there and back is a pair the chip's
    compiler may drop (it keeps excess precision where it can), and the
    control would then be the reference itself."""
    import jax

    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


_ROUND = {**_ROUNDING, "bfloat16": lambda x, axis: _to_bf16(x)}


def hybrid_sizes(hf: dict) -> dict:
    """The sizes an ``olmo_hybrid`` ``config.json`` fixes."""
    kinds = list(hf["layer_types"])
    heads = int(hf["num_attention_heads"])
    return {
        "hidden": int(hf["hidden_size"]), "layers": len(kinds),
        "kinds": kinds, "heads": heads,
        "head_dim": int(hf.get("head_dim") or int(hf["hidden_size"]) // heads),
        "inner": int(hf["intermediate_size"]),
        "lin_heads": int(hf["linear_num_value_heads"]),
        "dk": int(hf["linear_key_head_dim"]),
        "dv": int(hf["linear_value_head_dim"]),
        "taps": int(hf["linear_conv_kernel_dim"]),
        "neg_eigval": bool(hf.get("linear_allow_neg_eigval", False)),
        "vocab": int(hf["vocab_size"]),
        "eps": float(hf.get("rms_norm_eps", 1e-6)),
    }


# -- seeded weights --------------------------------------------------------------

def layer_leaves(hf: dict, layer: int) -> "dict[str, tuple]":
    """name -> (shape, kind) of one layer's weights. Kinds: ``kernel``
    (normal 0.02, the dense dtype), ``gain`` (1 + 0.05 normal, float32),
    ``conv`` (normal 0.2, float32), ``a_log`` (log of uniform(1, 16)),
    ``dt_bias`` (the inverse softplus of log-uniform(0.001, 0.1)): the gated
    delta rule's usual initialisation, so that a head's decay a token lies
    anywhere from 0.2 to 0.999 and the state is exercised at every time
    scale."""
    s = hybrid_sizes(hf)
    h, f = s["hidden"], s["inner"]
    out = {"post_attn_norm": ((h,), "gain"), "post_mlp_norm": ((h,), "gain"),
           "mlp.gate_proj": ((h, f), "kernel"),
           "mlp.up_proj": ((h, f), "kernel"),
           "mlp.down_proj": ((f, h), "kernel")}
    if s["kinds"][layer] == LINEAR:
        n, dk, dv, taps = s["lin_heads"], s["dk"], s["dv"], s["taps"]
        out.update({
            "linear_attn.q_proj": ((h, n * dk), "kernel"),
            "linear_attn.k_proj": ((h, n * dk), "kernel"),
            "linear_attn.v_proj": ((h, n * dv), "kernel"),
            "linear_attn.conv_q": ((taps, n * dk), "conv"),
            "linear_attn.conv_k": ((taps, n * dk), "conv"),
            "linear_attn.conv_v": ((taps, n * dv), "conv"),
            "linear_attn.a_proj": ((h, n), "kernel"),
            "linear_attn.b_proj": ((h, n), "kernel"),
            "linear_attn.A_log": ((n,), "a_log"),
            "linear_attn.dt_bias": ((n,), "dt_bias"),
            "linear_attn.g_proj": ((h, n * dv), "kernel"),
            "linear_attn.o_norm": ((dv,), "gain"),
            "linear_attn.o_proj": ((n * dv, h), "kernel")})
    else:
        q = s["heads"] * s["head_dim"]
        out.update({
            "attn.q_proj": ((h, q), "kernel"), "attn.k_proj": ((h, q), "kernel"),
            "attn.v_proj": ((h, q), "kernel"), "attn.o_proj": ((q, h), "kernel"),
            "attn.q_norm": ((q,), "gain"), "attn.k_norm": ((q,), "gain")})
    return out


def top_leaves(hf: dict) -> "dict[str, tuple]":
    s = hybrid_sizes(hf)
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
            k = jax.random.fold_in(key, i)
            if kind == "a_log":
                out[name] = jnp.log(jax.random.uniform(
                    k, tuple(shape), jnp.float32, 1.0, 16.0))
                continue
            if kind == "dt_bias":
                dt = jnp.exp(jax.random.uniform(
                    k, tuple(shape), jnp.float32, math.log(1e-3),
                    math.log(0.1)))
                out[name] = dt + jnp.log(-jnp.expm1(-dt))
                continue
            x = jax.random.normal(k, tuple(shape), jnp.float32)
            out[name] = (1.0 + 0.05 * x if kind == "gain"
                         else (0.02 * x).astype(dense) if kind == "kernel"
                         else 0.2 * x)
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
                                 for i in range(hybrid_sizes(hf)["layers"])]
    return sum(int(np.prod(shape)) * (dense if kind == "kernel" else 4)
               for t in tables for shape, kind in t.values())


# -- the forward -------------------------------------------------------------------

def _rms(x, gain, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def _l2(x):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def delta_rule(q, k, v, alpha, beta, state_bf16: bool = False):
    """The gated delta rule, token by token. q, k ``[L, H, d_k]``; v ``[L,
    H, d_v]``; alpha, beta ``[L, H]`` -> o ``[L, H, d_v]``, from a zero
    state. ``state_bf16`` rounds the state kept between tokens to bfloat16."""
    import jax
    import jax.numpy as jnp

    def keep(s):
        return _to_bf16(s) if state_bf16 else s

    def step(s, x):
        q, k, v, alpha, beta = x
        s = s * alpha[:, None, None]
        seen = jnp.einsum("hkv,hk->hv", s, k)
        s = keep(s + jnp.einsum("hk,hv->hkv", beta[:, None] * k, v - seen))
        return s, jnp.einsum("hkv,hk->hv", s, q)

    s0 = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), jnp.float32)
    return jax.lax.scan(step, s0, (q, k, v, alpha, beta))[1]


@functools.lru_cache(maxsize=None)
def _mixer_layer(hf_json: str, linear: bool, control: str):
    """``(weights, x [rows, L, hidden], seen [rows, L]) -> x``: the residual
    stream after the mixer half of a layer, one sequence at a time. ``seen``
    marks the positions a FULL layer may attend to (a linear layer takes
    every position in its order). One jitted program per kind of layer."""
    import jax
    import jax.numpy as jnp

    s = hybrid_sizes(json.loads(hf_json))
    q8 = _ROUND.get(control, _ROUND["f32"])
    eps = s["eps"]

    def conv(u, w):
        # causal, depthwise: out_t = sum_j w_j u_{t - (taps-1) + j}
        taps = w.shape[0]
        ext = jnp.concatenate(
            [jnp.zeros((taps - 1, u.shape[1]), u.dtype), u])
        return jax.nn.silu(sum(ext[j:j + u.shape[0]] * w[j]
                               for j in range(taps)))

    def linear_mixer(f32, x):
        length = x.shape[0]
        n, dk, dv = s["lin_heads"], s["dk"], s["dv"]
        xa = q8(x, -1)
        q = conv(xa @ q8(f32["linear_attn.q_proj"], 0),
                 f32["linear_attn.conv_q"]).reshape(length, n, dk)
        k = conv(xa @ q8(f32["linear_attn.k_proj"], 0),
                 f32["linear_attn.conv_k"]).reshape(length, n, dk)
        v = conv(xa @ q8(f32["linear_attn.v_proj"], 0),
                 f32["linear_attn.conv_v"]).reshape(length, n, dv)
        q, k = _l2(q) / math.sqrt(dk), _l2(k)
        beta = jax.nn.sigmoid(xa @ q8(f32["linear_attn.b_proj"], 0))
        if s["neg_eigval"] and control != "beta_single":
            beta = 2.0 * beta
        g = -jnp.exp(f32["linear_attn.A_log"]) * jax.nn.softplus(
            xa @ q8(f32["linear_attn.a_proj"], 0)
            + f32["linear_attn.dt_bias"])
        alpha = jnp.ones_like(g) if control == "no_decay" else jnp.exp(g)
        o = delta_rule(q, k, v, alpha, beta, control == "state_bf16")
        gate = jax.nn.silu(xa @ q8(f32["linear_attn.g_proj"], 0))
        o = _rms(o, f32["linear_attn.o_norm"], eps).reshape(length, n * dv)
        return q8(o * gate, -1) @ q8(f32["linear_attn.o_proj"], 0)

    def full_mixer(f32, x, seen):
        length = x.shape[0]
        nh, d = s["heads"], s["head_dim"]
        xa = q8(x, -1)
        q = _rms(xa @ q8(f32["attn.q_proj"], 0), f32["attn.q_norm"], eps)
        k = _rms(xa @ q8(f32["attn.k_proj"], 0), f32["attn.k_norm"], eps)
        v = xa @ q8(f32["attn.v_proj"], 0)
        q, k, v = (t.reshape(length, nh, d) for t in (q, k, v))
        qb = min(Q_BLOCK, length)
        if length % qb:
            raise ValueError(f"length {length} is no multiple of {qb}")
        kq, vq = q8(k, -1), q8(v, 0)
        pos = jnp.arange(length)

        def block(i):
            qi = q8(jax.lax.dynamic_slice_in_dim(q, i * qb, qb, 0), -1)
            sc = jnp.einsum("qhd,khd->hqk", qi, kq) / math.sqrt(d)
            ok = (((i * qb + jnp.arange(qb))[:, None] >= pos[None, :])
                  & seen[None, :])
            p = jax.nn.softmax(jnp.where(ok, sc, -1e30), axis=-1)
            return jnp.einsum("hqk,khd->qhd", q8(p, -1), vq).reshape(
                qb, nh * d)

        ctx = jax.lax.map(block, jnp.arange(length // qb)).reshape(
            length, nh * d)
        return q8(ctx, -1) @ q8(f32["attn.o_proj"], 0)

    def apply(w, x, seen):
        f32 = {k: v.astype(jnp.float32) for k, v in w.items()}
        a = linear_mixer(f32, x) if linear else full_mixer(f32, x, seen)
        return x + _rms(a, f32["post_attn_norm"], eps)

    return jax.jit(lambda w, x, seen: jax.lax.map(
        lambda r: apply(w, *r), (x, seen)))


@functools.lru_cache(maxsize=None)
def _mlp_layer(hf_json: str, control: str):
    import jax
    import jax.numpy as jnp

    s = hybrid_sizes(json.loads(hf_json))
    q8 = _ROUND.get(control, _ROUND["f32"])

    def apply(w, x):
        f32 = {k: v.astype(jnp.float32) for k, v in w.items()}
        xa = q8(x, -1)
        mid = (jax.nn.silu(xa @ q8(f32["mlp.gate_proj"], 0))
               * (xa @ q8(f32["mlp.up_proj"], 0)))
        m = q8(mid, -1) @ q8(f32["mlp.down_proj"], 0)
        return x + _rms(m, f32["post_mlp_norm"], s["eps"])

    return jax.jit(lambda w, x: jax.lax.map(lambda r: apply(w, r), x))


def hybrid_hidden(seed: int, hf: dict, seqs, dense_dtype: str = "bfloat16",
                  control: str = "f32", seen=None):
    """The residual stream after the last layer for each row of ``seqs``
    ``[rows, length]`` (right-padded; ``length`` a multiple of
    ``min(Q_BLOCK, length)``), one layer made and let go at a time. ``seen``
    ``[rows, length]`` bool marks the positions full attention may look at
    (None: all). Call under ``jax.default_matmul_precision("highest")``."""
    import jax.numpy as jnp

    if control not in CONTROLS:
        raise ValueError(f"unknown control {control!r} (one of {CONTROLS})")
    s = hybrid_sizes(hf)
    hf_json = json.dumps(hf, sort_keys=True)
    seqs = jnp.asarray(seqs)
    seen = (jnp.ones(seqs.shape, bool) if seen is None
            else jnp.asarray(seen, bool))
    x = top_weights(seed, hf, dense_dtype)["embed_tokens"][seqs].astype(
        jnp.float32)
    for layer in range(s["layers"]):
        w = layer_weights(seed, layer, hf, dense_dtype)
        mlp = {n: w[n] for n in w if n.startswith(("mlp.", "post_mlp"))}
        x = _mixer_layer(hf_json, s["kinds"][layer] == LINEAR, control)(
            {n: w[n] for n in w if n not in mlp}, x, seen)
        x = _mlp_layer(hf_json, control)(mlp, x)
        del w, mlp
    return x


@functools.lru_cache(maxsize=None)
def _head_program(eps: float, control: str):
    import jax
    import jax.numpy as jnp

    q8 = _ROUND.get(control, _ROUND["f32"])
    return jax.jit(lambda x, g, head: q8(_rms(x, g, eps), -1) @ q8(
        head.astype(jnp.float32), 0))


def hybrid_logits_at(top: dict, hf: dict, x_rows, control: str = "f32"):
    """Logits ``[n, vocab]`` of residual-stream rows ``[n, hidden]``."""
    return _head_program(hybrid_sizes(hf)["eps"], control)(
        x_rows, top["norm"], top["lm_head"])


def hybrid_logits(seed: int, hf: dict, ids, dense_dtype: str = "bfloat16",
                  control: str = "f32"):
    """Logits ``[length, vocab]`` of one short sequence (the tests')."""
    import jax

    with jax.default_matmul_precision("highest"):
        x = hybrid_hidden(seed, hf, np.asarray(ids)[None], dense_dtype,
                          control)
        return hybrid_logits_at(top_weights(seed, hf, dense_dtype), hf, x[0],
                                control)


def chunk_pad(prompt_len: int, chunk: int) -> int:
    """Pad tokens behind a prompt's LAST chunk as the engine dispatches it:
    chunks of ``chunk`` tokens, the last one's real count bucketed to a
    power of two (at least 8, at most ``chunk``)."""
    r = prompt_len % chunk or chunk
    return min(chunk, max(8, 1 << (r - 1).bit_length())) - r


def with_pad_tokens(seqs, spans, chunk: int):
    """``(seqs, spans, seen)`` with each row's ``chunk_pad`` tokens of id 0
    put in behind its prompt (``spans[r] = (a, b)``: the prompt ends at
    ``a``), the served positions moved behind them, and ``seen`` false on
    them: the ``pad_unmasked`` control's input. A linear layer takes them in
    their order; full attention never sees them (their K/V columns lie past
    every row's depth until real tokens overwrite them)."""
    seqs = np.asarray(seqs)
    pads = [chunk_pad(a + 1, chunk) for a, _ in spans]
    block = min(Q_BLOCK, seqs.shape[1])
    width = -(-(seqs.shape[1] + max(pads)) // block) * block
    out = np.zeros((seqs.shape[0], width), seqs.dtype)
    seen = np.ones(out.shape, bool)
    moved = []
    for r, ((a, b), pad) in enumerate(zip(spans, pads)):
        out[r, :a + 1] = seqs[r, :a + 1]
        rest = seqs[r, a + 1:]
        out[r, a + 1 + pad:a + 1 + pad + rest.size] = rest[:width - a - 1 - pad]
        seen[r, a + 1:a + 1 + pad] = False
        moved.append((a, b, pad))
    return out, moved, seen


def hybrid_token_gaps(seed: int, hf: dict, seqs, spans,
                      dense_dtype: str = "bfloat16", control: str = "f32",
                      chunk: int = 256):
    """The float32 reference's verdict on the tokens served at ``spans``:
    for row ``r`` and each position ``t`` of ``spans[r] = (a, b)``, how far
    the reference's logit of token ``seqs[r, t+1]`` lies below the
    reference's best at ``t`` (0 where the served token IS the best). With
    a ``control`` the token judged at each position is the one THAT forward
    puts first (a control need not decode). Returns ``(gaps [n], std of the
    reference's logits at those positions)``."""
    import jax
    import jax.numpy as jnp

    seqs = np.asarray(seqs)
    at = [(r, t) for r, (a, b) in enumerate(spans) for t in range(a, b)]
    rows_i = np.array([r for r, _ in at])
    cols_i = np.array([t for _, t in at])
    chosen = seqs[rows_i, cols_i + 1]

    def blocks(x, cols, fn, control):
        top = top_weights(seed, hf, dense_dtype)
        out = []
        for i in range(0, len(at), HEAD_BLOCK):
            j = min(i + HEAD_BLOCK, len(at))
            out.append(fn(hybrid_logits_at(
                top, hf, x[rows_i[i:j], cols[i:j]], control), i, j))
        return out

    with jax.default_matmul_precision("highest"):
        if control != "f32":
            c_seqs, c_cols, c_seen = seqs, cols_i, None
            if control == "pad_unmasked":
                c_seqs, moved, c_seen = with_pad_tokens(seqs, spans, chunk)
                # position a (the prompt's last token) stays; the served
                # ones behind it lie ``pad`` further on
                c_cols = np.array([t if t == a else t + pad
                                   for a, b, pad in moved
                                   for t in range(a, b)])
            x = hybrid_hidden(seed, hf, c_seqs, dense_dtype, control, c_seen)
            chosen = np.concatenate(blocks(
                x, c_cols,
                lambda lg, i, j: np.asarray(jnp.argmax(lg, -1)), control))
            del x
        x = hybrid_hidden(seed, hf, seqs, dense_dtype)

        def judge(lg, i, j):
            picked = jnp.take_along_axis(
                lg, jnp.asarray(chosen[i:j])[:, None], -1)[:, 0]
            return (np.asarray(lg.max(-1) - picked),
                    float(lg.sum()), float((lg * lg).sum()), lg.size)

        parts = blocks(x, cols_i, judge, "f32")
    gaps = np.concatenate([p[0] for p in parts])
    n = sum(p[3] for p in parts)
    mean = sum(p[1] for p in parts) / n
    std = math.sqrt(max(sum(p[2] for p in parts) / n - mean * mean, 0.0))
    return gaps, std
