"""The plain references and the seeded weights they and the program are given.

Nothing here imports ``sparkdl_tpu``. Each model is written out in
straightforward ``jax.numpy`` after its published description, in float32
at ``highest`` matmul precision: no kernels, no cache, no batching. The
weights are made from the seed by functions of this file; the runner hands
the same arrays to the program (as a variables tree for the serving engine),
so the reference takes nothing the program made.

``precision="int8"`` and ``precision="float8"`` are the reference put in the
program's place in the precisions next below the bfloat16 the configuration
states: every matmul operand rounded to int8 (one scale per vector along the
contracted axis) or to ``float8_e4m3fn``, accumulated in float32. They are
read beside the program's own lower-precision path (the configuration's
``control``); PERF.md, section 2, says which of them the limits are set
against and why.
"""

from __future__ import annotations

import math

import numpy as np

# -- seeds ---------------------------------------------------------------------


def seed_key(seed: int):
    """A jax PRNG key from any non-negative whole seed (the driver's are
    over 2**31, past what a signed 32-bit value holds)."""
    import jax

    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(0)
    for part in (seed & 0x3FFFFFFF, (seed >> 30) & 0x3FFFFFFF, seed >> 60):
        key = jax.random.fold_in(key, part)
    return key


# -- GPT-2 ---------------------------------------------------------------------

#: per-block leaves: name -> (shape from (hidden h, inner f), kind)
_GPT2_BLOCK = {
    "ln_1.scale": (lambda h, f: (h,), "scale"),
    "ln_1.bias": (lambda h, f: (h,), "lnbias"),
    "attn.q_proj.kernel": (lambda h, f: (h, h), "kernel"),
    "attn.q_proj.bias": (lambda h, f: (h,), "bias"),
    "attn.k_proj.kernel": (lambda h, f: (h, h), "kernel"),
    "attn.k_proj.bias": (lambda h, f: (h,), "bias"),
    "attn.v_proj.kernel": (lambda h, f: (h, h), "kernel"),
    "attn.v_proj.bias": (lambda h, f: (h,), "bias"),
    "attn.out_proj.kernel": (lambda h, f: (h, h), "resid"),
    "attn.out_proj.bias": (lambda h, f: (h,), "bias"),
    "ln_2.scale": (lambda h, f: (h,), "scale"),
    "ln_2.bias": (lambda h, f: (h,), "lnbias"),
    "up.kernel": (lambda h, f: (h, f), "kernel"),
    "up.bias": (lambda h, f: (f,), "bias"),
    "down.kernel": (lambda h, f: (f, h), "resid"),
    "down.bias": (lambda h, f: (h,), "bias"),
}


def gpt2_sizes(hf: dict) -> dict:
    """The sizes a GPT-2 ``config.json`` fixes."""
    h = int(hf["n_embd"])
    return {
        "hidden": h, "layers": int(hf["n_layer"]), "heads": int(hf["n_head"]),
        "inner": int(hf.get("n_inner") or 4 * h), "vocab": int(hf["vocab_size"]),
        "positions": int(hf["n_positions"]),
        "eps": float(hf.get("layer_norm_epsilon", 1e-5)),
    }


def gpt2_weights_fn(hf: dict, dense_dtype: str = "bfloat16"):
    """``key -> weights``: seeded GPT-2 weights as a pure function of a PRNG
    key, to be jitted by the caller so that they are made on the device in
    one call, in the types they are served in: dense kernels and biases in
    ``dense_dtype``, layer norms and embeddings in float32. Blocks are
    stacked over layers (``blocks[name]`` is ``[layers, ...]``), the layout
    the reference scans.

    GPT-2's own initialisation scale (normal 0.02, residual projections by
    1/sqrt(2 layers)); biases and layer-norm parameters get small random
    values rather than 0 and 1, so that leaving one out changes the logits.
    """
    import jax
    import jax.numpy as jnp

    s = gpt2_sizes(hf)
    h, f, n = s["hidden"], s["inner"], s["layers"]
    dense = jnp.dtype(dense_dtype)
    scale = {"kernel": 0.02, "resid": 0.02 / math.sqrt(2 * n), "bias": 0.01,
             "emb": 0.02, "lnbias": 0.02}

    def make(key):
        def leaf(i, shape, kind):
            x = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
            if kind == "scale":
                return 1.0 + 0.05 * x
            x = scale[kind] * x
            return x.astype(dense) if kind in ("kernel", "resid", "bias") else x

        out = {
            "wte": leaf(0, (s["vocab"], h), "emb"),
            "wpe": leaf(1, (s["positions"], h), "emb"),
            "ln_f": {"scale": leaf(2, (h,), "scale"),
                     "bias": leaf(3, (h,), "lnbias")},
            "blocks": {},
        }
        for j, (name, (shape, kind)) in enumerate(_GPT2_BLOCK.items()):
            out["blocks"][name] = leaf(10 + j, (n,) + shape(h, f), kind)
        return out

    return make


def gpt2_weights(seed: int, hf: dict, dense_dtype: str = "bfloat16") -> dict:
    """The weights of :func:`gpt2_weights_fn` for ``seed``, on the device."""
    import jax

    return jax.jit(gpt2_weights_fn(hf, dense_dtype))(seed_key(seed))


def _round_float8(x, axis):
    """Round to float8_e4m3fn and come back to float32: the lower
    precision's operand rounding with float32 accumulation."""
    import jax.numpy as jnp

    del axis
    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def _round_int8(x, axis):
    """Round to 255 levels with one scale per vector along ``axis`` (the
    contracted one) and come back to float32."""
    import jax.numpy as jnp

    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return jnp.round(x / scale) * scale


_ROUNDING = {"f32": lambda x, axis: x, "int8": _round_int8,
             "float8": _round_float8}


def gpt2_logits(weights: dict, ids, hf: dict, precision: str = "f32"):
    """GPT-2 forward over ``ids`` ``[length]`` -> logits ``[length, vocab]``
    in float32: pre-LN blocks, learned positions, tanh-gelu, tied head, full
    causal attention. Call under ``jax.default_matmul_precision("highest")``.
    ``precision`` ``"int8"`` or ``"float8"`` rounds every matmul operand."""
    import jax
    import jax.numpy as jnp

    s = gpt2_sizes(hf)
    heads, eps = s["heads"], s["eps"]
    q8 = _ROUNDING[precision]

    def f32(a):
        return a.astype(jnp.float32)

    def ln(x, scale, bias):
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + eps) * scale + bias

    def dense(x, kernel, bias):
        return q8(x, -1) @ q8(f32(kernel), 0) + f32(bias)

    (length,) = ids.shape
    wte = f32(weights["wte"])
    x = wte[ids] + f32(weights["wpe"])[:length]
    causal = jnp.tril(jnp.ones((length, length), bool))

    def block(x, b):
        hid = ln(x, b["ln_1.scale"], b["ln_1.bias"])
        q, k, v = (dense(hid, b[f"attn.{n}.kernel"], b[f"attn.{n}.bias"])
                   .reshape(length, heads, -1)
                   for n in ("q_proj", "k_proj", "v_proj"))
        sc = (jnp.einsum("qhd,khd->hqk", q8(q, -1), q8(k, -1))
              / math.sqrt(q.shape[-1]))
        p = jax.nn.softmax(jnp.where(causal, sc, -1e30), axis=-1)
        ctx = jnp.einsum("hqk,khd->qhd", q8(p, -1), q8(v, 0)).reshape(
            length, -1)
        x = x + dense(ctx, b["attn.out_proj.kernel"], b["attn.out_proj.bias"])
        hid = ln(x, b["ln_2.scale"], b["ln_2.bias"])
        up = jax.nn.gelu(dense(hid, b["up.kernel"], b["up.bias"]),
                         approximate=True)
        return x + dense(up, b["down.kernel"], b["down.bias"]), None

    x, _ = jax.lax.scan(block, x, weights["blocks"])
    x = ln(x, weights["ln_f"]["scale"], weights["ln_f"]["bias"])
    return q8(x, -1) @ q8(wte, -1).T


def gpt2_token_gaps(weights: dict, seqs, hf: dict, precision: str = "f32"):
    """For each row of ``seqs`` ``[rows, length]`` (a prompt followed by the
    served tokens, right-padded) and each position ``t``: how far the
    reference's logit of token ``seqs[r, t+1]`` lies below the reference's
    best at ``t`` (0 where the served token IS the best), and the logits'
    standard deviation. With a lower ``precision`` the token judged at each
    position is the one that forward puts first instead (the control need
    not decode). Returns ``(gaps [rows, length-1], std)``.
    """
    import jax
    import jax.numpy as jnp

    def one(weights, ids):
        ref = gpt2_logits(weights, ids, hf)
        if precision != "f32":
            chosen = jnp.argmax(gpt2_logits(weights, ids, hf, precision),
                                -1)[:-1]
        else:
            chosen = ids[1:]
        picked = jnp.take_along_axis(ref[:-1], chosen[:, None], -1)[:, 0]
        return ref[:-1].max(-1) - picked, ref.std()

    # the weights are an ARGUMENT of the jitted program: closed over, 3 GB
    # of them would be lowered as constants
    with jax.default_matmul_precision("highest"):
        gaps, std = jax.jit(
            lambda w, s: jax.lax.map(lambda ids: one(w, ids), s))(
                weights, jnp.asarray(seqs))
    return np.asarray(gaps), float(np.mean(np.asarray(std)))
