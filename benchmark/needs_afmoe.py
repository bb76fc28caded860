"""What a call of the ``afmoe`` family NEEDS, from its shapes: bytes and
floating-point operations the algorithm cannot avoid, never what the compiler
emitted, so a share of a roofline computed from these cannot pass 100% by
construction of the count.

An expert layer needs the kernels of the experts that were GIVEN a row, once
each, whatever the rows (``experts_hit``, which the engine counts on the
device and hands back with the tick's tokens); a sliding layer needs a row's
K/V inside its window, a full layer all of it; the embedding is needed a row
at a time, the head whole.
"""

from __future__ import annotations

from benchmark.reference_afmoe import (
    SLIDING,
    afmoe_sizes,
    layer_leaves,
    seeded_weight_bytes,
    top_leaves,
)


def _bytes(leaves: dict, names, dense_bytes: int) -> int:
    total = 0
    for name in names:
        shape, kind = leaves[name]
        n = 1
        for d in shape:
            n *= d
        total += n * (dense_bytes if kind == "kernel" else 4)
    return total


def afmoe_param_bytes(hf: dict, dense_bytes: int = 2) -> int:
    """Bytes of every parameter as served: kernels at ``dense_bytes``, norm
    gains, router kernels and expert biases in float32."""
    return seeded_weight_bytes(hf, "bfloat16" if dense_bytes == 2
                               else "float32")


def afmoe_expert_bytes(hf: dict, dense_bytes: int = 2) -> int:
    """One routed expert's three kernels."""
    s = afmoe_sizes(hf)
    return 3 * s["hidden"] * s["expert_inner"] * dense_bytes


def afmoe_fixed_bytes(hf: dict, dense_bytes: int = 2) -> int:
    """Parameter bytes EVERY call reads whatever its rows: all but the
    routed experts' kernels and the embedding (a row a token)."""
    s = afmoe_sizes(hf)
    total = _bytes(top_leaves(hf), ("norm", "lm_head"), dense_bytes)
    for i in range(s["layers"]):
        leaves = layer_leaves(hf, i)
        total += _bytes(leaves, [n for n in leaves
                                 if not n.startswith("moe.experts_")],
                        dense_bytes)
    return total


def afmoe_kv_bytes_per_token_layer(hf: dict, kv_bytes: int = 2) -> int:
    """K and V of one token in one layer."""
    s = afmoe_sizes(hf)
    return 2 * s["kv_heads"] * s["head_dim"] * kv_bytes


def _layer_counts(hf: dict) -> "tuple[int, int, int]":
    s = afmoe_sizes(hf)
    sliding = sum(k == SLIDING for k in s["kinds"])
    return sliding, s["layers"] - sliding, s["layers"] - s["dense_layers"]


def afmoe_call_bytes(hf: dict, rows: float, experts_hit: float,
                     tokens_window: float, tokens_full: float,
                     dense_bytes: int = 2, kv_bytes: int = 2) -> float:
    """Bytes one call over ``rows`` new tokens must move: the fixed
    parameters once, ``rows`` rows of the embedding, the kernels of the
    ``experts_hit`` experts that have a row (summed over the expert
    layers), the K/V the rows' contexts hold inside the window
    (``tokens_window``, per sliding layer) and whole (``tokens_full``, per
    full layer), and the new K/V of each row written in every layer."""
    s = afmoe_sizes(hf)
    sliding, full, _ = _layer_counts(hf)
    per = afmoe_kv_bytes_per_token_layer(hf, kv_bytes)
    return (afmoe_fixed_bytes(hf, dense_bytes)
            + rows * s["hidden"] * dense_bytes
            + experts_hit * afmoe_expert_bytes(hf, dense_bytes)
            + per * (sliding * tokens_window + full * tokens_full)
            + per * rows * s["layers"])


def afmoe_call_flops(hf: dict, rows: float, tokens_window: float,
                     tokens_full: float) -> float:
    """FLOPs of one call: 2 a weight a row for every dense product (the
    attention's five, the dense MLP, router and shared expert, the head),
    the ``top_k`` routed experts a row, and attention's 4 x heads x head
    size per (query, key) pair inside each layer's reach."""
    s = afmoe_sizes(hf)
    sliding, full, expert_layers = _layer_counts(hf)
    h, d = s["hidden"], s["head_dim"]
    attn = h * d * (3 * s["heads"] + 2 * s["kv_heads"])
    mlp = 3 * h * s["expert_inner"]
    per_row = (s["layers"] * attn + s["dense_layers"] * 3 * h * s["inner"]
               + expert_layers * (h * s["experts"] + mlp * (1 + s["top_k"]))
               + h * s["vocab"])
    pairs = sliding * tokens_window + full * tokens_full
    return 2 * rows * per_row + 4 * s["heads"] * d * pairs


def afmoe_expert_product_bytes(hf: dict, pairs: float, experts_hit: float,
                               dense_bytes: int = 2) -> float:
    """Bytes the grouped products of ONE expert layer must move: the hit
    experts' kernels, each (token, expert) pair's row in and out."""
    s = afmoe_sizes(hf)
    return (experts_hit * afmoe_expert_bytes(hf, dense_bytes)
            + 2 * pairs * s["hidden"] * dense_bytes)


def afmoe_expert_product_flops(hf: dict, pairs: float) -> float:
    s = afmoe_sizes(hf)
    return 2 * pairs * 3 * s["hidden"] * s["expert_inner"]
