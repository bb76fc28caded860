"""What a call of the ``lfm2_moe`` family NEEDS, from its shapes: bytes and
floating-point operations the mathematics cannot avoid, never what the
compiler emitted and never what the program's own forms spend (the merged-axis
products multiply other heads' zeros; the step gathers every slot's rows at
the deepest row's bucket), so a share of a roofline computed from these cannot
pass 100% by construction of the count.

An expert layer needs the kernels of the experts that were given a row, once
each (``experts_hit``, which the engine counts on the device and hands back
with the tick's tokens), each (token, expert) pair's row in and out, and the
pairs' products; an attention layer needs a row's whole K/V (8 heads of 64,
keys and values) and writes one column; a gated short convolution needs a
riding row's tail in and out (``conv_L_cache - 1`` columns of ``hidden``) and
its two projections; the embedding is needed a row at a time and, as the tied
head, whole.
"""

from __future__ import annotations

from benchmark.reference_lfm2_moe import (
    CONV,
    layer_leaves,
    lfm2_sizes,
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
        total += n * (dense_bytes if kind in ("kernel", "tap") else 4)
    return total


def lfm2_param_bytes(hf: dict, dense_bytes: int = 2) -> int:
    """Bytes of every parameter as served: kernels and taps at
    ``dense_bytes``, norm gains, router kernels and expert biases in
    float32; the tied head counted once."""
    return seeded_weight_bytes(hf, "bfloat16" if dense_bytes == 2
                               else "float32")


def lfm2_expert_bytes(hf: dict, dense_bytes: int = 2) -> int:
    """One routed expert's three kernels."""
    s = lfm2_sizes(hf)
    return 3 * s["hidden"] * s["expert_inner"] * dense_bytes


def lfm2_fixed_bytes(hf: dict, dense_bytes: int = 2) -> int:
    """Parameter bytes EVERY call reads whatever its rows: all but the
    routed experts' kernels. The embedding is among them: it is the head."""
    s = lfm2_sizes(hf)
    top = top_leaves(hf)
    total = _bytes(top, top, dense_bytes)
    for i in range(s["layers"]):
        leaves = layer_leaves(hf, i)
        total += _bytes(leaves, [n for n in leaves
                                 if not n.startswith("moe.experts_")],
                        dense_bytes)
    return total


def lfm2_kv_bytes_per_token_layer(hf: dict, kv_bytes: int = 2) -> int:
    """K and V of one token in one attention layer."""
    s = lfm2_sizes(hf)
    return 2 * s["kv_heads"] * s["head_dim"] * kv_bytes


def lfm2_tail_bytes_per_row_layer(hf: dict, tail_bytes: int = 2) -> int:
    """One convolution's tail of one row: what a step reads, and writes."""
    s = lfm2_sizes(hf)
    return (s["taps"] - 1) * s["hidden"] * tail_bytes


def layer_counts(hf: dict) -> "tuple[int, int, int]":
    """(convolution layers, attention layers, expert layers)."""
    s = lfm2_sizes(hf)
    conv = sum(k == CONV for k in s["kinds"])
    return conv, s["layers"] - conv, s["layers"] - s["dense_layers"]


def lfm2_expert_product_bytes(hf: dict, pairs: float, experts_hit: float,
                              dense_bytes: int = 2) -> float:
    """Bytes the grouped products of ONE expert layer must move: the hit
    experts' kernels, each (token, expert) pair's row in and out."""
    s = lfm2_sizes(hf)
    return (experts_hit * lfm2_expert_bytes(hf, dense_bytes)
            + 2 * pairs * s["hidden"] * dense_bytes)


def lfm2_expert_product_flops(hf: dict, pairs: float) -> float:
    s = lfm2_sizes(hf)
    return 2 * pairs * 3 * s["hidden"] * s["expert_inner"]


def lfm2_call_bytes(hf: dict, rows: float, pairs: float, experts_hit: float,
                    tokens_full: float, dense_bytes: int = 2,
                    kv_bytes: int = 2) -> float:
    """Bytes one call over ``rows`` new tokens (one a row) must move: the
    fixed parameters once, ``rows`` rows of the embedding, per expert layer
    the kernels of the ``experts_hit`` experts that have a row and its
    ``pairs`` rows in and out, the K/V of the rows' contexts
    (``tokens_full``, per attention layer) and a new column a row, and each
    row's tail in and out in every convolution."""
    s = lfm2_sizes(hf)
    conv, full, expert_layers = layer_counts(hf)
    return (lfm2_fixed_bytes(hf, dense_bytes)
            + rows * s["hidden"] * dense_bytes
            + expert_layers * lfm2_expert_product_bytes(
                hf, pairs, experts_hit, dense_bytes)
            + full * lfm2_kv_bytes_per_token_layer(hf, kv_bytes)
            * (tokens_full + rows)
            + conv * 2 * rows * lfm2_tail_bytes_per_row_layer(hf, kv_bytes))


def lfm2_call_flops(hf: dict, rows: float, pairs: float,
                    tokens_full: float) -> float:
    """FLOPs of one call: 2 a weight a row for every dense product (an
    attention's four projections, a convolution's two and its taps, the
    dense MLPs, the routers, the tied head), the ``pairs`` (token, expert)
    pairs of an expert layer, and attention's ``2 x heads x 2 x head_dim``
    per (query, key) pair."""
    s = lfm2_sizes(hf)
    conv, full, expert_layers = layer_counts(hf)
    h, d = s["hidden"], s["head_dim"]
    per_row = (h * s["vocab"]
               + full * h * 2 * d * (s["heads"] + s["kv_heads"])
               + conv * (4 * h * h + s["taps"] * h)
               + s["dense_layers"] * 3 * h * s["inner"]
               + expert_layers * h * s["experts"])
    return (2 * rows * per_row
            + expert_layers * lfm2_expert_product_flops(hf, pairs)
            + 2 * s["heads"] * 2 * d * full * tokens_full)
