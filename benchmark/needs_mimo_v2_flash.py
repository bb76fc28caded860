"""What a call of the ``mimo_v2_flash`` family NEEDS, from its shapes: bytes
and floating-point operations the mathematics cannot avoid, never what the
compiler emitted and never what the program's own forms spend (the merged-axis
products multiply other heads' zeros; the step reads every slot's ring), so a
share of a roofline computed from these cannot pass 100% by construction of
the count.

An expert layer needs the kernels of the HELD experts that were given a row,
once each (``experts_hit``, which the engine counts on the device and hands
back with the tick's tokens), and the products of the pairs routed to them; a
full layer needs a row's whole K/V (keys of 192, values of 128 under 4 heads);
a window layer needs the ``min(depth, window)`` ring columns of a row (under 8
heads) and writes one; the embedding is needed a row at a time, the head
whole (this chip's slice of it).
"""

from __future__ import annotations

from benchmark.reference_mimo_v2_flash import (
    FULL,
    WINDOW,
    layer_leaves,
    mimo_sizes,
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


def mimo_param_bytes(hf: dict, dense_bytes: int = 2) -> int:
    """Bytes of every parameter as served: kernels at ``dense_bytes``, norm
    gains, router kernels, expert biases and sinks in float32."""
    return seeded_weight_bytes(hf, "bfloat16" if dense_bytes == 2
                               else "float32")


def mimo_expert_bytes(hf: dict, dense_bytes: int = 2) -> int:
    """One routed expert's three kernels."""
    s = mimo_sizes(hf)
    return 3 * s["hidden"] * s["expert_inner"] * dense_bytes


def mimo_fixed_bytes(hf: dict, dense_bytes: int = 2) -> int:
    """Parameter bytes EVERY call reads whatever its rows: all but the
    routed experts' kernels and the embedding (a row a token)."""
    s = mimo_sizes(hf)
    total = _bytes(top_leaves(hf), ("norm", "lm_head"), dense_bytes)
    for i in range(s["layers"]):
        leaves = layer_leaves(hf, i)
        total += _bytes(leaves, [n for n in leaves
                                 if not n.startswith("moe.experts_")],
                        dense_bytes)
    return total


def mimo_kv_bytes_per_token_layer(hf: dict, kind: int,
                                  kv_bytes: int = 2) -> int:
    """K and V of one token in one layer of ``kind``: a full layer's column
    of the pool, a window layer's column of its ring."""
    s = mimo_sizes(hf)
    return s["kv_heads"][kind] * (s["head_dim"] + s["v_head_dim"]) * kv_bytes


def layer_counts(hf: dict) -> "tuple[int, int, int]":
    """(window layers, full layers, expert layers)."""
    s = mimo_sizes(hf)
    window = sum(k == WINDOW for k in s["kinds"])
    return window, s["layers"] - window, sum(s["moe"])


def mimo_call_bytes(hf: dict, rows: float, experts_hit: float,
                    tokens_full: float, tokens_window: float,
                    dense_bytes: int = 2, kv_bytes: int = 2) -> float:
    """Bytes one call over ``rows`` new tokens (one a row) must move: the
    fixed parameters once, ``rows`` rows of the embedding, the kernels of
    the ``experts_hit`` held experts that have a row (summed over the expert
    layers), the K/V of the rows' contexts (``tokens_full``, per full
    layer), the ring columns inside the rows' windows (``tokens_window``,
    per window layer), and each row's new column written in every layer."""
    s = mimo_sizes(hf)
    window, full, _ = layer_counts(hf)
    return (mimo_fixed_bytes(hf, dense_bytes)
            + rows * s["hidden"] * dense_bytes
            + experts_hit * mimo_expert_bytes(hf, dense_bytes)
            + full * mimo_kv_bytes_per_token_layer(hf, FULL, kv_bytes)
            * (tokens_full + rows)
            + window * mimo_kv_bytes_per_token_layer(hf, WINDOW, kv_bytes)
            * (tokens_window + rows))


def mimo_call_flops(hf: dict, rows: float, pairs_held: float,
                    tokens_full: float, tokens_window: float) -> float:
    """FLOPs of one call: 2 a weight a row for every dense product (the
    attention's four projections, the dense MLP, the routers, this chip's
    slice of the head), the ``pairs_held`` (token, expert) pairs an expert
    layer routed to experts held here, and attention's ``2 x heads x (key
    size + value size)`` per (query, key) pair inside each layer's reach."""
    s = mimo_sizes(hf)
    window, full, expert_layers = layer_counts(hf)
    h, dk, dv = s["hidden"], s["head_dim"], s["v_head_dim"]
    per_row = h * s["vocab"]
    for kind, n in ((FULL, full), (WINDOW, window)):
        g = s["kv_heads"][kind]
        per_row += n * h * (s["heads"] * (dk + dv) + g * (dk + dv))
    per_row += (s["layers"] - expert_layers) * 3 * h * s["inner"]
    per_row += expert_layers * h * s["experts"]
    pairs = full * tokens_full + window * tokens_window
    return (2 * rows * per_row
            + expert_layers * mimo_expert_product_flops(hf, pairs_held)
            + 2 * s["heads"] * (dk + dv) * pairs)


def mimo_expert_product_bytes(hf: dict, pairs: float, experts_hit: float,
                              dense_bytes: int = 2) -> float:
    """Bytes the grouped products of ONE expert layer must move: the hit
    held experts' kernels, each (token, expert) pair's row in and out."""
    s = mimo_sizes(hf)
    return (experts_hit * mimo_expert_bytes(hf, dense_bytes)
            + 2 * pairs * s["hidden"] * dense_bytes)


def mimo_expert_product_flops(hf: dict, pairs: float) -> float:
    s = mimo_sizes(hf)
    return 2 * pairs * 3 * s["hidden"] * s["expert_inner"]
