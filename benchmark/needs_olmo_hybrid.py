"""What a call of the ``olmo_hybrid`` family NEEDS, from its shapes: bytes and
floating-point operations the mathematics cannot avoid, never what the
compiler emitted and never what the program's own form of the recurrence
spends (the chunkwise form does more products than the rule it computes), so
a share of a roofline computed from these cannot pass 100% by construction of
the count.

A full-attention layer needs a row's whole K/V; a linear-attention layer
needs a row's recurrent state read and written ONCE a call, whatever the
context's length, and per token the three products of the gated delta rule
over it (``S^T k``, the rank-one update, ``S^T q``).
"""

from __future__ import annotations

from benchmark.reference_olmo_hybrid import (
    LINEAR,
    hybrid_sizes,
    layer_leaves,
    seeded_weight_bytes,
    top_leaves,
)


def _count(leaves: dict, names=None) -> "tuple[int, int]":
    """(elements of kernels, elements of everything else) of a table."""
    kernels = other = 0
    for name, (shape, kind) in leaves.items():
        if names is not None and name not in names:
            continue
        n = 1
        for d in shape:
            n *= d
        if kind == "kernel":
            kernels += n
        else:
            other += n
    return kernels, other


def hybrid_param_bytes(hf: dict, dense_bytes: int = 2) -> int:
    """Bytes of every parameter as served."""
    return seeded_weight_bytes(hf, "bfloat16" if dense_bytes == 2
                               else "float32")


def _layer_counts(hf: dict) -> "tuple[int, int]":
    """(linear layers, full layers)."""
    s = hybrid_sizes(hf)
    linear = sum(k == LINEAR for k in s["kinds"])
    return linear, s["layers"] - linear


def hybrid_fixed_bytes(hf: dict, dense_bytes: int = 2) -> int:
    """Parameter bytes EVERY call reads whatever its rows: all but the
    embedding (a row a token)."""
    s = hybrid_sizes(hf)
    k, o = _count(top_leaves(hf), ("norm", "lm_head"))
    total = k * dense_bytes + o * 4
    for i in range(s["layers"]):
        k, o = _count(layer_leaves(hf, i))
        total += k * dense_bytes + o * 4
    return total


def hybrid_kv_bytes_per_token_layer(hf: dict, kv_bytes: int = 2) -> int:
    """K and V of one token in one full layer."""
    s = hybrid_sizes(hf)
    return 2 * s["heads"] * s["head_dim"] * kv_bytes


def hybrid_state_bytes_per_row_layer(hf: dict, dense_bytes: int = 2) -> int:
    """One row's recurrent state in one linear layer: the float32 state of
    every head and the convolutions' tails."""
    s = hybrid_sizes(hf)
    n, dk, dv = s["lin_heads"], s["dk"], s["dv"]
    return (4 * n * dk * dv
            + dense_bytes * (s["taps"] - 1) * n * (2 * dk + dv))


def hybrid_delta_flops_per_token_layer(hf: dict) -> int:
    """The gated delta rule over one token in one layer, all heads: three
    products of ``2 d_k d_v`` and the decay's ``d_k d_v``."""
    s = hybrid_sizes(hf)
    return 7 * s["lin_heads"] * s["dk"] * s["dv"]


def hybrid_call_bytes(hf: dict, rows: float, tokens_full: float,
                      dense_bytes: int = 2, kv_bytes: int = 2) -> float:
    """Bytes one call over ``rows`` new tokens (one a row) must move: the
    fixed parameters once, ``rows`` rows of the embedding, the K/V of the
    rows' contexts (``tokens_full``, per full layer) and each row's new
    K/V, and each row's recurrent state in and out of every linear layer."""
    s = hybrid_sizes(hf)
    linear, full = _layer_counts(hf)
    per = hybrid_kv_bytes_per_token_layer(hf, kv_bytes)
    return (hybrid_fixed_bytes(hf, dense_bytes)
            + rows * s["hidden"] * dense_bytes
            + per * full * (tokens_full + rows)
            + 2 * rows * linear * hybrid_state_bytes_per_row_layer(
                hf, dense_bytes))


def hybrid_call_flops(hf: dict, rows: float, tokens_full: float) -> float:
    """FLOPs of one call: 2 a kernel weight a row (every projection, the
    MLPs, the head), attention's 4 x heads x head size per (query, key) pair
    of the full layers, and the delta rule a row a linear layer."""
    s = hybrid_sizes(hf)
    linear, full = _layer_counts(hf)
    per_row = _count(top_leaves(hf), ("lm_head",))[0]
    for i in range(s["layers"]):
        per_row += _count(layer_leaves(hf, i))[0]
    return (2 * rows * per_row
            + 4 * s["heads"] * s["head_dim"] * full * tokens_full
            + rows * linear * hybrid_delta_flops_per_token_layer(hf))


def hybrid_delta_step_bytes(hf: dict, rows: float,
                            dense_bytes: int = 2) -> float:
    """What the one-token state updates of a tick must move: each row's
    state in and out of every linear layer, and its q, k, v in and o out."""
    s = hybrid_sizes(hf)
    linear, _ = _layer_counts(hf)
    n, dk, dv = s["lin_heads"], s["dk"], s["dv"]
    return rows * linear * (
        2 * hybrid_state_bytes_per_row_layer(hf, dense_bytes)
        + dense_bytes * n * (2 * dk + 2 * dv))


def hybrid_delta_step_flops(hf: dict, rows: float) -> float:
    linear, _ = _layer_counts(hf)
    return rows * linear * hybrid_delta_flops_per_token_layer(hf)


def hybrid_delta_scan_bytes(hf: dict, tokens: float, chunks: float,
                            dense_bytes: int = 2) -> float:
    """What the recurrences of ``chunks`` chunk calls over ``tokens`` real
    tokens must move, all linear layers: the running state in and out once a
    call, and a token's q, k, v in and o out."""
    s = hybrid_sizes(hf)
    linear, _ = _layer_counts(hf)
    n, dk, dv = s["lin_heads"], s["dk"], s["dv"]
    return linear * (
        2 * chunks * hybrid_state_bytes_per_row_layer(hf, dense_bytes)
        + tokens * dense_bytes * n * (2 * dk + 2 * dv))


def hybrid_delta_scan_flops(hf: dict, tokens: float) -> float:
    linear, _ = _layer_counts(hf)
    return tokens * linear * hybrid_delta_flops_per_token_layer(hf)
