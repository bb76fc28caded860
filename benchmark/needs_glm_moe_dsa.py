"""What a call of the ``glm_moe_dsa`` family NEEDS, from its shapes: bytes
and floating-point operations the mathematics cannot avoid, never what the
compiler emitted and never what the program's own forms spend (a stored
column is padded from 576 values to 640; every slot's rows are fetched, live
or not; the indexer reads every slot to the deepest row's depth), so a share
of a roofline computed from these cannot pass 100% by construction of the
count.

An expert layer needs the kernels of the HELD experts that were given a row,
once each (``experts_hit``, which the engine counts on the device and hands
back with the tick's tokens), the shared expert's whole, and the products of
the pairs routed to held experts; a layer's attention needs the SELECTED
columns of a row (``sel_cols``: 576 values of 2 bytes each, K and V both) and
the absorbed products over them; a ``full`` layer's indexer needs one key of
128 values for every column it must score (``index_cols``: the columns of the
rows deeper than the selection; a row no deeper needs no scoring) and its
projections; the embedding is needed a row at a time, the head whole (this
chip's slice of it).
"""

from __future__ import annotations

from benchmark.reference_glm_moe_dsa import (
    FULL,
    SPARSE,
    glm_sizes,
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
        total += n * (dense_bytes if kind.startswith("kernel") else 4)
    return total


def glm_param_bytes(hf: dict, dense_bytes: int = 2) -> int:
    """Bytes of every parameter as served: kernels at ``dense_bytes``, norm
    gains, router kernels and biases in float32."""
    return seeded_weight_bytes(hf, "bfloat16" if dense_bytes == 2
                               else "float32")


def glm_expert_bytes(hf: dict, dense_bytes: int = 2) -> int:
    """One routed expert's three kernels."""
    s = glm_sizes(hf)
    return 3 * s["hidden"] * s["expert_inner"] * dense_bytes


def layer_counts(hf: dict) -> "tuple[int, int, int]":
    """(layers, ``full`` layers, expert layers)."""
    s = glm_sizes(hf)
    return (s["layers"], sum(k == FULL for k in s["kinds"]),
            sum(m == SPARSE for m in s["mlps"]))


def glm_fixed_bytes(hf: dict, dense_bytes: int = 2) -> int:
    """Parameter bytes EVERY call reads whatever its rows: all but the
    routed experts' kernels and the embedding (a row a token)."""
    s = glm_sizes(hf)
    total = _bytes(top_leaves(hf), ("norm", "lm_head"), dense_bytes)
    for i in range(s["layers"]):
        leaves = layer_leaves(hf, i)
        total += _bytes(leaves, [n for n in leaves
                                 if not n.startswith("moe.experts_")],
                        dense_bytes)
    return total


def latent_bytes_per_column(hf: dict, kv_bytes: int = 2) -> int:
    """One token's ``[c^kv | k^r]`` in one layer, UNPADDED: 576 values."""
    s = glm_sizes(hf)
    return (s["kv_rank"] + s["rope"]) * kv_bytes


def index_bytes_per_column(hf: dict, kv_bytes: int = 2) -> int:
    """One token's indexer key in one ``full`` layer: 128 values."""
    return glm_sizes(hf)["index_dim"] * kv_bytes


def indexer_projection_bytes(hf: dict, dense_bytes: int = 2) -> int:
    """The kernels of one indexer: ``W_qI``, ``W_kI``, ``W_w``."""
    s = glm_sizes(hf)
    return (s["q_rank"] * s["index_heads"] * s["index_dim"]
            + s["hidden"] * s["index_dim"]
            + s["hidden"] * s["index_heads"]) * dense_bytes


def sparse_attn_bytes(hf: dict, sel_cols: float, kv_bytes: int = 2) -> float:
    """Bytes the selected read of ONE layer must move: each attended column
    once (it is K and V both)."""
    return sel_cols * latent_bytes_per_column(hf, kv_bytes)


def sparse_attn_flops(hf: dict, sel_cols: float) -> float:
    """FLOPs of ONE layer's absorbed products over the attended columns: a
    score over ``kv_rank + rope`` values and a mix over ``kv_rank`` a head a
    column."""
    s = glm_sizes(hf)
    return 2 * s["heads"] * (2 * s["kv_rank"] + s["rope"]) * sel_cols


def indexer_bytes(hf: dict, index_cols: float, dense_bytes: int = 2,
                  kv_bytes: int = 2) -> float:
    """Bytes ONE indexer must move: a key for every column it must score,
    and its projections' kernels."""
    return (index_cols * index_bytes_per_column(hf, kv_bytes)
            + indexer_projection_bytes(hf, dense_bytes))


def indexer_flops(hf: dict, rows: float, index_cols: float) -> float:
    """FLOPs of ONE indexer: its projections a row, a product a head a
    scored column."""
    s = glm_sizes(hf)
    return (2 * rows * indexer_projection_bytes(hf, 1)
            + 2 * s["index_heads"] * s["index_dim"] * index_cols)


def glm_call_bytes(hf: dict, rows: float, experts_hit: float,
                   sel_cols: float, index_cols: float,
                   dense_bytes: int = 2, kv_bytes: int = 2) -> float:
    """Bytes one call over ``rows`` new tokens (one a row) must move: the
    fixed parameters once, ``rows`` rows of the embedding, the kernels of
    the ``experts_hit`` held experts that have a row (summed over the expert
    layers), the attended columns (``sel_cols``, per layer), a key for each
    column an indexer must score (``index_cols``, per ``full`` layer), and
    each row's new column and key written."""
    s = glm_sizes(hf)
    layers, full, _ = layer_counts(hf)
    return (glm_fixed_bytes(hf, dense_bytes)
            + rows * s["hidden"] * dense_bytes
            + experts_hit * glm_expert_bytes(hf, dense_bytes)
            + layers * latent_bytes_per_column(hf, kv_bytes)
            * (sel_cols + rows)
            + full * index_bytes_per_column(hf, kv_bytes)
            * (index_cols + rows))


def glm_call_flops(hf: dict, rows: float, pairs_held: float,
                   sel_cols: float, index_cols: float) -> float:
    """FLOPs of one call: 2 a weight a row for every dense product (the
    attention's projections with the absorbed ``W_uk`` and ``W_uv``, the
    indexers', the dense MLP, the shared experts, the routers, this chip's
    slice of the head), the ``pairs_held`` (token, expert) pairs an expert
    layer routed to experts held here, the absorbed products over the
    attended columns and the indexers' over the scored ones."""
    s = glm_sizes(hf)
    layers, full, expert_layers = layer_counts(hf)
    h, nh = s["hidden"], s["heads"]
    attn = (h * s["q_rank"] + s["q_rank"] * nh * (s["nope"] + s["rope"])
            + h * (s["kv_rank"] + s["rope"])
            + nh * s["kv_rank"] * (s["nope"] + s["v_head_dim"])
            + nh * s["v_head_dim"] * h)
    per_row = (h * s["vocab"] + layers * attn
               + full * indexer_projection_bytes(hf, 1)
               + (layers - expert_layers) * 3 * h * s["inner"]
               + expert_layers * (3 * h * s["expert_inner"]
                                  + h * s["experts"]))
    return (2 * rows * per_row
            + expert_layers * glm_expert_product_flops(hf, pairs_held)
            + layers * sparse_attn_flops(hf, sel_cols)
            + full * 2 * s["index_heads"] * s["index_dim"] * index_cols)


def glm_expert_product_bytes(hf: dict, pairs: float, experts_hit: float,
                             dense_bytes: int = 2) -> float:
    """Bytes the grouped products of ONE expert layer must move: the hit
    held experts' kernels, each (token, expert) pair's row in and out."""
    s = glm_sizes(hf)
    return (experts_hit * glm_expert_bytes(hf, dense_bytes)
            + 2 * pairs * s["hidden"] * dense_bytes)


def glm_expert_product_flops(hf: dict, pairs: float) -> float:
    s = glm_sizes(hf)
    return 2 * pairs * 3 * s["hidden"] * s["expert_inner"]
