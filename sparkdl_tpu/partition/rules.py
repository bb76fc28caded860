"""Regex partition rules over flattened param paths.

The single vocabulary every partitioner speaks: an ordered table of
``(regex, PartitionSpec)`` pairs matched against ``/``-joined param-tree
paths, **first match wins** (the ``match_partition_rules`` idiom of the
JAX LLM-training lineage — see SNIPPETS [1]). Scalars and size-1 leaves
are never partitioned; a non-scalar leaf no rule matches is a loud
``PartitionRuleError`` — silent replication of a 10-GB embedding is how
out-of-memory surprises happen on chip, so tables must be exhaustive
(end with an explicit ``(".*", P())`` catch-all when replication *is*
the intent).

Because matching uses ``re.search`` over the joined path, the same table
partitions a bare param tree **and** the optimizer state that mirrors it
(``0/mu/h_0/attn/q_proj/kernel`` still contains
``attn/q_proj/kernel``) — one rule table covers the whole TrainState.

Per-model default tables (GPT/BERT/ViT) put the Megatron tp split on
attention and MLP projections — column-parallel kernels ``[in, out/tp]``,
row-parallel ``[in/tp, out]`` — embeddings on (tp, fsdp), every other
kernel row-sharded on fsdp, and norms/biases replicated. On a mesh where
``tp``/``fsdp`` have size 1 those axes are inert and the specs resolve
to replication, so the tables are safe to apply unconditionally.

Every successful match lands in
``sparkdl_partition_rule_hits_total{rule=...}`` so a bench/operator can
see *which* rules actually shaped the model (bench_train.py embeds the
hit-counts in its JSON line).
"""

from __future__ import annotations

import re
from typing import Any, Sequence

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

from sparkdl_tpu.observability.registry import registry

__all__ = [
    "PartitionRuleError",
    "match_partition_rules",
    "tree_path_names",
    "rule_hit_counts",
    "GPT_RULES",
    "BERT_RULES",
    "VIT_RULES",
    "GENERIC_RULES",
    "KV_POOL_RULES",
    "sequence_activation_spec",
    "default_rules_for",
]

_M_RULE_HITS = registry().counter(
    "sparkdl_partition_rule_hits_total",
    "params matched by each partition rule", labels=("rule",))


class PartitionRuleError(ValueError):
    """A non-scalar param leaf matched no rule in the table."""


def _key_str(k: Any) -> str:
    """One path component as a plain string, across jax key types."""
    for attr in ("key", "name", "idx"):
        if hasattr(k, attr):
            return str(getattr(k, attr))
    return str(k)


def path_name(path: "tuple") -> str:
    """``/``-joined flattened-tree path (``h_0/attn/q_proj/kernel``)."""
    return "/".join(_key_str(k) for k in path)


def tree_path_names(tree: Any) -> "list[tuple[str, Any]]":
    """Flatten ``tree`` to ``[(joined_path, leaf), ...]`` in tree order."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [(path_name(p), leaf) for p, leaf in flat]


def match_partition_rules(
    rules: "Sequence[tuple[str, P]]", tree: Any, *,
    count_hits: bool = True,
) -> Any:
    """Pytree of ``PartitionSpec`` for ``tree``, first matching rule wins.

    Scalar / single-element leaves get ``P()`` without consulting the
    table (partitioning a scalar is never meaningful). A non-scalar leaf
    with no matching rule raises :class:`PartitionRuleError` naming the
    param — fail loud, never silently replicate.
    """
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    specs = []
    for path, leaf in flat:
        name = path_name(path)
        shape = getattr(leaf, "shape", ())
        if len(shape) == 0 or int(np.prod(shape)) == 1:
            specs.append(P())
            continue
        for rule, spec in rules:
            if re.search(rule, name) is not None:
                if count_hits:
                    _M_RULE_HITS.inc(rule=rule)
                specs.append(spec)
                break
        else:
            raise PartitionRuleError(
                f"no partition rule matched param {name!r} "
                f"(shape {tuple(shape)}); add a rule or an explicit "
                f"('.*', P()) catch-all if replication is intended"
            )
    return jax.tree_util.tree_unflatten(treedef, specs)


def rule_hit_counts() -> "dict[str, float]":
    """``{rule_pattern: hits}`` accumulated so far (registry-sourced)."""
    fam = registry().get("sparkdl_partition_rule_hits_total")
    if fam is None:
        return {}
    return fam.labelled_values("rule")


#: GPT decoder family (models/gpt.py naming). Attention q/k/v and the MLP
#: up-projection are column-parallel (out dim on tp), out_proj and the MLP
#: down-projection row-parallel (in dim on tp) — one psum per block, the
#: Megatron pairing the model's own tp metadata encodes.
GPT_RULES: "tuple[tuple[str, P], ...]" = (
    (r"attn/(q_proj|k_proj|v_proj)/kernel$", P("fsdp", "tp")),
    (r"attn/out_proj/kernel$", P("tp", "fsdp")),
    (r"(^|/)(up|wi)/kernel$", P("fsdp", "tp")),
    (r"(^|/)(down|wo)/kernel$", P("tp", "fsdp")),
    (r"(q_proj|k_proj|v_proj|up|wi)/bias$", P("tp")),
    (r"wte/embedding$", P("tp", "fsdp")),
    (r"wpe/embedding$", P(None, "fsdp")),
    (r"ln_.*/(scale|bias)$", P()),
    (r"kernel$", P("fsdp", None)),
    (r".*", P()),
)

#: BERT encoder family (models/bert.py naming).
BERT_RULES: "tuple[tuple[str, P], ...]" = (
    (r"attention/(query|key|value)/kernel$", P("fsdp", "tp")),
    (r"attention/output_dense/kernel$", P("tp", "fsdp")),
    (r"intermediate/kernel$", P("fsdp", "tp")),
    (r"(query|key|value|intermediate)/bias$", P("tp")),
    (r"layer_\d+/output/kernel$", P("tp", "fsdp")),
    (r"embeddings/.*/embedding$", P("tp", "fsdp")),
    (r"LayerNorm/(scale|bias)$", P()),
    (r"kernel$", P("fsdp", None)),
    (r".*", P()),
)

#: ViT encoder family (models/vit.py naming).
VIT_RULES: "tuple[tuple[str, P], ...]" = (
    (r"attention/(query|key|value)/kernel$", P("fsdp", "tp")),
    (r"attention/output_dense/kernel$", P("tp", "fsdp")),
    (r"intermediate/kernel$", P("fsdp", "tp")),
    (r"(query|key|value|intermediate)/bias$", P("tp")),
    (r"layer_\d+/output/kernel$", P("tp", "fsdp")),
    (r"patch_embed/kernel$", P(None, None, None, "fsdp")),
    (r"(cls_token|pos_embed)", P()),
    (r"layernorm.*/(scale|bias)$", P()),
    (r"kernel$", P("fsdp", None)),
    (r".*", P()),
)

#: Model-agnostic fallback: every kernel row-sharded on fsdp (leading
#: dim; trailing dims unsharded), everything else replicated — the
#: "everything else fsdp/replicated" floor for models without a table.
GENERIC_RULES: "tuple[tuple[str, P], ...]" = (
    (r"embedding$", P(None, "fsdp")),
    (r"kernel$", P("fsdp", None)),
    (r".*", P()),
)

#: Sequence-axis placement for the paged KV BLOCK POOL (ISSUE 13):
#: matched over a ``models.kv_pool.init_block_pool`` tree, the k/v arrays
#: ``[layers, n_blocks, block_size, *kv_tail]`` (and the int8 per-column
#: scale arrays ``[layers, n_blocks, block_size]``) shard their BLOCK
#: axis on ``sp`` — contiguous shards, so virtual block id ``b`` lives
#: on chip ``b // (n_blocks/sp)`` (the mapping
#: ``serving.kv_blocks.SeqShardedBlockPool`` mirrors host-side). ``sp``
#: shards *tokens*, never weights: params stay on the replicated /
#: tp-sharded tables above.
KV_POOL_RULES: "tuple[tuple[str, P], ...]" = (
    (r"(^|/)(k|v)$", P(None, "sp")),
    (r"_scale$", P(None, "sp")),
    (r".*", P()),
)


def sequence_activation_spec(*, ndim: int, seq_dim: int = 1,
                             sp_axis: str = "sp",
                             batch_axes: "Sequence[str]" = ()) -> P:
    """``PartitionSpec`` placing an activation's SEQUENCE dim on the
    ``sp`` mesh axis (and optionally its batch dim on ``batch_axes``) —
    the placement vocabulary for sequence-parallel prefill: token ids
    ``[B, L]`` (``ndim=2``), logits ``[B, L, V]`` (``ndim=3``), or
    per-layer K/V ``[layers, B, L, H, D]`` (``ndim=5, seq_dim=2``).
    Contiguous token shards: chip ``c`` holds columns
    ``[c*L/sp, (c+1)*L/sp)``, the layout the ring/all-gather causal
    masks assume."""
    if not 0 <= seq_dim < ndim:
        raise ValueError(
            f"seq_dim {seq_dim} out of range for ndim {ndim}")
    parts: "list" = [None] * ndim
    if batch_axes:
        parts[0] = tuple(batch_axes)
    parts[seq_dim] = sp_axis
    return P(*parts)

_TABLES = {
    "gpt": GPT_RULES,
    "bert": BERT_RULES,
    "vit": VIT_RULES,
    "generic": GENERIC_RULES,
}


def default_rules_for(model: str) -> "tuple[tuple[str, P], ...]":
    """Rule table for a model family name (``gpt``/``bert``/``vit``),
    :data:`GENERIC_RULES` for anything unrecognized."""
    key = model.lower()
    for name, table in _TABLES.items():
        if name in key:
            return table
    return GENERIC_RULES
