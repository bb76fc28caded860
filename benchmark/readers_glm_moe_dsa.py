"""Shared arithmetic of the per-layer readers of the ``glm_moe_dsa`` cells
(suffix ``.dsa``): what the family's decode tick needs against what it took,
how much of it lay in the expert products, in the indexers and in the
attention over the selected columns, and how sparse the traffic made a step.

As in ``readers.py`` a reader is ``compute(run) -> float | None`` and returns
None where its source is silent: a program whose ``serving.decode_step``
spans carry no ``sel_cols`` (any before the PR that added the family), a run
with no device trace, a trace none of whose operations matches.

**How the two stages' operations are found in a trace.** This installation's
device events hold their instruction's text and no metadata
(``readers_olmo_hybrid`` has the same reason; the ``jax.named_scope`` each
stage runs under shows in the lowered text, not here), so the operations of
a decode step are found by the SHAPES of what they make
(:func:`is_indexer_op`, :func:`is_sparse_attn_op`): the indexer's make a
rows' ``index_k`` through the table (bfloat16 ending in 128 over ``slots x
columns`` in all, whichever way its leading axes split them) or float32, integer or boolean arrays over a table's columns a slot (the index
heads' products, a row's ``I``, what the selection sorts); the attention's
make the selected columns (``[slots x 2048, 640]`` as the gather makes them),
the scores of 64 heads over them, or the absorbed query and mix (``[slots,
64, 640]``). No other part of the program makes those.
"""

from __future__ import annotations

import math
import re
import statistics

from benchmark import needs_glm_moe_dsa as needs_g
from benchmark import peaks, readers, readers_afmoe, readers_olmo_hybrid


def _ticks(run, traced: bool = False) -> "list[dict]":
    """The window's decode ticks that carry the selection's counters (those
    of the traced stretch alone if ``traced``)."""
    ticks = [s for s in readers.spans(run, "serving.decode_step")
             if "sel_cols" in s["args"]]
    if traced:
        if not run.traced_window:
            return []
        t0, t1 = run.traced_window
        ticks = [s for s in ticks if s["t0"] >= t0 and s["t1"] <= t1]
    return ticks


def experts_hit_share(run) -> "float | None":
    """Held experts given at least one row, as a share of the experts HELD:
    mean over expert layers and over the window's decode ticks."""
    ticks = [s for s in _ticks(run) if "experts_hit" in s["args"]]
    if not ticks:
        return None
    held = needs_g.glm_sizes(run.raw["hf_config"])["held"]
    return 100.0 * statistics.fmean(
        s["args"]["experts_hit"] / s["args"]["chain"] for s in ticks) / held


def kv_cols_read_over_live(run) -> "float | None":
    """``latent`` columns the decode ticks fetched (the selection's size a
    slot once any row's table passes it) over the columns of live rows'
    contexts, in each layer: under 1 where the selection bites."""
    ticks = _ticks(run)
    live = sum(s["args"]["kv_cols_live"] for s in ticks)
    if not live:
        return None
    return sum(s["args"]["kv_cols_read"] for s in ticks) / live


def selected_cols_share(run) -> "float | None":
    """Columns the riding rows' attention attended over the columns of
    their contexts: how sparse the traffic made a step (100% while every
    context is no deeper than the selection)."""
    ticks = _ticks(run)
    live = sum(s["args"]["kv_cols_live"] for s in ticks)
    if not live:
        return None
    return 100.0 * sum(s["args"]["sel_cols"] for s in ticks) / live


def _tick_needs(run) -> "dict | None":
    """Mean rows, pairs routed to held experts and held experts hit of an
    expert layer, attended columns of a layer and columns a ``full``
    layer's indexer must score, of the traced stretch's single-step ticks."""
    ticks = [s for s in _ticks(run, traced=True)
             if s["args"]["chain"] == 1 and "experts_hit" in s["args"]]
    if not ticks:
        return None

    def mean(key):
        return statistics.fmean(s["args"][key] for s in ticks)

    return {"rows": mean("slots"), "pairs": mean("expert_rows"),
            "experts_hit": mean("experts_hit"),
            "sel_cols": mean("sel_cols"), "index_cols": mean("index_cols")}


def _share(run, got, flops, bytes_) -> "float | None":
    if got is None:
        return None
    least, _ = readers.needs.roofline_seconds(
        flops, bytes_, peaks.peak_for(run.device_kind))
    return 100.0 * least / (got[0] / got[1])


def decode_roofline_share(run) -> "float | None":
    """What a decode tick needs (``needs_glm_moe_dsa``: fixed weights once,
    the hit held experts' kernels, the attended columns of every layer, a
    key a scored column of each ``full`` layer) over the chip's peaks,
    against the decode program's device time, both over the traced
    stretch."""
    need = _tick_needs(run)
    if need is None:
        return None
    hf = run.raw["hf_config"]
    _, _, expert_layers = needs_g.layer_counts(hf)
    return _share(
        run, readers._decode_device(run),
        needs_g.glm_call_flops(hf, need["rows"], need["pairs"],
                               need["sel_cols"], need["index_cols"]),
        needs_g.glm_call_bytes(hf, need["rows"],
                               expert_layers * need["experts_hit"],
                               need["sel_cols"], need["index_cols"]))


def expert_device_ms(run) -> "float | None":
    """Device ms of a decode tick inside the grouped products of its expert
    layers (the ``gmm`` kernel, found by name as ``readers_afmoe`` finds
    it)."""
    got = readers_afmoe._expert_device(run)
    return None if got is None or not _ticks(run) else 1e3 * got[0] / got[1]


def expert_product_roofline_share(run) -> "float | None":
    """What the grouped products of a decode tick need (the hit held
    experts' kernels once, each pair's row in and out) over the chip's
    peaks, against the device time inside them."""
    need = _tick_needs(run)
    if need is None:
        return None
    hf = run.raw["hf_config"]
    _, _, expert_layers = needs_g.layer_counts(hf)
    return _share(
        run, readers_afmoe._expert_device(run),
        expert_layers * needs_g.glm_expert_product_flops(hf, need["pairs"]),
        expert_layers * needs_g.glm_expert_product_bytes(
            hf, need["pairs"], need["experts_hit"]))


_ARRAY = re.compile(r"(\w+)\[([\d,]*)\]")


def _made(event_name: str) -> "list[tuple[str, tuple[int, ...]]]":
    """``(dtype, dimensions)`` of each array a LEAF instruction makes, from
    the event's name (``%fusion.7 = (f32[32,64]{..}, f32[32,64,2048]{..})
    fusion(...)``: a tuple of results gives several; the operands, which the
    text names without their shapes, give none). A ``while`` gives none: its
    event spans its body's operations, which have events of their own on the
    same line, and counting both would count the loop twice (the scores'
    passes over groups of index heads are such a loop)."""
    made = event_name.split(" = ", 1)[-1]
    depth = 0
    for i, ch in enumerate(made):
        depth += (ch in "([{") - (ch in ")]}")
        if ch == " " and depth == 0:
            made, opcode = made[:i], made[i + 1:].split("(", 1)[0]
            if opcode in ("while", "conditional", "call"):
                return []
            break
    return [(dtype, tuple(int(d) for d in dims.split(",") if d))
            for dtype, dims in _ARRAY.findall(made)]


def is_indexer_op(event_name: str, hf: dict,
                  slots: "int | None" = None) -> bool:
    """An operation of a decode step inside a ``full`` layer's indexer,
    scoring and selection, by what it makes: the rows' keys through the
    table (bfloat16 with a last axis of ``index_dim`` over ``slots x W``
    columns in all, however the leading axes split them: ``[slots x W / 16,
    16, 128]`` as the gather makes them by block, ``[slots, W, 128]`` as the
    scores take them), or scores, positions and masks over a table's ``W``
    columns a slot (``[slots, .., W]`` in any type but the compute dtype:
    the products of groups of index heads, a row's ``I``, what the selection
    sorts). ``W`` is a table's width past the selection's size, a power of
    two (the engine's buckets): the step's logits (``[slots, vocabulary]``)
    and its projections' outputs (bfloat16) are neither."""
    s = needs_g.glm_sizes(hf)
    for dtype, dims in _made(event_name):
        if len(dims) < 2:
            continue
        if dtype == "bf16":
            # (the gather's first axis is slots x blocks, not slots)
            columns = math.prod(dims[:-1])
            if dims[-1] == s["index_dim"] and (
                    _table_width(math.prod(dims[1:-1]), s) if slots is None
                    else columns % slots == 0
                    and _table_width(columns // slots, s)):
                return True
            continue
        if slots is not None and dims[0] != slots:
            continue
        if (_table_width(dims[-1], s)
                and all(d in (1, s["index_heads"]) or s["index_heads"] % d == 0
                        for d in dims[1:-1])):
            return True
    return False


def _table_width(n: int, s: dict) -> bool:
    return n > s["index_topk"] and n & (n - 1) == 0


def is_sparse_attn_op(event_name: str, hf: dict,
                      slots: "int | None" = None) -> bool:
    """An operation of a decode step inside the attention over the selected
    columns, by what it makes: the picked positions' block ids through the
    table (``s32[slots x 2048]``), the columns read one by one (``[slots x
    2048, 640]``, ``[slots, 2048, 640]``), the absorbed query and the mix
    (``[slots, 64, 640]``), the scores of 64 heads over the selection
    (``[.., 64, 2048]``)."""
    s = needs_g.glm_sizes(hf)
    topk, nh = s["index_topk"], s["heads"]
    width = -(-(s["kv_rank"] + s["rope"]) // 128) * 128
    for dtype, dims in _made(event_name):
        if slots is not None and dims == (slots * topk,) and dtype == "s32":
            return True      # the picked positions' blocks through the table
        if len(dims) < 2:
            continue
        if dims[-1] == width and (
                topk in dims[:-1] or nh in dims[:-1]
                or (slots is not None and dims[0] == slots * topk)):
            return True
        if dtype == "f32" and dims[-1] == topk and nh in dims[:-1]:
            return True
    return False


def _stage_device(run, is_op):
    if not _ticks(run):
        return None
    slots = run.raw.get("n_slots")

    def of_run(name, hf):
        return is_op(name, hf, slots)

    of_run.__name__ = is_op.__name__
    return readers_olmo_hybrid._op_device(run, of_run, "paged_step")


def indexer_device_ms(run) -> "float | None":
    """Device ms of a decode tick inside its indexers' scoring and
    selection, both ``full`` layers."""
    got = _stage_device(run, is_indexer_op)
    return None if got is None else 1e3 * got[0] / got[1]


def indexer_roofline_share(run) -> "float | None":
    """What a tick's indexers must move and compute (a key a scored column
    and the projections, each ``full`` layer) over the chip's peaks, against
    the device time inside them."""
    need = _tick_needs(run)
    if need is None:
        return None
    hf = run.raw["hf_config"]
    _, full, _ = needs_g.layer_counts(hf)
    return _share(
        run, _stage_device(run, is_indexer_op),
        full * needs_g.indexer_flops(hf, need["rows"], need["index_cols"]),
        full * needs_g.indexer_bytes(hf, need["index_cols"]))


def sparse_attn_device_ms(run) -> "float | None":
    """Device ms of a decode tick inside the selected read and the
    absorbed products, all layers."""
    got = _stage_device(run, is_sparse_attn_op)
    return None if got is None else 1e3 * got[0] / got[1]


def sparse_attn_roofline_share(run) -> "float | None":
    """What a tick's attention over the selected columns must move and
    compute (each attended column's 576 values once a layer) over the chip's
    peaks, against the device time inside it."""
    need = _tick_needs(run)
    if need is None:
        return None
    hf = run.raw["hf_config"]
    layers, _, _ = needs_g.layer_counts(hf)
    return _share(
        run, _stage_device(run, is_sparse_attn_op),
        layers * needs_g.sparse_attn_flops(hf, need["sel_cols"]),
        layers * needs_g.sparse_attn_bytes(hf, need["sel_cols"]))
