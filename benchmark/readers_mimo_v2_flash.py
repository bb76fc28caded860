"""Shared arithmetic of the per-layer readers of the ``mimo_v2_flash`` cells
(suffix ``.swa``): what the family's decode tick needs against what it took,
how much of it lay in the expert products and in the window layers' rings.

As in ``readers.py`` a reader is ``compute(run) -> float | None`` and returns
None where its source is silent: a program whose ``serving.decode_step``
spans carry no ``win_cols_live`` (any before the PR that added the family), a
run with no device trace, a trace none of whose operations touches a ring.

**How a ring's operations are found in a trace.** This installation's device
events hold their instruction's text and no metadata (``readers_olmo_hybrid``
has the same reason), so the operations of a decode step that read or write
a window layer's ring are found by SHAPE (:func:`is_ring_op`): an array whose
last two axes are the window and a window layer's merged K or V heads (the
ring itself, a slot's rows of it), or the scores of one query a row over a
ring, ``[slots, heads, window]``. No other part of the program computes on
those.
"""

from __future__ import annotations

import statistics

from benchmark import needs_mimo_v2_flash as needs_m
from benchmark import peaks, readers, readers_afmoe, readers_olmo_hybrid


def _ticks(run, traced: bool = False) -> "list[dict]":
    """The window's decode ticks that carry ring counters (those of the
    traced stretch alone if ``traced``)."""
    ticks = [s for s in readers.spans(run, "serving.decode_step")
             if "win_cols_live" in s["args"]]
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
    held = needs_m.mimo_sizes(run.raw["hf_config"])["held"]
    return 100.0 * statistics.fmean(
        s["args"]["experts_hit"] / s["args"]["chain"] for s in ticks) / held


def kv_cols_read_over_live(run) -> "float | None":
    """K/V columns the decode ticks gathered through the table over the
    columns of live rows' contexts, in the FULL layers (the same ratio in
    each): what a step that reads each row to its own depth could win."""
    ticks = _ticks(run)
    live = sum(s["args"]["kv_cols_live"] for s in ticks)
    if not live:
        return None
    return sum(s["args"]["kv_cols_read"] for s in ticks) / live


def _tick_needs(run) -> "dict | None":
    """Mean rows, pairs routed to held experts and held experts hit of an
    expert layer, live K/V columns of a full layer and live ring columns of
    a window layer, of the traced stretch's single-step ticks."""
    ticks = [s for s in _ticks(run, traced=True)
             if s["args"]["chain"] == 1 and "experts_hit" in s["args"]]
    if not ticks:
        return None

    def mean(key):
        return statistics.fmean(s["args"][key] for s in ticks)

    return {"rows": mean("slots"), "pairs": mean("expert_rows"),
            "experts_hit": mean("experts_hit"),
            "tokens_full": mean("kv_cols_live"),
            "tokens_window": mean("win_cols_live")}


def decode_roofline_share(run) -> "float | None":
    """What a decode tick needs (``needs_mimo_v2_flash``: fixed weights
    once, the hit held experts' kernels, the full layers' live columns, the
    rings' live columns in and one out a row a window layer) over the chip's
    peaks, against the decode program's device time, both over the traced
    stretch."""
    got = readers._decode_device(run)
    need = _tick_needs(run)
    if got is None or need is None:
        return None
    hf = run.raw["hf_config"]
    _, _, expert_layers = needs_m.layer_counts(hf)
    least, _ = readers.needs.roofline_seconds(
        needs_m.mimo_call_flops(hf, need["rows"], need["pairs"],
                                need["tokens_full"], need["tokens_window"]),
        needs_m.mimo_call_bytes(
            hf, need["rows"], expert_layers * need["experts_hit"],
            need["tokens_full"], need["tokens_window"]),
        peaks.peak_for(run.device_kind))
    return 100.0 * least / (got[0] / got[1])


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
    got = readers_afmoe._expert_device(run)
    need = _tick_needs(run)
    if got is None or need is None:
        return None
    hf = run.raw["hf_config"]
    _, _, expert_layers = needs_m.layer_counts(hf)
    least, _ = readers.needs.roofline_seconds(
        expert_layers * needs_m.mimo_expert_product_flops(hf, need["pairs"]),
        expert_layers * needs_m.mimo_expert_product_bytes(
            hf, need["pairs"], need["experts_hit"]),
        peaks.peak_for(run.device_kind))
    return 100.0 * least / (got[0] / got[1])


def is_ring_op(event_name: str, hf: dict, slots: "int | None" = None) -> bool:
    """An operation of a decode step that reads or writes a window layer's
    ring (the module docstring has the rule)."""
    s = needs_m.mimo_sizes(hf)
    g, w = s["kv_heads"][needs_m.WINDOW], s["window"]
    if (f",{w},{g * s['head_dim']}]" in event_name
            or f",{w},{g * s['v_head_dim']}]" in event_name):
        return True
    scores = (s["heads"], w)
    return any(dims[-2:] == scores and len(dims) == 3
               and (slots is None or dims[0] == slots)
               for dims in readers_olmo_hybrid._result_dims(event_name))


def window_attn_device_ms(run) -> "float | None":
    """Device ms of a decode tick inside the operations that read or write
    a window layer's ring."""
    if not _ticks(run):
        return None
    slots = run.raw.get("n_slots")

    def is_ring_op_of_run(name, hf):
        return is_ring_op(name, hf, slots)

    got = readers_olmo_hybrid._op_device(run, is_ring_op_of_run, "paged_step")
    return None if got is None else 1e3 * got[0] / got[1]
