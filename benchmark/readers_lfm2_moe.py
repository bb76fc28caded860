"""Shared arithmetic of the per-layer readers of the ``lfm2_moe`` cells
(suffix ``.conv``): what the family's decode tick needs against what it took,
how much of it lay in the expert products and in the gated short convolutions.

As in ``readers.py`` a reader is ``compute(run) -> float | None`` and returns
None where its source is silent: a program whose ``serving.decode_step``
spans carry no expert and no state counters (any before the PR that added the
family), a run with no device trace, a trace none of whose operations touches
a tail. Where another family's reader already does the arithmetic it is
imported and called: the experts' counters and the ``gmm`` kernel's time are
``readers_afmoe``'s, the gathers' ratio ``readers_olmo_hybrid``'s.

**How a convolution's operations are found in a trace.** This installation's
device events hold their instruction's text and no metadata
(``readers_olmo_hybrid`` has the same reason), so the operations of a decode
step that belong to a gated short convolution are found by SHAPE
(:func:`is_conv_op`): an array whose last three axes are the slots, the tail's
columns and the hidden size (the tails, a layer's row of them), or the split
``[slots, 3 * hidden]`` that the convolution's input projection makes. No
other part of the program computes on those.
"""

from __future__ import annotations

import statistics

from benchmark import needs_lfm2_moe as needs_l
from benchmark import peaks, readers, readers_afmoe, readers_olmo_hybrid
from benchmark.readers_afmoe import (  # noqa: F401  (this cell's readers too)
    expert_device_ms,
    expert_rows_max_over_mean,
    experts_hit_share,
)
from benchmark.readers_olmo_hybrid import (  # noqa: F401
    kv_cols_read_over_live,
)


def _tick_needs(run) -> "dict | None":
    """Mean rows, (token, expert) pairs and experts hit of an expert layer
    and live K/V columns of an attention layer, of the traced stretch's
    single-step ticks."""
    ticks = [s for s in readers_afmoe._ticks(run, traced=True)
             if s["args"]["chain"] == 1 and "state_rows" in s["args"]]
    if not ticks:
        return None

    def mean(key):
        return statistics.fmean(s["args"][key] for s in ticks)

    return {"rows": mean("slots"), "pairs": mean("expert_rows"),
            "experts_hit": mean("experts_hit"),
            "tokens_full": mean("kv_cols_live")}


def decode_roofline_share(run) -> "float | None":
    """What a decode tick needs (``needs_lfm2_moe``: fixed weights once, the
    hit experts' kernels and each pair's row in and out, the attention
    layers' live columns, the riding rows' tails in and out) over the chip's
    peaks, against the decode program's device time, both over the traced
    stretch: the share of the WHOLE step."""
    got = readers._decode_device(run)
    need = _tick_needs(run)
    if got is None or need is None:
        return None
    hf = run.raw["hf_config"]
    least, _ = readers.needs.roofline_seconds(
        needs_l.lfm2_call_flops(hf, need["rows"], need["pairs"],
                                need["tokens_full"]),
        needs_l.lfm2_call_bytes(hf, need["rows"], need["pairs"],
                                need["experts_hit"], need["tokens_full"]),
        peaks.peak_for(run.device_kind))
    return 100.0 * least / (got[0] / got[1])


def expert_product_roofline_share(run) -> "float | None":
    """What the grouped products of a decode tick need (the hit experts'
    kernels once, each pair's row in and out) over the chip's peaks, against
    the device time inside them."""
    got = readers_afmoe._expert_device(run)
    need = _tick_needs(run)
    if got is None or need is None:
        return None
    hf = run.raw["hf_config"]
    _, _, expert_layers = needs_l.layer_counts(hf)
    least, _ = readers.needs.roofline_seconds(
        expert_layers * needs_l.lfm2_expert_product_flops(hf, need["pairs"]),
        expert_layers * needs_l.lfm2_expert_product_bytes(
            hf, need["pairs"], need["experts_hit"]),
        peaks.peak_for(run.device_kind))
    return 100.0 * least / (got[0] / got[1])


def is_conv_op(event_name: str, hf: dict, slots: int) -> bool:
    """An operation of a decode step that reads or writes a convolution's
    tail or makes its split (the module docstring has the rule)."""
    s = needs_l.lfm2_sizes(hf)
    tail = (slots, s["taps"] - 1, s["hidden"])
    if ",".join(map(str, tail)) + "]" in event_name:
        return True
    split = (slots, 3 * s["hidden"])
    return any(dims[-3:] == tail or tuple(d for d in dims if d != 1) == split
               for dims in readers_olmo_hybrid._result_dims(event_name))


def short_conv_device_ms(run) -> "float | None":
    """Device ms of a decode tick inside the operations of its gated short
    convolutions: the tails read and written, the split made."""
    slots = run.raw.get("n_slots")
    if not slots or _tick_needs(run) is None:
        return None

    def is_conv_op_of_run(name, hf):
        return is_conv_op(name, hf, slots)

    got = readers_olmo_hybrid._op_device(run, is_conv_op_of_run, "paged_step")
    return None if got is None else 1e3 * got[0] / got[1]
