"""Shared arithmetic of the per-layer readers of the ``afmoe`` cells (suffix
``.mixed``): what an expert family's decode tick needs against what it took.

As in ``readers.py`` a reader is ``compute(run) -> float | None`` and returns
None where its source is silent: a program that has no expert counters on
its ``serving.decode_step`` spans (any before PR 29), a run with no device
trace.
"""

from __future__ import annotations

import bisect
import os
import statistics

from benchmark import needs_afmoe, peaks, readers, trace_reduce

#: an ``XLA Ops`` event is one of the grouped products if its instruction's
#: name starts so: the Pallas grouped matmul's Mosaic call, one a projection
#: (or ``lax.ragged_dot``'s, with its metadata call)
EXPERT_OPS = ("%gmm", "%ragged-dot")


def _ticks(run, traced: bool = False) -> "list[dict]":
    """The window's decode ticks that carry expert counters (those of the
    traced stretch alone if ``traced``)."""
    ticks = [s for s in readers.spans(run, "serving.decode_step")
             if "experts_hit" in s["args"]]
    if traced:
        if not run.traced_window:
            return []
        t0, t1 = run.traced_window
        ticks = [s for s in ticks if s["t0"] >= t0 and s["t1"] <= t1]
    return ticks


def _mean(ticks, key: str) -> float:
    return statistics.fmean(s["args"][key] for s in ticks)


def experts_hit_share(run) -> "float | None":
    """Experts given at least one row, as a share of the experts held: mean
    over expert layers and over the window's decode ticks."""
    ticks = _ticks(run)
    if not ticks:
        return None
    held = int(run.raw["hf_config"]["num_experts"])
    return 100.0 * statistics.fmean(
        s["args"]["experts_hit"] / s["args"]["chain"] for s in ticks) / held


def expert_rows_max_over_mean(run) -> "float | None":
    """The fullest expert's rows over the mean rows of an expert that was
    hit, mean over the window's decode ticks: 1 is an even spread."""
    ticks = [s for s in _ticks(run) if s["args"]["experts_hit"] > 0]
    if not ticks:
        return None
    return statistics.fmean(
        s["args"]["expert_rows_max"]
        / (s["args"]["expert_rows"] / s["args"]["experts_hit"])
        for s in ticks)


def kv_cols_read_over_live(run) -> "float | None":
    """K/V columns the decode ticks gathered through the table, all layers,
    over the columns of live rows' contexts that the layers' reach covers
    (the window in a sliding layer, everything in a full one): what
    grouping rows by length could win."""
    ticks = [s for s in _ticks(run) if "kv_cols_read_window" in s["args"]]
    if not ticks:
        return None
    sliding, full, _ = needs_afmoe._layer_counts(run.raw["hf_config"])
    read = sum(s["args"]["kv_cols_read_window"]
               + s["args"]["kv_cols_read_full"] for s in ticks)
    live = sum(sliding * s["args"]["kv_cols_live_window"]
               + full * s["args"]["kv_cols_live"] for s in ticks)
    return read / live if live else None


def _tick_needs(run) -> "dict | None":
    """Mean rows, (token, expert) pairs and experts hit of an expert layer,
    and live K/V columns (inside the window; whole) of the traced
    stretch's ticks."""
    ticks = [s for s in _ticks(run, traced=True)
             if "kv_cols_live_window" in s["args"]
             and s["args"]["chain"] == 1]
    if not ticks:
        return None
    return {"rows": _mean(ticks, "slots"),
            "pairs": _mean(ticks, "expert_rows"),
            "experts_hit": _mean(ticks, "experts_hit"),
            "tokens_window": _mean(ticks, "kv_cols_live_window"),
            "tokens_full": _mean(ticks, "kv_cols_live")}


def decode_roofline_share(run) -> "float | None":
    """What a decode tick needs (``needs_afmoe``) over the chip's peaks,
    against the decode program's device time, both over the traced
    stretch."""
    got = readers._decode_device(run)
    need = _tick_needs(run)
    if got is None or need is None:
        return None
    hf = run.raw["hf_config"]
    _, _, expert_layers = needs_afmoe._layer_counts(hf)
    least, _ = readers.needs.roofline_seconds(
        needs_afmoe.afmoe_call_flops(hf, need["rows"], need["tokens_window"],
                                     need["tokens_full"]),
        needs_afmoe.afmoe_call_bytes(
            hf, need["rows"], expert_layers * need["experts_hit"],
            need["tokens_window"], need["tokens_full"]),
        peaks.peak_for(run.device_kind))
    return 100.0 * least / (got[0] / got[1])


def _expert_device(run) -> "tuple[float, int] | None":
    """Device seconds inside the grouped products of the decode program's
    WHOLE executions in the traced stretch, and the count of those
    executions. The reduced trace keeps ten kinds of operation only, so the
    run's own trace (which the harness keeps) is opened again."""
    if "_expert_device" in run.raw:
        return run.raw["_expert_device"]
    got = None
    trace_dir = os.path.join(run.cell.root, ".benchmark_runs",
                             "trace-" + run.cell.name)
    if run.trace_summary and os.path.isdir(trace_dir):
        planes = trace_reduce.load_xplane(trace_reduce.find_xplane(trace_dir))
        mark = trace_reduce.find_mark(planes)
        secs, count = 0.0, 0
        for p in planes:
            if not p["name"].startswith(trace_reduce.DEVICE_PLANE_PREFIX):
                continue
            lines = {ln["name"]: ln["events"] for ln in p["lines"]}
            steps = sorted(
                (s, s + d) for name, s, d in lines.get(
                    trace_reduce.MODULE_LINE, ())
                if "paged_step" in name and mark
                and s >= mark[0] and s + d <= mark[1])
            starts = [s for s, _ in steps]
            count += len(steps)
            for name, s, d in lines.get(trace_reduce.OP_LINE, ()):
                if not name.startswith(EXPERT_OPS):
                    continue
                k = bisect.bisect_right(starts, s) - 1
                if k >= 0 and s + d <= steps[k][1]:
                    secs += d / 1e9
        got = (secs, count) if count and secs else None
    run.raw["_expert_device"] = got
    return got


def expert_device_ms(run) -> "float | None":
    got = _expert_device(run)
    return None if got is None else 1e3 * got[0] / got[1]


def expert_product_roofline_share(run) -> "float | None":
    """What the grouped products of a decode tick need (the hit experts'
    kernels once, each pair's row in and out; ``needs_afmoe``) over the
    chip's peaks, against the device time inside them."""
    got = _expert_device(run)
    need = _tick_needs(run)
    if got is None or need is None:
        return None
    hf = run.raw["hf_config"]
    _, _, expert_layers = needs_afmoe._layer_counts(hf)
    least, _ = readers.needs.roofline_seconds(
        expert_layers * needs_afmoe.afmoe_expert_product_flops(
            hf, need["pairs"]),
        expert_layers * needs_afmoe.afmoe_expert_product_bytes(
            hf, need["pairs"], need["experts_hit"]),
        peaks.peak_for(run.device_kind))
    return 100.0 * least / (got[0] / got[1])
