"""Shared arithmetic of the per-layer readers of the ``olmo_hybrid`` cells
(suffix ``.hybrid``): what a hybrid family's decode tick and prefill chunk
need against what they took, and how much of each lay inside the recurrence.

As in ``readers.py`` a reader is ``compute(run) -> float | None`` and returns
None where its source is silent: a program whose ``serving.decode_step``
spans carry no ``state_rows`` (any before the PR that added the family), a
run with no device trace, a trace none of whose operations carries the
recurrence's scope.

**How the recurrence is found in a trace.** The program computes both forms
of the gated delta rule under a named scope (``gated_delta_step``,
``gated_delta_scan``: ``sparkdl_tpu/models/olmo_hybrid.py``), which the
compiled text carries in each instruction's ``op_name``. This installation's
device events do NOT: an ``XLA Ops`` event holds its instruction's text
without metadata, and its only stats are ``device_offset_ps``,
``device_duration_ps`` and ``Time Scale Multiplier`` (``tools/
trace_event_probe.py`` prints them). So the recurrence's operations are found
by what no other part of the program computes on, their SHAPES
(:func:`is_step_op`, :func:`is_scan_op`): the state ``[.., heads, d_k, d_v]``
in a decode step; in a chunk program the state, and the arrays split into
sub-chunks, whose result has the heads and the sub-chunk's 64 among its
axes and no axis that is not one of 1, the count of sub-chunks (at most 8),
64, ``d_k``, ``d_v`` or ``d_k + d_v``. That takes in the re-layouts
into and out of the split form (they are the chunkwise form's own cost) and,
for a chunk of exactly 64 tokens, the few elementwise operations that make
q, k and v (the same axes): a later PR that makes the recurrence a kernel
gives it a name and these two functions a line each.
"""

from __future__ import annotations

import bisect
import os
import re
import statistics

from benchmark import needs_olmo_hybrid as needs_h
from benchmark import peaks, readers, trace_reduce



def _ticks(run, traced: bool = False) -> "list[dict]":
    """The window's decode ticks that carry state counters (those of the
    traced stretch alone if ``traced``)."""
    ticks = [s for s in readers.spans(run, "serving.decode_step")
             if "state_rows" in s["args"]]
    if traced:
        if not run.traced_window:
            return []
        t0, t1 = run.traced_window
        ticks = [s for s in ticks if s["t0"] >= t0 and s["t1"] <= t1]
    return ticks


def _chunks(run) -> "list[dict]":
    """The traced stretch's prefill chunks that carry scan counters."""
    if not run.traced_window:
        return []
    t0, t1 = run.traced_window
    return [s for s in readers.spans(run, "serving.prefill_chunk")
            if "scan_tokens" in s["args"] and s["t0"] >= t0 and s["t1"] <= t1]


def kv_cols_read_over_live(run) -> "float | None":
    """K/V columns the decode ticks gathered through the table over the
    columns of live rows' contexts, in the layers that keep K/V (the same
    ratio in each): what grouping rows by length could win."""
    ticks = _ticks(run)
    live = sum(s["args"]["kv_cols_live"] for s in ticks)
    if not live:
        return None
    return sum(s["args"]["kv_cols_read"] for s in ticks) / live


def _tick_needs(run) -> "dict | None":
    ticks = [s for s in _ticks(run, traced=True) if s["args"]["chain"] == 1]
    if not ticks:
        return None
    return {"rows": statistics.fmean(s["args"]["slots"] for s in ticks),
            "tokens_full": statistics.fmean(
                s["args"]["kv_cols_live"] for s in ticks),
            "state_bytes": statistics.fmean(
                s["args"]["state_bytes"] for s in ticks)}


def decode_roofline_share(run) -> "float | None":
    """What a decode tick needs (``needs_olmo_hybrid``) over the chip's
    peaks, against the decode program's device time, both over the traced
    stretch."""
    got = readers._decode_device(run)
    need = _tick_needs(run)
    if got is None or need is None:
        return None
    hf = run.raw["hf_config"]
    least, _ = readers.needs.roofline_seconds(
        needs_h.hybrid_call_flops(hf, need["rows"], need["tokens_full"]),
        needs_h.hybrid_call_bytes(hf, need["rows"], need["tokens_full"]),
        peaks.peak_for(run.device_kind))
    return 100.0 * least / (got[0] / got[1])


_SHAPE = re.compile(r"\w+\[([\d,]*)\]")


def _result_dims(event_name: str) -> "list[tuple[int, ...]]":
    """The dimensions of each array an instruction makes, from the event's
    name (``%fusion.7 = f32[1,30,96,192]{...} fusion(...)``; a tuple of
    results gives several)."""
    made = event_name.split(" = ", 1)[-1]
    depth = 0
    for i, ch in enumerate(made):
        depth += (ch in "([{") - (ch in ")]}")
        if ch == " " and depth == 0:
            made = made[:i]
            break
    return [tuple(int(d) for d in m.split(",") if d)
            for m in _SHAPE.findall(made)]


def _sizes(hf: dict) -> "tuple[int, int, int]":
    s = needs_h.hybrid_sizes(hf)
    return s["lin_heads"], s["dk"], s["dv"]


def is_step_op(event_name: str, hf: dict) -> bool:
    """An operation of a decode step that reads or writes the recurrent
    state: ``[.., heads, d_k, d_v]`` among its operands or results."""
    h, dk, dv = _sizes(hf)
    return f"{h},{dk},{dv}]" in event_name


def is_scan_op(event_name: str, hf: dict, sub: int = 64) -> bool:
    """An operation of a chunk program inside the chunkwise recurrence (the
    module docstring has the rule)."""
    h, dk, dv = _sizes(hf)
    for dims in _result_dims(event_name):
        if dims[-3:] == (h, dk, dv):
            return True
        if h in dims and sub in dims:
            rest = [d for d in dims if d not in (1, h, sub, dk, dv, dk + dv)]
            # what is left is the count of sub-chunks, once
            if not rest or (len(rest) == 1 and rest[0] <= 8):
                return True
    return False


def _op_device(run, is_op, program: str) -> "tuple[float, int] | None":
    """Device seconds inside the operations ``is_op`` picks, of the WHOLE
    executions in the traced stretch of the programs whose name holds
    ``program``, and the count of those executions. The reduced trace keeps
    ten kinds of operation only, so the run's own trace (which the harness
    keeps) is opened again."""
    key = f"_op_device:{is_op.__name__}:{program}"
    if key in run.raw:
        return run.raw[key]
    got = None
    trace_dir = os.path.join(run.cell.root, ".benchmark_runs",
                             "trace-" + run.cell.name)
    if run.trace_summary and os.path.isdir(trace_dir):
        hf = run.raw["hf_config"]
        planes = trace_reduce.load_xplane(trace_reduce.find_xplane(trace_dir))
        mark = trace_reduce.find_mark(planes)
        secs, count = 0.0, 0
        for p in planes:
            if not p["name"].startswith(trace_reduce.DEVICE_PLANE_PREFIX):
                continue
            lines = {ln["name"]: ln["events"] for ln in p["lines"]}
            runs = sorted(
                (s, s + d) for name, s, d in lines.get(
                    trace_reduce.MODULE_LINE, ())
                if program in name and mark
                and s >= mark[0] and s + d <= mark[1])
            starts = [s for s, _ in runs]
            count += len(runs)
            for name, s, d in lines.get(trace_reduce.OP_LINE, ()):
                k = bisect.bisect_right(starts, s) - 1
                if k >= 0 and s + d <= runs[k][1] and is_op(name, hf):
                    secs += d / 1e9
        got = (secs, count) if count and secs else None
    run.raw[key] = got
    return got


def delta_step_device_ms(run) -> "float | None":
    """Device ms of a decode tick inside the one-token state updates of its
    linear layers."""
    got = _op_device(run, is_step_op, "paged_step")
    return None if got is None else 1e3 * got[0] / got[1]


def delta_step_roofline_share(run) -> "float | None":
    """What the state updates of a tick must move (each live row's state in
    and out of every linear layer: ``state_bytes``, as the engine counts it
    on the tick's span) over the chip's bandwidth, against the device time
    inside them."""
    got = _op_device(run, is_step_op, "paged_step")
    need = _tick_needs(run)
    if got is None or need is None:
        return None
    hf = run.raw["hf_config"]
    least, _ = readers.needs.roofline_seconds(
        needs_h.hybrid_delta_step_flops(hf, need["rows"]),
        need["state_bytes"], peaks.peak_for(run.device_kind))
    return 100.0 * least / (got[0] / got[1])


def delta_scan_device_ms(run) -> "float | None":
    """Device ms of a prefill chunk inside the chunkwise recurrences of its
    linear layers."""
    got = _op_device(run, is_scan_op, "_chunk_")
    return None if got is None else 1e3 * got[0] / got[1]


def delta_scan_roofline_share(run) -> "float | None":
    """What the gated delta rule over the traced stretch's ``scan_tokens``
    needs (``needs_olmo_hybrid``: the rule's own products a token, the
    running state in and out once a chunk) over the chip's peaks, against
    the device time inside the chunkwise recurrences."""
    got = _op_device(run, is_scan_op, "_chunk_")
    chunks = _chunks(run)
    if got is None or not chunks:
        return None
    hf = run.raw["hf_config"]
    tokens = sum(s["args"]["scan_tokens"] for s in chunks)
    least, _ = readers.needs.roofline_seconds(
        needs_h.hybrid_delta_scan_flops(hf, tokens),
        needs_h.hybrid_delta_scan_bytes(hf, tokens, len(chunks)),
        peaks.peak_for(run.device_kind))
    # the spans' chunks and the trace's whole executions are the same
    # chunks but for one at either edge: per chunk on both sides
    return 100.0 * (least / len(chunks)) / (got[0] / got[1])
