"""Shared arithmetic of the per-layer readers under ``layer_metrics/``.

A reader is ``compute(run) -> float | None``: it takes its number from the
run's spans, counters or reduced device trace, and returns None where its
source is silent (then the harness leaves the metric out of the line).
"""

from __future__ import annotations

import statistics

from benchmark import needs, peaks, trace_reduce


def window_s(run) -> float:
    return run.window[1] - run.window[0]


def spans(run, *names: str, **attrs) -> "list[dict]":
    return [s for s in run.spans if s["name"] in names
            and all(s["args"].get(k) == v for k, v in attrs.items())]


def device_idle_share(run) -> "float | None":
    s = run.trace_summary
    if not s:
        return None
    return 100.0 * (1.0 - s["busy_s"] / s["window_s"])


# -- serve -----------------------------------------------------------------------

def batch_occupancy(run) -> "float | None":
    ticks = spans(run, "serving.decode_step")
    if not ticks:
        return None
    return (100.0 * statistics.fmean(s["args"]["slots"] for s in ticks)
            / run.raw["n_slots"])


def _decode_device(run) -> "tuple[float, int] | None":
    """Device seconds and count of the decode program's WHOLE executions in
    the traced stretch: one cut by an edge of the stretch would add a part
    of a tick to the seconds and a whole tick to the count."""
    if not run.trace_summary:
        return None
    secs, count = trace_reduce.program_seconds(run.trace_summary,
                                               "paged_step", whole=True)
    return (secs, count) if count else None


def decode_device_ms(run) -> "float | None":
    got = _decode_device(run)
    return None if got is None else 1e3 * got[0] / got[1]


def live_contexts(run, n: int = 200) -> "tuple[float, float] | None":
    """Mean rows in the engine and mean tokens in their contexts over the
    traced stretch, from the benchmark's own records of each request (sent,
    resolved, prompt tokens, output tokens): a request's context grows
    evenly from its prompt to prompt + output over its life."""
    lives = run.raw.get("lives") or []
    if not lives or not run.traced_window:
        return None
    t0, t1 = run.traced_window
    rows = tokens = 0.0
    for k in range(n):
        t = t0 + (k + 0.5) * (t1 - t0) / n
        for sent, resolved, n_prompt, n_out in lives:
            if sent <= t < resolved:
                rows += 1
                tokens += n_prompt + n_out * (t - sent) / (resolved - sent)
    return rows / n, tokens / n


def decode_roofline_share(run) -> "float | None":
    """Bytes a decode tick needs (the parameters once, the K/V of every
    token in the live rows' contexts once, one new column per row) over the
    peak bandwidth, against the decode program's device time, both over the
    traced stretch."""
    got = _decode_device(run)
    live = live_contexts(run)
    if got is None or live is None or live[0] <= 0:
        return None
    hf = run.raw["hf_config"]
    rows, tokens = live
    least, _ = needs.roofline_seconds(
        needs.gpt2_decode_flops(hf, rows, tokens),
        needs.gpt2_decode_bytes(hf, rows, tokens),
        peaks.peak_for(run.device_kind))
    return 100.0 * least / (got[0] / got[1])
