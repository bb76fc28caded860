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


def _overlap(s: dict, w: "tuple[float, float]") -> float:
    return max(0.0, min(s["t1"], w[1]) - max(s["t0"], w[0]))


def span_share(run, *names: str) -> "float | None":
    """Share (%) of the window spent inside the named spans."""
    found = spans(run, *names)
    if not run.spans:
        return None
    return 100.0 * sum(_overlap(s, run.window) for s in found) / window_s(run)


def device_idle_share(run) -> "float | None":
    s = run.trace_summary
    if not s:
        return None
    return 100.0 * (1.0 - s["busy_s"] / s["window_s"])


def traced_spans(run, *names: str, **attrs) -> "list[dict]":
    """The named spans that START inside the traced stretch of the window."""
    if not run.traced_window:
        return []
    t0, t1 = run.traced_window
    return [s for s in spans(run, *names, **attrs) if t0 <= s["t0"] < t1]


# -- serve -----------------------------------------------------------------------

def batch_occupancy(run) -> "float | None":
    ticks = spans(run, "serving.decode_step")
    if not ticks:
        return None
    return (100.0 * statistics.fmean(s["args"]["slots"] for s in ticks)
            / run.raw["n_slots"])


def prefill_share(run) -> "float | None":
    return span_share(run, "serving.prefill", "serving.prefill_chunk")


def _decode_device(run) -> "tuple[float, int] | None":
    if not run.trace_summary:
        return None
    secs, count = trace_reduce.program_seconds(run.trace_summary,
                                               "paged_step")
    return (secs, count) if count else None


def decode_device_ms(run) -> "float | None":
    got = _decode_device(run)
    return None if got is None else 1e3 * got[0] / got[1]


def tick_host_ms(run) -> "float | None":
    """The mean decode tick as the host saw it, less the decode program's
    mean device time, both over the traced stretch (the decode program's
    cost changes with the depth it gathers, so the two are taken over the
    same ticks): dispatch, token read-back and the engine's bookkeeping."""
    ticks = traced_spans(run, "serving.decode_step")
    dev = decode_device_ms(run)
    if not ticks or dev is None:
        return None
    return 1e3 * statistics.fmean(s["t1"] - s["t0"] for s in ticks) - dev


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
