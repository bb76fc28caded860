"""Shared arithmetic of the per-layer readers that read the serving engine's
own span tree (``serving.tick`` and what lies inside it, ``xla.*`` from the
compile listener): PERF.md section 3 says which span each metric reads.

As in ``readers.py`` a reader is ``compute(run) -> float | None`` and returns
None where its spans are absent, which is what a program from before these
spans gives. ``run.spans`` holds the spans that touch the window; the set-up
readers need what came BEFORE it and read the program's ring themselves.
"""

from __future__ import annotations

import statistics
import time

from benchmark import readers, trace_reduce, traffic

#: the program keeps its last 100,000 finished spans; a ring that has wrapped
#: has lost the start of the run and is never read as a short set-up
RING_EVENTS = 100_000


def _dur(s: dict) -> float:
    return s["t1"] - s["t0"]


def ending_in_window(run, *names: str) -> "list[dict]":
    """The named spans that END inside the window: the instant a span ends
    is the instant its tokens, or its wait, were over."""
    w0, w1 = run.window
    return [s for s in readers.spans(run, *names) if w0 <= s["t1"] <= w1]


# -- Serving engine ------------------------------------------------------------------

def engine_tokens_per_s(run) -> "float | None":
    """Tokens the engine appended to live requests inside the window, by its
    own count: ``tokens`` of every retire (or speculative verify) that ended
    in it, and one for every first token read in it."""
    made = ending_in_window(run, "serving.retire", "serving.spec_verify")
    if not made:
        return None
    firsts = ending_in_window(run, "serving.first_token")
    return ((sum(s["args"].get("tokens", 0) for s in made) + len(firsts))
            / readers.window_s(run))


def queue_wait_ms(run) -> "float | None":
    waits = ending_in_window(run, "serving.queue_wait")
    return 1e3 * statistics.median(map(_dur, waits)) if waits else None


def first_token_ms(run) -> "float | None":
    """Median, over the requests whose first token reached the host inside
    the window, of that instant less the instant the request was enqueued
    (the start of its ``serving.queue_wait``). A request enqueued before the
    window's spans begin is left out."""
    enqueued = {s["args"].get("request_id"): s["t0"]
                for s in readers.spans(run, "serving.queue_wait")}
    took = [s["t1"] - enqueued[s["args"].get("request_id")]
            for s in ending_in_window(run, "serving.first_token")
            if s["args"].get("request_id") in enqueued]
    return 1e3 * statistics.median(took) if took else None


def token_times(run) -> "dict[int, list[float]]":
    """When each token of each request reached the host, by request id: the
    first at the end of its ``serving.first_token``, each later one at the
    end of a ``serving.decode_step`` that links the request (a chained step's
    ``chain`` tokens share its end)."""
    times: "dict[int, list[float]]" = {}
    for s in readers.spans(run, "serving.first_token"):
        times.setdefault(s["args"].get("request_id"), []).append(s["t1"])
    for s in readers.spans(run, "serving.decode_step"):
        for rid in s["args"].get("links", ()):
            times.setdefault(rid, []).extend(
                [s["t1"]] * int(s["args"].get("chain", 1)))
    return {rid: sorted(ts) for rid, ts in times.items()}


def token_gap_p95_ms(run) -> "float | None":
    """95th percentile of the gaps between successive tokens of one request,
    over the gaps that closed inside the window."""
    if not readers.spans(run, "serving.first_token"):
        return None
    w0, w1 = run.window
    gaps = [b - a for ts in token_times(run).values()
            for a, b in zip(ts, ts[1:]) if w0 <= b <= w1]
    return 1e3 * traffic.percentile(gaps, 95) if gaps else None


# -- Models --------------------------------------------------------------------------

def prefill_device_share(run) -> "float | None":
    """Share (%) of the traced stretch in which the device ran a prefill
    chunk program (``jit__chunk_one``, ``_first``, ``_mid``, ``_final``);
    0 when no prompt was prefilled in those seconds."""
    if not run.trace_summary:
        return None
    secs, _ = trace_reduce.program_seconds(run.trace_summary, "_chunk_")
    return 100.0 * secs / run.trace_summary["window_s"]


# -- Dispatch + completion -----------------------------------------------------------

def tick_dispatch_ms(run) -> "float | None":
    found = readers.spans(run, "serving.decode_dispatch")
    return 1e3 * statistics.fmean(map(_dur, found)) if found else None


def tick_host_self_ms(run) -> "float | None":
    """Mean over the ticks that ended inside the window of the tick less the
    spans inside it in which the host only waited for a program to finish
    (``serving.decode_wait``, ``serving.first_token``). ``fetch.wait``, a copy
    the host asked for, stays the host's."""
    ticks = ending_in_window(run, "serving.tick")
    if not ticks:
        return None
    waits = sorted(readers.spans(run, "serving.decode_wait",
                                 "serving.first_token"),
                   key=lambda s: s["t0"])
    total, i = 0.0, 0
    for tick in sorted(ticks, key=lambda s: s["t0"]):
        while i < len(waits) and waits[i]["t0"] < tick["t0"]:
            i += 1
        waited, j = 0.0, i
        while j < len(waits) and waits[j]["t1"] <= tick["t1"]:
            waited += _dur(waits[j])
            j += 1
        i = j
        total += _dur(tick) - waited
    return 1e3 * total / len(ticks)


# -- Compile + load ------------------------------------------------------------------

def compile_spans(run) -> "list[tuple[str, float, float]] | None":
    """``(name, end, seconds)`` of every ``xla.*`` span the program recorded
    from the start of the process to the end of the window, ``end`` in
    ``time.monotonic()`` seconds as ``harness.program_spans`` maps them. None
    where the ring has wrapped, and where it holds no ``xla.compile`` span in
    that time: a program with no compile listener."""
    from sparkdl_tpu.observability import tracing

    if run.window is None:
        return None
    events = tracing.trace_events()
    if len(events) >= RING_EVENTS:
        return None
    epoch = time.monotonic() - tracing.trace_clock_us() / 1e6
    found = [(ev["name"], epoch + (ev["ts"] + ev["dur"]) / 1e6, ev["dur"] / 1e6)
             for ev in events if ev["name"].startswith("xla.")]
    found = [f for f in found if run.t_process <= f[1] <= run.window[1]]
    return found if any(n == "xla.compile" for n, _, _ in found) else None


def _before_window(run, *names: str) -> "list[float] | None":
    found = compile_spans(run)
    if found is None:
        return None
    return [dur for n, end, dur in found
            if n in names and end <= run.window[0]]


def _setup_seconds(run, *names: str) -> "float | None":
    """Seconds inside the named ``xla.*`` spans that ended during set-up."""
    durs = _before_window(run, *names)
    return None if durs is None else sum(durs)


def setup_trace_lower_s(run) -> "float | None":
    return _setup_seconds(run, "xla.trace", "xla.lower")


def setup_compile_load_s(run) -> "float | None":
    """``xla.compile`` alone: jax times the cache's part inside it."""
    return _setup_seconds(run, "xla.compile")


def setup_cache_load_s(run) -> "float | None":
    return _setup_seconds(run, "xla.cache_load")


def setup_programs(run) -> "float | None":
    durs = _before_window(run, "xla.compile")
    return None if durs is None else len(durs)


def window_compiles(run) -> "float | None":
    found = compile_spans(run)
    if found is None:
        return None
    return sum(1 for n, end, _ in found
               if n == "xla.compile" and end >= run.window[0])
