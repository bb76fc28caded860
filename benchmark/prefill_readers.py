"""Readers of the prefill path and of the host's share of the wall clock
(ISSUE 38): what a chunk program costs, per execution and per real prompt
token; how long a request's prefill stood by while the chunk budget went to
other prompts; the share of the window in which the engine thread worked;
and the longest single stretch the device sat idle.

As in ``engine_readers.py`` a reader is ``compute(run) -> float | None`` and
returns None where its spans or the reduced trace are absent, which is what
a program from before ``serving.prefill`` gives the one that reads it. None
of them opens the trace again: they read ``run.spans``, ``run.traced_window``
and ``run.trace_summary`` as they stand.
"""

from __future__ import annotations

from benchmark import engine_readers, readers, trace_reduce

#: what every chunk program's name holds (``jit__chunk_one``, ``_first``,
#: ``_mid``, ``_final``), as ``engine_readers.prefill_device_share`` finds them
CHUNK = "_chunk_"


# -- Models --------------------------------------------------------------------------

def chunk_device_ms(run) -> "float | None":
    """Device time of one chunk program, a mean over the WHOLE executions of
    the traced stretch: one cut by an edge of the stretch would add a part
    of a chunk to the seconds and a whole chunk to the count."""
    if not run.trace_summary:
        return None
    secs, count = trace_reduce.program_seconds(run.trace_summary, CHUNK,
                                               whole=True)
    return 1e3 * secs / count if count else None


def prefill_device_us_per_token(run) -> "float | None":
    """Device seconds of the chunk programs inside the traced stretch (as
    ``prefill_device_share`` takes them) over the REAL prompt tokens the
    engine dispatched in it: ``tokens`` of the ``serving.prefill_chunk``
    spans that start inside the stretch, one span a program. (A
    sequence-parallel chunk is ``jit__sp_chunk``, which ``CHUNK`` does not
    match: neither its seconds nor its span's tokens are taken.) The pad of
    a chunk's width, and logits at positions nobody reads, are device time
    and no token: they show as cost. The stretch cuts the programs by the
    device's clock and the spans by the host's, a dispatch ahead of it: a
    chunk at either edge can fall on one side only, one or two of the few
    dozen a stretch holds."""
    if not run.trace_summary or not run.traced_window:
        return None
    t0, t1 = run.traced_window
    tokens = sum(s["args"].get("tokens", 0)
                 for s in readers.spans(run, "serving.prefill_chunk")
                 if t0 <= s["t0"] <= t1)
    if not tokens:
        return None
    secs, _ = trace_reduce.program_seconds(run.trace_summary, CHUNK)
    return 1e6 * secs / tokens


# -- Serving engine ------------------------------------------------------------------

def prefill_turn_wait_ms(run) -> "float | None":
    """Mean, over the paged prefills that ended inside the window, of the
    part of ``serving.prefill`` spent in ticks that gave the request no
    chunk: ``duration x (ticks - chunks) / ticks``. A mean and not a median:
    where most prompts fit one tick's budget (GPT-2 XL's, MiMo's) the median
    is 0 whatever the few that wait pay. It takes a request's ticks for
    equally long; one that dispatches a chunk is the longer, so the wait is
    read a little high. (The dense layout's span of that name has no
    ``ticks``: a dense prefill waits for nobody.)"""
    waited = [(s["t1"] - s["t0"])
              * (s["args"]["ticks"] - s["args"]["chunks"]) / s["args"]["ticks"]
              for s in engine_readers.ending_in_window(run, "serving.prefill")
              if s["args"].get("ticks")]
    return 1e3 * sum(waited) / len(waited) if waited else None


# -- Dispatch + completion -----------------------------------------------------------

def host_busy_share(run) -> "float | None":
    """Share (%) of the window's wall clock in which the engine thread
    worked and did not wait for a program: the sum that ``tick_host_self_ms``
    averages (its own walk, called and not copied) over the window."""
    mean_ms = engine_readers.tick_host_self_ms(run)
    if mean_ms is None:
        return None
    ticks = len(engine_readers.ending_in_window(run, "serving.tick"))
    return 100.0 * (mean_ms / 1e3) * ticks / readers.window_s(run)


# -- Device --------------------------------------------------------------------------

def idle_longest_gap_ms(run) -> "float | None":
    """The longest single stretch of the traced seconds in which no
    operation ran on a device: one stall reads here as itself, where
    ``device_idle_share`` spreads it over the stretch."""
    if not run.trace_summary:
        return None
    return 1e3 * run.trace_summary["longest_gap_s"]
