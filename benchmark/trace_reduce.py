"""From a profiler trace to numbers: device busy and idle time, time per program,
the kinds of operation that took most of the device, and the idle time by what
the host was doing in it.

The reduction works on a plain structure (``planes``: a list of ``{"name",
"lines": [{"name", "events": [(name, start_ns, dur_ns), ...]}]}``) so that it
can be checked on a small trace built by hand; :func:`load_xplane` makes that
structure from an ``.xplane.pb`` with nothing but jax.

What the planes look like on the TPU v5e of this installation is written
down in PERF.md ("Reading a trace"); the names matched here come from there.
"""

from __future__ import annotations

import bisect
import heapq
import itertools
import os
import re

#: a device plane, the line holding one event per executed operation, and
#: the line holding one event per executed program (jitted module)
DEVICE_PLANE_PREFIX = "/device:TPU:"
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
#: host annotations the harness writes so that clocks can be aligned
WINDOW_MARK = "benchmark.trace_window"
#: who owns the idle time that no host span covers
NO_SPAN = "no span"
_REMARK = re.compile(r"/\*.*?\*/")


def find_xplane(trace_dir: str) -> str:
    for root, _, files in os.walk(trace_dir):
        for f in files:
            if f.endswith(".xplane.pb"):
                return os.path.join(root, f)
    raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")


def load_xplane(path: str, everything: bool = False) -> "list[dict]":
    """The planes of an ``.xplane.pb`` as the plain structure above. Host
    planes can hold millions of runtime events; unless ``everything`` is
    asked for (a by-hand look), only the device planes' operation and
    program lines are kept, and of the host planes only the harness's own
    annotation."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        device = plane.name.startswith(DEVICE_PLANE_PREFIX)
        lines = []
        for line in plane.lines:
            if everything or (device and line.name in (OP_LINE, MODULE_LINE)):
                events = [(ev.name, int(ev.start_ns), int(ev.duration_ns))
                          for ev in line.events]
            elif device:
                continue
            else:
                events = [(ev.name, int(ev.start_ns), int(ev.duration_ns))
                          for ev in line.events if ev.name == WINDOW_MARK]
                if not events:
                    continue
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def describe(planes: "list[dict]", top: int = 8) -> str:
    """A by-hand look: every plane and line with its event count and its
    most frequent event names."""
    out = []
    for p in planes:
        out.append(f"plane {p['name']!r}")
        for ln in p["lines"]:
            names: "dict[str, int]" = {}
            for name, _, _ in ln["events"]:
                names[name] = names.get(name, 0) + 1
            common = sorted(names.items(), key=lambda kv: -kv[1])[:top]
            out.append(f"  line {ln['name']!r}: {len(ln['events'])} events; "
                       + ", ".join(f"{n[:60]} x{c}" for n, c in common))
    return "\n".join(out)


def _union(intervals: "list[tuple[int, int]]") -> "list[tuple[int, int]]":
    merged: "list[list[int]]" = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _clip(events, w0: int, w1: int):
    for name, s, d in events:
        s2, e2 = max(s, w0), min(s + d, w1)
        if e2 > s2:
            yield name, s2, e2


def find_mark(planes: "list[dict]", mark: str = WINDOW_MARK):
    """``(start_ns, end_ns)`` of the harness's window annotation on a host
    plane, or None."""
    for p in planes:
        if p["name"].startswith(DEVICE_PLANE_PREFIX):
            continue
        for ln in p["lines"]:
            for name, s, d in ln["events"]:
                if name == mark:
                    return s, s + d
    return None


def _tuple_shape(text: str) -> str:
    """The tuple of result shapes that ``text`` opens with, its
    ``/*index=5*/`` remarks dropped and each run of one shape written once
    with its count: a fusion that slices 19 slabs out of the pool reads
    ``(18x bf16[1,512,16,25,64]{...}, bf16[1,512,16,25,64]{...S(1)})``, not a
    thousand characters."""
    parts, depth, start = [], 0, 1
    for i, ch in enumerate(text):
        depth += (ch in "([{") - (ch in ")]}")
        if (ch == "," and depth == 1) or depth == 0:
            parts.append(_REMARK.sub("", text[start:i]).strip())
            start = i + 1
            if depth == 0:
                break
    runs = [(part, len(list(run))) for part, run in itertools.groupby(parts)]
    return "(" + ", ".join(p if n == 1 else f"{n}x {p}" for p, n in runs) + ")"


def op_kind(name: str) -> str:
    """The kind of a device operation, from the event's name as the trace
    prints it (``%copy.1036 = bf16[50257,1600]{1,0:T(8,128)(2,1)} copy(...)``):
    the opcode as the operation's name spells it, its serial number dropped,
    and the result's shape with its layout (``copy bf16[50257,1600]{1,0:T(8,
    128)(2,1)}``; a tuple of results by :func:`_tuple_shape`). A name that is
    no such line is its own kind less the serial number (``%fusion.1`` is
    ``fusion``)."""
    head, eq, rest = name.partition(" = ")
    stem = head.lstrip("%").split(".", 1)[0]
    if not eq:
        return stem
    if rest.startswith("("):
        return f"{stem} {_tuple_shape(rest)}"
    return f"{stem} {rest.split(' ', 1)[0]}"


def span_cover(spans: "list[tuple[str, int, int]]", window: "tuple[int, int]"
               ) -> "tuple[list[int], list[str], int]":
    """Which of ``spans`` owns each instant of ``window``: the innermost,
    that is the shortest of those with ``start <= t < end``, spans of one
    length in the order they were handed in, :data:`NO_SPAN` where none
    covers it. Returned as ascending instants ``starts`` (the first is the
    window's start) and ``owners``, ``owners[k]`` holding from ``starts[k]``
    to ``starts[k + 1]`` (the last to the window's end); and how many spans
    touch the window at all (the others can own nothing).

    One sweep over the spans' edges in order: a span enters a heap, keyed by
    its length and its place in ``spans``, once an edge has reached its
    start, and leaves from the top once an edge has reached its end. The
    cost is ``spans x log``, however deep they nest."""
    w0, w1 = window
    live = sorted((max(s, w0), e - s, i, min(e, w1), n)
                  for i, (n, s, e) in enumerate(spans)
                  if s < w1 and e > w0 and e > s)
    edges = sorted({w0, *(sp[0] for sp in live),
                    *(sp[3] for sp in live if sp[3] < w1)})
    starts: "list[int]" = []
    owners: "list[str]" = []
    heap: "list[tuple[int, int, int, str]]" = []
    nxt = 0
    for t in edges:
        while nxt < len(live) and live[nxt][0] <= t:
            _, length, i, e, n = live[nxt]
            heapq.heappush(heap, (length, i, e, n))
            nxt += 1
        while heap and heap[0][2] <= t:
            heapq.heappop(heap)
        owner = heap[0][3] if heap else NO_SPAN
        if not owners or owners[-1] != owner:
            starts.append(t)
            owners.append(owner)
    return starts, owners, len(live)


def share_gaps(gaps: "list[tuple[int, int]]", starts: "list[int]",
               owners: "list[str]") -> "dict[str, int]":
    """Nanoseconds of ``gaps`` (each inside the window that ``starts`` and
    ``owners`` of :func:`span_cover` describe) by owner: a gap's time goes to
    each owner by the part of the gap it holds."""
    idle: "dict[str, int]" = {}
    for g0, g1 in gaps:
        k = bisect.bisect_right(starts, g0) - 1
        while g0 < g1:
            end = min(starts[k + 1], g1) if k + 1 < len(starts) else g1
            idle[owners[k]] = idle.get(owners[k], 0) + end - g0
            g0, k = end, k + 1
    return idle


def reduce_trace(planes: "list[dict]", window: "tuple[int, int] | None" = None,
                 host_spans: "list[tuple[str, int, int]] | None" = None,
                 top: int = 10) -> dict:
    """Busy and idle time of the devices inside ``window`` (profiler
    nanoseconds; default: the window annotation, else the span of the device
    events); time and count per program, clipped to the window
    (``programs``) and of the executions that lie wholly inside it
    (``whole_programs``: one cut by either edge is left out of both its
    numbers); the ``top`` KINDS of operation by time (:func:`op_kind`; a row
    is named ``<operations>x <kind> in <executions> runs``); and idle time by
    the innermost of ``host_spans`` (``(name, start_ns, end_ns)`` on the
    profiler's clock) over each part of each gap (:func:`span_cover`).

    Busy is the union of the operation intervals of a device, averaged over
    the devices that ran anything, as are the kinds' seconds and runs and
    the idle time."""
    return reduce_trace_counted(planes, window, host_spans, top)[0]


def reduce_trace_counted(planes: "list[dict]",
                         window: "tuple[int, int] | None" = None,
                         host_spans: "list[tuple[str, int, int]] | None" = None,
                         top: int = 10) -> "tuple[dict, dict]":
    """:func:`reduce_trace`'s result, and beside it the counts that set what
    the reduction costs: device operations inside the window, idle gaps,
    spans handed in and spans that touch the window."""
    devices = [p for p in planes if p["name"].startswith(DEVICE_PLANE_PREFIX)]

    def line(p, name):
        return [ln for ln in p["lines"] if ln["name"] == name]

    if window is None:
        window = find_mark(planes)
    if window is None:
        starts = [s for p in devices for ln in line(p, OP_LINE)
                  for _, s, _ in ln["events"]]
        ends = [s + d for p in devices for ln in line(p, OP_LINE)
                for _, s, d in ln["events"]]
        if not starts:
            raise ValueError("the trace holds no device operation")
        window = (min(starts), max(ends))
    w0, w1 = window
    busy_ns, n_ops = [], 0
    per_op: "dict[str, list]" = {}    # operation -> [runs, ns]
    programs: "dict[str, dict]" = {}
    whole: "dict[str, dict]" = {}

    def tally(recs, name, ns):
        rec = recs.setdefault(name, {"seconds": 0.0, "count": 0})
        rec["seconds"] += ns / 1e9
        rec["count"] += 1

    gaps: "list[tuple[int, int]]" = []
    for p in devices:
        ops = [ev for ln in line(p, OP_LINE)
               for ev in _clip(ln["events"], w0, w1)]
        if not ops:
            continue
        n_ops += len(ops)
        merged = _union([(s, e) for _, s, e in ops])
        busy_ns.append(sum(e - s for s, e in merged))
        for name, s, e in ops:
            rec = per_op.get(name)
            if rec is None:
                rec = per_op[name] = [0, 0]
            rec[0] += 1
            rec[1] += e - s
        for ln in line(p, MODULE_LINE):
            for name, s, d in ln["events"]:
                inside = min(s + d, w1) - max(s, w0)
                if inside > 0:
                    tally(programs, name, inside)
                    if inside == d:
                        tally(whole, name, inside)
        edges = [w0] + [t for s, e in merged for t in (s, e)] + [w1]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    if not busy_ns:
        raise ValueError("no operation ran on a device inside the window")
    spans = list(host_spans or ())
    starts, owners, n_live = span_cover(spans, (w0, w1))
    idle_ns = share_gaps(gaps, starts, owners)
    kinds: "dict[str, list]" = {}    # kind -> [operations, runs, ns]
    for name, (runs, ns) in per_op.items():
        rec = kinds.setdefault(op_kind(name), [0, 0, 0])
        rec[0] += 1
        rec[1] += runs
        rec[2] += ns
    n_dev = len(busy_ns)

    def ranked(d):
        return [[k, v / 1e9 / n_dev] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    counts = {"device_ops": n_ops, "gaps": len(gaps), "spans": len(spans),
              "spans_in_window": n_live}
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": sum(busy_ns) / len(busy_ns) / 1e9,
        "devices": len(busy_ns),
        "programs": programs,
        "whole_programs": whole,
        "device_ops": ranked({f"{n}x {kind} in {runs / n_dev:g} runs": ns
                              for kind, (n, runs, ns) in kinds.items()}),
        "idle_gaps": ranked(idle_ns),
        "longest_gap_s": max((g1 - g0 for g0, g1 in gaps), default=0) / 1e9,
    }, counts


def program_seconds(summary: dict, *needles: str,
                    whole: bool = False) -> "tuple[float, int]":
    """Device seconds and executions of the programs whose name holds any of
    ``needles`` (a jitted function is ``jit_<its name>(<id>)``): every
    execution by its part inside the window, or the ``whole`` ones alone."""
    secs, count = 0.0, 0
    for name, rec in summary["whole_programs" if whole
                             else "programs"].items():
        if any(n in name for n in needles):
            secs += rec["seconds"]
            count += rec["count"]
    return secs, count


if __name__ == "__main__":
    import sys

    print(describe(load_xplane(find_xplane(sys.argv[1]), everything=True)))
