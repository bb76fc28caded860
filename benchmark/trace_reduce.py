"""From a profiler trace to numbers: device busy and idle time, time per program,
the operations that took most of the device, and the idle gaps by what the host
was doing in them.

The reduction works on a plain structure (``planes``: a list of ``{"name",
"lines": [{"name", "events": [(name, start_ns, dur_ns), ...]}]}``) so that it
can be checked on a small trace built by hand; :func:`load_xplane` makes that
structure from an ``.xplane.pb`` with nothing but jax.

What the planes look like on the TPU v5e of this installation is written
down in PERF.md ("Reading a trace"); the names matched here come from there.
"""

from __future__ import annotations

import heapq
import os

#: a device plane, the line holding one event per executed operation, and
#: the line holding one event per executed program (jitted module)
DEVICE_PLANE_PREFIX = "/device:TPU:"
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
#: host annotations the harness writes so that clocks can be aligned
WINDOW_MARK = "benchmark.trace_window"


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


def gap_owners(mids: "list[int]", spans: "list[tuple[str, int, int]]",
               window: "tuple[int, int]") -> "tuple[list[str], int]":
    """For each of ``mids`` (instants inside ``window``) the name of the
    shortest of ``spans`` with ``start <= mid < end``, spans of one length in
    the order they were handed in, ``"no span"`` where none covers it; and
    how many spans touch the window at all (the others can own nothing).

    One sweep over the instants in order: a span enters a heap, keyed by its
    length and its place in ``spans``, once an instant has reached its start,
    and leaves from the top once an instant has reached its end. The cost is
    ``(instants + spans) x log``, however many spans cover each instant."""
    w0, w1 = window
    live = sorted((s, e - s, i, e, n) for i, (n, s, e) in enumerate(spans)
                  if s < w1 and e > w0)
    owners = ["no span"] * len(mids)
    heap: "list[tuple[int, int, int, str]]" = []
    nxt = 0
    for k in sorted(range(len(mids)), key=mids.__getitem__):
        mid = mids[k]
        while nxt < len(live) and live[nxt][0] <= mid:
            _, length, i, e, n = live[nxt]
            heapq.heappush(heap, (length, i, e, n))
            nxt += 1
        while heap and heap[0][2] <= mid:
            heapq.heappop(heap)
        if heap:
            owners[k] = heap[0][3]
    return owners, len(live)


def reduce_trace(planes: "list[dict]", window: "tuple[int, int] | None" = None,
                 host_spans: "list[tuple[str, int, int]] | None" = None,
                 top: int = 10) -> dict:
    """Busy and idle time of the devices inside ``window`` (profiler
    nanoseconds; default: the window annotation, else the span of the device
    events), time and count per program, the ``top`` operations by time, and
    idle time by the innermost of ``host_spans`` (``(name, start_ns,
    end_ns)`` on the profiler's clock) that covers each gap's middle.

    Busy is the union of the operation intervals of a device, averaged over
    the devices that ran anything."""
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
    busy_ns, op_ns, programs, n_ops = [], {}, {}, 0
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
            op_ns[name] = op_ns.get(name, 0) + (e - s)
        for ln in line(p, MODULE_LINE):
            for name, s, e in _clip(ln["events"], w0, w1):
                rec = programs.setdefault(name, {"seconds": 0.0, "count": 0})
                rec["seconds"] += (e - s) / 1e9
                rec["count"] += 1
        edges = [w0] + [t for s, e in merged for t in (s, e)] + [w1]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    if not busy_ns:
        raise ValueError("no operation ran on a device inside the window")
    idle_by: "dict[str, float]" = {}
    spans = list(host_spans or ())
    owners, n_live = gap_owners([(g0 + g1) // 2 for g0, g1 in gaps], spans,
                                (w0, w1))
    for (g0, g1), owner in zip(gaps, owners):
        idle_by[owner] = idle_by.get(owner, 0.0) + (g1 - g0) / 1e9 / len(busy_ns)

    def ranked(d):
        return [[k, v] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    counts = {"device_ops": n_ops, "gaps": len(gaps), "spans": len(spans),
              "spans_in_window": n_live}
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": sum(busy_ns) / len(busy_ns) / 1e9,
        "devices": len(busy_ns),
        "programs": programs,
        "device_ops": ranked({k: v / 1e9 / len(busy_ns)
                              for k, v in op_ns.items()}),
        "idle_gaps": ranked(idle_by),
        "longest_gap_s": max((g1 - g0 for g0, g1 in gaps), default=0) / 1e9,
    }, counts


def program_seconds(summary: dict, *needles: str) -> "tuple[float, int]":
    """Device seconds and executions of the programs whose name holds any of
    ``needles`` (a jitted function is ``jit_<its name>(<id>)``)."""
    secs, count = 0.0, 0
    for name, rec in summary["programs"].items():
        if any(n in name for n in needles):
            secs += rec["seconds"]
            count += rec["count"]
    return secs, count


if __name__ == "__main__":
    import sys

    print(describe(load_xplane(find_xplane(sys.argv[1]), everything=True)))
