"""The trace reduction against a plain oracle: idle time shared out over the
host spans that cover it, device time summed by kind of operation, programs
counted whole and clipped; at a cost that does not grow with gaps x spans.

``oracle_reduce_trace`` says the same thing the slow way: for every gap it
walks every span (PR 24's loop did; it gave a whole gap to the shortest span
over its MIDDLE, and since PR 28 each part of a gap goes to the shortest
span over that part), and it looks a kind up in the table the random names
were drawn from. The tests hold the sweep to it on seeded random traces, key
for key, and to one wall-clock ceiling on a trace the size a 21 ms tick
leaves. Pure Python, no jax, no child process.
"""

import json
import time

import numpy as np
import pytest

from benchmark import trace_reduce
from benchmark.trace_reduce import (DEVICE_PLANE_PREFIX, MODULE_LINE, OP_LINE,
                                    _clip, _union, find_mark)

#: operation names as the chip's trace prints them, and the kind of each:
#: the serial number is no part of a kind, the result's shape and layout are
#: (a tuple of results with each run of one shape written once)
KINDS = {
    "%fusion.11 = bf16[8,1600]{1,0:T(8,128)(2,1)} fusion(bf16[8,1600]{1,0} "
    "%p.1), kind=kLoop, calls=%fused.11": "fusion bf16[8,1600]{1,0:T(8,128)(2,1)}",
    "%fusion.12 = bf16[8,1600]{1,0:T(8,128)(2,1)} fusion(bf16[8,6400]{1,0} "
    "%p.2), kind=kLoop, calls=%fused.12": "fusion bf16[8,1600]{1,0:T(8,128)(2,1)}",
    "%fusion.13 = bf16[8,1600]{1,0} fusion(bf16[8,1600]{1,0} %p.3), "
    "kind=kLoop": "fusion bf16[8,1600]{1,0}",
    "%fusion.2951 = (bf16[1,512,16,25,64]{1,4,3,2,0:T(8,128)(2,1)}, "
    "bf16[1,512,16,25,64]{1,4,3,2,0:T(8,128)(2,1)}) fusion(bf16[48,512,16,25,"
    "64]{1,4,3,2,0:T(8,128)(2,1)} %pool), kind=kLoop":
        "fusion (2x bf16[1,512,16,25,64]{1,4,3,2,0:T(8,128)(2,1)})",
    "%fusion.2953 = (bf16[1,512,16,25,64]{1,4,3,2,0:T(8,128)(2,1)}, "
    "bf16[1,512,16,25,64]{1,4,3,2,0:T(8,128)(2,1)}) fusion(bf16[48,512,16,25,"
    "64]{1,4,3,2,0:T(8,128)(2,1)} %pool), kind=kLoop":
        "fusion (2x bf16[1,512,16,25,64]{1,4,3,2,0:T(8,128)(2,1)})",
    "%copy.1036 = bf16[1,512,16,25,64]{4,3,2,1,0} copy(bf16[1,512,16,25,64]"
    "{1,4,3,2,0:T(8,128)(2,1)} %slab.1)": "copy bf16[1,512,16,25,64]{4,3,2,1,0}",
    "%copy.1037 = bf16[1,512,16,25,64]{4,3,2,1,0} copy(bf16[1,512,16,25,64]"
    "{1,4,3,2,0:T(8,128)(2,1)} %slab.2)": "copy bf16[1,512,16,25,64]{4,3,2,1,0}",
    "%copy.5 = f32[8]{0} copy(f32[8]{0} %x)": "copy f32[8]{0}",
    "%multiply_reduce_fusion.3 = f32[8,1024,25]{2,1,0} fusion(f32[8] %q)":
        "multiply_reduce_fusion f32[8,1024,25]{2,1,0}",
    "%while.14 = (s32[]{:T(128)}, bf16[48,512]{1,0}, bf16[48,512]{1,0}, "
    "/*index=3*/bf16[8]{0:T(8,128)(2,1)S(1)}, s32[]{:T(128)}) while((s32[], "
    "bf16[48,512]) %t), body=%b":
        "while (s32[]{:T(128)}, 2x bf16[48,512]{1,0}, "
        "bf16[8]{0:T(8,128)(2,1)S(1)}, s32[]{:T(128)})",
    "%op.7": "op", "%op.9.clone": "op", "plain": "plain",
}
NAMES = sorted(KINDS)


def oracle_reduce_trace(planes, window=None, host_spans=None, top=10):
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
    busy_ns, programs, whole = [], {}, {}
    kinds: "dict[str, dict]" = {}
    gaps: "list[tuple[int, int]]" = []
    for p in devices:
        ops = [ev for ln in line(p, OP_LINE)
               for ev in _clip(ln["events"], w0, w1)]
        if not ops:
            continue
        merged = _union([(s, e) for _, s, e in ops])
        busy_ns.append(sum(e - s for s, e in merged))
        for name, s, e in ops:
            rec = kinds.setdefault(KINDS[name],
                                   {"names": set(), "runs": 0, "ns": 0})
            rec["names"].add(name)
            rec["runs"] += 1
            rec["ns"] += e - s
        for ln in line(p, MODULE_LINE):
            for name, s, d in ln["events"]:
                s2, e2 = max(s, w0), min(s + d, w1)
                if e2 <= s2:
                    continue
                rec = programs.setdefault(name, {"seconds": 0.0, "count": 0})
                rec["seconds"] += (e2 - s2) / 1e9
                rec["count"] += 1
                if w0 <= s and s + d <= w1:
                    rec = whole.setdefault(name, {"seconds": 0.0, "count": 0})
                    rec["seconds"] += d / 1e9
                    rec["count"] += 1
        edges = [w0] + [t for s, e in merged for t in (s, e)] + [w1]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    if not busy_ns:
        raise ValueError("no operation ran on a device inside the window")
    idle_ns: "dict[str, int]" = {}
    spans = sorted((sp for sp in host_spans or () if sp[2] > sp[1]),
                   key=lambda sp: sp[2] - sp[1])
    for g0, g1 in gaps:
        # every piece of the gap between two edges of any span has one owner
        cuts = sorted({g0, g1, *(t for _, s, e in spans for t in (s, e)
                                 if g0 < t < g1)})
        for a, b in zip(cuts, cuts[1:]):
            owner = next((n for n, s, e in spans if s <= a and b <= e),
                         "no span")
            idle_ns[owner] = idle_ns.get(owner, 0) + b - a
    n_dev = len(busy_ns)

    def ranked(d):
        return [[k, v / 1e9 / n_dev] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": sum(busy_ns) / n_dev / 1e9,
        "devices": n_dev,
        "programs": programs,
        "whole_programs": whole,
        "device_ops": ranked({
            f"{len(r['names'])}x {kind} in {r['runs'] / n_dev:g} runs": r["ns"]
            for kind, r in kinds.items()}),
        "idle_gaps": ranked(idle_ns),
        "longest_gap_s": max((g1 - g0 for g0, g1 in gaps), default=0) / 1e9,
    }


W0, W1 = 1_000_000, 2_000_000     # the reduction window, ns


def device_plane(rng, k: int, n_ops: int) -> dict:
    """Operations from before the window to past it, some touching, some
    overlapping, with a program line over them."""
    t, ops = W0 - 50_000, []
    for i in range(n_ops):
        t += int(rng.choice([0, 1, 2, 7, 100, 1_000, 5_000]))
        d = int(rng.integers(1, 4_000))
        ops.append((NAMES[int(rng.integers(0, len(NAMES)))], t, d))
        t += d - int(rng.choice([0, 0, 0, d // 2]))
        if t > W1 + 50_000:
            break
    # some executions straddle an edge of the window: clipped, never whole
    programs = [(f"jit_step({int(rng.integers(0, 3))})", s, 20_000)
                for s in range(W0 - 30_000, W1 + 30_000, 45_000)]
    return {"name": f"{DEVICE_PLANE_PREFIX}{k}", "lines": [
        {"name": OP_LINE, "events": ops},
        {"name": MODULE_LINE, "events": programs},
        {"name": "Steps", "events": [("0", W0, W1 - W0)]}]}


def gap_middles(planes) -> "list[int]":
    """The middles the reduction will ask about, so that spans can be laid
    with their first and last nanosecond on them."""
    mids = []
    for p in planes:
        ops = [ev for ln in p["lines"] if ln["name"] == OP_LINE
               for ev in _clip(ln["events"], W0, W1)]
        merged = _union([(s, e) for _, s, e in ops])
        edges = [W0] + [t for s, e in merged for t in (s, e)] + [W1]
        mids += [(edges[i] + edges[i + 1]) // 2
                 for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]
    return mids


def random_spans(rng, mids, n: int) -> "list[tuple[str, int, int]]":
    names = [f"serving.{w}" for w in ("tick", "decode_step", "decode_wait",
                                      "decode_dispatch", "retire", "admit")]
    spans = []

    def add(s, e):
        spans.append((names[int(rng.integers(0, len(names)))], int(s), int(e)))

    for _ in range(n):
        kind = int(rng.integers(0, 8))
        s = int(rng.integers(W0 - 400_000, W1 + 400_000))
        if kind == 0:       # a tick with its children nested inside it
            e = s + int(rng.integers(10_000, 120_000))
            add(s, e)
            add(s + 1_000, e - 1_000)
            add(s + 1_000, s + 3_000)
        elif kind == 1:     # two that overlap without nesting
            add(s, s + 50_000)
            add(s + 25_000, s + 75_000)
        elif kind == 2:     # equal lengths over one instant: the first wins
            for shift in rng.permutation(4):
                add(s + int(shift) * 10, s + int(shift) * 10 + 30_000)
        elif kind == 3:     # wholly outside the window, on either side
            add(W0 - 900_000 - s % 1_000, W0 - s % 7)
            add(W1 + s % 7, W1 + 900_000)
        elif kind == 4 and mids:    # first and last nanosecond on a middle
            mid = mids[int(rng.integers(0, len(mids)))]
            add(mid, mid + int(rng.integers(1, 5_000)))       # covers it
            add(mid - int(rng.integers(1, 5_000)), mid)       # ends on it
            add(mid - int(rng.integers(1, 5_000)), mid + 1)   # its last ns
            add(mid + 1, mid + 40)                            # starts past it
        elif kind == 5:     # empty and backwards: cover nothing
            add(s, s)
            add(s + 10, s)
        elif kind == 6:     # long, over the whole window or straddling an end
            add(W0 - int(rng.integers(0, 500_000)),
                W1 + int(rng.integers(-500_000, 500_000)))
        else:
            add(s, s + int(rng.integers(1, 200_000)))
    order = rng.permutation(len(spans))
    return [spans[i] for i in order]


def random_trace(seed: int):
    rng = np.random.default_rng(seed)
    planes = [device_plane(rng, k, int(rng.integers(50, 600)))
              for k in range(int(rng.integers(1, 4)))]
    # a device that ran nothing inside the window, and a host plane
    planes.append({"name": f"{DEVICE_PLANE_PREFIX}7", "lines": [
        {"name": OP_LINE, "events": [("plain", W1 + 10_000_000, 5)]}]})
    planes.append({"name": "/host:CPU", "lines": [{"name": "python", "events": [
        (trace_reduce.WINDOW_MARK, W0, W1 - W0)]}]})
    # seeds 0 and 1 hand in no spans at all: None, and an empty list
    spans = (None if seed == 0 else [] if seed == 1 else
             random_spans(rng, gap_middles(planes[:-2]),
                          int(rng.integers(1, 120))))
    return planes, spans


@pytest.mark.parametrize("seed", range(16))
def test_the_sweep_gives_the_plain_loops_result_key_for_key(seed):
    planes, spans = random_trace(seed)
    for window in (None, (W0, W1), (W0 + 123_457, W1 - 98_765)):
        # each reading of the spans is on the window it is reduced over
        want = oracle_reduce_trace(planes, window, spans)
        got = trace_reduce.reduce_trace(planes, window, spans)
        assert list(got) == list(want)
        for key in want:
            assert got[key] == want[key], key
        assert json.dumps(got) == json.dumps(want)
        summary, counts = trace_reduce.reduce_trace_counted(planes, window,
                                                            spans)
        assert summary == want
        assert counts["spans"] == len(spans or ())
        assert counts["spans_in_window"] <= counts["spans"]
        assert len(want["idle_gaps"]) > 0
        # shared out or not, the idle time is all there
        assert sum(v for _, v in trace_reduce.reduce_trace(
            planes, window, spans, top=1000)["idle_gaps"]) + got[
                "busy_s"] == pytest.approx(got["window_s"], rel=1e-12)
        clipped, whole = got["programs"], got["whole_programs"]
        assert all(whole[n]["count"] <= clipped[n]["count"] for n in whole)
    assert sum(r["count"] for r in whole.values()) < sum(
        r["count"] for r in clipped.values())      # the narrowest window cuts
    if seed > 1:
        owners = dict(want["idle_gaps"])
        assert len(owners) > 1 or "no span" not in owners


def test_spans_that_touch_the_window_by_one_nanosecond_still_own_a_gap():
    """The filter keeps a span by ``start < w1 and end > w0``: one that
    reaches one nanosecond into the window owns the gap that is that
    nanosecond, and one that stops at the window's edge owns none."""
    planes = [{"name": f"{DEVICE_PLANE_PREFIX}0", "lines": [
        {"name": OP_LINE, "events": [("%op.7", W0 + 1, W1 - W0 - 2)]}]}]
    spans = [("ends at the edge", W0 - 9, W0), ("first ns", W0 - 5, W0 + 1),
             ("last ns", W1 - 1, W1 + 5), ("starts at the edge", W1, W1 + 9),
             ("over all", W0 - 100, W1 + 100)]
    want = oracle_reduce_trace(planes, (W0, W1), spans)
    got, counts = trace_reduce.reduce_trace_counted(planes, (W0, W1), spans)
    assert got == want
    assert dict(got["idle_gaps"]) == {"first ns": 1e-9, "last ns": 1e-9}
    assert counts == {"device_ops": 1, "gaps": 2, "spans": 5,
                      "spans_in_window": 3}


def test_a_trace_of_a_fast_tick_reduces_under_one_ceiling():
    """200,000 operations and 15,000 spans, 1,000 of them touching the
    window: what a 51 s window at a 21 ms tick hands the reduction. The
    loop this replaced took over a minute on it (gaps x spans); a ceiling
    on one call, not a ratio of two CPU times."""
    ms = 1_000_000
    w0, w1 = 10_000 * ms, 13_400 * ms            # the traced stretch, of 51 s
    step = (w1 - w0) // 200_000                  # 17 us an operation
    ops = [(f"%fusion.{i % 97}", w0 + i * step, step - 40 - i % 7)
           for i in range(200_000)]
    tick = 51_000 * ms // 2_500                  # 2,500 ticks of 20.4 ms
    spans = []
    for i in range(2_500):
        t = i * tick
        spans += [("serving.tick", t, t + tick - 50_000),
                  ("serving.decode_step", t + 300_000, t + tick - 200_000),
                  ("serving.decode_dispatch", t + 310_000, t + 2 * ms),
                  ("serving.decode_wait", t + 2 * ms, t + tick - 700_000),
                  ("fetch.wait", t + tick - 700_000, t + tick - 300_000),
                  ("serving.retire", t + tick - 190_000, t + tick - 60_000)]
    planes = [{"name": f"{DEVICE_PLANE_PREFIX}0", "lines": [
        {"name": OP_LINE, "events": ops},
        {"name": MODULE_LINE, "events": [
            ("jit__paged_step(1)", i * tick, tick - ms)
            for i in range(490, 657)]}]}]
    t0 = time.monotonic()
    summary, counts = trace_reduce.reduce_trace_counted(planes, (w0, w1),
                                                        spans)
    took = time.monotonic() - t0
    assert counts["device_ops"] == 200_000 and counts["spans"] == 15_000
    assert 990 <= counts["spans_in_window"] <= 1_010
    assert counts["gaps"] >= 199_999
    assert dict(summary["idle_gaps"]).keys() >= {"serving.decode_wait"}
    # 97 serial numbers of one opcode and no shape: one kind, one row
    (row, secs), = summary["device_ops"]
    assert row == "97x fusion in 200000 runs"
    assert secs == pytest.approx(summary["busy_s"])
    # 167 ticks touch the stretch, the first and the last cut by its edges
    step_ = "jit__paged_step(1)"
    assert summary["programs"][step_]["count"] == 167
    assert summary["whole_programs"][step_]["count"] == 165
    assert took < 20.0, f"{took:.1f} s: the reduction is not near-linear"
