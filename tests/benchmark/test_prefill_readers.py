"""The readers of the prefill path and of the host's share of the wall clock
(ISSUE 38), each on a ``Run`` built by hand against a value computed by
hand, and on a silent one. CPU only; nothing here starts a process."""

import pytest

from benchmark import engine_readers, harness, prefill_readers, readers
from benchmark import manifest as mf

ROOT = mf.repo_root()
MANIFEST = mf.load_manifest(ROOT)
CELLS = [w["name"] for w in MANIFEST["workloads"]]
WINDOW = (100.0, 110.0)
#: the traced stretch: two seconds after the window opens, three long
TRACED = (102.0, 105.0)

FIVE = ["chunk_device_ms.backlog", "prefill_device_us_per_token.backlog",
        "prefill_turn_wait_ms.backlog", "host_busy_share.backlog",
        "idle_longest_gap_ms.backlog"]


def reader(metric):
    return harness.load_module(mf.reader_file(MANIFEST, ROOT, metric),
                               "prefill_reader_under_test").compute


def a_run(spans=(), trace_summary=None, traced_window=None, window=WINDOW):
    run = harness.Run(cell=mf.resolve_cell(CELLS[0], ROOT), seed=0,
                      seconds=10.0, trace=True, rehearse=True, t_process=0.0,
                      window=window)
    run.spans = [{"name": n, "t0": t0, "t1": t1, "args": dict(args)}
                 for n, t0, t1, args in spans]
    run.trace_summary = trace_summary
    run.traced_window = traced_window
    return run


#: four ticks. A ends inside the window and starts before the traced
#: stretch; B and C start inside the stretch; D starts inside it and ends
#: after the WINDOW has closed (a window cut short for the purpose). A, B
#: and D each dispatched one chunk: A's before the stretch, D's 56 real
#: tokens in a program 64 wide. Three paged prefills end in the window, one
#: after it, and one span is the dense layout's, which has no ticks
SPANS = [
    ("serving.tick", 101.00, 101.50, {}),
    ("serving.prefill_chunk", 101.01, 101.04,
     {"request_id": 6, "tokens": 256, "width": 256}),
    ("serving.decode_dispatch", 101.05, 101.10, {}),
    ("serving.decode_wait", 101.10, 101.40, {}),
    ("serving.tick", 102.00, 102.40, {}),
    ("serving.prefill_chunk", 102.005, 102.015,
     {"request_id": 7, "tokens": 200, "width": 256}),
    ("serving.decode_dispatch", 102.02, 102.05, {}),
    ("serving.decode_wait", 102.05, 102.25, {}),
    ("serving.first_token", 102.25, 102.30, {"request_id": 7}),
    ("serving.tick", 103.00, 103.30, {}),
    ("serving.decode_wait", 103.05, 103.25, {}),
    ("serving.tick", 104.90, 110.20, {}),
    ("serving.prefill_chunk", 104.91, 104.95,
     {"request_id": 9, "tokens": 56, "width": 64}),
    ("serving.decode_wait", 105.00, 110.00, {}),
    # 400 ms of which 3 ticks of 4 were other prompts' turns
    ("serving.prefill", 101.90, 102.30,
     {"request_id": 7, "ticks": 4, "chunks": 1}),
    # 1 s, every tick its own
    ("serving.prefill", 103.00, 104.00,
     {"request_id": 8, "ticks": 5, "chunks": 5}),
    # 2 s of which half stood by
    ("serving.prefill", 105.00, 107.00,
     {"request_id": 9, "ticks": 8, "chunks": 4}),
    ("serving.prefill", 109.00, 110.50,
     {"request_id": 10, "ticks": 3, "chunks": 1}),
    ("serving.prefill", 106.00, 106.10,
     {"request_id": 11, "prompt_len": 6, "bucket": 8}),
]
#: the traced stretch's programs: every execution by its part inside the
#: stretch, and the whole ones alone (a ``chunk_mid`` cut by its edge is in
#: the first and not in the second)
TRACE = {"window_s": 3.0, "busy_s": 2.9, "longest_gap_s": 0.116,
         "programs": {
             "jit__chunk_one(123)": {"seconds": 0.020, "count": 2},
             "jit__chunk_mid(5)": {"seconds": 0.0056, "count": 2},
             "jit__paged_step(9)": {"seconds": 2.7, "count": 15}},
         "whole_programs": {
             "jit__chunk_one(123)": {"seconds": 0.020, "count": 2},
             "jit__chunk_mid(5)": {"seconds": 0.004, "count": 1},
             "jit__paged_step(9)": {"seconds": 2.5, "count": 14}}}

BY_HAND = {
    # the whole executions alone: (0.020 + 0.004) s over 2 + 1
    "chunk_device_ms.backlog": 8.0,
    # 0.0256 s of chunk programs over the 200 + 56 real tokens of the chunk
    # spans that START in the stretch (A's 256 were dispatched before it,
    # and D's width of 64 is not what is counted)
    "prefill_device_us_per_token.backlog": 100.0,
    # 0.4 x 3/4, 1.0 x 0, 2.0 x 1/2: the mean of 300, 0 and 1000 ms (their
    # median, 300, would not move with the 1000)
    "prefill_turn_wait_ms.backlog": 1300.0 / 3,
    # A: 0.50 less 0.30; B: 0.40 less 0.20 and 0.05; C: 0.30 less 0.20;
    # D ends outside the window: 0.45 s of 10
    "host_busy_share.backlog": 4.5,
    "idle_longest_gap_ms.backlog": 116.0,
}


def test_the_five_are_the_metrics_under_test():
    assert sorted(BY_HAND) == sorted(FIVE)


@pytest.mark.parametrize("metric", FIVE)
def test_reader_gives_the_hand_computed_value(metric):
    got = reader(metric)(a_run(SPANS, TRACE, TRACED))
    assert got == pytest.approx(BY_HAND[metric], rel=1e-9)


@pytest.mark.parametrize("metric", FIVE)
def test_reader_gives_none_on_a_silent_run(metric):
    assert reader(metric)(a_run()) is None


@pytest.mark.parametrize("metric", FIVE)
def test_reader_gives_none_where_its_own_source_is_absent(metric):
    """The spans without the trace, and the trace without the spans: each
    reader is silent without the one it reads."""
    spans_only = reader(metric)(a_run(SPANS))
    trace_only = reader(metric)(a_run((), TRACE, TRACED))
    source = {m["name"]: m["source"] for m in MANIFEST["per_layer"]}[metric]
    if source == "program_span":
        assert spans_only == pytest.approx(BY_HAND[metric])
        assert trace_only is None
    else:
        assert spans_only is None
        # (a cost per token needs both: the tokens are the program's count)
        assert (trace_only is None) == (
            metric == "prefill_device_us_per_token.backlog")


def test_a_chunk_cut_by_the_stretchs_edge_is_left_out_of_chunk_device_ms():
    """Through the reduction itself: three executions of a chunk program,
    the last cut by the end of the stretch."""
    from benchmark import trace_reduce

    ms = 1_000_000
    planes = [{"name": "/device:TPU:0", "lines": [
        {"name": trace_reduce.OP_LINE, "events": [
            ("%fusion.1 = f32[8]{0} fusion()", 0, 100 * ms)]},
        {"name": trace_reduce.MODULE_LINE, "events": [
            ("jit__chunk_mid(1)", 10 * ms, 12 * ms),
            ("jit__chunk_final(2)", 40 * ms, 16 * ms),
            ("jit__chunk_mid(1)", 95 * ms, 12 * ms),
            ("jit__paged_step(3)", 60 * ms, 9 * ms)]}]}]
    summary = trace_reduce.reduce_trace(planes, (0, 100 * ms))
    run = a_run((), summary, TRACED)
    assert reader("chunk_device_ms.backlog")(run) == pytest.approx(14.0)
    # ...and what it leaves out is in the share: whole executions times
    # their mean is no more than the share of the stretch
    share = engine_readers.prefill_device_share(run)
    assert share == pytest.approx(100.0 * (12 + 16 + 5) / 100)
    assert 2 * 14.0 <= share / 100 * 1e3 * summary["window_s"]


def test_no_whole_chunk_in_the_stretch_reads_none_and_not_zero():
    only_cut = {**TRACE, "whole_programs": {
        "jit__paged_step(9)": TRACE["whole_programs"]["jit__paged_step(9)"]}}
    assert reader("chunk_device_ms.backlog")(
        a_run(SPANS, only_cut, TRACED)) is None


def test_cost_per_token_is_none_where_no_chunk_started_in_the_stretch():
    early = [sp for sp in SPANS if not (sp[0] == "serving.prefill_chunk"
                                        and TRACED[0] <= sp[1] <= TRACED[1])]
    assert len(early) == len(SPANS) - 2
    assert reader("prefill_device_us_per_token.backlog")(
        a_run(early, TRACE, TRACED)) is None


def test_cost_per_token_reads_on_a_program_from_before_this_pr():
    """The parent's chunk spans carry ``tokens`` too; its requests leave no
    paged ``serving.prefill``, so the wait for a turn is silent there."""
    parents = [sp for sp in SPANS if sp[0] != "serving.prefill"]
    run = a_run(parents, TRACE, TRACED)
    assert reader("prefill_device_us_per_token.backlog")(run) == \
        pytest.approx(100.0)
    assert reader("prefill_turn_wait_ms.backlog")(run) is None


def test_a_turn_wait_of_nobody_is_zero_and_not_none():
    alone = [sp for sp in SPANS
             if sp[0] != "serving.prefill" or sp[3].get("request_id") == 8]
    assert reader("prefill_turn_wait_ms.backlog")(
        a_run(alone, TRACE, TRACED)) == 0.0


def test_host_busy_share_is_the_sum_tick_host_self_ms_averages():
    run = a_run(SPANS, TRACE, TRACED)
    ticks = engine_readers.ending_in_window(run, "serving.tick")
    assert len(ticks) == 3
    assert reader("host_busy_share.backlog")(run) == pytest.approx(
        100.0 * engine_readers.tick_host_self_ms(run) / 1e3 * len(ticks)
        / readers.window_s(run), rel=1e-12)
    # back from the share to the mean, as the acceptance line reads it
    assert (prefill_readers.host_busy_share(run) / 100.0
            * readers.window_s(run) / len(ticks) * 1e3) == pytest.approx(
        engine_readers.tick_host_self_ms(run), rel=1e-6)


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_reports_the_five_after_what_it_reported(cell):
    names = [m["name"] for m in mf.resolve_cell(cell, ROOT).per_layer]
    assert names[-5:] == FIVE
    by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
    for name in FIVE:
        assert by_name[name]["workloads"] == CELLS
        assert by_name[name]["moves"] == "tokens_per_s"


def test_the_manifest_with_the_five_validates_clean():
    assert [m["name"] for m in MANIFEST["per_layer"]][-5:] == FIVE
    assert mf.validate(ROOT) == []
