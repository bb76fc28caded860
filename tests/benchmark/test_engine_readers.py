"""The readers of the engine's span tree (ISSUE 24), each on a ``Run`` built
by hand against a value computed by hand, and on a silent one. CPU only;
nothing here starts a process (the rehearsal that prints the names of the
cell's metrics is tests/benchmark/test_engine_rehearsal.py, a file of one
test so that xdist hands it out last)."""

import time

import pytest

from benchmark import engine_readers, harness
from benchmark import manifest as mf
from sparkdl_tpu.observability import tracing

ROOT = mf.repo_root()
MANIFEST = mf.load_manifest(ROOT)
CELL = "gpt2xl-backlog"
WINDOW = (100.0, 110.0)


def reader(metric):
    return harness.load_module(mf.reader_file(MANIFEST, ROOT, metric),
                               "engine_reader_under_test").compute


def a_run(spans=(), trace_summary=None, window=WINDOW, t_process=0.0):
    run = harness.Run(cell=mf.resolve_cell(CELL, ROOT), seed=0, seconds=10.0,
                      trace=True, rehearse=True, t_process=t_process,
                      window=window)
    run.spans = [{"name": n, "t0": t0, "t1": t1, "args": dict(args)}
                 for n, t0, t1, args in spans]
    run.trace_summary = trace_summary
    return run


#: three ticks: A and B inside the window, C ending after it. Request 7 is
#: admitted in A, request 5 was decoding before the window opened, step B is
#: a chain of two
SPANS = [
    ("serving.queue_wait", 99.90, 99.99, {"request_id": 7}),
    ("serving.tick", 100.00, 100.50, {}),
    ("serving.first_token", 100.00, 100.04, {"request_id": 7}),
    ("serving.decode_step", 100.05, 100.45, {"links": [5, 7], "chain": 1}),
    ("serving.decode_dispatch", 100.05, 100.10, {}),
    ("serving.decode_wait", 100.10, 100.40, {}),
    ("fetch.wait", 100.40, 100.44, {}),
    ("serving.retire", 100.45, 100.48, {"tokens": 2, "completed": 0}),
    ("serving.queue_wait", 100.20, 100.55, {"request_id": 8}),
    ("serving.tick", 100.60, 101.00, {}),
    ("serving.decode_step", 100.60, 100.93, {"links": [5, 7], "chain": 2}),
    ("serving.decode_dispatch", 100.60, 100.62, {}),
    ("serving.decode_wait", 100.62, 100.92, {}),
    ("serving.retire", 100.93, 100.95, {"tokens": 3, "completed": 1}),
    ("serving.first_token", 101.20, 101.30, {"request_id": 8}),
    ("serving.queue_wait", 103.00, 103.05, {"request_id": 9}),
    ("serving.queue_wait", 104.00, 104.15, {"request_id": 10}),
    ("serving.first_token", 105.00, 105.10, {"request_id": 11}),
    ("serving.tick", 109.90, 110.20, {}),
    ("serving.decode_dispatch", 109.90, 109.93, {}),
    ("serving.decode_wait", 109.95, 110.05, {}),
    ("serving.decode_step", 109.90, 110.10, {"links": [5], "chain": 1}),
    ("serving.retire", 110.10, 110.15, {"tokens": 5, "completed": 0}),
]
TRACE = {"window_s": 3.0, "busy_s": 2.9, "programs": {
    "jit__chunk_one(123)": {"seconds": 0.06, "count": 2},
    "jit__chunk_mid(5)": {"seconds": 0.03, "count": 1},
    "jit__paged_step(9)": {"seconds": 2.7, "count": 15}}}

BY_HAND = {
    # 2 + 3 tokens retired inside the window, 3 first tokens read in it
    "engine_tokens_per_s.backlog": (2 + 3 + 3) / 10.0,
    # waits of 0.35, 0.05 and 0.15 s ended inside the window
    "queue_wait_ms.backlog": 150.0,
    # request 7: 100.04 - 99.90; request 8: 101.30 - 100.20; 11: no wait
    "first_token_ms.backlog": 1e3 * (0.14 + 1.10) / 2,
    # request 7: 100.04, 100.45, 100.93 twice; request 5: 100.45, 100.93
    # twice, 110.10 (closes outside): gaps 0.41 0.48 0 0.48 0
    "token_gap_p95_ms.backlog": 480.0,
    "prefill_device_share.backlog": 100.0 * 0.09 / 3.0,
    "tick_dispatch_ms.backlog": 1e3 * (0.05 + 0.02 + 0.03) / 3,
    # A: 0.50 less 0.30 and 0.04; B: 0.40 less 0.30; C ends outside
    "tick_host_self_ms.backlog": 1e3 * (0.16 + 0.10) / 2,
}


@pytest.mark.parametrize("metric", sorted(BY_HAND))
def test_reader_gives_the_hand_computed_value(metric):
    got = reader(metric)(a_run(SPANS, TRACE))
    assert got == pytest.approx(BY_HAND[metric], rel=1e-9)


@pytest.mark.parametrize("metric", sorted(BY_HAND))
def test_reader_gives_none_on_a_silent_run(metric):
    assert reader(metric)(a_run()) is None


def test_prefill_device_share_is_zero_when_no_chunk_ran():
    quiet = {**TRACE, "programs": {"jit__paged_step(9)": TRACE["programs"][
        "jit__paged_step(9)"]}}
    assert reader("prefill_device_share.backlog")(a_run((), quiet)) == 0.0


def test_token_gaps_need_a_first_token_span():
    """The parent program's decode steps alone give no gap metric."""
    decode_only = [s for s in SPANS if s[0] == "serving.decode_step"]
    assert reader("token_gap_p95_ms.backlog")(a_run(decode_only)) is None


# -- set-up: the readers read the program's ring themselves -----------------------

SETUP_BY_HAND = {
    "setup_trace_lower_s.backlog": 2.0 + 0.5,
    "setup_compile_load_s.backlog": 7.5 + 1.0,
    "setup_cache_load_s.backlog": 4.0,
    "setup_programs.backlog": 2,
    "window_compiles.backlog": 1,
}


@pytest.fixture
def ring():
    """A ring holding one run's ``xla.*`` spans: process start 100 s ago,
    window from 10 to 5 s ago."""
    tracing.clear_trace()
    tracing.enable_tracing()
    now = time.monotonic()
    try:
        for name, start, end in [
                ("xla.compile", -200, -150),       # another run's
                ("xla.trace", -90, -88), ("xla.lower", -88, -87.5),
                ("xla.compile", -87.5, -80), ("xla.cache_load", -85, -81),
                ("xla.compile", -50, -49),
                ("serving.tick", -40, -39),
                ("xla.compile", -8, -7.5),          # inside the window
                ("xla.trace", -2, -1), ("xla.compile", -2, -1)]:  # the check's
            tracing.record_span(name, now + start, now + end)
        yield a_run(window=(now - 10, now - 5), t_process=now - 100)
    finally:
        tracing.disable_tracing()
        tracing.clear_trace()


@pytest.mark.parametrize("metric", sorted(SETUP_BY_HAND))
def test_setup_reader_gives_the_hand_computed_value(ring, metric):
    assert reader(metric)(ring) == pytest.approx(SETUP_BY_HAND[metric],
                                                 abs=1e-6)


@pytest.mark.parametrize("metric", sorted(SETUP_BY_HAND))
def test_setup_reader_gives_none_without_a_compile_span_of_this_run(
        ring, metric):
    # an empty ring, and a ring that holds only another run's spans
    before_any = a_run(window=(ring.t_process - 60, ring.t_process - 55),
                       t_process=ring.t_process - 70)
    assert reader(metric)(before_any) is None
    tracing.clear_trace()
    assert reader(metric)(ring) is None


@pytest.mark.parametrize("metric", sorted(SETUP_BY_HAND))
def test_setup_reader_gives_none_on_a_wrapped_ring(ring, metric,
                                                   monkeypatch):
    monkeypatch.setattr(engine_readers, "RING_EVENTS",
                        len(tracing.trace_events()))
    assert reader(metric)(ring) is None


def test_a_cold_set_up_reads_zero_seconds_of_cache_loading():
    tracing.clear_trace()
    tracing.enable_tracing()
    now = time.monotonic()
    try:
        tracing.record_span("xla.compile", now - 9, now - 8)
        run = a_run(window=(now - 5, now - 1), t_process=now - 10)
        assert reader("setup_cache_load_s.backlog")(run) == 0.0
        assert reader("window_compiles.backlog")(run) == 0
    finally:
        tracing.disable_tracing()
        tracing.clear_trace()


#: the per-layer metrics ``gpt2xl-backlog`` reports, in the order they were
#: accepted: the six of PR 23 and the twelve of PR 24 less the two PR 28
#: retired (``prefill_share``, ``tick_host_ms``). A later PR may add to them
SIXTEEN = [
    "batch_occupancy.backlog", "decode_device_ms.backlog",
    "decode_roofline_share.backlog", "device_idle_share.backlog",
    "engine_tokens_per_s.backlog", "queue_wait_ms.backlog",
    "first_token_ms.backlog", "token_gap_p95_ms.backlog",
    "prefill_device_share.backlog", "tick_dispatch_ms.backlog",
    "tick_host_self_ms.backlog", "setup_trace_lower_s.backlog",
    "setup_compile_load_s.backlog", "setup_cache_load_s.backlog",
    "setup_programs.backlog", "window_compiles.backlog"]


def test_the_manifest_names_the_cells_sixteen_metrics_and_validates():
    """What THIS cell resolves to: a metric that only another cell reports
    is none of its business, and one a later PR gives this cell comes after
    the sixteen."""
    names = [m["name"] for m in mf.resolve_cell(CELL, ROOT).per_layer]
    assert len(set(names)) == len(names)
    assert names[:len(SIXTEEN)] == SIXTEEN
    assert set(BY_HAND) | set(SETUP_BY_HAND) <= set(names)
    assert mf.validate(ROOT) == []
