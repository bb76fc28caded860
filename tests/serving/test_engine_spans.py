"""The engine's own span tree, compile to retire (ISSUE 24): one
``serving.tick`` a working tick with its phases inside it, token counts
that reconcile three ways (spans, futures, registry counter), ``xla.*``
spans under the span that paid for the program, the profiler mirror, and
the guard on the tracing-off path. CPU only, a two-layer GPT.
"""

import contextlib
import glob
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import wait_until
from sparkdl_tpu.models.gpt import GPTConfig, GPTLMHeadModel
from sparkdl_tpu.observability import profiling, tracing
from sparkdl_tpu.observability.registry import registry
from sparkdl_tpu.serving import ContinuousGPTEngine

#: (prompt tokens, max new tokens): two slots, so requests queue, overlap,
#: prefill in several chunks (chunk 8) and retire at different ticks
REQUESTS = ((5, 4), (20, 6), (9, 1), (3, 7), (17, 3))
#: what lies inside ONE tick (a paged ``serving.decode_step`` does not: it
#: runs from its launch in one tick to the read of its ids in the next)
PHASES = ("serving.admit", "serving.prefill_chunk", "serving.first_token",
          "serving.decode_dispatch", "serving.decode_wait", "serving.retire")


@pytest.fixture(scope="module")
def bundle():
    cfg = GPTConfig.tiny()
    variables = GPTLMHeadModel(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    return cfg, variables


@pytest.fixture
def traced():
    tracing.clear_trace()
    tracing.enable_tracing()
    try:
        yield
    finally:
        tracing.disable_tracing()
        tracing.clear_trace()


def _engine(bundle, **kw):
    cfg, variables = bundle
    kw.setdefault("n_slots", 2)
    kw.setdefault("max_len", 64)
    kw.setdefault("prefill_chunk", 8)
    kw.setdefault("auto_start", False)
    return ContinuousGPTEngine(cfg, variables, **kw)


def _prompts(cfg, requests=REQUESTS, seed=0):
    rng = np.random.default_rng(seed)
    return [(rng.integers(0, cfg.vocab_size, n, np.int32), m)
            for n, m in requests]


def _serve(eng, prompts):
    futs = [eng.submit(p, m) for p, m in prompts]
    if eng._thread is not None:  # the engine's own loop ticks
        wait_until(lambda: all(f.done() for f in futs), timeout_s=120,
                   desc="the engine's loop to finish")
        return futs, [f.result(timeout=0) for f in futs]
    deadline = time.monotonic() + 120
    while not all(f.done() for f in futs):
        assert time.monotonic() < deadline, "engine did not finish"
        eng.tick()
    return futs, [f.result(timeout=0) for f in futs]


def _tokens_counter() -> float:
    fam = registry().get("sparkdl_serving_tokens_total")
    return sum(fam.snapshot_values().values()) if fam else 0.0


def _spans(*names, events=None):
    return [e for e in (tracing.trace_events() if events is None else events)
            if e["name"] in names]


def _end(e):
    return e["ts"] + e["dur"]


def _traced_run(bundle, **kw):
    """One traced run of REQUESTS through an engine, and the
    spans it left (the ring is copied, then cleared for the next test)."""
    tracing.clear_trace()
    tracing.enable_tracing()
    try:
        before = _tokens_counter()
        eng = _engine(bundle, **kw)
        try:
            futs, outs = _serve(eng, _prompts(bundle[0]))
            snap = eng.snapshot()
        finally:
            eng.close()
        events = tracing.trace_events()
        return {"futs": futs, "outs": outs, "snap": snap,
                "counted": _tokens_counter() - before,
                "spans": lambda *names: _spans(*names, events=events)}
    finally:
        tracing.disable_tracing()
        tracing.clear_trace()


@pytest.fixture(scope="module")
def served(bundle):
    """:func:`_traced_run` through a manual-tick engine."""
    return _traced_run(bundle)


class TestTickTree:
    def test_every_working_tick_is_one_span_with_its_phases_inside(
            self, served):
        ticks = sorted(served["spans"]("serving.tick"), key=lambda e: e["ts"])
        assert ticks
        # ticks of one engine thread never overlap
        for a, b in zip(ticks, ticks[1:]):
            assert _end(a) <= b["ts"]
        phases = sorted(served["spans"](*PHASES), key=lambda e: e["ts"])
        for p in phases:
            inside = [t for t in ticks
                      if t["ts"] <= p["ts"] and _end(p) <= _end(t)]
            assert len(inside) == 1, (p["name"], len(inside))
        # ...and inside one tick the phases follow one another
        for t in ticks:
            mine = [p for p in phases
                    if t["ts"] <= p["ts"] and _end(p) <= _end(t)]
            for a, b in zip(mine, mine[1:]):
                assert _end(a) <= b["ts"], (a["name"], b["name"])

    def test_decode_step_runs_from_its_dispatch_to_its_wait(self, served):
        """Every step has one launch and one wait, in the order the steps
        were launched: the step starts with its ``decode_dispatch`` and
        ends when the ``decode_wait`` for ITS ids and their read are over,
        which is a tick later wherever the loop ran ahead."""
        def by_start(*names):
            return sorted(served["spans"](*names), key=lambda e: e["ts"])

        steps = by_start("serving.decode_step")
        launches = by_start("serving.decode_dispatch")
        waits = by_start("serving.decode_wait")
        assert steps and len(launches) == len(waits) == len(steps)
        ticks = by_start("serving.tick")

        def tick_of(e):
            (i,) = [i for i, t in enumerate(ticks)
                    if t["ts"] <= e["ts"] and _end(e) <= _end(t)]
            return i

        for step, launch, wait in zip(steps, launches, waits):
            assert step["ts"] <= launch["ts"] <= _end(launch) <= wait["ts"]
            assert _end(wait) <= _end(step)
            assert step["args"]["nb"] == launch["args"]["nb"] >= 1
            assert step["args"]["chain"] == launch["args"]["k"] == 1
            assert _end(step) <= _end(ticks[tick_of(wait)])
        # a step launched with the one before it unread: the launch comes
        # first, in the tick that then waits for the earlier step's ids
        n_ahead = 0
        for n in range(len(steps) - 1):
            if launches[n + 1]["args"]["ahead"]:
                n_ahead += 1
                assert launches[n + 1]["ts"] < waits[n]["ts"]
                assert tick_of(launches[n + 1]) == tick_of(waits[n])
            else:
                assert _end(waits[n]) <= launches[n + 1]["ts"]
        assert n_ahead and not launches[0]["args"]["ahead"]

    def test_retire_and_decode_hang_under_their_tick(self, served):
        ticks = {e["args"]["span_id"]: e for e in served["spans"]("serving.tick")}
        for e in served["spans"]("serving.retire", "serving.decode_step"):
            assert e["args"]["parent_id"] in ticks, e["name"]
        for t in ticks.values():
            assert {"inflight", "prefilling", "admitted",
                    "links"} <= set(t["args"])

    @pytest.mark.parametrize("auto_start", [False, True])
    def test_tokens_reconcile_spans_futures_and_counter(
            self, bundle, served, auto_start):
        if auto_start:  # the engine's own thread ticks, submits race it
            served = _traced_run(bundle, auto_start=True)
        returned = sum(len(o) for o in served["outs"])
        from_spans = (sum(e["args"]["tokens"]
                          for e in served["spans"]("serving.retire"))
                      + len(served["spans"]("serving.first_token")))
        assert returned == sum(m for _, m in REQUESTS)
        assert from_spans == returned
        assert served["counted"] == returned
        assert served["snap"]["tokens"] == returned
        assert (sum(e["args"]["completed"]
                    for e in served["spans"]("serving.retire"))
                + sum(1 for _, m in REQUESTS if m == 1)) == len(REQUESTS)

    def test_every_token_has_a_time_and_they_do_not_decrease(self, served):
        for fut, out in zip(served["futs"], served["outs"]):
            rid = fut.request_id
            first = [e for e in served["spans"]("serving.first_token")
                     if e["args"]["request_id"] == rid]
            steps = [e for e in served["spans"]("serving.decode_step")
                     if rid in e["args"]["links"]]
            assert len(first) == 1
            times = [_end(first[0])] + sorted(_end(e) for e in steps)
            assert len(times) == len(out)
            assert times == sorted(times)
            queued = [e for e in served["spans"]("serving.queue_wait")
                      if e["args"]["request_id"] == rid]
            assert len(queued) == 1 and queued[0]["ts"] <= times[0]

    @pytest.mark.parametrize("name,keys", [
        ("serving.engine_init",
         {"n_slots", "max_len", "kv_blocks"}),
        ("serving.admit", {"request_id", "slot", "prompt_len",
                           "cached_tokens", "blocks", "deferred"}),
        ("serving.prefill_chunk", {"width", "cols", "program", "tokens"}),
        ("serving.first_token", {"request_id", "slot", "prompt_len"}),
        ("serving.decode_step", {"slots", "chain", "links", "nb"}),
        ("serving.retire", {"completed", "tokens", "links"}),
    ])
    def test_span_carries_its_attributes(self, served, name, keys):
        found = served["spans"](name)
        assert found, name
        for e in found:
            assert keys <= set(e["args"]), (name, sorted(e["args"]))

    def test_chunk_programs_are_named_by_their_place(self, served):
        by_request = {}
        for e in sorted(served["spans"]("serving.prefill_chunk"),
                        key=lambda e: e["ts"]):
            by_request.setdefault(e["args"]["request_id"], []).append(
                e["args"]["program"])
        assert sorted(by_request.values()) == sorted([
            ["chunk_one"], ["chunk_first", "chunk_mid", "chunk_final"],
            ["chunk_first", "chunk_final"], ["chunk_one"],
            ["chunk_first", "chunk_mid", "chunk_final"]])


@pytest.mark.parametrize("auto_start", [True, False])
def test_an_idle_engine_adds_no_span(bundle, traced, auto_start):
    eng = _engine(bundle, auto_start=auto_start)
    try:
        tracing.clear_trace()
        if auto_start:
            time.sleep(0.2)  # some 40 idle ticks
        else:
            for _ in range(40):
                assert eng.tick() is False
        assert [e["name"] for e in tracing.trace_events()] == []
    finally:
        eng.close()


def test_chained_decode_counts_dropped_tokens_out(bundle, traced):
    """Budgets of 5 and 3 under chains of up to 4: what a chain decoded
    past a spent budget is dropped and not counted."""
    eng = _engine(bundle, chain_tokens=4)
    try:
        _, outs = _serve(eng, _prompts(bundle[0], ((6, 5), (4, 3))))
    finally:
        eng.close()
    assert [len(o) for o in outs] == [5, 3]
    assert sum(e["args"]["tokens"]
               for e in _spans("serving.retire")) == 8 - 2


def test_speculative_verify_counts_its_tokens(bundle, traced):
    before = _tokens_counter()
    eng = _engine(bundle, spec_k=4)
    try:
        # a repetitive prompt, so that the n-gram proposer has drafts
        prompt = np.tile(np.arange(1, 5, dtype=np.int32), 4)
        _, outs = _serve(eng, [(prompt, 12)])
    finally:
        eng.close()
    made = (sum(e["args"]["tokens"] for e in _spans(
        "serving.retire", "serving.spec_verify"))
        + len(_spans("serving.first_token")))
    assert made == len(outs[0]) == 12
    assert _tokens_counter() - before == 12


def _served_by_a_new_engine(bundle, request, **sizes):
    eng = _engine(bundle, **sizes)
    try:
        return _serve(eng, _prompts(bundle[0], (request,)))[0][0].request_id
    finally:
        eng.close()


def test_a_new_prompt_width_compiles_under_the_chunk_that_paid(
        bundle, traced):
    """The FIRST engine of a shape in a process compiles its programs,
    each under the span that paid; a SECOND engine of that shape is handed
    the same bound functions (``paged_programs.bound``) and compiles
    nothing. Three slots: no other test of this file builds that shape, so
    what ran earlier in the worker does not decide which engine is first."""
    rid = _served_by_a_new_engine(bundle, (13, 2), n_slots=3)
    chunks = {e["args"]["span_id"]: e
              for e in _spans("serving.prefill_chunk")
              if e["args"]["request_id"] == rid}
    compiles = [e for e in _spans("xla.compile")
                if e["args"].get("parent_id") in chunks]
    assert compiles, [e["args"] for e in _spans("xla.compile")]
    for e in compiles:
        assert e["args"]["trace_id"] == rid
        assert e["args"]["event"].endswith("backend_compile_duration")
        assert "_chunk_" in e["args"]["fun_name"]
    # the decode depth it first reached compiled under the dispatch of ITS
    # decode step, whose nb and k say which shape it was
    dispatches = {e["args"]["span_id"]
                  for e in _spans("serving.decode_dispatch")}
    assert any(e["args"].get("parent_id") in dispatches
               and "_paged_step" in e["args"]["fun_name"]
               for e in _spans("xla.compile"))
    for kind in ("xla.trace", "xla.lower"):
        assert any(e["args"].get("parent_id") in chunks
                   for e in _spans(kind)), kind

    tracing.clear_trace()
    rid = _served_by_a_new_engine(bundle, (13, 2), n_slots=3)
    assert [e["args"]["request_id"]
            for e in _spans("serving.prefill_chunk")] == [rid, rid]
    assert _spans("xla.trace", "xla.lower", "xla.compile") == []


def test_compile_counters_count_with_tracing_off(bundle):
    tracing.disable_tracing()
    count = registry().counter("sparkdl_compiles_total", labels=("kind",))
    secs = registry().counter("sparkdl_compile_seconds_total",
                              labels=("kind",))

    def read():
        return (dict(count.snapshot_values()), dict(secs.snapshot_values()))

    # five slots: a shape of this test's own (see the test above)
    n0, s0 = read()
    _served_by_a_new_engine(bundle, (11, 2), n_slots=5)
    n1, s1 = read()
    for kind in ("trace", "lower", "compile"):
        key = f'kind="{kind}"'
        assert n1[key] - n0.get(key, 0) >= 2, (kind, n0, n1)
        assert s1[key] > s0.get(key, 0.0)
    # the second engine of the shape, constructor and all, moves none
    _served_by_a_new_engine(bundle, (11, 2), n_slots=5)
    assert read() == (n1, s1)
    assert tracing.trace_events() == []


def test_a_tick_with_tracing_off_allocates_nothing(bundle, monkeypatch):
    """The guard on the off path: no ``_Span``, no profiler annotation,
    an empty ring."""
    tracing.disable_tracing()
    tracing.clear_trace()
    made = []

    class Loud(tracing._Span):
        def __init__(self, *a, **kw):
            made.append("span")
            super().__init__(*a, **kw)

    def loud_annotation(*a, **kw):
        made.append("annotation")
        return contextlib.nullcontext()

    monkeypatch.setattr(tracing, "_Span", Loud)
    monkeypatch.setattr(tracing, "_profiler_annotation", loud_annotation)
    eng = _engine(bundle)
    try:
        _, outs = _serve(eng, _prompts(bundle[0]))
    finally:
        eng.close()
    assert sum(len(o) for o in outs) == sum(m for _, m in REQUESTS)
    assert made == []
    assert tracing.trace_events() == []
    # and the guard itself can see: with tracing on the same calls fire
    tracing.enable_tracing()
    try:
        with tracing.span("loud"):
            pass
    finally:
        tracing.disable_tracing()
        tracing.clear_trace()
    assert made == ["span", "annotation"]


def test_a_discarded_span_leaves_nothing_and_restores_the_ambient(traced):
    with tracing.span("outer") as outer:
        with tracing.span("idle") as idle:
            idle.discard()
        assert tracing.current_context() == outer.context
    assert [e["name"] for e in tracing.trace_events()] == ["outer"]


def test_a_profiler_capture_holds_the_engines_spans(bundle, traced,
                                                    tmp_path):
    """One clock: a capture through ``observability.profiling.trace`` of an
    engine with tracing on has the spans in its host plane, with their
    scalar attributes, beside whatever the device ran."""
    eng = _engine(bundle)
    try:
        _serve(eng, _prompts(bundle[0], ((6, 2),)))  # compile outside
        with profiling.trace(tmp_path):
            _serve(eng, _prompts(bundle[0], ((6, 3),), seed=1))
    finally:
        eng.close()
    paths = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    assert len(paths) == 1
    data = jax.profiler.ProfileData.from_file(paths[0])
    host = {}
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("serving."):
                    host.setdefault(ev.name, []).append(dict(ev.stats))
    assert {"serving.tick", "serving.decode_dispatch",
            "serving.decode_wait", "serving.retire", "serving.admit",
            "serving.prefill_chunk", "serving.first_token"} <= set(host)
    # the LIVE spans are mirrored. A paged ``serving.decode_step`` is
    # recorded when its ids are read, a tick after it began, so the capture
    # holds the two live spans that bracket it: its launch and its wait
    assert "serving.decode_step" not in host
    assert len(host["serving.decode_dispatch"]) == 2
    assert all(s["k"] == 1 and s["nb"] >= 1
               for s in host["serving.decode_dispatch"])
    assert [s["ahead"] for s in host["serving.decode_dispatch"]] == [0, 1]
    assert len(host["serving.decode_wait"]) == 2
    assert host["serving.prefill_chunk"][0]["program"] == "chunk_one"


# -- the prefill path measures itself (ISSUE 38) -----------------------------

#: two prompts longer than the chunk of 8, on two slots at once: they take
#: turns at one tick's budget; then the first prompt again, alone, all of
#: which but its last token the prefix cache still holds
SHARING = ((20, 3), (17, 2))


@pytest.fixture(scope="module")
def shared(bundle):
    tracing.clear_trace()
    tracing.enable_tracing()
    try:
        eng = _engine(bundle, kv_block_size=8)
        try:
            prompts = _prompts(bundle[0], SHARING, seed=38)
            futs, _ = _serve(eng, prompts)
            again, _ = _serve(eng, [(prompts[0][0], 2)])
            rids = [f.request_id for f in futs + again]
            traces = {rid: eng.trace(rid) for rid in rids}
        finally:
            eng.close()
        events = tracing.trace_events()
        return {"rids": rids, "traces": traces,
                "prompt_len": dict(zip(rids, (20, 17, 20))),
                "spans": lambda *names: _spans(*names, events=events)}
    finally:
        tracing.disable_tracing()
        tracing.clear_trace()


def _of(shared, name, rid):
    return [e for e in shared["spans"](name)
            if e["args"].get("request_id") == rid]


def test_a_paged_request_leaves_one_prefill_span_on_its_own_trace(shared):
    for rid in shared["rids"]:
        (e,) = _of(shared, "serving.prefill", rid)
        a = e["args"]
        # the request's root context: its trace id IS its request id
        assert a["trace_id"] == a["parent_id"] == rid
        assert a["prompt_len"] == shared["prompt_len"][rid]
        (admit,) = _of(shared, "serving.admit", rid)
        assert a["slot"] == admit["args"]["slot"]
        assert a["cached_tokens"] == admit["args"]["cached_tokens"]
        # it starts inside the admission and holds every chunk
        assert admit["ts"] <= e["ts"] <= _end(admit)
        for c in _of(shared, "serving.prefill_chunk", rid):
            assert e["ts"] <= c["ts"] and _end(c) <= _end(e)


def test_prefill_counts_its_chunks_and_the_ticks_it_stood_by(shared):
    spans = [_of(shared, "serving.prefill", rid)[0]["args"]
             for rid in shared["rids"]]
    for a in spans:
        uncached = a["prompt_len"] - a["cached_tokens"]
        assert a["chunks"] == -(-uncached // 8)
        assert a["chunks"] == len(_of(shared, "serving.prefill_chunk",
                                      a["request_id"]))
        assert a["ticks"] >= a["chunks"]
    # 20 and 17 tokens under a budget of 8 a tick: somebody waits its turn
    assert [a["cached_tokens"] for a in spans] == [0, 0, 19]
    assert any(a["ticks"] > a["chunks"] for a in spans[:2])
    # ...and a prompt alone, most of it cached, waits for nobody
    assert spans[2]["ticks"] == spans[2]["chunks"] == 1


def test_prefill_ends_with_the_first_token_inside_the_same_tick(shared):
    ticks = shared["spans"]("serving.tick")
    for rid in shared["rids"]:
        (e,) = _of(shared, "serving.prefill", rid)
        (first,) = _of(shared, "serving.first_token", rid)
        assert _end(first) <= _end(e)
        (tick,) = [t for t in ticks
                   if t["ts"] <= first["ts"] and _end(first) <= _end(t)]
        assert _end(e) <= _end(tick)


def test_a_ticks_chunks_stay_inside_the_budget_and_say_their_pad(shared):
    """What a tick dispatched is read off its ``serving.prefill_chunk``
    spans (``tokens`` real, ``width`` with the pad): the tick itself
    carries no count of them."""
    ticks = shared["spans"]("serving.tick")
    chunks = shared["spans"]("serving.prefill_chunk")
    per_tick = []
    for t in ticks:
        assert not {"chunk_tokens", "chunk_width"} & set(t["args"])
        mine = [c["args"] for c in chunks
                if t["ts"] <= c["ts"] and _end(c) <= _end(t)]
        assert sum(c["tokens"] for c in mine) <= 8
        per_tick.append(mine)
    assert sum(len(mine) for mine in per_tick) == len(chunks)
    assert sum(c["args"]["tokens"] for c in chunks) == 20 + 17 + 1
    assert all(c["args"]["width"] >= c["args"]["tokens"] for c in chunks)
    # a last chunk of 1 token ran in a program 8 wide: the pad is there
    assert any(c["args"]["width"] > c["args"]["tokens"] for c in chunks)
    assert any(not mine for mine in per_tick)


def test_a_paged_requests_trace_reads_without_a_hole(shared):
    """Queue wait, admission, its chunks, the first token and the prefill
    that ends with it, its decode steps, the request: by the instant each
    was over."""
    for rid in shared["rids"]:
        trace = sorted((e for e in shared["traces"][rid]
                        if e["args"].get("request_id") == rid
                        or rid in e["args"].get("links", ())),
                       key=_end)
        names = [e["name"] for e in trace
                 if e["name"] not in ("serving.tick", "serving.retire",
                                      "serving.decode_dispatch",
                                      "serving.decode_wait")]
        n_chunks = names.count("serving.prefill_chunk")
        n_steps = names.count("serving.decode_step")
        assert n_chunks >= 1 and n_steps >= 1
        assert names == (["serving.queue_wait", "serving.admit"]
                         + ["serving.prefill_chunk"] * n_chunks
                         + ["serving.first_token", "serving.prefill"]
                         + ["serving.decode_step"] * n_steps
                         + ["serving.request"])
        by_name = {e["name"]: e for e in trace}
        # no hole: the wait ends where the admission starts, the prefill
        # starts inside it and runs to the first token
        assert _end(by_name["serving.queue_wait"]) <= \
            by_name["serving.admit"]["ts"]
        assert by_name["serving.prefill"]["ts"] <= \
            _end(by_name["serving.admit"])
        assert by_name["serving.request"]["ts"] <= \
            by_name["serving.queue_wait"]["ts"]


def test_prefill_counts_with_tracing_off_and_records_nothing(bundle,
                                                             monkeypatch):
    tracing.disable_tracing()
    tracing.clear_trace()
    eng = _engine(bundle)
    counted = []
    finish = eng._finish_prefill

    def watched(slot, st, first):
        counted.append((st.chunks, st.ticks))
        finish(slot, st, first)

    monkeypatch.setattr(eng, "_finish_prefill", watched)
    try:
        _serve(eng, _prompts(bundle[0], SHARING, seed=38))
    finally:
        eng.close()
    assert sorted(c for c, _ in counted) == [3, 3]
    assert all(t >= c for c, t in counted)
    assert any(t > c for c, t in counted)
    assert tracing.trace_events() == []


# -- what the paged decode reads through the table (ISSUE 27) ----------------

def _hand_count(slots, block, steps_of):
    """(read, live) of a script: ``steps_of`` lists, a dispatch, the
    depths of its live rows, the blocks under the deepest and its model
    passes."""
    read = live = 0
    for depths, nb, steps in steps_of:
        read += slots * nb * block * steps
        live += sum(d + j for d in depths for j in range(steps))
    return read, live


def test_kv_cols_read_and_live_equal_a_hand_count(bundle, traced):
    """Three requests on two slots, blocks of 4: each decode step says how
    many K/V columns it gathers through the table (every slot's ``nb``
    blocks) and how many of them are a live row's context; the totals are
    in ``snapshot()`` and in the registry."""
    eng = _engine(bundle, kv_block_size=4, prefill_chunk=16)
    fam = registry().get("sparkdl_serving_kv_cols_read_total")
    before = sum(fam.snapshot_values().values()) if fam else 0.0
    try:
        # (prompt, new): the first token comes from the prefill, so a
        # request decodes new - 1 times, at depths prompt, prompt + 1, ...
        _, outs = _serve(eng, _prompts(bundle[0], ((5, 3), (9, 4), (2, 2))))
        snap = eng.snapshot()
    finally:
        eng.close()
    assert [len(o) for o in outs] == [3, 4, 2]
    # tick 1 admits (5, 3) and (9, 4) and launches both: depths 5 and 9
    # under 3 blocks, bucketed to 4; tick 2: 6 and 10, which is the first
    # one's last by count, so its slot is free; tick 3 admits (2, 2), whose
    # first token is read after the second's last step, at 11, is
    # launched; tick 4 launches its one step alone, under one block
    want = _hand_count(2, 4, [([5, 9], 4, 1), ([6, 10], 4, 1),
                              ([11], 4, 1), ([2], 1, 1)])
    steps = sorted(_spans("serving.decode_step"), key=lambda e: e["ts"])
    assert [(e["args"]["kv_cols_read"], e["args"]["kv_cols_live"])
            for e in steps] == [(32, 14), (32, 16), (32, 11), (8, 2)]
    assert (sum(e["args"]["kv_cols_read"] for e in steps),
            sum(e["args"]["kv_cols_live"] for e in steps)) == want
    assert (snap["kv_cols_read"], snap["kv_cols_live"]) == want
    assert sum(registry().get("sparkdl_serving_kv_cols_read_total")
               .snapshot_values().values()) - before == want[0]


def test_kv_cols_count_every_pass_of_a_chain(bundle, traced):
    """A chain of 4 gathers four times, a row deepening by one a pass; a
    verify span gathers once, however wide."""
    eng = _engine(bundle, kv_block_size=4, prefill_chunk=16, chain_tokens=4)
    try:
        _serve(eng, _prompts(bundle[0], ((6, 5),)))
        snap = eng.snapshot()
    finally:
        eng.close()
    (step,) = _spans("serving.decode_step")
    assert step["args"]["chain"] == 4 and step["args"]["nb"] == 4
    assert (step["args"]["kv_cols_read"], step["args"]["kv_cols_live"]) == \
        _hand_count(2, 4, [([6], 4, 4)]) == (128, 6 + 7 + 8 + 9)
    assert snap["kv_cols_live"] == 30
    tracing.clear_trace()
    eng = _engine(bundle, kv_block_size=4, prefill_chunk=16, spec_k=4)
    try:
        prompt = np.tile(np.arange(1, 5, dtype=np.int32), 4)
        _serve(eng, [(prompt, 12)])
        snap = eng.snapshot()
    finally:
        eng.close()
    verifies = _spans("serving.spec_verify")
    assert verifies
    passes = verifies + _spans("serving.decode_step")
    for e in passes:
        # one pass a dispatch: both slots' blocks (8 under a row of 16 to
        # 27 tokens), of which the one live row's depth is live
        assert e["args"]["kv_cols_read"] == 2 * 8 * 4
        assert 16 <= e["args"]["kv_cols_live"] < 16 + 12
    assert snap["kv_cols_read"] == 64 * len(passes)
    assert snap["kv_cols_live"] == sum(
        e["args"]["kv_cols_live"] for e in passes)
