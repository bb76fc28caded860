"""The paged decode loop runs one step ahead of the host (ISSUE 32): step
n+1 is launched from step n's tokens while they are still on the device.

Two things are held here. The tokens are the unbatched, synchronous
reference's, token for token, for both families and a compressed pool,
whichever way a row ends and whenever it joins. And whatever looks at a
row's blocks, its ``produced`` or its Future with a step in flight finds
them as a loop that had read every step would have left them.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparkdl_tpu.models.afmoe import AfmoeConfig, AfmoeLMHeadModel
from sparkdl_tpu.models.gpt import GPTConfig, GPTLMHeadModel, generate
from sparkdl_tpu.observability import tracing
from sparkdl_tpu.serving import ContinuousGPTEngine, continuous
from sparkdl_tpu.serving.queue import (
    DeadlineExceededError,
    EngineClosedError,
)
from sparkdl_tpu.serving.tenancy import PRIORITY_BACKGROUND, TenantRegistry

MAX_LEN = 64
#: (prompt tokens, budget): three rows on two or three slots, so that rows
#: end at different steps and a slot changes hands
CASES = ((5, 6), (9, 3), (3, 8))
FAMILIES = ("gpt", "gpt-int8", "afmoe")


@pytest.fixture(scope="module")
def bundles():
    """family -> (config, variables, engine arguments, prompts)."""
    out = {}
    ids = jnp.zeros((1, 8), jnp.int32)
    gpt = GPTConfig.tiny()
    gpt_vars = GPTLMHeadModel(gpt).init(jax.random.PRNGKey(0), ids)
    moe = AfmoeConfig.tiny()
    moe_vars = AfmoeLMHeadModel(moe).init(jax.random.PRNGKey(1), ids)
    for name, cfg, variables, kw in (
            ("gpt", gpt, gpt_vars, {}),
            ("gpt-int8", gpt, gpt_vars, {"kv_dtype": "int8"}),
            ("afmoe", moe, moe_vars, {})):
        rng = np.random.default_rng(32)
        prompts = [rng.integers(1, cfg.vocab_size, n).astype(np.int32)
                   for n, _ in CASES]
        if cfg is gpt:
            # its greedy stream opens on distinct ids (the eos case needs
            # a token that FIRST shows mid-stream)
            prompts[0] = np.asarray([16, 93, 39, 11, 38], np.int32)
        out[name] = (cfg, variables, kw, prompts)
    return out


def _engine(bundle, **kw):
    cfg, variables, family_kw, _ = bundle
    kw = {"n_slots": 2, "max_len": MAX_LEN, "kv_block_size": 4,
          "prefill_chunk": 8, "auto_start": False, **family_kw, **kw}
    return ContinuousGPTEngine(cfg, variables, **kw)


def _drain(eng, futs):
    deadline = time.monotonic() + 120
    while not all(f.done() for f in futs):
        assert time.monotonic() < deadline, "engine did not finish"
        eng.tick()


def _tick_and_read(eng):
    """One tick of a SYNCHRONOUS loop: whatever it launched is read and
    retired before the next tick begins, so every step takes the host's
    word for every token."""
    eng.tick()
    eng._settle()


@pytest.fixture(scope="module")
def reference(bundles):
    """family -> each prompt's greedy tokens from the unbatched,
    synchronous loop: one request alone in the engine, every step read
    before the next is launched. For a float32 GPT they are also
    ``generate``'s, the oracle of the existing parity tests."""
    out = {}
    for name, bundle in bundles.items():
        cfg, variables, _, prompts = bundle
        want = []
        for prompt, (_, budget) in zip(prompts, CASES):
            eng = _engine(bundle)
            try:
                fut = eng.submit(prompt, budget)
                while not fut.done():
                    _tick_and_read(eng)
                want.append(fut.result(timeout=0).tolist())
            finally:
                eng.close()
            if name == "gpt":
                oracle = generate(GPTLMHeadModel(cfg), variables,
                                  jnp.asarray(prompt[None]), budget)
                assert want[-1] == np.asarray(
                    oracle[0, len(prompt):]).tolist()
        out[name] = want
    return out


def _until_eos(tokens, eos):
    return tokens[:tokens.index(eos) + 1] if eos in tokens else tokens


@pytest.mark.parametrize("how", ["budget", "eos", "joins_mid_decode",
                                 "chain_2"])
@pytest.mark.parametrize("family", FAMILIES)
def test_tokens_are_the_synchronous_references(bundles, reference, family,
                                               how):
    bundle, want = bundles[family], reference[family]
    prompts = bundle[3]
    kw = {}
    if how == "eos":
        # a token the first row first makes in a decode step, with budget
        # to spare: found one step late, after the row has ridden the
        # step ahead
        eos = next(t for j, t in enumerate(want[0][:-1])
                   if j and t not in want[0][:j])
        want = [_until_eos(w, eos) for w in want]
        kw["eos_id"] = eos
    elif how == "joins_mid_decode":
        kw["n_slots"] = 3
    elif how == "chain_2":
        kw["chain_tokens"] = 2
    eng = _engine(bundle, **kw)
    try:
        futs = [eng.submit(p, b) for p, (_, b) in zip(prompts[:2], CASES)]
        eng.tick()
        (first,) = [f for f in eng._inflight.values()
                    if f.req.future is futs[0]]
        owned = list(first.blocks)
        if how != "eos":
            eng.tick()
            eng.tick()
            assert eng._steps_out and eng.active_slots >= 1
        else:
            while not futs[0].done():
                eng.tick()
            # the row is gone and the step it rode past its eos is out:
            # that step's one column was launched BEFORE the blocks went
            # back, and nothing launched after writes to them (no row is
            # admitted into them before the look below)
            assert first.unread > 0 and eng._steps_out
            held = {n: np.asarray(a)[:, owned]
                    for n, a in eng._pool_kv.items()}
            eng.tick()  # that step is read, its token for the row dropped
            assert futs[0].result(timeout=0).tolist() == want[0]
            for n, a in eng._pool_kv.items():
                np.testing.assert_array_equal(np.asarray(a)[:, owned],
                                              held[n], err_msg=n)
        # a step is out when the third row arrives: its first token is the
        # host's word, the others' are on the device
        futs.append(eng.submit(prompts[2], CASES[2][1]))
        _drain(eng, futs)
        assert [f.result(timeout=0).tolist() for f in futs] == want
        eng._settle()
        # every block went back once (a second release raises), and what
        # is still held is the prefix cache's
        assert eng.active_slots == 0 and not eng._steps_out
        assert eng._pool.used_count == eng._prefix.cached_blocks
    finally:
        eng.close()


# -- with a step in flight -------------------------------------------------------

def _outcome(fut):
    if not fut.done():
        return "pending"
    exc = fut.exception()
    if exc is not None:
        return (type(exc).__name__, str(exc))
    return fut.result().tolist()


def _view(eng, futs):
    """What the host holds of every request: the pool's counts, each live
    row's ``produced``, each Future."""
    ids = {f.request_id: i for i, f in enumerate(futs)}
    return {
        "blocks_used": eng._pool.used_count,
        "blocks_free": eng._pool.free_count,
        "blocks_cached": eng._prefix.cached_blocks,
        "active_slots": eng.active_slots,
        "produced": {ids[f.req.request_id]: list(f.produced)
                     for f in eng._inflight.values()},
        "futures": [_outcome(f) for f in futs],
    }


def _close_now(eng, futs):
    eng.close(drain=False)


def _begin_drain(eng, futs):
    assert [r.future for r in eng.begin_drain()] == [futs[2]]
    # what stays to finish here is what a synchronous loop would hold:
    # the first row has its tokens when the call returns
    return _outcome(futs[0])


def _deadline_expires(eng, futs):
    (flight,) = eng._inflight.values()
    flight.req.deadline = time.monotonic() - 1.0
    eng.tick()


def _park_cold(eng, futs):
    return eng.park_cold()


def _snapshot(eng, futs):
    snap = eng.snapshot()
    return {k: snap[k] for k in ("completed", "failed", "tokens",
                                 "active_slots")} | {
        "blocks_used": snap["kv"]["blocks_used"]}


EVENTS = {"close_now": _close_now, "begin_drain": _begin_drain,
          "deadline_expires": _deadline_expires, "park_cold": _park_cold,
          "snapshot": _snapshot}


@pytest.mark.parametrize("event", sorted(EVENTS))
def test_with_a_step_in_flight_the_host_sees_a_settled_engine(bundles,
                                                              event):
    """Two rows, the first at its last token by count: the loop ahead has
    that token on the device and its row out of its slot, the synchronous
    loop has read it and resolved the Future. After ``event`` both hold
    the same."""
    bundle = bundles["gpt"]
    prompts = bundle[3]
    views = {}
    for loop, tick in (("ahead", ContinuousGPTEngine.tick),
                       ("synchronous", _tick_and_read)):
        eng = _engine(bundle, host_kv_blocks=32)
        try:
            # (5 + 3 prompt tokens: one tick's prefill budget)
            futs = [eng.submit(prompts[0], 3), eng.submit(prompts[2], 8)]
            tick(eng)  # both admitted, their first tokens; step 1
            tick(eng)  # step 2: the first row's last
            futs.append(eng.submit(prompts[1], 4))  # waits in the queue
            if loop == "ahead":
                assert eng._steps_out and not futs[0].done()
            else:
                assert not eng._steps_out and futs[0].done()
            said = EVENTS[event](eng, futs)
            # whatever the event's own tick launched is read on both sides
            eng._settle()
            views[loop] = (said, _view(eng, futs))
        finally:
            if event != "close_now":
                eng.close(drain=False)
    assert views["ahead"] == views["synchronous"]
    said, view = views["ahead"]
    assert view["futures"][0] == [int(t) for t in view["futures"][0]]
    if event == "close_now":
        assert view["futures"][1][0] == EngineClosedError.__name__
    elif event == "deadline_expires":
        assert view["futures"][1] == (
            DeadlineExceededError.__name__,
            "deadline exceeded mid-decode (3/8 tokens)")
    elif event == "park_cold":
        assert said > 0
    elif event == "snapshot":
        assert said["completed"] == 1 and said["tokens"] == 3 + 3


def test_a_preemption_finds_the_step_in_flight_read(bundles, monkeypatch):
    """A background prefill is torn down for an interactive arrival while
    another row decodes one step ahead on the other slot: at the teardown
    nothing is out, and pool, ``produced`` and Futures are the synchronous
    loop's."""
    bundle = bundles["gpt"]
    cfg, prompts = bundle[0], bundle[3]
    bg_prompt = np.random.default_rng(20).integers(1, cfg.vocab_size, 12)
    seen = {}
    note = continuous.tenancy.note_preemption
    for loop, tick in (("ahead", ContinuousGPTEngine.tick),
                       ("synchronous", _tick_and_read)):
        reg = TenantRegistry()
        reg.configure("offline", priority=PRIORITY_BACKGROUND)
        eng = _engine(bundle, prefill_chunk=4, tenants=reg)
        try:
            futs = [eng.submit(prompts[2], 8, tenant="acme")]
            tick(eng)  # admitted in one chunk, first token, step 1
            futs.append(eng.submit(bg_prompt, 4, tenant="offline"))
            tick(eng)  # the background prompt's first chunk; step 2
            assert eng._prefilling and eng.active_slots == 1
            assert bool(eng._steps_out) == (loop == "ahead")
            futs.append(eng.submit(prompts[0], 2, tenant="acme"))

            def noted(eng=eng, futs=futs, loop=loop):
                seen[loop] = (len(eng._steps_out), _view(eng, futs))
                note()

            monkeypatch.setattr(continuous.tenancy, "note_preemption", noted)
            tick(eng)  # both slots taken, an interactive row waits: preempt
            assert loop in seen
            _drain(eng, futs)
        finally:
            monkeypatch.setattr(continuous.tenancy, "note_preemption", note)
            eng.close()
    assert seen["ahead"][0] == 0
    assert seen["ahead"] == seen["synchronous"]
    assert seen["ahead"][1]["produced"] == {0: seen["ahead"][1]["produced"][0]}
    assert len(seen["ahead"][1]["produced"][0]) == 3


# -- what the spans and the counter say --------------------------------------------

def _ahead_counter():
    fam = continuous.registry().get("sparkdl_serving_decode_ahead_total")
    values = fam.snapshot_values() if fam else {}
    return {a: values.get(f'ahead="{a}"', 0.0) for a in ("0", "1")}


def _traced_run(bundle, tick):
    """CASES through a two-slot engine driven by ``tick``; the spans it
    left, request ids replaced by the request's place in CASES."""
    tracing.clear_trace()
    eng = _engine(bundle)
    try:
        futs = [eng.submit(p, b) for p, (_, b) in zip(bundle[3], CASES)]
        while not all(f.done() for f in futs):
            tick(eng)
        eng._settle()
    finally:
        eng.close()
    place = {f.request_id: i for i, f in enumerate(futs)}
    spans = sorted(tracing.trace_events(), key=lambda e: e["ts"])
    for e in spans:
        if "links" in e["args"]:
            e["args"]["links"] = [place[r] for r in e["args"]["links"]]
    return [f.result(timeout=0).tolist() for f in futs], spans


@pytest.mark.parametrize("family", ["gpt", "afmoe"])
def test_each_step_says_what_it_was_and_the_counter_adds_up(bundles, family):
    """Step for step the loop ahead does what the synchronous loop does,
    and each ``serving.decode_step`` carries the numbers of ITS step (rows,
    links, depth, gathers and, for an expert family, what its experts were
    given, which come back with its ids), ending when its ids were read."""
    def named(spans, name):
        return [e for e in spans if e["name"] == name]

    def end(e):
        return e["ts"] + e["dur"]

    tracing.enable_tracing()
    try:
        before = _ahead_counter()
        tokens, spans = _traced_run(bundles[family], ContinuousGPTEngine.tick)
        counted = {a: n - before[a] for a, n in _ahead_counter().items()}
        want_tokens, want_spans = _traced_run(bundles[family],
                                              _tick_and_read)
    finally:
        tracing.disable_tracing()
        tracing.clear_trace()
    assert tokens == want_tokens
    steps, launches, waits, retires = (
        named(spans, "serving." + n) for n in
        ("decode_step", "decode_dispatch", "decode_wait", "retire"))
    keys = {"slots", "chain", "links", "nb", "kv_cols_read", "kv_cols_live"}
    if family == "afmoe":
        keys |= {"expert_rows", "experts_hit", "expert_rows_max",
                 "kv_cols_read_window", "kv_cols_read_full",
                 "kv_cols_live_window"}
    def told(steps):
        # (an idle slot's row goes through the experts too, on whatever
        # token the slot last held: only a full step's counts are the
        # requests' own)
        return [{k: e["args"][k] for k in keys
                 if e["args"]["slots"] == 2 or "expert" not in k}
                for e in steps]

    assert sum(e["args"]["slots"] == 2 for e in steps) >= 2
    assert told(steps) == told(named(want_spans, "serving.decode_step"))
    assert len(launches) == len(waits) == len(retires) == len(steps)
    for n, (step, launch, wait, retire) in enumerate(
            zip(steps, launches, waits, retires)):
        # from its launch to the read of ITS ids, and its rows retire next
        assert step["ts"] <= launch["ts"] and end(wait) <= end(step)
        assert end(step) <= retire["ts"]
        assert retire["args"]["links"] == step["args"]["links"]
        assert retire["args"]["tokens"] == step["args"]["slots"]
        if n:
            # ahead: launched before the step before it was waited for
            assert launch["args"]["ahead"] == int(
                launch["ts"] < waits[n - 1]["ts"])
    # only a row-set's first step finds nothing out: the very first, and
    # one where every earlier row had had its last step launched
    said = [e["args"]["ahead"] for e in launches]
    assert said[0] == 0 and sum(said) >= len(said) - 2
    assert all(e["args"]["ahead"] == 0
               for e in named(want_spans, "serving.decode_dispatch"))
    assert counted == {"0": float(said.count(0)), "1": float(sum(said))}
