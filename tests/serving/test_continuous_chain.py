"""Chained continuous decode: k tokens per dispatch must keep the greedy
token-identity oracle (chaining is scheduling, never approximation), cut
the decode dispatch counter ~k*, respect remaining-budget bounds so no
retirement is delayed, and collapse to k=1 under a tight deadline.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import oracle
from sparkdl_tpu.models.gpt import GPTConfig, GPTLMHeadModel
from sparkdl_tpu.runtime.dispatch import dispatch_count
from sparkdl_tpu.serving import ContinuousGPTEngine

MAX_LEN = 32


@pytest.fixture(scope="module")
def bundle():
    cfg = GPTConfig.tiny()
    model = GPTLMHeadModel(cfg)
    variables = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )
    return cfg, model, variables


def _engine(cfg, variables, **kw):
    kw.setdefault("n_slots", 2)
    kw.setdefault("max_len", MAX_LEN)
    kw.setdefault("auto_start", False)
    return ContinuousGPTEngine(cfg, variables, **kw)


@pytest.mark.parametrize("chain_tokens", [2, 4])
def test_chained_greedy_tokens_oracle_identical(bundle, chain_tokens):
    cfg, model, variables = bundle
    eng = _engine(cfg, variables, chain_tokens=chain_tokens)
    cases = [([5, 3, 9, 2, 7], 9), ([1, 4], 7), ([6, 8, 6], 5)]
    futs = [eng.submit(p, n) for p, n in cases[:2]]
    while not all(f.done() for f in futs):
        eng.tick()
    futs.append(eng.submit(*cases[2]))  # joins after the others left
    while not futs[2].done():
        eng.tick()
    eng.close()
    for (prompt, max_new), fut in zip(cases, futs):
        np.testing.assert_array_equal(
            fut.result(timeout=0),
            oracle(model, variables, prompt, max_new),
            err_msg=f"prompt {prompt} diverged under chain_tokens="
                    f"{chain_tokens}",
        )


def test_decode_dispatch_count_drops_k_fold(bundle):
    cfg, _, variables = bundle
    # 1 prefill token + 8 decode tokens per request
    for k, want_decode_dispatches in ((1, 8), (4, 2)):
        eng = _engine(cfg, variables, chain_tokens=k)
        before = dispatch_count("decode")
        fut = eng.submit([5, 3, 9], 9)
        while not fut.done():
            eng.tick()
        eng.close()
        got = dispatch_count("decode") - before
        assert got == want_decode_dispatches, (k, got)


def test_budget_bound_never_delays_retirement(bundle):
    # max_new=3 (1 prefill + 2 decode): a fixed chain of 8 must be cut to
    # the remaining budget, so the row retires exactly on schedule and
    # only 2 decode tokens are ever produced
    cfg, model, variables = bundle
    eng = _engine(cfg, variables, chain_tokens=8)
    before = dispatch_count("decode")
    fut = eng.submit([5, 3, 9, 2, 7], 3)
    eng.tick()  # admit + one chained decode dispatch of exactly k=2
    # its last tokens by count: the slot is free, the Future not yet done
    (step,) = eng._steps_out
    assert step.attrs["chain"] == 2 and eng.active_slots == 0
    assert not fut.done()
    eng.tick()  # nothing left to launch: its ids are read, the row retires
    assert fut.done()
    assert dispatch_count("decode") - before == 1
    np.testing.assert_array_equal(
        fut.result(timeout=0), oracle(model, variables, [5, 3, 9, 2, 7], 3)
    )
    eng.close()


def test_eos_mid_chain_truncates_and_frees_slot(bundle):
    cfg, model, variables = bundle
    prompt = [16, 93, 39, 11, 38]  # its greedy stream opens on distinct ids
    want = oracle(model, variables, prompt, 8)
    eos = int(want[2])  # fires mid-chain at chain_tokens=4
    assert eos not in want[:2], want  # the premise: eos FIRST fires at 3
    eng = _engine(cfg, variables, eos_id=eos, chain_tokens=4)
    fut = eng.submit(prompt, 8)
    while not fut.done():
        eng.tick()
    np.testing.assert_array_equal(fut.result(timeout=0), want[:3])
    assert eng.active_slots == 0  # freed despite finishing mid-chain
    eng.close()


def test_cold_first_dispatch_with_deadline_probes_at_k1(bundle):
    # before ANY per-token measurement exists, an in-flight deadline must
    # force the first decode dispatch down to k=1 (the measurement probe)
    # — a request may never expire inside an unmeasured chain
    cfg, _, variables = bundle
    eng = _engine(cfg, variables, chain_tokens=8)
    assert eng._chain_policy.program_s is None
    fut = eng.submit([3, 4], 9, timeout_s=30.0)
    eng.tick()
    flight = next(iter(eng._inflight.values()))
    # prefill token, and ONE probed token on its way
    assert (len(flight.produced), flight.unread) == (1, 1)
    eng.tick()  # still unmeasured when the next is launched: one again
    assert (len(flight.produced), flight.unread) == (2, 1)
    assert not fut.done()
    eng.close(drain=False)


def test_tight_deadline_bounds_chain_len(bundle):
    cfg, _, variables = bundle
    eng = _engine(cfg, variables, chain_tokens=8)
    # warm the per-token estimate with a deadline-free request
    fut = eng.submit([1, 2], 5)
    while not fut.done():
        eng.tick()
    assert eng._chain_policy.program_s is not None
    # a deadline tighter than 2x one measured token forces k=1
    tok_s = eng._chain_policy.program_s
    fut = eng.submit([3, 4], 9, timeout_s=max(tok_s, 1e-4))
    eng.tick()  # admission + first decode dispatch
    flight = next(iter(eng._inflight.values()), None)
    if flight is not None:  # not already expired on a slow host
        # prefill produced 1; a bounded dispatch launches exactly 1 token
        assert (len(flight.produced), flight.unread) == (1, 1)
    eng.close(drain=False)


def test_the_chain_policy_is_fed_the_pace_of_the_loop_ahead(bundle,
                                                             monkeypatch):
    """The paged loop reads a step a tick after it launched it, so a
    launch-to-read wall holds two steps. What ``ChainPolicy.record`` and
    ``record_dispatch`` get is the interval between two successive reads,
    one step's worth, so ``chain_tokens=None`` still sizes its chains by
    what a step costs: here a step is made to cost 50 ms (a sleep after
    each launch, the host going on underneath)."""
    from sparkdl_tpu.serving import continuous

    cfg, model, variables = bundle
    step_s = 0.05
    eng = _engine(cfg, variables, chain_tokens=1)
    launch = eng._launch_step
    walls = []

    def slow_launch(ahead):
        launch(ahead)
        time.sleep(step_s)

    def record(path, k, wall_s=None):
        walls.append((path, k, wall_s))

    monkeypatch.setattr(eng, "_launch_step", slow_launch)
    monkeypatch.setattr(continuous, "record_dispatch", record)
    try:
        fut = eng.submit([5, 3, 9, 2, 7], 10)
        while not fut.done():
            eng.tick()
    finally:
        eng.close()
    assert [(p, k) for p, k, _ in walls] == [("decode", 1)] * 9
    # the first step was launched with nothing out: launch to read, which
    # is its own tick's sleep and the next tick's
    assert walls[0][2] >= 2 * step_s
    for _, _, wall in walls[1:]:
        assert step_s <= wall < 2 * step_s, walls
    est = eng._chain_policy.program_s
    assert 0.7 * step_s < est < 2 * step_s
    np.testing.assert_array_equal(
        fut.result(timeout=0), oracle(model, variables, [5, 3, 9, 2, 7], 10))


def test_threaded_engine_with_chaining(bundle):
    cfg, model, variables = bundle
    eng = ContinuousGPTEngine(
        cfg, variables, n_slots=2, max_len=MAX_LEN,
        idle_wait_s=0.001, chain_tokens=4,
    )
    cases = [([7, 1, 3], 6), ([2, 9], 5), ([4, 4, 4, 4], 7), ([8], 4)]
    futs = []
    for p, n in cases:
        futs.append(eng.submit(p, n))
        time.sleep(0.005)
    eng.close(drain=True)
    for (prompt, max_new), fut in zip(cases, futs):
        np.testing.assert_array_equal(
            fut.result(timeout=0),
            oracle(model, variables, prompt, max_new),
            err_msg=f"prompt {prompt}",
        )
    assert eng.snapshot()["completed"] == len(cases)


def test_chain_tokens_validation(bundle):
    cfg, _, variables = bundle
    with pytest.raises(ValueError, match="chain_tokens"):
        _engine(cfg, variables, chain_tokens=0)
