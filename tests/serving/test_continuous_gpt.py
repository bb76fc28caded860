"""Continuous-batching GPT engine: the join/leave token-identity oracle
plus serving edge cases (deadline mid-decode, capacity rejects, drain).

The oracle is the whole point of the design: rows joining and leaving an
in-flight decode batch must produce greedy tokens IDENTICAL to their
unbatched ``generate`` decode — continuous batching is scheduling, not
approximation.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import oracle
from sparkdl_tpu.models.gpt import GPTConfig, GPTLMHeadModel
from sparkdl_tpu.serving import (
    ContinuousGPTEngine,
    DeadlineExceededError,
    EngineClosedError,
    QueueFullError,
)

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


def test_join_leave_oracle_manual_ticks(bundle):
    """Requests join mid-stream (staggered submits, fewer slots than
    requests) and leave at different depths; every row must match its
    unbatched decode."""
    cfg, model, variables = bundle
    eng = _engine(cfg, variables)
    cases = [
        ([5, 3, 9, 2, 7], 6),
        ([1, 4], 3),          # joins after A is mid-decode, leaves early
        ([6, 8, 6, 8, 6], 5),  # takes the slot B frees
        ([2, 2, 2], 4),
    ]
    futs = [eng.submit(p, n) for p, n in cases[:2]]
    # the paged loop reads a step's ids a tick after it launched the step
    eng.tick()              # admit A+B, first tokens; step 1 launched
    eng.tick()              # step 2 launched; step 1 read (2 tokens each)
    assert not futs[1].done()
    eng.tick()              # 3rd tokens: B (max_new=3) leaves, A decodes on
    assert futs[1].done() and not futs[0].done()
    futs.append(eng.submit(*cases[2]))
    assert eng.queue.depth == 1          # C waits for the tick to admit
    eng.tick()                           # C joins the slot B freed, mid-A
    assert eng.active_slots == 2 and not futs[0].done()
    futs.append(eng.submit(*cases[3]))
    while not all(f.done() for f in futs):
        eng.tick()
    eng.close()
    for (prompt, max_new), fut in zip(cases, futs):
        got = fut.result(timeout=0)
        want = oracle(model, variables, prompt, max_new)
        np.testing.assert_array_equal(
            got, want, err_msg=f"prompt {prompt} diverged from unbatched"
        )


@pytest.mark.parametrize("n_engines", [1, 2])
def test_threaded_engine_oracle_and_drain(bundle, n_engines):
    """Background-thread mode: async submits, close(drain=True) finishes
    every admitted request. Two engines of one configuration run the SAME
    bound programs (``paged_programs.bound``), each on its own thread and
    donating its own pool: what is shared is code, never a buffer."""
    cfg, model, variables = bundle
    engines = [ContinuousGPTEngine(cfg, variables, n_slots=2,
                                   max_len=MAX_LEN, idle_wait_s=0.001)
               for _ in range(n_engines)]
    cases = [([7, 1, 3], 5), ([2, 9], 4), ([4, 4, 4, 4], 6), ([8], 3)]
    futs = [[] for _ in engines]
    for p, n in cases:
        for eng, mine in zip(engines, futs):
            mine.append(eng.submit(p, n))
        time.sleep(0.01)  # stagger arrivals into the running decode
    for a, b in zip(engines, engines[1:]):
        assert a._paged_step_fn.__wrapped__ is b._paged_step_fn.__wrapped__
        assert a._chunk_one_fn.__wrapped__ is b._chunk_one_fn.__wrapped__
        assert a._pool_kv is not b._pool_kv
    for eng in engines:
        eng.close(drain=True)  # shutdown with inflight + queued requests
    for eng, mine in zip(engines, futs):
        for (prompt, max_new), fut in zip(cases, mine):
            np.testing.assert_array_equal(
                fut.result(timeout=0),
                oracle(model, variables, prompt, max_new),
                err_msg=f"prompt {prompt}",
            )
        snap = eng.snapshot()
        assert snap["completed"] == len(cases)
        assert snap["active_slots"] == 0
        assert snap["latency_s"]["p99"] is not None
        assert 0 < snap["batch_occupancy_pct"] <= 100


def test_eos_frees_slot_early(bundle):
    cfg, model, variables = bundle
    prompt = [16, 93, 39, 11, 38]  # its greedy stream opens on distinct ids
    want = oracle(model, variables, prompt, 8)
    eos = int(want[2])  # third generated token becomes the stop token
    assert eos not in want[:2], want  # the premise: eos FIRST fires at 3
    eng = _engine(cfg, variables, eos_id=eos)
    fut = eng.submit(prompt, 8)
    while not fut.done():
        eng.tick()
    got = fut.result(timeout=0)
    np.testing.assert_array_equal(got, want[:3])  # stops AT the eos
    assert eng.active_slots == 0  # slot freed
    eng.close()


def test_deadline_expiry_mid_decode(bundle):
    from sparkdl_tpu.observability.registry import registry

    def _expired_count():
        fam = registry().get("sparkdl_requests_failed_total")
        if fam is None:
            return 0.0
        return fam.labelled_values("reason").get("expired", 0.0)

    cfg, _, variables = bundle
    eng = _engine(cfg, variables)
    expired0 = _expired_count()
    fut = eng.submit([1, 2, 3], 20, timeout_s=0.01)
    eng.tick()  # admitted into a slot
    assert eng.active_slots == 1
    time.sleep(0.05)
    eng.tick()  # expiry sweep cancels it and frees the slot
    with pytest.raises(DeadlineExceededError, match="mid-decode"):
        fut.result(timeout=0)
    assert eng.active_slots == 0
    assert eng.snapshot()["failed"] == 1
    # a mid-decode expiry is shed load too: it must land in the
    # registry alongside queue-level expiries
    assert _expired_count() == expired0 + 1
    eng.close()


def test_deadline_expiry_mid_queue(bundle):
    cfg, model, variables = bundle
    eng = _engine(cfg, variables, n_slots=1)
    blocker = eng.submit([9, 9], 6)
    doomed = eng.submit([1, 1], 6, timeout_s=0.01)
    eng.tick()  # blocker takes the only slot; doomed waits in queue
    time.sleep(0.05)
    while not blocker.done():
        eng.tick()
    with pytest.raises(DeadlineExceededError):
        doomed.result(timeout=0)
    np.testing.assert_array_equal(
        blocker.result(timeout=0), oracle(model, variables, [9, 9], 6)
    )
    eng.close()


def test_backpressure_and_capacity_rejects(bundle):
    cfg, _, variables = bundle
    eng = _engine(cfg, variables, max_queue_depth=2)
    # cache capacity: bucketed prompt + budget must fit max_len
    with pytest.raises(ValueError, match="exceeds cache max_len"):
        eng.submit(list(range(8)), MAX_LEN)
    with pytest.raises(ValueError, match="non-empty 1-D"):
        eng.submit([], 4)
    with pytest.raises(ValueError, match="max_new_tokens"):
        eng.submit([1], 0)
    eng.submit([1], 2)
    eng.submit([2], 2)
    with pytest.raises(QueueFullError):
        eng.submit([3], 2)
    assert eng.snapshot()["rejected"] == 1
    eng.close()  # drains the two admitted requests


def test_non_graceful_close_fails_inflight_and_queued(bundle):
    cfg, _, variables = bundle
    eng = _engine(cfg, variables, n_slots=1)
    inflight = eng.submit([1, 2], 10)
    queued = eng.submit([3, 4], 10)
    eng.tick()
    eng.close(drain=False)
    with pytest.raises(EngineClosedError):
        inflight.result(timeout=0)
    with pytest.raises(EngineClosedError):
        queued.result(timeout=0)
    with pytest.raises(EngineClosedError):
        eng.submit([5], 2)


@pytest.mark.slow
def test_soak_many_requests_random_arrivals(bundle):
    """Soak: 24 ragged requests trickle into a 4-slot threaded engine;
    every output must match its unbatched decode."""
    cfg, model, variables = bundle
    rng = np.random.default_rng(0)
    eng = ContinuousGPTEngine(cfg, variables, n_slots=4, max_len=MAX_LEN,
                              idle_wait_s=0.001)
    cases, futs = [], []
    for _ in range(24):
        prompt = rng.integers(1, cfg.vocab_size, rng.integers(1, 9)).tolist()
        max_new = int(rng.integers(1, 8))
        cases.append((prompt, max_new))
        futs.append(eng.submit(prompt, max_new))
        time.sleep(float(rng.uniform(0, 0.01)))
    eng.close(drain=True)
    for (prompt, max_new), fut in zip(cases, futs):
        np.testing.assert_array_equal(
            fut.result(timeout=0),
            oracle(model, variables, prompt, max_new),
            err_msg=f"prompt {prompt} x{max_new}",
        )
    assert eng.snapshot()["completed"] == 24
