"""Speculative multi-token decoding: exact greedy acceptance.

The contract (ROADMAP item 3): speculation is a DISPATCH-count
decision, never a quality decision — accepted tokens are
bitwise-identical to one-token-at-a-time paged decode and the unbatched
``generate`` oracle at every draft length, through rejection at
position 0, EOS inside an accepted span, budget/deadline shrinking, and
an injected verify failure (which must fall back to plain decode with
zero lost requests).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import oracle
from sparkdl_tpu.models.gpt import GPTConfig, GPTLMHeadModel
from sparkdl_tpu.observability.registry import registry
from sparkdl_tpu.reliability.faults import inject
from sparkdl_tpu.runtime.dispatch import SpecPolicy, dispatch_count
from sparkdl_tpu.serving import ContinuousGPTEngine
from sparkdl_tpu.serving.kv_blocks import KVBlockPool
from sparkdl_tpu.serving.prefix_cache import PrefixCache
from sparkdl_tpu.serving.spec_decode import (
    ChainedDraftSource,
    NGramDraftSource,
    PrefixCacheDraftSource,
    greedy_accept,
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
    kw.setdefault("kv_block_size", 4)
    kw.setdefault("prefill_chunk", 8)
    return ContinuousGPTEngine(cfg, variables, **kw)


def _drain(eng, futs):
    while not all(f.done() for f in futs):
        eng.tick()


def _counter(name):
    fam = registry().snapshot().get(name)
    if fam is None:
        return 0.0
    return sum(fam["values"].values())


class _OracleDraft:
    """Perfect proposer: drafts the request's true greedy continuation
    (every position accepts) — the deterministic upper bound."""

    def __init__(self, model, variables):
        self.model = model
        self.variables = variables
        self._memo = {}

    def propose(self, context, k):
        key = tuple(int(t) for t in context)
        if key not in self._memo:
            self._memo[key] = [int(t) for t in oracle(
                self.model, self.variables, list(key), k)]
        return self._memo[key][:k]


class _WrongDraft:
    """Adversarial proposer: every draft token differs from the true
    greedy continuation — every verify rejects at position 0."""

    def __init__(self, oracle_draft, vocab):
        self._oracle = oracle_draft
        self._vocab = vocab

    def propose(self, context, k):
        right = self._oracle.propose(context, k)
        return [(t + 1) % self._vocab for t in right]


# -- the token-identity contract ---------------------------------------------

@pytest.mark.parametrize("spec_k", [2, 4, 8])
def test_spec_bitwise_vs_plain_and_oracle(bundle, spec_k):
    """Greedy tokens under speculation (default trie+n-gram proposer)
    must be bitwise-identical to the k=1 paged engine AND the unbatched
    oracle at every draft length — including a repetitive prompt (high
    acceptance) and mid-stream joins."""
    cfg, model, variables = bundle
    cases = [
        ([5, 3, 9, 2, 7], 12),
        ([6, 8, 6, 1, 6, 8, 6, 1], 10),  # periodic: n-gram hits
        ([1, 4], 8),
    ]
    outs = {}
    for spec in (None, spec_k):
        eng = _engine(cfg, variables, spec_k=spec)
        futs = [eng.submit(p, n) for p, n in cases[:2]]
        _drain(eng, futs)
        futs.append(eng.submit(*cases[2]))  # joins after the others left
        _drain(eng, [futs[2]])
        eng.close()
        outs[spec] = [f.result(timeout=0) for f in futs]
    for (prompt, max_new), got_s, got_p in zip(
            cases, outs[spec_k], outs[None]):
        want = oracle(model, variables, prompt, max_new)
        np.testing.assert_array_equal(
            got_s, want,
            err_msg=f"spec_k={spec_k} diverged from oracle: {prompt}")
        np.testing.assert_array_equal(
            got_s, got_p,
            err_msg=f"spec_k={spec_k} diverged from k=1: {prompt}")


def test_perfect_drafts_cut_decode_dispatches(bundle):
    """With every draft accepted, a max_new=9 request (1 prefill token +
    8 decode) at spec_k=4 costs 8/4 = 2 verify dispatches instead of 8
    plain steps — the whole point of the tentpole."""
    cfg, model, variables = bundle
    eng = _engine(cfg, variables,
                  spec_k=4, draft_source=_OracleDraft(model, variables))
    before = dispatch_count("decode")
    fut = eng.submit([5, 3, 9], 9)
    _drain(eng, [fut])
    eng.close()
    assert dispatch_count("decode") - before == 2
    np.testing.assert_array_equal(
        fut.result(timeout=0), oracle(model, variables, [5, 3, 9], 9))
    snap = eng._spec_snapshot()
    assert snap["dispatches"] == 2
    assert snap["acceptance_rate"] == 1.0
    assert snap["tokens_per_dispatch"] == 4.0


def test_draft_rejected_at_position_0(bundle):
    """An adversarial proposer whose every draft is wrong: each verify
    still yields exactly its one real token, the stream stays
    oracle-exact, and nothing is ever accepted."""
    cfg, model, variables = bundle
    oracle_src = _OracleDraft(model, variables)
    eng = _engine(cfg, variables, spec_k=4,
                  draft_source=_WrongDraft(oracle_src, cfg.vocab_size))
    fut = eng.submit([5, 3, 9, 2, 7], 10)
    _drain(eng, [fut])
    eng.close()
    np.testing.assert_array_equal(
        fut.result(timeout=0),
        oracle(model, variables, [5, 3, 9, 2, 7], 10))
    assert eng._spec_dispatches >= 1
    assert eng._spec_accepted == 0
    assert eng._spec_proposed > 0


def test_eos_inside_accepted_span_truncates_and_frees(bundle):
    """EOS produced mid-span by an all-accepted verify: the tokens past
    it are dropped, the Future resolves at the EOS, and the slot frees
    in that same tick — one verify dispatch end to end."""
    cfg, model, variables = bundle
    prompt = [16, 93, 39, 11, 38]  # its greedy stream opens on distinct ids
    want = oracle(model, variables, prompt, 8)
    eos = int(want[3])  # inside the first spec_k=8 accepted span
    assert eos not in want[:3], want  # the premise: eos FIRST fires at 4
    eng = _engine(cfg, variables, eos_id=eos, spec_k=8,
                  draft_source=_OracleDraft(model, variables))
    before = dispatch_count("decode")
    fut = eng.submit(prompt, 8)
    _drain(eng, [fut])
    np.testing.assert_array_equal(fut.result(timeout=0), want[:4])
    assert eng.active_slots == 0
    assert dispatch_count("decode") - before == 1
    eng.close()


def test_budget_bounds_verify_width(bundle):
    """spec_k=8 against a max_new=3 request: the verify width must cut
    to the remaining budget (2 after the prefill token), retiring the
    row on schedule in ONE dispatch."""
    cfg, model, variables = bundle
    eng = _engine(cfg, variables, spec_k=8,
                  draft_source=_OracleDraft(model, variables))
    before = dispatch_count("decode")
    fut = eng.submit([5, 3, 9, 2, 7], 3)
    eng.tick()
    assert fut.done()
    assert dispatch_count("decode") - before == 1
    np.testing.assert_array_equal(
        fut.result(timeout=0),
        oracle(model, variables, [5, 3, 9, 2, 7], 3))
    eng.close()


def test_deadline_shrinks_spec_to_single_token_mid_stream(bundle):
    """A tight in-flight deadline must pull the verify width below 2 —
    speculation stands down and the tick serves plain single-token
    decode (cold engines probe at k=1; measured engines bound by the
    per-token estimate), so a request can never expire inside a wide
    verify it could have survived."""
    cfg, model, variables = bundle
    eng = _engine(cfg, variables, spec_k=8,
                  draft_source=_OracleDraft(model, variables))
    assert eng._chain_policy.program_s is None
    fut = eng.submit([3, 4], 9, timeout_s=30.0)
    eng.tick()  # cold + deadline: probe at k=1, no spec dispatch
    assert eng._spec_dispatches == 0
    flight = next(iter(eng._inflight.values()))
    assert len(flight.produced) == 2  # prefill token + ONE probed token
    # mid-stream: a measured per-token time far beyond the headroom
    # must keep the width at 1 on every later tick too
    eng._chain_policy.program_s = 10.0
    n = len(flight.produced)
    eng.tick()
    assert eng._spec_dispatches == 0
    assert len(flight.produced) == n + 1
    # restored headroom re-enables speculation mid-stream
    eng._chain_policy.program_s = 1e-6
    eng.tick()
    assert eng._spec_dispatches == 1
    _drain(eng, [fut])
    eng.close()
    np.testing.assert_array_equal(
        fut.result(timeout=0), oracle(model, variables, [3, 4], 9))


# -- chaos: the spec.verify fault site ---------------------------------------

def test_injected_verify_failure_falls_back_single_token(bundle):
    """An armed spec.verify site (simulating a failed verify dispatch)
    must degrade that tick to plain decode: zero lost requests, tokens
    still oracle-exact, fallbacks counted in the spine."""
    cfg, model, variables = bundle
    cases = [([5, 3, 9, 2, 7], 9), ([1, 4], 7)]
    fb0 = _counter("sparkdl_spec_fallbacks_total")
    with inject("spec.verify:RuntimeError@1*2"):
        eng = _engine(cfg, variables, spec_k=4,
                      draft_source=_OracleDraft(model, variables))
        futs = [eng.submit(p, n) for p, n in cases]
        _drain(eng, futs)
        eng.close()
    for (prompt, max_new), fut in zip(cases, futs):
        np.testing.assert_array_equal(
            fut.result(timeout=0),
            oracle(model, variables, prompt, max_new))
    assert eng._spec_fallbacks == 2
    assert eng._spec_dispatches >= 1  # speculation resumed after
    assert _counter("sparkdl_spec_fallbacks_total") == fb0 + 2


# -- proposers ---------------------------------------------------------------

def test_greedy_accept_rule():
    assert greedy_accept([7, 8, 9], [7, 8, 9]) == 3
    assert greedy_accept([7, 8, 9], [7, 8, 1]) == 2
    assert greedy_accept([7, 8, 9], [1, 8, 9]) == 0
    assert greedy_accept([], [5]) == 0


def test_ngram_draft_source_proposes_repetition():
    src = NGramDraftSource(max_n=3)
    ctx = np.asarray([4, 9, 1, 2, 3, 7, 5, 1, 2, 3], np.int32)
    # trailing [1, 2, 3] occurred at position 2: propose what followed
    assert src.propose(ctx, 2) == [7, 5]
    # recency wins: the LATEST earlier occurrence donates
    ctx2 = np.asarray([1, 2, 5, 8, 1, 2, 6, 1, 2], np.int32)
    assert src.propose(ctx2, 1) == [6]
    assert src.propose(np.asarray([3, 4, 5], np.int32), 4) == []


def test_prefix_cache_draft_source_suggests_cached_continuation():
    pool = KVBlockPool(8, 4)
    cache = PrefixCache(pool)
    blocks = pool.allocate(3)
    cache.register(tuple([5, 3, 9, 2, 7, 11, 4, 8, 1, 6]), blocks)
    src = PrefixCacheDraftSource(cache)
    # context mid-block: the cached prompt's tail is the draft
    assert src.propose(np.asarray([5, 3, 9, 2, 7, 11]), 4) == [4, 8, 1, 6]
    # block-aligned context walks children then partials
    assert src.propose(np.asarray([5, 3, 9, 2]), 8) == [7, 11, 4, 8, 1, 6]
    assert src.propose(np.asarray([5, 3, 1]), 4) == []
    assert cache.pool.refcount(blocks[0]) == 1  # drafting never refs
    pool.close()


def test_chained_draft_source_first_nonempty_wins():
    class A:
        def propose(self, ctx, k):
            return []

    class B:
        def propose(self, ctx, k):
            return [42]

    assert ChainedDraftSource(A(), B()).propose(
        np.asarray([1]), 2) == [42]
    assert ChainedDraftSource(A(), A()).propose(
        np.asarray([1]), 2) == []


def test_spec_policy_adapts_width_to_acceptance():
    pol = SpecPolicy(max_k=8)
    assert pol.spec_len() == 8  # optimistic cold start
    for _ in range(8):
        pol.record(7, 7)  # perfect acceptance
    assert pol.spec_len() == 8
    for _ in range(20):
        pol.record(7, 0)  # acceptance collapses
    assert pol.spec_len() == 1  # drafting stood down
    for _ in range(30):
        pol.record(7, 5)  # recovers to ~0.7
    assert pol.spec_len() in (2, 4)
    assert SpecPolicy(max_k=1).spec_len() == 1


# -- metrics -----------------------------------------------------------------

def test_spec_metrics_land_in_registry_and_snapshot(bundle):
    cfg, model, variables = bundle
    p0 = _counter("sparkdl_spec_proposed_total")
    a0 = _counter("sparkdl_spec_accepted_total")
    eng = _engine(cfg, variables, spec_k=4,
                  draft_source=_OracleDraft(model, variables))
    fut = eng.submit([5, 3, 9], 9)
    _drain(eng, [fut])
    snap = eng.snapshot()
    eng.close()
    assert _counter("sparkdl_spec_proposed_total") - p0 == 6
    assert _counter("sparkdl_spec_accepted_total") - a0 == 6
    spec = snap["spec"]
    assert spec["proposed"] == 6 and spec["accepted"] == 6
    assert spec["acceptance_rate"] == 1.0
    rate = registry().snapshot().get("sparkdl_spec_acceptance_rate")
    assert rate is not None and 0 < max(rate["values"].values()) <= 1
