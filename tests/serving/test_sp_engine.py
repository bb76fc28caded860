"""Sequence-parallel serving engine (ISSUE 13): spatial prefill chunks
at sp=2 on the conftest CPU mesh, pinned bitwise against sp=1
and the unbatched oracle — across prefix hits, COW tails,
chained decode, speculative decode, and quantized pools — plus the
prefill→decode handoff bookkeeping and the sp.permute/sp.gather chaos
contract (injected collective fault → typed flight event, request
re-queued, zero lost)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import oracle
from sparkdl_tpu.models.gpt import GPTConfig, GPTLMHeadModel
from sparkdl_tpu.observability.flight import flight_recorder
from sparkdl_tpu.reliability import faults
from sparkdl_tpu.serving import ContinuousGPTEngine

MAX_LEN = 64
SHARED = [5, 3, 9, 2, 7, 11, 4, 8]
CASES = [
    (SHARED + [1, 6], 6),
    (SHARED + [2, 2, 9], 5),       # prefix hit
    ([6, 8, 6], 4),                # no shared prefix
    (SHARED + [1, 6], 3),          # full-prompt hit
    (list(range(1, 20)), 5),       # spans >= 3 chunks at prefill_chunk=8
]


@pytest.fixture(scope="module")
def bundle():
    cfg = GPTConfig.tiny()
    model = GPTLMHeadModel(cfg)
    variables = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    return cfg, model, variables


def _run(cfg, variables, cases=CASES, **kw):
    kw.setdefault("n_slots", 2)
    kw.setdefault("max_len", MAX_LEN)
    kw.setdefault("kv_block_size", 4)
    kw.setdefault("prefill_chunk", 8)
    eng = ContinuousGPTEngine(cfg, variables, auto_start=False, **kw)
    futs = [eng.submit(p, n) for p, n in cases]
    for _ in range(500):
        eng.tick()
        if all(f.done() for f in futs):
            break
    snap = eng.snapshot()
    eng.close()
    return [np.asarray(f.result(timeout=0)) for f in futs], snap


# -- parity ------------------------------------------------------------------

@pytest.mark.parametrize("decode_kw", [
    {},                        # plain per-token decode
    {"chain_tokens": 4},       # chained decode
    {"spec_k": 4},             # speculative verify
])
def test_sp2_bitwise_vs_sp1_and_oracle(bundle, decode_kw):
    """The acceptance bar: greedy tokens identical across sp∈{1,2} and
    vs the unbatched oracle, under every decode mode — the handoff
    leaves the per-token loop literally untouched."""
    cfg, model, variables = bundle
    sp1, _ = _run(cfg, variables, **decode_kw)
    sp2, snap = _run(cfg, variables, sp=2, **decode_kw)
    for (prompt, max_new), a, b in zip(CASES, sp1, sp2):
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(
            b, oracle(model, variables, prompt, max_new))
    kv = snap["kv"]
    assert kv["sp"]["axis"] == 2
    assert kv["sp"]["handoffs"] == len(CASES)
    assert kv["prefix_hits"] > 0  # the hit survived the sharded gather


def test_sp2_quantized_pool_matches_sp1(bundle):
    """int8 decode pools under sp: staging stays compute-dtype, the
    handoff install quantizes once — sp=2 tokens equal sp=1 tokens."""
    cfg, _, variables = bundle
    sp1, _ = _run(cfg, variables, kv_dtype="int8")
    sp2, snap = _run(cfg, variables, sp=2, kv_dtype="int8")
    for a, b in zip(sp1, sp2):
        np.testing.assert_array_equal(a, b)
    assert snap["kv"]["dtype"] == "int8"


def test_sp_cow_partial_block_across_sharded_gather(bundle):
    """A COW-shared partial tail block: the sharer's sp prefill seeds
    its staged copy from the donor's registered blocks MID-DONOR-DECODE
    and the donor decodes on untouched — both bitwise vs their
    oracles."""
    cfg, model, variables = bundle
    donor = (SHARED + [1], 8)            # partial tail block
    sharer = (SHARED + [1, 9, 9], 6)     # shares INTO the donor tail
    eng = ContinuousGPTEngine(
        cfg, variables, n_slots=2, max_len=MAX_LEN, kv_block_size=4,
        prefill_chunk=8, sp=2, auto_start=False)
    f_donor = eng.submit(*donor)
    while eng.active_slots == 0:  # donor through prefill, into decode
        eng.tick()
    f_sharer = eng.submit(*sharer)
    while not (f_donor.done() and f_sharer.done()):
        eng.tick()
    snap = eng.snapshot()
    eng.close()
    for (prompt, max_new), fut in ((donor, f_donor), (sharer, f_sharer)):
        np.testing.assert_array_equal(
            np.asarray(fut.result(timeout=0)),
            oracle(model, variables, prompt, max_new))
    assert snap["kv"]["prefix_hits"] > 0


def test_sp_final_chunk_never_clamps_at_table_edge(bundle):
    """Regression: a 3-token prefix hit offsets the chunk grid so the
    63-token prompt's FINAL chunk (c0=59, bucketed width 8) reaches
    column 67 — past the 64-column table span. The staged head must
    carry chunk headroom (_mb_sp, the sp analogue of the private
    cache's wp = w + chunk_cap); a head capped at the table span would
    let the cached write clamp and silently corrupt real keys."""
    cfg, model, variables = bundle
    donor = ([7, 7, 7], 2)
    edge = ([7, 7, 7] + list(range(1, 61)), 1)  # 63 tokens, hit=3
    eng = ContinuousGPTEngine(
        cfg, variables, n_slots=2, max_len=MAX_LEN, kv_block_size=4,
        prefill_chunk=8, sp=2, auto_start=False)
    f1 = eng.submit(*donor)
    while not f1.done():
        eng.tick()
    f2 = eng.submit(*edge)
    while not f2.done():
        eng.tick()
    snap = eng.snapshot()
    eng.close()
    assert snap["kv"]["prefix_hits"] >= 3  # the grid really is offset
    np.testing.assert_array_equal(
        np.asarray(f2.result(timeout=0)),
        oracle(model, variables, edge[0], edge[1]))


def test_sp_staging_exhaustion_defers_on_staging_pool(bundle):
    """Regression: a deferral caused by the STAGING pool must record
    its streak (and its /healthz degraded signal) on the staging pool
    — charged to the decode pool it would read healthy forever."""
    cfg, _, variables = bundle
    eng = ContinuousGPTEngine(
        cfg, variables, n_slots=2, max_len=MAX_LEN, kv_block_size=4,
        prefill_chunk=8, sp=2, sp_kv_blocks=8, auto_start=False)
    # 32 tokens = 8 staging blocks: one 4-chunk prefill holds the
    # whole staging pool for 4 ticks, so the second request defers on
    # STAGING mid-prefill (decode pool has 2*16=32 blocks — plenty)
    blocker = eng.submit(list(range(1, 33)), 2)
    eng.tick()                      # admit blocker (staging now full)
    starved = eng.submit(list(range(30, 46)), 2)
    eng.tick()                      # starved defers; blocker chunk 2/4
    snap = eng.snapshot()["kv"]
    assert snap["sp"]["staging_streak"] >= 1, snap
    assert snap["exhausted_streak"] >= 1, snap  # healthz sees it
    while not (blocker.done() and starved.done()):
        eng.tick()                  # self-recovers at the handoff
    snap = eng.snapshot()["kv"]
    assert snap["sp"]["staging_streak"] == 0, snap
    eng.close()


# -- staging bookkeeping -----------------------------------------------------

def test_staging_blocks_release_after_handoff(bundle):
    cfg, _, variables = bundle
    _, snap = _run(cfg, variables, sp=2)
    sp = snap["kv"]["sp"]
    assert sp["staging_blocks_used"] == 0, sp  # all handed off
    assert sp["shard_used"] == [0, 0]
    assert sp["handoffs"] == len(CASES)


def test_sp_non_divisible_chunk_cap_floors_to_sp_multiple(bundle):
    """Regression: a prefill_chunk that does not divide sp (or an odd
    table span) must not crash the sharded ids placement — the chunk
    PROGRAM cap floors to a multiple of sp at construction while the
    per-tick token budget keeps the configured value."""
    cfg, model, variables = bundle
    prompt = list(range(1, 25))  # 24 tokens: 3 chunks at budget 9
    eng = ContinuousGPTEngine(
        cfg, variables, n_slots=2, max_len=MAX_LEN, kv_block_size=4,
        prefill_chunk=9, sp=2, auto_start=False)
    assert eng._chunk_cap % 2 == 0
    fut = eng.submit(prompt, 4)
    for _ in range(200):
        eng.tick()
        if fut.done():
            break
    eng.close()
    np.testing.assert_array_equal(
        np.asarray(fut.result(timeout=0)),
        oracle(model, variables, prompt, 4))


def test_sp_power_of_two_validated(bundle):
    cfg, _, variables = bundle
    with pytest.raises(ValueError, match="power of two"):
        ContinuousGPTEngine(cfg, variables, sp=3, auto_start=False)
    with pytest.raises(ValueError, match=">= 1"):
        ContinuousGPTEngine(cfg, variables, sp=0, auto_start=False)


def test_sp_staging_bound_rejects_unprefillable_prompt(bundle):
    cfg, _, variables = bundle
    eng = ContinuousGPTEngine(
        cfg, variables, n_slots=2, max_len=MAX_LEN, kv_block_size=4,
        sp=2, sp_kv_blocks=2, auto_start=False)
    with pytest.raises(ValueError, match="staging"):
        eng.submit(list(range(1, 14)), 2)  # 13 tokens -> 4 blocks > 2
    eng.close()


# -- chaos contract ----------------------------------------------------------

@pytest.mark.parametrize("site, plan", [
    ("sp.permute", "sp.permute:OSError@2"),
    ("sp.gather", "sp.gather:OSError@2"),
])
def test_sp_collective_fault_requeues_without_loss(bundle, site, plan):
    """An injected collective fault mid-prefill: the victim request is
    re-queued (zero lost), retried bitwise, and the typed failure lands
    in the flight ring."""
    cfg, model, variables = bundle
    faults.disarm()
    faults.arm(faults.FaultPlan.parse(plan))
    try:
        outs, _ = _run(cfg, variables, sp=2)
    finally:
        faults.disarm()
    for (prompt, max_new), got in zip(CASES, outs):
        np.testing.assert_array_equal(
            got, oracle(model, variables, prompt, max_new))
    evs = [e for e in flight_recorder().events()
           if e.get("kind") == "sp.collective_failed"]
    assert any(e["site"] == site for e in evs), (site, evs)


def test_sp_staging_alloc_fault_defers_without_leak(bundle):
    """Regression: an injected kv.alloc fault landing on the STAGING
    allocate (the 2nd kv.alloc hit of an sp admission — the decode
    alloc is the 1st) must defer like any exhaustion, never fail the
    request, and release the decode blocks already taken."""
    cfg, model, variables = bundle
    prompt = list(range(1, 14))
    faults.disarm()
    faults.arm(faults.FaultPlan.parse("kv.alloc:OSError@2"))
    try:
        eng = ContinuousGPTEngine(
            cfg, variables, n_slots=2, max_len=MAX_LEN,
            kv_block_size=4, prefill_chunk=8, sp=2, auto_start=False)
        fut = eng.submit(prompt, 3)
        for _ in range(300):
            eng.tick()
            if fut.done():
                break
        got = np.asarray(fut.result(timeout=0))  # deferred, not failed
        snap = eng.snapshot()["kv"]
        eng.close()
    finally:
        faults.disarm()
    np.testing.assert_array_equal(
        got, oracle(model, variables, prompt, 3))
    # no leak: the retired request's cached prompt blocks are all that
    # remain off the free list, and staging drained fully
    assert snap["blocks_used"] == snap["blocks_cached"], snap
    assert snap["sp"]["staging_blocks_used"] == 0, snap


# -- metrics -----------------------------------------------------------------

def test_sp_dispatches_recorded_under_own_path(bundle):
    """Satellite: sp prefill dispatches land in
    sparkdl_dispatch_seconds{path="sp_prefill"} / ring-step + permute-
    byte counters — and never feed the decode ChainPolicy calibration."""
    from sparkdl_tpu.observability.registry import registry

    cfg, _, variables = bundle
    registry().reset()
    _run(cfg, variables, sp=2)
    snap = registry().snapshot()
    disp = snap["sparkdl_dispatches_total"]["values"]
    assert disp.get('path="sp_prefill"', 0) > 0, disp
    assert snap["sparkdl_sp_ring_steps_total"]["values"][""] > 0
    assert snap["sparkdl_sp_permute_bytes_total"]["values"][""] > 0


def test_sp_mode_config_rejects_unknown():
    cfg = dataclasses.replace(
        GPTConfig.tiny(), attn_impl="ring", sp_mode="allgather")
    assert cfg.sp_mode == "allgather"
    with pytest.raises(ValueError, match="sp_mode"):
        dataclasses.replace(cfg, sp_mode="all-gather")
