"""Quantized KV block pools: capacity, exactness properties, COW.

The compressed pool is a MEMORY decision with a measured quality trade
(bench_serving reports the parity delta): these tests pin what must
stay exact — per-column int8 requantization round-trips losslessly (so
copy-on-write sharing re-installs bit-identical blocks), decode under a
quantized pool is deterministic, capacity ratios hold arithmetically —
plus the kv.quantize fault site and the deferral-streak reset on
release (satellite: /healthz degraded self-clears when frees make the
pool healthy, not only on the next admission).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import oracle
from sparkdl_tpu.models.gpt import GPTConfig, GPTLMHeadModel
from sparkdl_tpu.models.kv_pool import dequantize_kv, quantize_kv
from sparkdl_tpu.observability.flight import healthz_report
from sparkdl_tpu.observability.registry import registry
from sparkdl_tpu.reliability.faults import inject
from sparkdl_tpu.serving import ContinuousGPTEngine
from sparkdl_tpu.serving.kv_blocks import (
    KVBlockPool,
    kv_bytes_per_token,
    kv_capacity_ratio,
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


def _run(cfg, variables, cases, **kw):
    eng = _engine(cfg, variables, **kw)
    futs = [eng.submit(p, n) for p, n in cases]
    _drain(eng, futs)
    eng.close()
    return [np.asarray(f.result(timeout=0)) for f in futs]


# -- quantization math -------------------------------------------------------

@pytest.mark.parametrize("shape, tail", [
    ((3, 5, 32), 1),     # a column on the pool's one merged axis
    ((3, 5, 4, 8), 2),   # a pool that keeps heads and head size apart
])
def test_quantize_roundtrip_is_idempotent(shape, tail):
    """requantize(dequantize(q, s)) == (q, s) exactly: the absmax of a
    column maps to ±127, so a second trip changes nothing — the
    property that makes COW re-installation lossless. A column is the
    merged axis, or the ``tail`` trailing axes of a per-head pool."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    # a merged axis ends in the storage's zero pad, which stays zero
    x = x.at[..., -3:].set(0) if tail == 1 else x
    q, s = quantize_kv(x, tail)
    assert q.dtype == jnp.int8 and s.shape == (3, 5)
    q2, s2 = quantize_kv(dequantize_kv(q, s), tail)
    np.testing.assert_array_equal(np.asarray(q2), np.asarray(q))
    np.testing.assert_array_equal(np.asarray(s2), np.asarray(s))
    if tail == 1:
        np.testing.assert_array_equal(np.asarray(q[..., -3:]), 0)
    # zero columns: floor scale, zero values, no NaN
    qz, sz = quantize_kv(jnp.zeros(shape[1:], jnp.float32), tail)
    assert not np.isnan(np.asarray(sz)).any()
    np.testing.assert_array_equal(np.asarray(qz), 0)


def test_capacity_ratio_arithmetic():
    tiny = GPTConfig.tiny()
    # resident bytes: 2 heads of 16 lie on one merged axis, stored padded
    # to a whole lane tile of 128 (ServingFamily.kv_tail)
    assert tiny.serving_family().kv_tail == (128,)
    assert kv_bytes_per_token(tiny, "fp32") == 2 * 2 * 128 * 4
    assert kv_capacity_ratio(tiny, "bf16") == 2.0
    assert kv_capacity_ratio(tiny, "int8") >= 2.0
    # the "fp32" layout stores at the MODEL dtype: a bf16-compute
    # model's native pool is already half-size, and the ratios must
    # report the honest (smaller) gain, not fp32 arithmetic
    bf = GPTConfig.tiny(dtype=jnp.bfloat16)
    assert kv_bytes_per_token(bf, "fp32") == 2 * 2 * 128 * 2
    assert kv_capacity_ratio(bf, "bf16") == 1.0
    assert 1.5 < kv_capacity_ratio(bf, "int8") < 2.0
    # a production-ish width: int8 approaches 4x
    big = GPTConfig(hidden_size=768, num_heads=12, num_layers=12)
    assert kv_capacity_ratio(big, "int8") > 3.5
    # the acceptance bar: the SAME pool bytes fit >= 2x live tokens
    pool_bytes = 1 << 20
    fp32_tokens = pool_bytes // kv_bytes_per_token(big, "fp32")
    int8_tokens = pool_bytes // kv_bytes_per_token(big, "int8")
    assert int8_tokens >= 2 * fp32_tokens
    with pytest.raises(ValueError, match="unknown KV dtype"):
        kv_bytes_per_token(tiny, "fp8")


# -- engine under compressed pools -------------------------------------------

@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_quantized_engine_deterministic_and_near_oracle(bundle, kv_dtype):
    """A compressed pool must be deterministic run-to-run (quantization
    is a pure function) and stay NEAR the fp32 oracle on the tiny
    model; the exact delta is workload-dependent and measured by
    bench_serving, not asserted here."""
    cfg, model, variables = bundle
    shared = [5, 3, 9, 2, 7, 11, 4, 8]
    cases = [(shared + [1, 6], 8), (shared + [2, 2, 9], 6),
             ([6, 8, 6], 5)]
    a = _run(cfg, variables, cases, kv_dtype=kv_dtype)
    b = _run(cfg, variables, cases, kv_dtype=kv_dtype)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)  # deterministic
    agree = total = 0
    for (p, n), got in zip(cases, a):
        want = oracle(model, variables, p, n)
        assert len(got) == len(want)
        agree += int((got == want).sum())
        total += len(want)
    assert agree / total > 0.8, (agree, total)


def test_quantized_cow_shared_partial_block(bundle):
    """COW on a shared partial tail block under int8: the sharer
    gathers a DEQUANTIZED copy and re-installs into its own block
    (exact requant round trip), so the donor — still decoding into
    that very block — produces exactly what it produces with no
    sharer at all."""
    cfg, model, variables = bundle
    prefix = [5, 3, 9, 2, 7, 11]  # block 0 full, block 1 holds 2
    solo = _run(cfg, variables, [(prefix, 8)], kv_dtype="int8")[0]

    eng = _engine(cfg, variables, kv_dtype="int8")
    fa = eng.submit(prefix, 8)
    eng.tick()
    eng.tick()
    assert not fa.done()  # donor mid-decode into its tail block
    fb = eng.submit(prefix + [1, 4], 6)  # matches block 0 + 2 partial
    _drain(eng, [fa, fb])
    assert eng._prefix.hit_tokens == 4 + 2
    np.testing.assert_array_equal(
        np.asarray(fa.result(timeout=0)), solo,
        err_msg="int8 donor perturbed by COW sharer")
    # sharer: deterministic vs a fresh identical pairing
    eng2 = _engine(cfg, variables, kv_dtype="int8")
    fa2 = eng2.submit(prefix, 8)
    eng2.tick()
    eng2.tick()
    fb2 = eng2.submit(prefix + [1, 4], 6)
    _drain(eng2, [fa2, fb2])
    np.testing.assert_array_equal(
        np.asarray(fb.result(timeout=0)),
        np.asarray(fb2.result(timeout=0)))
    eng.close()
    eng2.close()


def test_fp32_default_unchanged_and_unknown_dtype_refused(bundle):
    cfg, model, variables = bundle
    cases = [([5, 3, 9, 2, 7], 6)]
    got = _run(cfg, variables, cases)  # default fp32: exact
    np.testing.assert_array_equal(
        got[0], oracle(model, variables, *cases[0]))
    with pytest.raises(ValueError, match="unknown KV"):
        _engine(cfg, variables, kv_dtype="fp8")


def test_spec_decode_composes_with_quantized_pool(bundle):
    """Speculation over an int8 pool: same compressed cache read/write
    path as plain decode, deterministic, and every request completes.
    (Bitwise spec-vs-k1 holds at fp32 only: within a verify span the
    later draft positions attend FRESH compute-dtype keys, where k=1
    re-reads them through the int8 round trip — a precision gain, not
    a loss, measured by the bench parity harness.)"""
    cfg, model, variables = bundle
    cases = [([6, 8, 6, 1, 6, 8, 6, 1], 10), ([5, 3, 9], 8)]
    a = _run(cfg, variables, cases, kv_dtype="int8", spec_k=4)
    b = _run(cfg, variables, cases, kv_dtype="int8", spec_k=4)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    for (p, n), got in zip(cases, a):
        assert 1 <= len(got) <= n


# -- fault site + gauges -----------------------------------------------------

def test_kv_quantize_fault_fails_build_loudly(bundle):
    """An armed kv.quantize site fails the COMPRESSED pool bring-up at
    construction — before any process-wide registration leaks — and
    leaves fp32 engines untouched."""
    cfg, model, variables = bundle
    with inject("kv.quantize:RuntimeError@1"):
        with pytest.raises(RuntimeError, match="kv.quantize"):
            _engine(cfg, variables, kv_dtype="int8")
        eng = _engine(cfg, variables)  # fp32 never hits the site
        eng.close()
    # the failed build registered nothing: no stray pool gauges
    fam = registry().get("sparkdl_kv_pool_dtype")
    vals = fam.snapshot_values() if fam is not None else {}
    assert vals.get('dtype="int8"', 0) == 0, vals


def test_pool_dtype_gauge_tracks_live_pools():
    fam = registry().get("sparkdl_kv_pool_dtype")
    before = (fam.snapshot_values() if fam is not None else {}).get(
        'dtype="int8"', 0)
    pool = KVBlockPool(4, 4, dtype="int8")
    fam = registry().get("sparkdl_kv_pool_dtype")
    assert fam.snapshot_values().get('dtype="int8"', 0) == before + 1
    pool.close()
    assert fam.snapshot_values().get('dtype="int8"', 0) == before


# -- deferral-streak reset on release (satellite fix) ------------------------

def test_release_resets_deferral_streak_unit():
    pool = KVBlockPool(2, 4)
    blocks = pool.allocate(2)
    for _ in range(3):
        pool.record_deferral()
    assert pool.deferral_streak == 3
    pool.deref(blocks[:1])
    pool.release(blocks[:1])  # frees capacity: episode over
    assert pool.deferral_streak == 0
    pool.close()


def test_partial_free_does_not_clear_a_larger_deferred_need():
    """A large request starving behind small-block churn must KEEP its
    streak (and eventually reach the postmortem trigger): only a
    release that leaves enough free capacity for the deferred need
    ends the episode."""
    pool = KVBlockPool(8, 4)
    churn = pool.allocate(4)  # 4 free left; a 6-block request defers
    pool.record_deferral(need=6)
    pool.record_deferral(need=6)
    pool.deref(churn[:1])
    pool.release(churn[:1])  # 5 free < 6: not recovery
    assert pool.deferral_streak == 2
    pool.deref(churn[1:])
    pool.release(churn[1:])  # 8 free >= 6: episode over
    assert pool.deferral_streak == 0
    pool.close()


def test_healthz_degraded_clears_on_release_not_admission(bundle):
    """The engine-level satellite contract: when the blocking request
    retires (its blocks RELEASE), /healthz must already read ok —
    BEFORE the deferred request gets its next admission attempt."""
    cfg, model, variables = bundle
    eng = _engine(cfg, variables, n_slots=2, kv_block_size=16,
                  kv_blocks=2, prefill_chunk=None)
    fa = eng.submit([5, 3, 9], 14)  # 17 tokens: the whole pool
    eng.tick()
    fb = eng.submit([1, 4], 4)
    eng.tick()  # defer: streak begins
    assert eng._pool.deferral_streak >= 1
    assert healthz_report()["status"] == "degraded"
    while not fa.done():
        eng.tick()
    # fa's retirement released blocks; the streak cleared on the
    # release path itself, with fb still waiting in the queue
    assert eng._pool.deferral_streak == 0
    assert healthz_report()["status"] == "ok"
    _drain(eng, [fb])
    eng.close()
    np.testing.assert_array_equal(
        fb.result(timeout=0), oracle(model, variables, [1, 4], 4))


# -- compressed pools through the block table (ISSUE 27) ---------------------

@pytest.mark.parametrize("which", ["step", "verify"])
@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_compressed_pool_is_never_dequantized_whole(bundle, kv_dtype, which):
    """The decode programs dequantize one layer's gathered slice at a
    time: nothing of the pool's shape, nor of the all-layer view's, at a
    wider type than the pool is stored in (the int8 step once made a
    float32 copy of the whole pool: 16.65 GB of the chip's 15.75 at 8 x
    1024), and the scales ride along in place."""
    from tests.serving.test_kv_paged import (
        IN_PLACE,
        decode_program,
        program_arrays,
    )

    cfg, _, variables = bundle
    eng = _engine(cfg, variables, n_slots=3, kv_blocks=11, kv_dtype=kv_dtype,
                  spec_k=2)
    try:
        pool = eng._pool_kv
        fn, args = decode_program(eng, which, k=2, nb=2)
        made = program_arrays(fn, args)
        layers, _, bs, merged = pool["k"].shape
        view = (layers, eng.n_slots, 2 * bs, merged)
        stored = pool["k"].dtype
        assert stored == {"bf16": jnp.bfloat16, "int8": jnp.int8}[kv_dtype]
        whole = [m for m in made if m[1] in (pool["k"].shape, view)]
        assert whole, "the program never touches the pool"
        assert {m[2] for m in whole} == {stored}
        assert {m[0] for m in whole} <= IN_PLACE
        # a layer's slice at the compute dtype is what attention reads
        assert [m for m in made if m[1] == view[1:] and m[2] == cfg.dtype]
        if kv_dtype == "int8":
            scales = [m for m in made if m[1] == pool["k_scale"].shape]
            assert scales and {m[0] for m in scales} <= IN_PLACE
    finally:
        eng.close()


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_columns_written_through_the_loop_are_the_scatters(bundle, kv_dtype):
    """What one decode tick leaves in a compressed pool: the new column of
    every live row, quantized by the rule every other pool write uses,
    at its (block, offset) and nowhere else; an idle slot's sentinel
    entry writes nothing."""
    cfg, _, variables = bundle
    eng = _engine(cfg, variables, n_slots=3, kv_blocks=11, kv_dtype=kv_dtype)
    try:
        fut = eng.submit([5, 3, 9, 2, 7, 11], 4)
        eng.tick()  # admitted and prefilled: one live row, two idle
        before = {n: np.asarray(a) for n, a in eng._pool_kv.items()}
        table, pidx = eng._table.copy(), eng._pidx.copy()
        (slot,) = list(eng._inflight)
        eng.tick()  # one decode step
        after = {n: np.asarray(a) for n, a in eng._pool_kv.items()}
        blk = table[slot, pidx[slot] // 4]
        off = pidx[slot] % 4
        for n in before:
            changed = np.argwhere(
                (before[n] != after[n]).reshape(*before[n].shape[:3], -1)
                .any(axis=-1))
            assert {tuple(c[1:]) for c in changed} == {(blk, off)}, n
            assert {c[0] for c in changed} == set(range(cfg.num_layers)), n
        _drain(eng, [fut])
    finally:
        eng.close()
