"""Paged KV cache: parity, prefix reuse, COW, eviction, deferral.

The paged cache is a memory/scheduling decision, never a quality
decision: every test here ultimately pins greedy tokens against the
unbatched ``generate`` oracle, while asserting the
paged machinery (block accounting, prefix hits, copy-on-write tail
blocks, LRU eviction, deferred admission, chunk budgets) actually
engaged.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import oracle
from sparkdl_tpu.models.gpt import GPTConfig, GPTLMHeadModel
from sparkdl_tpu.models.kv_pool import (
    init_block_pool,
    kv_per_head,
    kv_stored,
)
from sparkdl_tpu.observability import tracing
from sparkdl_tpu.observability.flight import healthz_report
from sparkdl_tpu.observability.registry import registry
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


def _drain(eng, futs):
    while not all(f.done() for f in futs):
        eng.tick()


def _counter(name):
    fam = registry().snapshot().get(name)
    if fam is None:
        return 0.0
    return sum(fam["values"].values())


# -- parity ------------------------------------------------------------------

@pytest.mark.parametrize("kv_dtype", ["fp32", "bf16"])
@pytest.mark.parametrize("kv_block_size, prefill_chunk", [
    (4, 4), (16, None), (4, None), (16, 4)])
def test_paged_tokens_are_generates(bundle, kv_block_size, prefill_chunk,
                                    kv_dtype):
    """Shared-prefix traffic through the engine must produce greedy
    tokens bitwise-identical to the unbatched oracle — across prefix
    hits, chunked and one-chunk prefill, blocks of a few tokens and blocks
    wider than a prompt, and mid-stream joins. A ``bf16`` pool is held to
    the oracle at the agreement ``test_kv_quant.py`` states."""
    cfg, model, variables = bundle
    shared = [5, 3, 9, 2, 7, 11, 4, 8]
    cases = [
        (shared + [1, 6], 6),
        (shared + [2, 2, 9], 5),   # prefix hit on the first case
        ([6, 8, 6], 4),            # no shared prefix
        (shared + [1, 6], 3),      # full-prompt hit (minus last token)
    ]
    eng = _engine(cfg, variables, kv_block_size=kv_block_size,
                  prefill_chunk=prefill_chunk, kv_dtype=kv_dtype)
    futs = [eng.submit(p, n) for p, n in cases]
    _drain(eng, futs)
    eng.close()
    agree = total = 0
    for (prompt, max_new), fut in zip(cases, futs):
        got = fut.result(timeout=0)
        want = oracle(model, variables, prompt, max_new)
        if kv_dtype == "fp32":
            np.testing.assert_array_equal(
                got, want, err_msg=f"diverged from oracle: {prompt}")
        assert len(got) == len(want)
        agree += int((got == want).sum())
        total += len(want)
    assert agree / total > 0.8, (agree, total)


# -- prefix reuse ------------------------------------------------------------

def test_prefix_hit_skips_prefill_of_cached_span(bundle):
    """A prefix-hit admit must prefill ONLY the suffix: the hit lands
    in sparkdl_prefix_hits_total and the request's trace carries
    prefill_chunk spans covering exactly the un-cached tokens."""
    cfg, model, variables = bundle
    shared = [5, 3, 9, 2, 7, 11, 4, 8]
    eng = _engine(cfg, variables, kv_block_size=4, prefill_chunk=4)
    tracing.enable_tracing()
    try:
        hits0 = _counter("sparkdl_prefix_hits_total")
        f1 = eng.submit(shared + [1, 6], 4)
        _drain(eng, [f1])
        assert _counter("sparkdl_prefix_hits_total") == hits0  # cold
        f2 = eng.submit(shared + [2, 9], 4)
        _drain(eng, [f2])
        eng.close()
        # prompt 10 tokens, cached span = 2 full blocks (8 tokens):
        # full-block match only — the divergent suffix shares no
        # partial content with the first prompt's tail block
        assert _counter("sparkdl_prefix_hits_total") == hits0 + 8
        assert eng._prefix.hit_tokens == 8
        spans2 = [s for s in tracing.spans_for_trace(f2.request_id)
                  if s["name"] == "serving.prefill_chunk"]
        assert sum(s["args"]["tokens"] for s in spans2) == 2  # 10-8 cached
        spans1 = [s for s in tracing.spans_for_trace(f1.request_id)
                  if s["name"] == "serving.prefill_chunk"]
        assert sum(s["args"]["tokens"] for s in spans1) == 10  # cold: all
        np.testing.assert_array_equal(
            f2.result(timeout=0),
            oracle(model, variables, shared + [2, 9], 4))
    finally:
        tracing.disable_tracing()
        tracing.clear_trace()


def test_cow_shared_partial_block_never_corrupts_sibling(bundle):
    """B admits matching A's partially-filled tail block while A is
    still DECODING into that very block: B must copy, not share the
    writes — both decodes stay oracle-identical."""
    cfg, model, variables = bundle
    prefix = [5, 3, 9, 2, 7, 11]  # 6 tokens: block 0 full, block 1 has 2
    eng = _engine(cfg, variables, kv_block_size=4, prefill_chunk=8)
    fa = eng.submit(prefix, 8)
    eng.tick()  # A admitted, prefilled, decoding into its tail block
    eng.tick()
    assert not fa.done()
    fb = eng.submit(prefix + [1, 4], 6)  # matches block 0 + partial 2
    _drain(eng, [fa, fb])
    eng.close()
    assert eng._prefix.hit_tokens == 4 + 2  # 1 full block + 2 partial
    np.testing.assert_array_equal(
        fa.result(timeout=0), oracle(model, variables, prefix, 8),
        err_msg="donor decode corrupted by COW sharer")
    np.testing.assert_array_equal(
        fb.result(timeout=0),
        oracle(model, variables, prefix + [1, 4], 6))


def test_lru_eviction_under_pool_pressure(bundle):
    """Distinct prompts past pool capacity: refcount-0 cached prefixes
    must evict LRU so admission keeps succeeding, and correctness
    survives block recycling."""
    cfg, model, variables = bundle
    rng = np.random.default_rng(3)
    # 6 blocks of 8: each request needs 2, cached prefixes pile up
    eng = _engine(cfg, variables, n_slots=1, kv_block_size=8,
                  kv_blocks=6, prefill_chunk=8)
    ev0 = _counter("sparkdl_prefix_evictions_total")
    cases = []
    for _ in range(6):
        prompt = rng.integers(1, cfg.vocab_size, 7).tolist()
        cases.append((prompt, 4))
        fut = eng.submit(prompt, 4)
        _drain(eng, [fut])
        np.testing.assert_array_equal(
            fut.result(timeout=0),
            oracle(model, variables, prompt, 4))
    eng.close()
    assert _counter("sparkdl_prefix_evictions_total") > ev0
    assert eng._prefix.evictions > 0


# -- admission ---------------------------------------------------------------

def test_admission_bounds_raw_length_and_the_pool(bundle):
    """Tokens are stored unpadded, so a request is admitted by its raw
    length, and only what can truly never fit is rejected (raw length
    or whole-pool block need)."""
    cfg, _, variables = bundle
    paged = _engine(cfg, variables)
    fut = paged.submit(list(range(1, 10)), 20)  # 9 + 20 <= 32: fits
    _drain(paged, [fut])
    assert len(fut.result(timeout=0)) == 20
    with pytest.raises(ValueError, match="exceeds cache max_len"):
        paged.submit(list(range(1, 10)), 30)  # raw 9 + 30 > 32
    paged.close()
    tiny_pool = _engine(cfg, variables, kv_blocks=1, kv_block_size=16)
    with pytest.raises(ValueError, match="can never fit"):
        tiny_pool.submit([1, 2, 3], 20)  # needs 2 blocks, pool holds 1
    tiny_pool.close()


def test_deferred_admission_preserves_order(bundle):
    """Pool exhaustion defers (re-queues) instead of erroring, and the
    deferred request admits BEFORE anything submitted after it."""
    cfg, model, variables = bundle
    # pool = 2 blocks of 16: one request's worst case consumes both
    eng = _engine(cfg, variables, n_slots=2, kv_block_size=16,
                  kv_blocks=2)
    fa = eng.submit([5, 3, 9], 14)  # 17 tokens: both pool blocks
    eng.tick()  # A holds the whole pool
    fb = eng.submit([1, 4], 4)
    fc = eng.submit([2, 2], 4)
    eng.tick()  # B defers (C re-queued behind it, order kept)
    assert not fb.done() and not fc.done()
    assert eng._deferrals >= 1
    assert eng.queue.requeued >= 1
    while not fa.done():
        eng.tick()
    # first post-retirement tick: B must claim the freed blocks first
    eng.tick()
    ids = [st.req.request_id
           for st in list(eng._prefilling.values())] + [
        fl.req.request_id for fl in list(eng._inflight.values())]
    assert fb.request_id in ids, "deferred request was not admitted first"
    _drain(eng, [fb, fc])
    eng.close()
    np.testing.assert_array_equal(
        fb.result(timeout=0), oracle(model, variables, [1, 4], 4))
    np.testing.assert_array_equal(
        fc.result(timeout=0), oracle(model, variables, [2, 2], 4))


def test_healthz_degraded_on_exhaustion_streak(bundle):
    """An exhaustion streak reads as degraded in healthz_report() —
    never unhealthy, because it self-recovers as slots retire."""
    cfg, _, variables = bundle
    eng = _engine(cfg, variables, n_slots=2, kv_block_size=16,
                  kv_blocks=2)
    fa = eng.submit([5, 3, 9], 14)  # 17 tokens: both pool blocks
    eng.tick()
    fb = eng.submit([1, 4], 4)
    eng.tick()  # defer: streak begins
    assert eng._pool.deferral_streak >= 1
    report = healthz_report()
    assert report["status"] == "degraded", report
    mine = [p for p in report["kv_pools"]
            if p["exhausted_streak"]]
    assert mine and mine[0]["blocks_total"] == 2
    _drain(eng, [fa, fb])  # A retires -> B admits -> streak clears
    assert eng._pool.deferral_streak == 0
    assert healthz_report()["status"] in ("ok", "degraded")
    assert not [p for p in healthz_report()["kv_pools"]
                if p["exhausted_streak"]]
    eng.close()


# -- memory + chunk budget ---------------------------------------------------

def test_kv_blocks_scale_with_live_tokens(bundle):
    """Peak pool usage must track admitted requests' token worst case,
    not the dense layout's n_slots x max_len contract."""
    cfg, model, variables = bundle
    eng = _engine(cfg, variables, n_slots=8, kv_block_size=4)
    dense_equiv_blocks = 8 * eng._mb  # the dense layout's footprint
    assert eng._pool.used_count == 0  # no tokens, no blocks
    futs = [eng.submit([7, 1, 3], 5), eng.submit([2, 9], 4)]
    eng.tick()
    # worst case: ceil((3+5)/4) + ceil((2+4)/4) = 2 + 2
    used_live = eng._pool.used_count
    assert used_live == 4
    assert used_live < dense_equiv_blocks / 4
    _drain(eng, futs)
    # retired: only the cached prompt prefixes stay resident
    assert eng._pool.used_count == eng._prefix.cached_blocks
    assert eng._pool.used_count <= 2
    eng.close()


def test_long_prompt_admit_never_stalls_decode_beyond_chunk(bundle):
    """Chunked prefill: while a long prompt admits, every tick still
    advances the in-flight decode, and no tick prefills more than the
    chunk budget."""
    cfg, model, variables = bundle
    chunk = 4
    eng = _engine(cfg, variables, prefill_chunk=chunk, kv_block_size=4)
    short = eng.submit([6, 8], 12)
    eng.tick()
    produced_before = len(next(iter(eng._inflight.values())).produced)
    long_prompt = list(np.random.default_rng(0).integers(1, 64, 17))
    longf = eng.submit(long_prompt, 3)
    eng.tick()  # admits the long prompt: first chunk only
    assert eng._prefilling, "17-token prompt should span several chunks"
    ticks_to_admit = 1
    while eng._prefilling:
        before = len(next(iter(eng._inflight.values())).produced)
        eng.tick()
        ticks_to_admit += 1
        if short.done():
            break
        after = len(next(iter(eng._inflight.values())).produced)
        assert after > before, "decode stalled during long-prompt admit"
    assert ticks_to_admit >= 2  # 17 tokens / chunk 4: several ticks
    assert eng._max_tick_prefill_tokens <= chunk
    _drain(eng, [short, longf])
    eng.close()
    np.testing.assert_array_equal(
        short.result(timeout=0), oracle(model, variables, [6, 8], 12))
    np.testing.assert_array_equal(
        longf.result(timeout=0),
        oracle(model, variables, long_prompt, 3))
    del produced_before


@pytest.mark.slow
def test_soak_mixed_long_short_chunk_budget(bundle):
    """Threaded soak, mixed long/short prompts under a small chunk:
    every output oracle-identical, prefix cache exercised, and no tick
    ever prefilled past the chunk budget."""
    cfg, model, variables = bundle
    rng = np.random.default_rng(1)
    chunk = 4
    eng = ContinuousGPTEngine(
        cfg, variables, n_slots=4, max_len=MAX_LEN, idle_wait_s=0.001,
        prefill_chunk=chunk, kv_block_size=4,
    )
    shared = rng.integers(1, cfg.vocab_size, 8).tolist()
    cases, futs = [], []
    for i in range(20):
        if i % 3 == 0:  # long, shared prefix
            prompt = shared + rng.integers(1, cfg.vocab_size,
                                           int(rng.integers(4, 12))).tolist()
        else:  # short
            prompt = rng.integers(1, cfg.vocab_size,
                                  int(rng.integers(1, 6))).tolist()
        max_new = int(rng.integers(1, 8))
        cases.append((prompt, max_new))
        futs.append(eng.submit(prompt, max_new))
        time.sleep(float(rng.uniform(0, 0.008)))
    eng.close(drain=True)
    for (prompt, max_new), fut in zip(cases, futs):
        np.testing.assert_array_equal(
            fut.result(timeout=0),
            oracle(model, variables, prompt, max_new),
            err_msg=f"prompt {prompt} x{max_new}",
        )
    assert eng._max_tick_prefill_tokens <= chunk
    assert eng._prefix.hit_tokens > 0  # the shared prefix got reused
    assert eng.snapshot()["completed"] == 20


# -- decode through the block table (ISSUE 27) -------------------------------

def walk_jaxpr(jaxpr):
    """Every equation of a jaxpr and of the jaxprs nested in it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from walk_jaxpr(sub)


def decode_program(eng, which, k=2, nb=2):
    """``_paged_step`` or ``_paged_verify`` of ``eng`` with the arguments
    a tick would hand it (k tokens a row, ``nb`` blocks of the table)."""
    fn = {"step": eng._paged_step_fn, "verify": eng._paged_verify_fn}[which]
    # the step takes each row's token from the host or, where the host has
    # none yet, from the step before (its second output, ``_dev_tok``)
    tok = ((jnp.asarray(eng._last_tok), eng._dev_tok) if which == "step"
           else (jnp.zeros((eng.n_slots, k), jnp.int32),))
    return fn, (eng.variables, eng._pool_kv, jnp.asarray(eng._table),
                jnp.asarray(eng._pidx), *tok, k, nb)


def program_arrays(fn, args):
    """(primitive, shape, dtype) of everything the traced program makes."""
    n = len(args)
    closed = jax.make_jaxpr(fn, static_argnums=(n - 2, n - 1))(*args)
    return [(eqn.primitive.name, tuple(v.aval.shape), v.aval.dtype)
            for eqn in walk_jaxpr(closed.jaxpr) for v in eqn.outvars]


#: the only ways a decode program may make an array of the pool's shape:
#: the column update in place (one scatter indexed by layer, block and
#: offset on the merged axis) and the loops and calls that carry it
IN_PLACE = {"scatter", "while", "scan", "pjit", "jit", "closed_call",
            "core_call"}


@pytest.mark.parametrize("which", ["step", "verify"])
def test_decode_program_holds_no_dense_view_and_no_second_pool(bundle, which):
    """The lowered program reads K/V through the table a layer at a time:
    no array of the all-layer gathered view's shape, nothing of the pool's
    shape but the in-place column update, and the compiled program gives
    the pool's input buffers back as its outputs."""
    cfg, _, variables = bundle
    # 3 slots x 2 blocks of the table against a pool of 11 blocks, so that
    # no two of the shapes below coincide
    eng = _engine(cfg, variables, n_slots=3, kv_block_size=4, kv_blocks=11,
                  spec_k=2)
    try:
        fn, args = decode_program(eng, which, k=2, nb=2)
        pool_shape = eng._pool_kv["k"].shape
        layers, _, bs, merged = pool_shape
        view = (layers, eng.n_slots, 2 * bs, merged)
        made = program_arrays(fn, args)
        assert made, "the program traced to nothing"
        assert [m for m in made if m[1] == view] == []
        # one layer's slice of the view is what a layer gathers
        assert [m for m in made if m[1] == view[1:]]
        assert {m[0] for m in made if m[1] == pool_shape} <= IN_PLACE
        text = fn.lower(*args).compile().as_text()
        aliases = text.split("input_output_alias={", 1)[1].split(
            "entry_computation_layout", 1)[0]
        assert aliases.count("-alias") == len(eng._pool_kv)
    finally:
        eng.close()


def _dense_rows(pool, table, layer):
    """pool[layer] gathered into per-slot rows, the oracle's way."""
    g = np.asarray(pool)[layer][np.minimum(table, pool.shape[1] - 1)]
    return g.reshape(table.shape[0], -1, *g.shape[3:])


@pytest.mark.parametrize("stored", ["merged", "per_head"])
@pytest.mark.parametrize("width", [1, 3])
def test_model_reads_a_paged_cache_like_the_dense_one(bundle, width, stored):
    """``GPTLMHeadModel`` on a paged cache (pool + table) against the same
    K/V laid out as a per-slot dense cache: logits bitwise equal, and the
    columns handed back are the ones the dense cache wrote, in the shape
    the pool stores a token in. Rows sit at different depths, one row's
    table is all sentinel (an idle slot) and two rows share their first
    block (a copy-on-write prefix). The pool as ``init_block_pool`` shapes
    it for heads of 16 (one merged axis, zero-padded to a lane tile), and
    with heads and head size apart (what heads of 128 would keep)."""
    cfg, model, variables = bundle
    layers, bs, n_blocks = cfg.num_layers, 4, 9
    nh, hd = cfg.num_heads, cfg.hidden_size // cfg.num_heads
    fam = cfg.serving_family()
    tail = fam.kv_tail if stored == "merged" else (nh, hd)
    assert init_block_pool(cfg, n_blocks, bs)["k"].shape == (
        layers, n_blocks, bs) + fam.kv_tail == (layers, n_blocks, bs, 128)
    rng = np.random.default_rng(width)
    pool = {n: kv_stored(jnp.asarray(
        rng.normal(size=(layers, n_blocks, bs, nh, hd)), jnp.float32), tail)
        for n in ("k", "v")}
    sentinel = n_blocks
    table = np.asarray([[0, 1, 2], [0, 3, sentinel],
                        [sentinel, sentinel, sentinel]], np.int32)
    idx = np.asarray([9, 5, 0], np.int32)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (3, width)), jnp.int32)
    dense = {n: jnp.stack([
        kv_per_head(jnp.asarray(_dense_rows(pool[n], table, l)), nh, hd)
        for l in range(layers)]) for n in ("k", "v")}
    want, wrote = model.apply(variables, toks,
                              cache=dict(dense, idx=jnp.asarray(idx)))
    got, new = model.apply(
        variables, toks,
        cache=dict(pool, table=jnp.asarray(table), idx=jnp.asarray(idx)))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert new["k"].shape == (layers, 3, width) + tail
    np.testing.assert_array_equal(np.asarray(new["idx"]), idx + width)
    for n in ("k", "v"):
        for row, at in enumerate(idx):
            np.testing.assert_array_equal(
                np.asarray(new[n][:, row]),
                np.asarray(kv_stored(wrote[n][:, row, at:at + width], tail)))


TABLE_CASES = {
    "one_token": {},
    "chain_tokens_4": {"chain_tokens": 4},
    "spec_k_4": {"spec_k": 4},
}


@pytest.mark.parametrize("n_slots", [3, 2])
@pytest.mark.parametrize("case", list(TABLE_CASES))
def test_tokens_through_the_table_are_bitwise_generates(bundle, case,
                                                        n_slots):
    """Greedy tokens of the engine (one token a tick, chains of 4,
    speculative verify spans of 4) against ``generate``: three
    slots for four requests, so rows sit at different depths in one batch
    and a slot idles on sentinel entries (two slots: a queue forms behind
    full slots); the second request shares a
    partial block with the first while the first still decodes into it."""
    cfg, model, variables = bundle
    prefix = [5, 3, 9, 2, 7, 11]
    # the repetitive last prompt gives the n-gram proposer drafts
    cases = [(prefix, 14), (prefix + [1, 4], 6), ([6, 8, 6], 12),
             ([1, 2, 3, 4] * 3, 10)]
    kw = TABLE_CASES[case]
    eng = _engine(cfg, variables, n_slots=n_slots, kv_block_size=4,
                  prefill_chunk=8, **kw)
    futs = [eng.submit(*cases[0])]
    eng.tick()
    eng.tick()
    assert not futs[0].done()
    futs += [eng.submit(p, n) for p, n in cases[1:]]
    _drain(eng, futs)
    assert eng._prefix.hit_tokens >= 4 + 2  # a block and a part
    if "spec_k" in kw:
        assert eng._spec_dispatches > 0
    eng.close()
    for (prompt, n), fut in zip(cases, futs):
        got = fut.result(timeout=0)
        assert len(got) == n
        np.testing.assert_array_equal(
            got, oracle(model, variables, prompt, n),
            err_msg=f"{case}: diverged from generate: {prompt}")


@pytest.mark.parametrize("kw", [
    {}, {"spec_k": 4}, {"kv_dtype": "int8"}],
    ids=["one_token", "spec_k_4", "int8"])
def test_heads_that_fill_lane_tiles_keep_their_own_axis(kw):
    """A GPT with heads of 128: the stored shape follows the head size
    alone (``models/family.py``), so its pool keeps ``[.., heads, 128]``
    and its columns go in one at a time (the engine's loop, the afmoe
    family's path); the model merges the gathered rows itself. Same greedy
    tokens as ``generate``; int8 (one scale a column of both
    axes) serves its requests whole."""
    cfg = GPTConfig.tiny(hidden_size=256, num_heads=2)
    assert cfg.serving_family().kv_tail == (2, 128)
    model = GPTLMHeadModel(cfg)
    variables = model.init(
        jax.random.PRNGKey(1), jnp.zeros((1, 8), jnp.int32))
    cases = [([5, 3, 9, 2, 7, 11], 9), ([5, 3, 9, 2, 7, 11, 1, 4], 5),
             ([1, 2, 3, 4] * 3, 8)]
    eng = _engine(cfg, variables, kv_block_size=4, prefill_chunk=8, **kw)
    assert eng._pool_kv["k"].shape[2:] == (4, 2, 128)
    futs = [eng.submit(p, n) for p, n in cases]
    _drain(eng, futs)
    eng.close()
    for (prompt, n), fut in zip(cases, futs):
        got = fut.result(timeout=0)
        assert len(got) == n
        if "kv_dtype" not in kw:
            np.testing.assert_array_equal(
                got, oracle(model, variables, prompt, n))
