"""``ContinuousGPTEngine`` serving the ``olmo_hybrid`` family at the
benchmark's rehearsal size, float32: a request gets the plain reference's
greedy tokens alone, among others and in a slot another request left; the
recurrent state of a row that is not live in a step does not move (an idle
slot, a row that joins while a step is in flight); a chained step is its
single steps; what the family cannot carry through is refused or passed up
by name; the spans and counters it brings."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_olmo_hybrid as ref
from sparkdl_tpu.disagg.workers import DecodeWorker, PrefillWorker
from sparkdl_tpu.models.olmo_hybrid import (
    OlmoHybridLMHeadModel,
    config_from_hf_olmo_hybrid,
    init_olmo_hybrid_cache,
)
from sparkdl_tpu.observability import tracing
from sparkdl_tpu.observability.registry import registry
from sparkdl_tpu.serving import ContinuousGPTEngine, continuous
from sparkdl_tpu.serving.kv_blocks import kv_bytes_per_token
from sparkdl_tpu.serving.tenancy import PRIORITY_BACKGROUND, TenantRegistry
from tests.models.test_olmo_hybrid import SEED, rehearsal_hf

N_OUT = 10
#: eight prompts on four slots: shorter than a block, across blocks, one and
#: several prefill chunks of 32, a last chunk that needs no pad (64)
LENGTHS = (5, 40, 70, 17, 33, 90, 64, 12)


@pytest.fixture(scope="module")
def family():
    from benchmark.runners import serve_olmo_hybrid

    hf = rehearsal_hf()
    cfg = config_from_hf_olmo_hybrid(hf)
    variables = serve_olmo_hybrid.program_variables(
        OlmoHybridLMHeadModel(cfg), hf, "float32", SEED)
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in LENGTHS]
    return hf, cfg, variables, prompts


def _reference_greedy(hf, prompts, n_out):
    """The float32 token-by-token reference decoding greedily, every row at
    one padded width (what lies behind a position cannot reach it)."""
    seqs = np.zeros((len(prompts), 128), np.int32)
    for r, p in enumerate(prompts):
        seqs[r, :len(p)] = p
    margins = []
    with jax.default_matmul_precision("highest"):
        top = ref.top_weights(SEED, hf, "float32")
        for j in range(n_out):
            x = ref.hybrid_hidden(SEED, hf, seqs, "float32")
            at = np.array([len(p) - 1 + j for p in prompts])
            logits = np.asarray(ref.hybrid_logits_at(
                top, hf, x[np.arange(len(at)), at]))
            seqs[np.arange(len(at)), at + 1] = logits.argmax(-1)
            best = np.sort(logits, axis=-1)
            margins.append(best[:, -1] - best[:, -2])
    return ([seqs[r, len(p):len(p) + n_out] for r, p in enumerate(prompts)],
            np.stack(margins, axis=1))


@pytest.fixture(scope="module")
def served(family):
    """Each of the first three requests alone, then all eight at once on
    four slots (every slot serves a second request), on one engine."""
    hf, cfg, variables, prompts = family
    tracing.enable_tracing()
    tracing.clear_trace()
    try:
        with ContinuousGPTEngine(cfg, variables, n_slots=4, max_len=128,
                                 prefill_chunk=32) as eng:
            alone = [np.asarray(eng.submit(p, N_OUT).result(timeout=600))
                     for p in prompts[:3]]
            futures = [eng.submit(p, N_OUT) for p in prompts]
            among = [np.asarray(f.result(timeout=600)) for f in futures]
            snap = eng.snapshot()
            capacity = eng.capacity()
        events = tracing.trace_events()
    finally:
        tracing.disable_tracing()
    want, margins = _reference_greedy(hf, prompts, N_OUT)
    return {"alone": alone, "among": among, "want": want, "snap": snap,
            "margins": margins, "events": events, "capacity": capacity}


@pytest.mark.parametrize("i", range(len(LENGTHS)))
def test_a_request_gets_the_references_greedy_tokens_in_a_fresh_or_a_used_slot(
        served, i):
    # the reference's own margin between its best and second token is far
    # over float32's rounding at every served position: an argmax that
    # agrees is no accident of a tie
    assert served["margins"][i].min() > 1e-4
    assert served["among"][i].tolist() == served["want"][i].tolist()
    if i < len(served["alone"]):
        # alone it ran in a fresh slot; among the others, four of the eight
        # ran in a slot whose state another request had left behind
        assert served["alone"][i].tolist() == served["among"][i].tolist()


def test_the_spans_count_state_rows_scan_tokens_and_matches_passed_up(
        served, family):
    _, cfg, _, _ = family
    fam = cfg.serving_family()
    steps = [e["args"] for e in served["events"]
             if e["name"] == "serving.decode_step"]
    assert steps
    per_row = 2 * fam.state_bytes_per_slot
    for a in steps:
        assert a["state_rows"] == a["slots"] * a["chain"]
        assert a["state_bytes"] == a["state_rows"] * per_row
        assert a["kv_cols_read"] == 4 * a["nb"] * 16 * a["chain"]
    chunks = [e["args"] for e in served["events"]
              if e["name"] == "serving.prefill_chunk"]
    assert chunks
    for a in chunks:
        assert a["scan_tokens"] == a["tokens"]
        assert a["pad_tokens"] == a["width"] - a["tokens"]
        # linear heads of 8 x 16: the solve kernel's rule refuses them
        assert a["scan_solved_in_kernel"] == 0
    assert not fam.scan_solved_in_kernel
    assert any(a["pad_tokens"] for a in chunks)
    assert any(a["pad_tokens"] == 0 and a["final"] for a in chunks)
    # prompts 0-2 came a second time: the first 3 of 5 tokens' block is not
    # whole, 39 of 40 and 69 of 70 match; every match was passed up
    admits = [e["args"] for e in served["events"]
              if e["name"] == "serving.admit"]
    assert all(a["cached_tokens"] == 0 for a in admits)
    passed = [a["prefix_passed_up"] for a in admits]
    assert sum(passed) == served["snap"]["kv"]["prefix_passed_up"] > 60
    assert served["snap"]["kv"]["prefix_hits"] == 0
    scanned = registry().get("sparkdl_linear_scan_tokens_total")
    assert sum(scanned.snapshot_values().values()) >= sum(
        a["scan_tokens"] for a in chunks)


def test_an_engine_whose_widths_the_rule_takes_solves_in_the_kernel():
    """Linear heads of 32 x 96 (the narrowest ``ops/delta_solve``'s rule
    takes): the chunk programs solve through the kernel (the interpreter
    here), the chunks' spans say so, and the tokens are the reference's, a
    prompt of several chunks with a padded last one among them. The
    published widths are taken too."""
    from benchmark.runners import serve_olmo_hybrid
    from sparkdl_tpu.models.olmo_hybrid import OlmoHybridConfig

    assert OlmoHybridConfig().serving_family().scan_solved_in_kernel
    hf = {**rehearsal_hf(), "linear_key_head_dim": 32,
          "linear_value_head_dim": 96}
    cfg = config_from_hf_olmo_hybrid(hf)
    assert cfg.serving_family().scan_solved_in_kernel
    variables = serve_olmo_hybrid.program_variables(
        OlmoHybridLMHeadModel(cfg), hf, "float32", SEED)
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (70, 9)]
    tracing.enable_tracing()
    tracing.clear_trace()
    try:
        with ContinuousGPTEngine(cfg, variables, n_slots=2, max_len=128,
                                 prefill_chunk=32) as eng:
            got = [np.asarray(f.result(timeout=600))
                   for f in [eng.submit(p, 4) for p in prompts]]
        events = tracing.trace_events()
    finally:
        tracing.disable_tracing()
    chunks = [e["args"] for e in events
              if e["name"] == "serving.prefill_chunk"]
    assert len(chunks) == 4
    assert all(a["scan_solved_in_kernel"] == 1 for a in chunks)
    want, margins = _reference_greedy(hf, prompts, 4)
    assert margins.min() > 1e-4
    for g, w in zip(got, want):
        assert g.tolist() == w.tolist()


def test_capacity_counts_the_layers_that_keep_kv_and_says_the_states_bytes(
        served, family):
    _, cfg, _, _ = family
    fam = cfg.serving_family()
    kv = served["snap"]["kv"]
    # K and V of 4 heads of 16 in float32 on one merged axis stored as 128,
    # in the TWO full layers of the eight
    assert (fam.layers, fam.pool_layers, fam.state_layers) == (8, 2, 6)
    assert kv["bytes_per_token"] == kv_bytes_per_token(cfg) == 2 * 128 * 4 * 2
    per_slot = 6 * (4 * 8 * 16 * 4 + 3 * 128 * 4)
    assert kv["state_bytes_per_slot"] == per_slot
    assert kv["state_bytes"] == 4 * per_slot
    assert served["capacity"]["state_bytes"] == 4 * per_slot
    assert served["capacity"]["kv_bytes_per_token"] == kv["bytes_per_token"]


# -- rows that are not live in a step ---------------------------------------------

def _engine(family, **kw):
    _, cfg, variables, _ = family
    kw = {"n_slots": 3, "max_len": 128, "kv_block_size": 8,
          "prefill_chunk": 16, "auto_start": False, **kw}
    return ContinuousGPTEngine(cfg, variables, **kw)


def _drain(eng, futs):
    deadline = time.monotonic() + 300
    while not all(f.done() for f in futs):
        assert time.monotonic() < deadline, "engine did not finish"
        eng.tick()


def _state(eng):
    return {name: np.asarray(eng._pool_kv[name])
            for name in ("state", "conv")}


def _prefilled(family, prompt):
    """The state a prompt leaves, from one dense call outside the engine."""
    _, cfg, variables, _ = family
    _, cache = OlmoHybridLMHeadModel(cfg).apply(
        variables, jnp.asarray(prompt[None]),
        cache=init_olmo_hybrid_cache(cfg, 1, 128))
    return {name: np.asarray(cache[name][:, 0]) for name in ("state", "conv")}


def test_an_idle_slots_state_is_the_same_bits_after_steps(family):
    _, _, _, prompts = family
    eng = _engine(family)
    try:
        rng = np.random.default_rng(3)
        # sparkdl-lint: disable=lock-discipline -- a test's engine, ticked by hand
        eng._pool_kv = {**eng._pool_kv, **{
            name: jnp.asarray(rng.normal(size=a.shape), a.dtype)
            for name, a in _state(eng).items()}}
        before = _state(eng)
        fut = eng.submit(prompts[1], 6)
        _drain(eng, [fut])
        eng._settle()
        after = _state(eng)
        (slot,) = {0, 1, 2} - {1, 2}   # the request took slot 0
        for name in before:
            assert not np.array_equal(before[name][:, slot],
                                      after[name][:, slot]), name
            for idle in (1, 2):
                assert np.array_equal(before[name][:, idle],
                                      after[name][:, idle]), (name, idle)
    finally:
        eng.close()


def test_a_row_that_joins_behind_a_step_in_flight_is_not_advanced_by_it(
        family):
    """Row A decodes one step ahead. B's last chunk is dispatched, then step
    n+1 is launched for A alone (B joins when its first token is read), and
    runs BEHIND the chunk that installed B's state: it must leave that state
    alone. Read at once, B's row holds exactly what its prompt left."""
    _, _, _, prompts = family
    eng = _engine(family)
    try:
        a = eng.submit(prompts[1], 30)
        while not (eng._steps_out and eng.active_slots == 1):
            eng.tick()
        b = eng.submit(prompts[3], 8)   # 17 tokens: chunks of 16 and 1
        while not any(f.req.future is b for f in eng._inflight.values()):
            out_before = len(eng._steps_out)
            eng.tick()
        # B has just joined: the step launched in this tick did not carry it
        (slot,) = [s for s, f in eng._inflight.items() if f.req.future is b]
        assert out_before and eng._steps_out
        assert all(s != slot for s, _ in eng._steps_out[-1].rows)
        eng._settle()   # that step has run, behind B's install
        want, got = _prefilled(family, prompts[3]), _state(eng)
        for name in want:
            # (chunks of 16 and 1 against one call of 17: float32's last
            # digits; a step's advance would move the state by its own size)
            np.testing.assert_allclose(got[name][:, slot], want[name],
                                       rtol=1e-3, atol=1e-5, err_msg=name)
            assert np.abs(want[name]).max() > 1e-2
        _drain(eng, [a, b])
    finally:
        eng.close()
    # and both rows' tokens are a synchronous loop's
    sync = _engine(family)
    try:
        a2, b2 = sync.submit(prompts[1], 30), None
        while not sync.active_slots:
            sync.tick()
            sync._settle()
        b2 = sync.submit(prompts[3], 8)
        while not (a2.done() and b2.done()):
            sync.tick()
            sync._settle()
    finally:
        sync.close()
    assert a.result(timeout=0).tolist() == a2.result(timeout=0).tolist()
    assert b.result(timeout=0).tolist() == b2.result(timeout=0).tolist()


def test_a_chain_of_four_is_four_single_steps(family):
    _, _, _, prompts = family
    got = {}
    for chain in (1, 4):
        eng = _engine(family, chain_tokens=chain)
        try:
            futs = [eng.submit(prompts[1], 9), eng.submit(prompts[4], 9)]
            _drain(eng, futs)
            eng._settle()
            got[chain] = ([f.result(timeout=0).tolist() for f in futs],
                          _state(eng))
        finally:
            eng.close()
    assert got[1][0] == got[4][0]
    for name in ("state", "conv"):
        np.testing.assert_allclose(got[4][1][name], got[1][1][name],
                                   rtol=1e-5, atol=1e-7, err_msg=name)


# -- refused, or passed up, by name ------------------------------------------------

@pytest.mark.parametrize("option", [
    {"sp": 2}, {"spec_k": 4}, {"kv_dtype": "int8"}])
def test_what_the_family_has_no_path_for_is_refused_at_construction(
        family, option):
    _, cfg, variables, _ = family
    with pytest.raises(ValueError, match="native K/V dtype alone"):
        ContinuousGPTEngine(cfg, variables, auto_start=False, **option)


def test_tiered_kv_is_refused_at_construction_by_the_states_name(family):
    _, cfg, variables, _ = family
    with pytest.raises(ValueError, match="recurrent state.*host_kv_blocks"):
        ContinuousGPTEngine(cfg, variables, auto_start=False,
                            host_kv_blocks=8)
    eng = _engine(family)
    try:
        with pytest.raises(RuntimeError, match="host tier"):
            eng.park_cold()
        assert eng.export_parked_sessions() is None
    finally:
        eng.close()


def test_a_handoff_between_tiers_is_refused_at_the_call(family):
    _, cfg, variables, prompts = family
    worker = PrefillWorker(cfg, variables, n_slots=2, max_len=128,
                           auto_start=False)
    try:
        with pytest.raises(NotImplementedError, match="recurrent state"):
            worker.submit(prompts[0], 4)
    finally:
        worker.close()
    worker = DecodeWorker(cfg, variables, n_slots=2, max_len=128,
                          auto_start=False)
    try:
        with pytest.raises(NotImplementedError, match="recurrent state"):
            worker.submit_handoff(object())
        # a prompt of its own it still serves
        fut = worker.submit(prompts[0], 3)
        _drain(worker, [fut])
        assert len(fut.result(timeout=0)) == 3
    finally:
        worker.close()


def test_a_repeated_prompt_is_prefilled_whole_and_the_match_counted(family):
    _, _, _, prompts = family
    eng = _engine(family)
    try:
        first = eng.submit(prompts[2], 5)
        _drain(eng, [first])
        chunks = eng.snapshot()["kv"]["prefill_chunks"]
        assert chunks == 5                   # 70 tokens, 16 a chunk
        again = eng.submit(prompts[2], 5)
        _drain(eng, [again])
        kv = eng.snapshot()["kv"]
        assert kv["prefill_chunks"] == 2 * chunks
        assert kv["prefix_passed_up"] == 69 and kv["prefix_hits"] == 0
        assert again.result(timeout=0).tolist() == first.result(
            timeout=0).tolist()
    finally:
        eng.close()


def test_a_preempted_prompt_starts_again_and_serves_the_same_tokens(
        family, monkeypatch):
    """A background prompt is torn down between its chunks for an
    interactive arrival: its running state goes with its private cache, it
    is prefilled again from token 0, and its tokens are those it gets
    alone."""
    _, _, _, prompts = family
    alone = _engine(family)
    try:
        fut = alone.submit(prompts[5], 6)
        _drain(alone, [fut])
        want = fut.result(timeout=0).tolist()
    finally:
        alone.close()
    preempted = []
    note = continuous.tenancy.note_preemption
    monkeypatch.setattr(continuous.tenancy, "note_preemption",
                        lambda: (preempted.append(1), note()))
    reg = TenantRegistry()
    reg.configure("offline", priority=PRIORITY_BACKGROUND)
    eng = _engine(family, n_slots=2, tenants=reg)
    try:
        futs = [eng.submit(prompts[3], 12, tenant="acme")]
        eng.tick()
        eng.tick()
        futs.append(eng.submit(prompts[5], 6, tenant="offline"))
        eng.tick()
        eng.tick()
        (st,) = eng._prefilling.values()
        assert 0 < st.pos < len(prompts[5]) and st.rec
        futs.append(eng.submit(prompts[0], 2, tenant="acme"))
        _drain(eng, futs)
        assert preempted
        assert futs[1].result(timeout=0).tolist() == want
        # ninety tokens twice but for the chunks done before the teardown
        assert eng.snapshot()["kv"]["prefix_passed_up"] == 0
    finally:
        eng.close()


# -- the other families' programs are what they were --------------------------------

#: sha256 (first 16 hex digits) of the lowered text of each paged program of
#: a tiny GPT, the same with an int8 pool, and a tiny afmoe, read at the
#: PARENT of the PR that brought state layers (2 slots x 64, blocks of 4,
#: chunks of 8; this installation's jax). The slot arrays, the ``live`` mask
#: and the chunk programs' trailing arguments must not reach them.
PARENT_LOWERED = {
    "gpt.step": "665639c23bb223fa", "gpt.chain": "749ecaa8c2c7b835",
    "gpt.one": "81b569735946e826", "gpt.first": "09b13aa6b3eb57e1",
    "gpt.mid": "397bb9e8a749254f", "gpt.final": "ea833ea430732f85",
    "gpt-int8.step": "96ee82773b3e7d81", "gpt-int8.chain": "1c355bc66fb77995",
    "gpt-int8.one": "2a4d1613826337f9", "gpt-int8.first": "8883d9a4613a5a2e",
    "gpt-int8.mid": "397bb9e8a749254f", "gpt-int8.final": "47d9908d771816c7",
    "afmoe.step": "e99637e4b1815cfe", "afmoe.chain": "26928293efc8ccc3",
    "afmoe.one": "809333259675e02d", "afmoe.first": "8ffed122b097bcc4",
    "afmoe.mid": "9e617cab9deef833", "afmoe.final": "9847cb389412176e",
}


@pytest.fixture(scope="module")
def lowered_digests():
    import hashlib

    from sparkdl_tpu.models.afmoe import AfmoeConfig, AfmoeLMHeadModel
    from sparkdl_tpu.models.gpt import GPTConfig, GPTLMHeadModel

    out = {}
    ids = jnp.zeros((1, 8), jnp.int32)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    for name, cfg, model, kw in (
            ("gpt", GPTConfig.tiny(), GPTLMHeadModel, {}),
            ("gpt-int8", GPTConfig.tiny(), GPTLMHeadModel,
             {"kv_dtype": "int8"}),
            ("afmoe", AfmoeConfig.tiny(), AfmoeLMHeadModel, {})):
        variables = jax.eval_shape(
            lambda: model(cfg).init(jax.random.PRNGKey(0), ids))
        eng = ContinuousGPTEngine(cfg, variables, n_slots=2, max_len=64,
                                  kv_block_size=4, prefill_chunk=8,
                                  auto_start=False, **kw)
        try:
            pool, mb = eng._pool_kv, eng._mb
            priv = jax.ShapeDtypeStruct(
                (pool["k"].shape[0], 1, eng._wp) + pool["k"].shape[3:],
                eng._sizes.dtype)
            step = (variables, pool, i32(2, mb), i32(2), i32(2), i32(2))
            progs = {
                "step": eng._paged_step_fn.lower(*step, 1, 4),
                "chain": eng._paged_step_fn.lower(*step, 2, 4),
                "one": eng._chunk_one_fn.lower(
                    variables, pool, i32(mb), i32(), i32(1, 8), i32(mb), 16),
                "first": eng._chunk_first_fn.lower(
                    variables, pool, i32(mb), i32(), i32(1, 8), 16),
                "mid": eng._chunk_mid_fn.lower(
                    variables, priv, priv, i32(), i32(1, 8), 16),
                "final": eng._chunk_final_fn.lower(
                    variables, pool, priv, priv, i32(), i32(1, 8), i32(mb),
                    16),
            }
        finally:
            eng.close()
        for prog, low in progs.items():
            out[f"{name}.{prog}"] = hashlib.sha256(
                low.as_text().encode()).hexdigest()[:16]
    return out


@pytest.mark.parametrize("program", sorted(PARENT_LOWERED))
def test_a_family_without_state_lowers_to_the_parents_text(lowered_digests,
                                                           program):
    assert lowered_digests[program] == PARENT_LOWERED[program]
