"""``ContinuousGPTEngine`` serving the ``lfm2_moe`` family at the benchmark's
rehearsal size, float32: a request gets the plain reference's greedy tokens
alone (more slots than clients), among others admitted mid-flight (fewer) and
in a slot a LONGER request left, through chunked prefill (a tail carried
across every chunk boundary, padded last chunks, a prompt of one token) and
decode; the tail of a row that is not live in a step does not move; a chained
step is its single steps; what moves blocks alone is refused or passed up by
name, through the code the families with arrays by slot share; the spans,
counters and the gauge it brings."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_lfm2_moe as ref
from benchmark.runners import serve_lfm2_moe
from sparkdl_tpu.disagg.workers import DecodeWorker, PrefillWorker
from sparkdl_tpu.models.lfm2_moe import (
    Lfm2MoeLMHeadModel,
    config_from_hf_lfm2_moe,
    init_lfm2_moe_cache,
)
from sparkdl_tpu.observability import tracing
from sparkdl_tpu.observability.registry import registry
from sparkdl_tpu.serving import ContinuousGPTEngine
from sparkdl_tpu.serving.kv_blocks import kv_bytes_per_token
from tests.models.test_lfm2_moe import SEED, rehearsal_hf

N_OUT = 8
#: eight prompts on four slots: one token, two, three; one chunk of 16 less
#: one, whole, and one more; several chunks with a padded last one; the
#: second four run in slots the first four left, the short behind the long
LENGTHS = (40, 17, 33, 16, 1, 2, 15, 3)


@pytest.fixture(scope="module")
def family():
    hf = rehearsal_hf()
    cfg = config_from_hf_lfm2_moe(hf)
    variables = serve_lfm2_moe.program_variables(
        Lfm2MoeLMHeadModel(cfg), hf, "float32", SEED)
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in LENGTHS]
    return hf, cfg, variables, prompts


def _reference_greedy(hf, prompts, n_out):
    """The float32 reference decoding greedily, every row at one padded
    width (what lies behind a position cannot reach it)."""
    seqs = np.zeros((len(prompts), 64), np.int32)
    for r, p in enumerate(prompts):
        seqs[r, :len(p)] = p
    margins = []
    rows = np.arange(len(prompts))
    with jax.default_matmul_precision("highest"):
        top = ref.top_weights(SEED, hf, "float32")
        for j in range(n_out):
            x, _ = ref.lfm2_hidden(SEED, hf, seqs, "float32")
            at = np.array([len(p) - 1 + j for p in prompts])
            logits = np.asarray(ref.lfm2_logits_at(top, hf, x[rows, at]))
            seqs[rows, at + 1] = logits.argmax(-1)
            best = np.sort(logits, axis=-1)
            margins.append(best[:, -1] - best[:, -2])
    return ([seqs[r, len(p):len(p) + n_out] for r, p in enumerate(prompts)],
            np.stack(margins, axis=1))


@pytest.fixture(scope="module")
def served(family):
    """Each of the first three requests alone (one client on four slots),
    then all eight at once on four slots (the second four are admitted
    mid-flight, each into a slot another request left), on one engine."""
    hf, cfg, variables, prompts = family
    tracing.enable_tracing()
    tracing.clear_trace()
    try:
        with ContinuousGPTEngine(cfg, variables, n_slots=4, max_len=64,
                                 prefill_chunk=16) as eng:
            alone = [np.asarray(eng.submit(p, N_OUT).result(timeout=600))
                     for p in prompts[:3]]
            futures = [eng.submit(p, N_OUT) for p in prompts]
            among = [np.asarray(f.result(timeout=600)) for f in futures]
            snap = eng.snapshot()
            capacity = eng.capacity()
            shapes = {k: v.shape for k, v in eng._pool_kv.items()}
        events = tracing.trace_events()
    finally:
        tracing.disable_tracing()
    want, margins = _reference_greedy(hf, prompts, N_OUT)
    return {"alone": alone, "among": among, "want": want, "snap": snap,
            "margins": margins, "events": events, "capacity": capacity,
            "shapes": shapes}


@pytest.mark.parametrize("i", range(len(LENGTHS)))
def test_a_request_gets_the_references_greedy_tokens_in_a_fresh_or_a_used_slot(
        served, i):
    # the reference's own margin between its best and second token is far
    # over float32's rounding at every served position: an argmax that
    # agrees is no accident of a tie
    assert served["margins"][i].min() > 1e-4
    assert served["among"][i].tolist() == served["want"][i].tolist()
    if i < len(served["alone"]):
        assert served["alone"][i].tolist() == served["among"][i].tolist()


def test_the_spans_count_tails_expert_pairs_and_matches_passed_up(
        served, family):
    _, cfg, _, _ = family
    fam = cfg.serving_family()
    steps = [e["args"] for e in served["events"]
             if e["name"] == "serving.decode_step"]
    assert steps
    # a riding row's tails, in and out, in all four convolutions
    per_row = 2 * fam.state_bytes_per_slot
    assert per_row == 2 * 4 * 2 * 64 * 4
    for a in steps:
        assert a["state_rows"] == a["slots"] * a["chain"]
        assert a["state_bytes"] == a["state_rows"] * per_row
        # the one attention layer gathers every slot's nb blocks
        assert a["kv_cols_read"] == 4 * a["nb"] * 16 * a["chain"]
        assert 0 < a["kv_cols_live"] <= a["kv_cols_read"]
        assert "win_cols_read" not in a and "kv_cols_read_window" not in a
        # every expert is held: every pair the router made is computed
        assert a["expert_pairs"] == 4 * 2 * a["chain"]
        # (a step computes all four rows of its batch, riding or not)
        assert a["expert_rows"] == a["expert_pairs"]
        assert 0 < a["experts_hit"] <= fam.experts * a["chain"]
        assert a["expert_rows_max"] <= 4
    assert any(a["slots"] == 4 for a in steps)
    chunks = [e["args"] for e in served["events"]
              if e["name"] == "serving.prefill_chunk"]
    assert chunks
    for a in chunks:
        assert a["scan_tokens"] == a["tokens"]
        assert a["pad_tokens"] == a["width"] - a["tokens"]
        assert a["scan_solved_in_kernel"] == 0
    assert any(a["pad_tokens"] for a in chunks)
    assert {a["program"] for a in chunks} == {
        "chunk_one", "chunk_first", "chunk_mid", "chunk_final"}
    admits = [e["args"] for e in served["events"]
              if e["name"] == "serving.admit"]
    assert all(a["cached_tokens"] == 0 for a in admits)
    passed = [a["prefix_passed_up"] for a in admits]
    # the first three prompts came again: their blocks matched, and no hit
    # was honoured (no block holds a tail at the boundary)
    assert sum(passed) == served["snap"]["kv"]["prefix_passed_up"] > 60
    assert served["snap"]["kv"]["prefix_hits"] == 0


def test_capacity_counts_the_attention_layer_and_says_the_tails_bytes(
        served, family):
    _, cfg, _, _ = family
    fam = cfg.serving_family()
    kv = served["snap"]["kv"]
    # K and V of 2 heads of 8 in float32, each on one merged axis stored as
    # 128, in the ONE attention layer of the five
    assert (fam.layers, fam.pool_layers, fam.state_layers) == (5, 1, 4)
    assert kv["bytes_per_token"] == kv_bytes_per_token(cfg) == 2 * 128 * 4
    per_slot = 4 * 2 * 64 * 4
    assert kv["state_bytes_per_slot"] == per_slot
    assert kv["state_bytes"] == 4 * per_slot
    assert served["capacity"]["state_bytes"] == 4 * per_slot
    # the tails' shape is the taps', not the context's
    assert served["shapes"]["conv"] == (4, 4, 2, 64)
    assert served["shapes"]["k"] == served["shapes"]["v"] == (1, 16, 16, 128)


def test_the_tail_gauge_says_the_tails_bytes_while_the_engine_lives(family):
    _, cfg, _, _ = family
    gauge = registry().get("sparkdl_conv_tail_bytes")
    others = [registry().get(name) for name in (
        "sparkdl_linear_state_bytes", "sparkdl_window_ring_bytes")]
    total = lambda g: sum(g.snapshot_values().values())  # noqa: E731
    before, before_others = total(gauge), [total(g) for g in others]
    eng = _engine(family)
    try:
        per_slot = cfg.serving_family().state_bytes_per_slot
        assert total(gauge) == before + 3 * per_slot
        assert [total(g) for g in others] == before_others
    finally:
        eng.close()
    assert total(gauge) == before


# -- rows that are not live in a step ---------------------------------------------

def _engine(family, **kw):
    _, cfg, variables, _ = family
    kw = {"n_slots": 3, "max_len": 64, "kv_block_size": 8,
          "prefill_chunk": 16, "auto_start": False, **kw}
    return ContinuousGPTEngine(cfg, variables, **kw)


def _drain(eng, futs):
    deadline = time.monotonic() + 300
    while not all(f.done() for f in futs):
        assert time.monotonic() < deadline, "engine did not finish"
        eng.tick()


def _tails(eng):
    return np.asarray(eng._pool_kv["conv"])


def _prefilled(family, prompt):
    """The tails a prompt leaves, from one dense call outside the engine."""
    _, cfg, variables, _ = family
    _, cache = Lfm2MoeLMHeadModel(cfg).apply(
        variables, jnp.asarray(prompt[None]),
        cache=init_lfm2_moe_cache(cfg, 1, 64))
    return np.asarray(cache["conv"][:, 0])


def test_an_idle_slots_tail_is_the_same_bits_after_steps(family):
    _, _, _, prompts = family
    eng = _engine(family)
    try:
        rng = np.random.default_rng(3)
        # sparkdl-lint: disable=lock-discipline -- a test's engine, ticked by hand
        eng._pool_kv = {**eng._pool_kv, "conv": jnp.asarray(
            rng.normal(size=_tails(eng).shape), jnp.float32)}
        before = _tails(eng)
        fut = eng.submit(prompts[1], 6)
        _drain(eng, [fut])
        eng._settle()
        after = _tails(eng)
        assert not np.array_equal(before[:, 0], after[:, 0])
        for idle in (1, 2):
            assert np.array_equal(before[:, idle], after[:, idle]), idle
    finally:
        eng.close()


def test_a_slot_taken_by_a_shorter_request_starts_from_zeros(family):
    """17 tokens leave their tail in the one slot; each next prompt in that
    slot (one token, then two) starts from zeros and its one chunk installs
    ITS tail whole: what one dense call over it leaves (a prompt of one
    token: ``[0, z_0]``)."""
    _, _, _, prompts = family
    eng = _engine(family, n_slots=1)
    try:
        first = eng.submit(prompts[1], 3)
        _drain(eng, [first])
        eng._settle()
        assert np.abs(_tails(eng)[:, 0]).min(axis=-1).max() > 0
        for second in (4, 5):
            fut = eng.submit(prompts[second], 1)
            _drain(eng, [fut])
            eng._settle()
            want = _prefilled(family, prompts[second])
            np.testing.assert_allclose(_tails(eng)[:, 0], want, atol=2e-5)
            if len(prompts[second]) == 1:
                assert (_tails(eng)[:, 0, 0] == 0).all()
                assert np.abs(_tails(eng)[:, 0, 1]).max() > 0
    finally:
        eng.close()


def test_a_row_that_joins_behind_a_step_in_flight_is_not_advanced_by_it(
        family):
    """Row A decodes one step ahead. B's last chunk is dispatched, then a
    step is launched for A alone (B joins when its first token is read): it
    runs BEHIND the chunk that installed B's tail and must not shift it."""
    _, _, _, prompts = family
    eng = _engine(family)
    try:
        a = eng.submit(prompts[2], 24)
        while not (eng._steps_out and eng.active_slots == 1):
            eng.tick()
        b = eng.submit(prompts[1], 8)   # 17 tokens: chunks of 16 and 1
        while not any(f.req.payload.prompt.shape[0] == 17
                      for f in eng._inflight.values()):
            eng.tick()
        (slot,) = [s for s, f in eng._inflight.items()
                   if f.req.payload.prompt.shape[0] == 17]
        np.testing.assert_allclose(
            _tails(eng)[:, slot], _prefilled(family, prompts[1]), atol=2e-5)
        _drain(eng, [a, b])
    finally:
        eng.close()


def test_a_chain_of_four_is_four_single_steps(family):
    _, _, _, prompts = family
    outs = []
    for chain in (1, 4):
        eng = _engine(family, chain_tokens=chain)
        try:
            futs = [eng.submit(prompts[1], 9), eng.submit(prompts[2], 9)]
            _drain(eng, futs)
            outs.append([np.asarray(f.result()) for f in futs])
        finally:
            eng.close()
    for one, four in zip(*outs):
        assert one.tolist() == four.tolist()


# -- refused, or passed up, by name: the code PR 34 wrote, no new refusal ----------

@pytest.mark.parametrize("kw", [
    {"spec_k": 2}, {"kv_dtype": "int8"}, {"sp": 2}],
    ids=lambda kw: next(iter(kw)))
def test_what_the_family_has_no_path_for_is_refused_at_construction(
        family, kw):
    _, cfg, variables, _ = family
    with pytest.raises(ValueError, match="Lfm2MoeConfig.*native K/V dtype alone"):
        ContinuousGPTEngine(cfg, variables, n_slots=2, max_len=64,
                            auto_start=False, **kw)


def test_tiered_kv_is_refused_at_construction_by_the_familys_name(family):
    _, cfg, variables, _ = family
    with pytest.raises(
            ValueError,
            match="Lfm2MoeConfig keeps arrays by slot.*4 of its layers"
                  ".*host_kv_blocks"):
        ContinuousGPTEngine(cfg, variables, n_slots=2, max_len=64,
                            host_kv_blocks=8, auto_start=False)
    eng = _engine(family)
    try:
        with pytest.raises(RuntimeError, match="host tier"):
            eng.park_cold()
    finally:
        eng.close()


def test_a_handoff_between_tiers_is_refused_at_the_call(family):
    _, cfg, variables, prompts = family
    said = "Lfm2MoeConfig: 4 of its layers keep arrays by slot"
    worker = PrefillWorker(cfg, variables, n_slots=2, max_len=64,
                           auto_start=False)
    try:
        with pytest.raises(NotImplementedError, match=said):
            worker.submit(prompts[1], 4)
    finally:
        worker.close()
    worker = DecodeWorker(cfg, variables, n_slots=2, max_len=64,
                          auto_start=False)
    try:
        with pytest.raises(NotImplementedError, match=said):
            worker.submit_handoff(object())
        # a prompt of its own it still serves
        fut = worker.submit(prompts[1], 3)
        _drain(worker, [fut])
        assert len(fut.result(timeout=0)) == 3
    finally:
        worker.close()


def test_a_repeated_prompt_is_prefilled_whole_and_the_match_counted(family):
    _, _, _, prompts = family
    eng = _engine(family)
    try:
        first = eng.submit(prompts[0], 4)
        _drain(eng, [first])
        again = eng.submit(prompts[0], 4)
        _drain(eng, [again])
        eng._settle()
        kv = eng.snapshot()["kv"]
        # 39 of the 40 tokens matched (4 whole blocks of 8 and a partial)
        assert kv["prefix_passed_up"] >= 32 and kv["prefix_hits"] == 0
        assert np.asarray(first.result()).tolist() == np.asarray(
            again.result()).tolist()
    finally:
        eng.close()
