"""``ContinuousGPTEngine`` serving the ``glm_moe_dsa`` family at the
benchmark's rehearsal size, float32: a request gets the plain reference's
greedy tokens alone, among others and in a slot another request left, through
chunked prefill (chunks under and across the selection's 16 columns, padded
last chunks) and decode past it; the pool holds the family's OWN two arrays,
``latent`` over five layers and ``index_k`` over two, through install, gather,
scatter, a prefix hit and a park; what the family cannot carry through is
refused by name; the spans and counters it brings."""

import jax
import numpy as np
import pytest

from benchmark import reference_glm_moe_dsa as ref
from sparkdl_tpu.disagg.workers import DecodeWorker, PrefillWorker
from sparkdl_tpu.models import kv_pool
from sparkdl_tpu.models.glm_moe_dsa import GlmMoeDsaLMHeadModel
from sparkdl_tpu.observability import tracing
from sparkdl_tpu.observability.registry import registry
from sparkdl_tpu.serving import ContinuousGPTEngine
from sparkdl_tpu.serving.kv_blocks import kv_bytes_per_token
from tests.models.test_glm_moe_dsa import SEED, program_config, rehearsal_hf

N_OUT = 10
#: eight prompts on four slots: under the selection's 16 columns, across
#: blocks, one and several prefill chunks of 32, a last chunk that needs no
#: pad (64), contexts to six times the selection
LENGTHS = (5, 40, 70, 17, 33, 90, 64, 12)
NAMES = ("latent", "index_k")


@pytest.fixture(scope="module")
def family():
    from benchmark.runners import serve_glm_moe_dsa

    hf = rehearsal_hf()
    cfg = program_config(hf)
    variables = serve_glm_moe_dsa.program_variables(
        GlmMoeDsaLMHeadModel(cfg), hf, "float32", SEED)
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in LENGTHS]
    return hf, cfg, variables, prompts


def _reference_greedy(hf, prompts, n_out):
    """The float32 reference decoding greedily, every row at one padded
    width (what lies behind a position cannot reach it)."""
    seqs = np.zeros((len(prompts), 128), np.int32)
    for r, p in enumerate(prompts):
        seqs[r, :len(p)] = p
    margins = []
    rows = np.arange(len(prompts))
    with jax.default_matmul_precision("highest"):
        top = ref.top_weights(SEED, hf, "float32")
        for j in range(n_out):
            x, _ = ref.glm_hidden(SEED, hf, seqs, "float32")
            at = np.array([len(p) - 1 + j for p in prompts])
            logits = np.asarray(ref.glm_logits_at(top, hf, x[rows, at]))
            seqs[rows, at + 1] = logits.argmax(-1)
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
            shapes = {k: v.shape for k, v in eng._pool_kv.items()}
            gauge = _gauge()
        events = tracing.trace_events()
    finally:
        tracing.disable_tracing()
    want, margins = _reference_greedy(hf, prompts, N_OUT)
    return {"alone": alone, "among": among, "want": want, "snap": snap,
            "margins": margins, "events": events, "capacity": capacity,
            "shapes": shapes, "gauge": gauge}


def _gauge():
    fam = registry().snapshot().get("sparkdl_latent_pool_bytes")
    return 0.0 if fam is None else sum(fam["values"].values())


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
        # ran in a slot, and in blocks, another request had left behind
        assert served["alone"][i].tolist() == served["among"][i].tolist()


def test_the_pool_holds_the_familys_own_arrays_over_their_own_layers(
        served, family):
    _, cfg, _, _ = family
    fam = cfg.serving_family()
    # one column [c_kv 16 | k_r 8] stored as 128 in each of the FIVE layers,
    # an indexer's key of 16 in the TWO full layers, float32; no k, no v,
    # nothing by slot
    assert served["shapes"] == {"latent": (5, 32, 16, 128),
                                "index_k": (2, 32, 16, 16)}
    assert fam.pool_arrays == (("latent", 5, (128,)), ("index_k", 2, (16,)))
    assert (fam.state_layers, fam.state_arrays) == (0, ())
    kv = served["snap"]["kv"]
    assert kv["bytes_per_token"] == kv_bytes_per_token(cfg) == (
        5 * 128 * 4 + 2 * 16 * 4)
    assert kv["state_bytes"] == 0
    # the gauge says the named arrays' bytes while the engine lives
    assert served["gauge"] == 32 * 16 * kv["bytes_per_token"]
    assert _gauge() == 0
    assert tuple(kv_pool.init_block_pool(cfg, 2, 4)) == NAMES


@pytest.mark.parametrize("selections, read", [
    # rows 5, 40 and 90 deep ride a table of 8 blocks (8 selections of 16)
    pytest.param(None, 16 + 48 + 96, id="inside the rule: the riding rows' "
                                        "whole blocks"),
    pytest.param(4, 4 * 16, id="past it: the selection's size a slot"),
])
def test_the_count_of_a_steps_reads_follows_the_rule(family, monkeypatch,
                                                     selections, read):
    """``_count_kv_read`` asks ``sparse_attention.attends_in_place`` as the
    module's step does: what a tick FETCHES follows the form, what it
    ATTENDS and must score do not."""
    from sparkdl_tpu.ops import sparse_attention

    _, cfg, variables, _ = family
    if selections is not None:
        monkeypatch.setattr(sparse_attention, "IN_PLACE_SELECTIONS",
                            selections)
    eng = ContinuousGPTEngine(cfg, variables, n_slots=4, max_len=128,
                              auto_start=False)
    try:
        eng._pidx[:3] = (5, 40, 90)
        got = eng._count_kv_read(8, [0, 1, 2])
        # one block: the table is no wider than the selection, every
        # slot's is gathered whole whatever the rule says
        shallow = eng._count_kv_read(1, [0])
    finally:
        eng.close()
    assert got["kv_cols_read"] == read
    assert (got["kv_cols_live"], got["sel_cols"], got["index_cols"]) == (
        135, 5 + 16 + 16, 40 + 90)
    assert shallow["kv_cols_read"] == 4 * 16


def test_the_spans_count_selected_and_scored_columns(served, family):
    _, cfg, _, _ = family
    fam = cfg.serving_family()
    steps = [e["args"] for e in served["events"]
             if e["name"] == "serving.decode_step"]
    assert steps
    for a in steps:
        picks = a["nb"] * 16 > 16
        # attended: every column of a row no deeper than 16, 16 of a deeper
        assert 0 < a["sel_cols"] <= min(a["kv_cols_live"],
                                        16 * a["slots"] * a["chain"])
        # scored: the whole context of the rows deeper than the selection
        assert 0 <= a["index_cols"] <= a["kv_cols_live"]
        assert "index_cols_read" not in a      # no reader: no counter
        # fetched for the attention: every slot's table before any row's
        # passes the selection; past it (a table of this engine is at most
        # 8 selections wide, which the step attends IN PLACE) the riding
        # rows' whole blocks and no other slot's
        if picks:
            assert a["kv_cols_live"] <= a["kv_cols_read"] < (
                a["kv_cols_live"] + 16 * a["slots"] * a["chain"])
            assert a["kv_cols_read"] % 16 == 0
        else:
            assert a["kv_cols_read"] == 4 * a["nb"] * 16 * a["chain"]
        assert a["expert_pairs"] == 4 * 2 * a["chain"]
        assert 0 <= a["experts_hit"] <= fam.experts * a["chain"]
    deep = [a for a in steps if a["slots"] == 1 and a["chain"] == 1
            and a["kv_cols_live"] > 16]
    assert deep and all(a["sel_cols"] == 16 for a in deep)
    assert all(a["index_cols"] == a["kv_cols_live"] for a in deep)
    # where the selection bites a step ATTENDS fewer columns than are live
    # (and, in place, fetches them all)
    busy = [a for a in steps if a["kv_cols_live"] > 4 * 16 * a["chain"]]
    assert busy and all(a["sel_cols"] < a["kv_cols_live"] for a in busy)
    shallow = [a for a in steps if a["slots"] == 1 and a["nb"] == 1]
    assert shallow and all(a["index_cols"] == 0 and
                           a["sel_cols"] == a["kv_cols_live"]
                           for a in shallow)
    chunks = [e["args"] for e in served["events"]
              if e["name"] == "serving.prefill_chunk"]
    assert chunks
    for a in chunks:
        upto = np.arange(a["start"] + 1, a["start"] + a["tokens"] + 1)
        assert a["sel_cols"] == int(np.minimum(upto, 16).sum())
        assert a["index_cols"] == (int(upto.sum()) if a["cols"] > 16 else 0)
        assert "scan_tokens" not in a
    assert any(a["index_cols"] == 0 for a in chunks)
    assert any(a["sel_cols"] == 16 * a["tokens"] for a in chunks)
    # nothing by slot: a prefix match is honoured, none is passed up
    admits = [e["args"] for e in served["events"]
              if e["name"] == "serving.admit"]
    assert all("prefix_passed_up" not in a for a in admits)


def _engine(family, **kw):
    _, cfg, variables, _ = family
    kw.setdefault("n_slots", 2)
    kw.setdefault("max_len", 128)
    return ContinuousGPTEngine(cfg, variables, auto_start=False,
                               kv_block_size=8, prefill_chunk=32, **kw)


def _drain(eng, futs):
    while not all(f.done() for f in futs):
        eng.tick()


def test_a_repeated_prompt_takes_both_arrays_of_its_prefix_from_the_cache(
        served, family):
    """70 tokens again: 64 of them come out of the pool's blocks, ``latent``
    AND ``index_k`` (the second prefill scores its last tokens against the
    cached keys), and the tokens are the same and the reference's."""
    _, _, _, prompts = family
    eng = _engine(family)
    try:
        first = eng.submit(prompts[2], N_OUT)
        _drain(eng, [first])
        again = eng.submit(prompts[2], N_OUT)
        _drain(eng, [again])
        eng._settle()
        kv = eng.snapshot()["kv"]
        assert kv["prefix_hits"] >= 1 and kv["prefix_passed_up"] == 0
        assert (np.asarray(first.result()).tolist()
                == np.asarray(again.result()).tolist()
                == served["want"][2].tolist())
    finally:
        eng.close()


def test_a_parked_session_resumes_with_both_arrays(served, family):
    """Tiered KV moves whole blocks of EVERY array the pool has, by name:
    a session parked to the host and resumed decodes the tokens of one that
    never left the device."""
    _, cfg, variables, prompts = family
    prompt = prompts[2]

    def two_turns(park):
        eng = _engine(family, host_kv_blocks=32)
        try:
            first = eng.submit(prompt, 4)
            _drain(eng, [first])
            eng._settle()
            parked = eng.park_cold() if park else 0
            turn2 = np.concatenate(
                [prompt, np.asarray(first.result()), [7]]).astype(np.int32)
            fut = eng.submit(turn2, N_OUT)
            _drain(eng, [fut])
            return parked, np.asarray(fut.result()).tolist()
        finally:
            eng.close()

    parked, resumed = two_turns(True)
    _, kept = two_turns(False)
    assert parked > 0
    assert resumed == kept


def test_a_reused_slot_and_reused_blocks_start_clean(family):
    """A long request, then a short one in the same slot and blocks, alone:
    the short one's tokens are what a fresh engine gives it."""
    _, _, _, prompts = family
    eng = _engine(family, n_slots=1, kv_blocks=16)
    try:
        for p in (prompts[5], prompts[0], prompts[3]):
            fut = eng.submit(p, 6)
            _drain(eng, [fut])
        used = np.asarray(fut.result()).tolist()
    finally:
        eng.close()
    eng = _engine(family, n_slots=1, kv_blocks=16)
    try:
        fut = eng.submit(prompts[3], 6)
        _drain(eng, [fut])
        assert np.asarray(fut.result()).tolist() == used
    finally:
        eng.close()


def test_a_chain_of_four_is_four_single_steps(family):
    _, _, _, prompts = family
    outs = []
    for chain in (1, 4):
        eng = _engine(family, chain_tokens=chain)
        try:
            futs = [eng.submit(prompts[1], 9), eng.submit(prompts[4], 9)]
            _drain(eng, futs)
            outs.append([np.asarray(f.result()).tolist() for f in futs])
        finally:
            eng.close()
    assert outs[0] == outs[1]


def test_what_the_family_has_no_path_for_is_refused_at_construction(family):
    _, cfg, variables, _ = family
    for kw in ({"spec_k": 2}, {"kv_dtype": "int8"}, {"sp": 2}):
        with pytest.raises(ValueError, match="native K/V dtype alone"):
            ContinuousGPTEngine(cfg, variables, n_slots=2, max_len=64,
                                auto_start=False, **kw)


def test_a_handoff_between_tiers_is_refused_at_the_call(family):
    _, cfg, variables, prompts = family
    said = "GlmMoeDsaConfig.*latent and index_k.*payload is K and V"
    worker = PrefillWorker(cfg, variables, n_slots=2, max_len=128,
                           auto_start=False)
    try:
        with pytest.raises(NotImplementedError, match=said):
            worker.submit(prompts[0], 4)
    finally:
        worker.close()
    worker = DecodeWorker(cfg, variables, n_slots=2, max_len=128,
                          auto_start=False)
    try:
        with pytest.raises(NotImplementedError, match=said):
            worker.submit_handoff(object())
        # a prompt of its own it still serves
        fut = worker.submit(prompts[0], 3)
        _drain(worker, [fut])
        assert len(fut.result(timeout=0)) == 3
    finally:
        worker.close()
