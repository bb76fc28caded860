"""``ContinuousGPTEngine`` serving the ``mimo_v2_flash`` family at the
benchmark's rehearsal size, float32: a request gets the plain reference's
greedy tokens alone, among others and in a slot another request left, through
chunked prefill (chunks wider than the window, padded last chunks) and decode
past several windows; the ring of a row that is not live in a step does not
move; a chained step is its single steps; what the family cannot carry
through is refused or passed up by name; the spans and counters it brings;
the accepted families' programs lower to the text they lowered to."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_mimo_v2_flash as ref
from sparkdl_tpu.disagg.workers import DecodeWorker, PrefillWorker
from sparkdl_tpu.models.mimo_v2_flash import (
    MimoV2FlashLMHeadModel,
    init_mimo_v2_flash_cache,
)
from sparkdl_tpu.observability import tracing
from sparkdl_tpu.observability.registry import registry
from sparkdl_tpu.serving import ContinuousGPTEngine
from sparkdl_tpu.serving.kv_blocks import kv_bytes_per_token
from tests.models.test_mimo_v2_flash import SEED, program_config, rehearsal_hf

N_OUT = 10
#: eight prompts on four slots: shorter than a window, across blocks, one and
#: several prefill chunks of 32 (each two windows wide), a last chunk that
#: needs no pad (64), contexts to six windows
LENGTHS = (5, 40, 70, 17, 33, 90, 64, 12)
RINGS = ("win_k", "win_v")


@pytest.fixture(scope="module")
def family():
    from benchmark.runners import serve_mimo_v2_flash

    hf = rehearsal_hf()
    cfg = program_config(hf)
    variables = serve_mimo_v2_flash.program_variables(
        MimoV2FlashLMHeadModel(cfg), hf, "float32", SEED)
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
            x, _ = ref.mimo_hidden(SEED, hf, seqs, "float32")
            at = np.array([len(p) - 1 + j for p in prompts])
            logits = np.asarray(ref.mimo_logits_at(top, hf, x[rows, at]))
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
        # alone it ran in a fresh slot; among the others, four of the eight
        # ran in a slot whose ring another request had left behind
        assert served["alone"][i].tolist() == served["among"][i].tolist()


def test_the_spans_count_ring_columns_expert_pairs_and_matches_passed_up(
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
        # the full layers gather every slot's nb blocks; a window layer
        # reads every slot's ring of 16, whatever the depth
        assert a["kv_cols_read"] == 4 * a["nb"] * 16 * a["chain"]
        assert a["win_cols_read"] == 4 * 16 * a["chain"]
        assert 0 < a["win_cols_live"] <= a["slots"] * 16 * a["chain"]
        assert a["win_cols_live"] <= a["kv_cols_live"]
        # all four rows' pairs were routed; the held experts got some
        assert a["expert_pairs"] == 4 * 2 * a["chain"]
        assert 0 <= a["expert_rows"] <= a["expert_pairs"]
        assert 0 <= a["experts_hit"] <= fam.experts * a["chain"]
        assert a["expert_rows_max"] <= 4
    # a row past the window has exactly the window's columns live
    deep = [a for a in steps if a["slots"] == 1 and a["chain"] == 1
            and a["kv_cols_live"] >= 16]
    assert deep and all(a["win_cols_live"] == 16 for a in deep)
    # a share: two held experts of eight get about a quarter of the pairs
    share = (sum(a["expert_rows"] for a in steps)
             / sum(a["expert_pairs"] for a in steps))
    assert 0.1 < share < 0.45
    chunks = [e["args"] for e in served["events"]
              if e["name"] == "serving.prefill_chunk"]
    assert chunks
    for a in chunks:
        assert a["scan_tokens"] == a["tokens"]
        assert a["pad_tokens"] == a["width"] - a["tokens"]
        assert "expert_rows" not in a     # width x 2, a constant: gone
    assert any(a["pad_tokens"] for a in chunks)
    assert any(a["width"] == 32 for a in chunks)      # two windows wide
    admits = [e["args"] for e in served["events"]
              if e["name"] == "serving.admit"]
    assert all(a["cached_tokens"] == 0 for a in admits)
    passed = [a["prefix_passed_up"] for a in admits]
    assert sum(passed) == served["snap"]["kv"]["prefix_passed_up"] > 60
    assert served["snap"]["kv"]["prefix_hits"] == 0


def test_capacity_counts_the_full_layers_and_says_the_rings_bytes(
        served, family):
    _, cfg, _, _ = family
    fam = cfg.serving_family()
    kv = served["snap"]["kv"]
    # K of 2 heads of 24 and V of 2 heads of 16 in float32, each on one
    # merged axis stored as 128, in the TWO full layers of the seven
    assert (fam.layers, fam.pool_layers, fam.state_layers) == (7, 2, 5)
    assert kv["bytes_per_token"] == kv_bytes_per_token(cfg) == 2 * 128 * 4 * 2
    per_slot = 5 * 16 * (4 * 24 + 4 * 16) * 4
    assert kv["state_bytes_per_slot"] == per_slot
    assert kv["state_bytes"] == 4 * per_slot
    assert served["capacity"]["state_bytes"] == 4 * per_slot
    # the rings' shape is the window's, not the context's
    assert served["shapes"]["win_k"] == (5, 4, 16, 96)
    assert served["shapes"]["win_v"] == (5, 4, 16, 64)
    assert served["shapes"]["k"] == served["shapes"]["v"] == (2, 32, 16, 128)


@pytest.mark.parametrize("widths, taken", [
    pytest.param({}, True,
                 id="published: 4 x 192 on 768 over 4 x 128 on 512"),
    pytest.param({"v_head_dim": 64}, True,
                 id="a value head of 64: V's axis of 256 is whole tiles"),
    pytest.param({"num_kv_heads": 1}, False,
                 id="one K/V head: K's axis of 192 is no whole tile"),
    pytest.param({"head_dim": 128, "num_kv_heads": 8}, False,
                 id="equal heads of whole tiles keep their own axis"),
    pytest.param({"hidden_size": 64, "num_heads": 8, "num_kv_heads": 2,
                  "head_dim": 24, "v_head_dim": 16}, False,
                 id="the tiny configuration"),
])
def test_decode_reads_in_place_follows_the_paged_kernels_rule(widths, taken):
    """The family's flag, which the engine's count of a step's reads
    follows, is the rule the module's full layers ask
    (``ops/paged_decode.reads_in_place``) on the tails the pool is built
    with: no argument and no switch."""
    from sparkdl_tpu.models.mimo_v2_flash import MimoV2FlashConfig
    from sparkdl_tpu.ops import paged_decode

    fam = MimoV2FlashConfig(**widths).serving_family()
    assert fam.decode_reads_in_place is taken
    assert paged_decode.reads_in_place(
        fam.kv_tail, fam.v_tail, fam.kv_heads, fam.head_dim,
        fam.v_head_dim) is taken


def test_the_ring_gauge_says_the_rings_bytes_while_the_engine_lives(family):
    _, cfg, variables, _ = family
    gauge = registry().get("sparkdl_window_ring_bytes")
    linear = registry().get("sparkdl_linear_state_bytes")
    before = sum(gauge.snapshot_values().values())
    before_linear = sum(linear.snapshot_values().values())
    eng = _engine(family)
    try:
        per_slot = cfg.serving_family().state_bytes_per_slot
        assert sum(gauge.snapshot_values().values()) == before + 3 * per_slot
        assert sum(linear.snapshot_values().values()) == before_linear
    finally:
        eng.close()
    assert sum(gauge.snapshot_values().values()) == before


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


def _rings(eng):
    return {name: np.asarray(eng._pool_kv[name]) for name in RINGS}


def _prefilled(family, prompt):
    """The rings a prompt leaves, from one dense call outside the engine."""
    _, cfg, variables, _ = family
    _, cache = MimoV2FlashLMHeadModel(cfg).apply(
        variables, jnp.asarray(prompt[None]),
        cache=init_mimo_v2_flash_cache(cfg, 1, 128))
    return {name: np.asarray(cache[name][:, 0]) for name in RINGS}


def test_an_idle_slots_ring_is_the_same_bits_after_steps(family):
    _, _, _, prompts = family
    eng = _engine(family)
    try:
        rng = np.random.default_rng(3)
        # sparkdl-lint: disable=lock-discipline -- a test's engine, ticked by hand
        eng._pool_kv = {**eng._pool_kv, **{
            name: jnp.asarray(rng.normal(size=a.shape), a.dtype)
            for name, a in _rings(eng).items()}}
        before = _rings(eng)
        fut = eng.submit(prompts[1], 6)
        _drain(eng, [fut])
        eng._settle()
        after = _rings(eng)
        for name in before:
            assert not np.array_equal(before[name][:, 0], after[name][:, 0])
            for idle in (1, 2):
                assert np.array_equal(before[name][:, idle],
                                      after[name][:, idle]), (name, idle)
    finally:
        eng.close()


def test_the_last_chunk_installs_the_prompts_last_window_whole(family):
    """17 tokens in chunks of 16 and 1 (the last padded to 8): the slot's
    rings are what one dense call over the prompt leaves, and what the row
    before it held is gone."""
    _, _, _, prompts = family
    eng = _engine(family)
    try:
        first = eng.submit(prompts[1], 3)
        _drain(eng, [first])
        eng._settle()
        fut = eng.submit(prompts[3], 1)
        _drain(eng, [fut])
        eng._settle()
        want = _prefilled(family, prompts[3])
        got = _rings(eng)
        for name in RINGS:
            np.testing.assert_allclose(got[name][:, 0], want[name],
                                       atol=1e-6, err_msg=name)
    finally:
        eng.close()


def test_a_row_that_joins_behind_a_step_in_flight_is_not_advanced_by_it(
        family):
    """Row A decodes one step ahead. B's last chunk is dispatched, then a
    step is launched for A alone (B joins when its first token is read): it
    runs BEHIND the chunk that installed B's rings and must write no column
    into them."""
    _, _, _, prompts = family
    eng = _engine(family)
    try:
        a = eng.submit(prompts[1], 30)
        while not (eng._steps_out and eng.active_slots == 1):
            eng.tick()
        b = eng.submit(prompts[3], 8)   # 17 tokens: chunks of 16 and 1
        while not any(f.req.payload.prompt.shape[0] == 17
                      for f in eng._inflight.values()):
            eng.tick()
        (slot,) = [s for s, f in eng._inflight.items()
                   if f.req.payload.prompt.shape[0] == 17]
        got = _rings(eng)
        want = _prefilled(family, prompts[3])
        for name in RINGS:
            np.testing.assert_allclose(got[name][:, slot], want[name],
                                       atol=1e-6, err_msg=name)
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


# -- refused, or passed up, by name -------------------------------------------------

def test_what_the_family_has_no_path_for_is_refused_at_construction(family):
    _, cfg, variables, _ = family
    for kw in ({"spec_k": 2}, {"kv_dtype": "int8"}, {"sp": 2}):
        with pytest.raises(ValueError, match="native K/V dtype alone"):
            ContinuousGPTEngine(cfg, variables, n_slots=2, max_len=64,
                                auto_start=False, **kw)


def test_tiered_kv_is_refused_at_construction_by_the_familys_name(family):
    _, cfg, variables, _ = family
    with pytest.raises(
            ValueError,
            match="MimoV2FlashConfig keeps arrays by slot.*window's last "
                  "columns.*5 of its layers.*host_kv_blocks"):
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
    said = "MimoV2FlashConfig.*window's last columns"
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


def test_a_repeated_prompt_is_prefilled_whole_and_the_match_counted(family):
    _, _, _, prompts = family
    eng = _engine(family)
    try:
        first = eng.submit(prompts[2], 4)
        _drain(eng, [first])
        again = eng.submit(prompts[2], 4)
        _drain(eng, [again])
        eng._settle()
        kv = eng.snapshot()["kv"]
        # 69 of the 70 tokens matched (8 whole blocks of 8 and a partial)
        assert kv["prefix_passed_up"] >= 64 and kv["prefix_hits"] == 0
        assert np.asarray(first.result()).tolist() == np.asarray(
            again.result()).tolist()
    finally:
        eng.close()


# -- the accepted families' programs are what they were ------------------------------

#: sha256 (first 16 hex digits) of the lowered text of each paged program of
#: a tiny olmo_hybrid, read at the PARENT of the PR that let K and V differ
#: in their tail (2 slots x 64, blocks of 4, chunks of 8; this installation's
#: jax). GPT-2's, the int8 pool's and afmoe's are pinned in
#: ``test_olmo_hybrid_engine.py`` and hold here too.
PARENT_LOWERED = {
    "olmo.step": "03a554d7df448430", "olmo.chain": "dae2b44261b09bc3",
    "olmo.one": "dce08f8cf0b165eb", "olmo.first": "3cd9a349a0d782a5",
    "olmo.mid": "720efd964636db75", "olmo.final": "a0d8a87ed340aebc",
}


@pytest.fixture(scope="module")
def olmo_digests():
    import hashlib

    from sparkdl_tpu.models.olmo_hybrid import (
        OlmoHybridConfig,
        OlmoHybridLMHeadModel,
    )

    ids = jnp.zeros((1, 8), jnp.int32)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    cfg = OlmoHybridConfig.tiny()
    variables = jax.eval_shape(
        lambda: OlmoHybridLMHeadModel(cfg).init(jax.random.PRNGKey(0), ids))
    eng = ContinuousGPTEngine(cfg, variables, n_slots=2, max_len=64,
                              kv_block_size=4, prefill_chunk=8,
                              auto_start=False)
    try:
        pool, mb = eng._pool_kv, eng._mb
        priv = jax.ShapeDtypeStruct(
            (pool["k"].shape[0], 1, eng._wp) + pool["k"].shape[3:],
            eng._sizes.dtype)
        rec = {n: jax.ShapeDtypeStruct((a.shape[0], 1) + a.shape[2:], a.dtype)
               for n, a in pool.items() if n in ("state", "conv")}
        step = (variables, pool, i32(2, mb), i32(2), i32(2), i32(2))
        progs = {
            "step": eng._paged_step_fn.lower(*step, 1, 4),
            "chain": eng._paged_step_fn.lower(*step, 2, 4),
            "one": eng._chunk_one_fn.lower(
                variables, pool, i32(mb), i32(), i32(1, 8), i32(mb), 16,
                i32(), i32()),
            "first": eng._chunk_first_fn.lower(
                variables, pool, i32(mb), i32(), i32(1, 8), 16, i32()),
            "mid": eng._chunk_mid_fn.lower(
                variables, priv, priv, i32(), i32(1, 8), 16, i32(), rec),
            "final": eng._chunk_final_fn.lower(
                variables, pool, priv, priv, i32(), i32(1, 8), i32(mb), 16,
                i32(), rec, i32()),
        }
    finally:
        eng.close()
    return {f"olmo.{name}": hashlib.sha256(
        low.as_text().encode()).hexdigest()[:16]
            for name, low in progs.items()}


@pytest.mark.parametrize("program", sorted(PARENT_LOWERED))
def test_olmo_hybrids_programs_lower_to_the_parents_text(olmo_digests,
                                                         program):
    assert olmo_digests[program] == PARENT_LOWERED[program]
