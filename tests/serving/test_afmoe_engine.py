"""``ContinuousGPTEngine`` serving the ``afmoe`` family through the seam of
``models/family.py``, at the benchmark's rehearsal size, float32: a request
gets the same tokens alone and among seven others (no token is dropped by
the expert layer), and they are the plain reference's greedy tokens; the
spans and counters the new family brings; what the family refuses."""

import jax
import numpy as np
import pytest

from benchmark import reference_afmoe as ref
from sparkdl_tpu.models.afmoe import AfmoeLMHeadModel, config_from_hf_afmoe
from sparkdl_tpu.models.gpt import GPTConfig, GPTLMHeadModel
from sparkdl_tpu.observability import tracing
from sparkdl_tpu.observability.registry import registry
from sparkdl_tpu.serving import ContinuousGPTEngine
from tests.models.test_afmoe import SEED, rehearsal_hf

N_OUT = 10
#: eight prompts: shorter than a block, across blocks, past the window of 32
#: and past one prefill chunk of 32
LENGTHS = (5, 40, 70, 17, 33, 90, 64, 12)


@pytest.fixture(scope="module")
def served():
    """Each of the first three requests alone, then all eight at once, on
    one engine; beside them the float32 reference's greedy tokens."""
    from benchmark.runners import serve_afmoe

    hf = rehearsal_hf()
    cfg = config_from_hf_afmoe(hf)
    variables = serve_afmoe.program_variables(
        AfmoeLMHeadModel(cfg), hf, "float32", SEED)
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in LENGTHS]
    tracing.enable_tracing()
    tracing.clear_trace()
    try:
        with ContinuousGPTEngine(cfg, variables, n_slots=8, max_len=128,
                                 prefill_chunk=32) as eng:
            alone = [np.asarray(eng.submit(p, N_OUT).result(timeout=600))
                     for p in prompts[:3]]
            futures = [eng.submit(p, N_OUT) for p in prompts]
            among = [np.asarray(f.result(timeout=600)) for f in futures]
            snap = eng.snapshot()
        events = tracing.trace_events()
    finally:
        tracing.disable_tracing()
    # the reference decodes greedily, every row at one padded width (what
    # lies behind a position cannot reach it)
    seqs = np.zeros((len(prompts), 128), np.int32)
    for r, p in enumerate(prompts):
        seqs[r, :len(p)] = p
    with jax.default_matmul_precision("highest"):
        top = ref.top_weights(SEED, hf, "float32")
        for j in range(N_OUT):
            x, _ = ref.afmoe_hidden(SEED, hf, seqs, "float32")
            at = np.array([len(p) - 1 + j for p in prompts])
            logits = ref.afmoe_logits_at(top, hf, x[np.arange(len(at)), at])
            seqs[np.arange(len(at)), at + 1] = np.asarray(logits.argmax(-1))
    want = [seqs[r, len(p):len(p) + N_OUT] for r, p in enumerate(prompts)]
    return {"alone": alone, "among": among, "want": want, "snap": snap,
            "events": events, "cfg": cfg, "variables": variables}


@pytest.mark.parametrize("i", range(len(LENGTHS)))
def test_a_request_gets_the_references_greedy_tokens_whoever_shares_its_batch(
        served, i):
    assert served["among"][i].tolist() == served["want"][i].tolist()
    if i < len(served["alone"]):
        assert served["alone"][i].tolist() == served["among"][i].tolist()


def test_a_decode_tick_counts_its_experts_and_its_gathers_by_layer_kind(
        served):
    steps = [e["args"] for e in served["events"]
             if e["name"] == "serving.decode_step"]
    assert steps
    for a in steps:
        # 8 slots x 2 experts a token in each of the 4 expert layers, idle
        # slots and all; 8 experts held
        assert a["expert_rows"] == 8 * 2
        assert 1 <= a["experts_hit"] <= 8
        assert 16 / a["experts_hit"] <= a["expert_rows_max"] <= 8
        # a full layer gathers nb blocks a slot, a sliding one (window 32,
        # blocks of 16) at most 3
        per_layer = 8 * a["nb"] * 16
        assert a["kv_cols_read"] == a["kv_cols_read_full"] == per_layer
        assert a["kv_cols_read_window"] == 4 * 8 * min(a["nb"], 3) * 16
        assert a["kv_cols_live_window"] <= min(a["kv_cols_live"],
                                               32 * a["slots"])
    deep = [a for a in steps if a["nb"] > 3]
    assert deep and all(a["kv_cols_read_window"] < 4 * a["kv_cols_read_full"]
                        for a in deep)
    chunks = [e["args"] for e in served["events"]
              if e["name"] == "serving.prefill_chunk"]
    # (a constant of the width the span carries: gone with ISSUE 38)
    assert chunks and all("expert_rows" not in a for a in chunks)
    m = served["snap"]
    assert m["expert_rows"] == sum(4 * a["expert_rows"] for a in steps)
    assert m["experts_hit"] == round(sum(4 * a["experts_hit"] for a in steps))
    for name in ("sparkdl_moe_expert_rows_total",
                 "sparkdl_moe_experts_hit_total"):
        fam = registry().get(name)
        assert fam is not None and sum(
            fam.snapshot_values().values()) >= m[name[12:-6]]


def test_the_pool_is_shaped_by_the_familys_kv_heads_and_head_size(served):
    cfg = served["cfg"]
    kv = served["snap"]["kv"]
    # K and V of 2 KV heads of 16 in float32, five layers: a head under a
    # lane tile, so the 32 values lie on one merged axis stored as 128
    fam = cfg.serving_family()
    assert fam.kv_tail == (128,)
    assert kv["bytes_per_token"] == 2 * 128 * 4 * 5
    assert (fam.layers, fam.kv_heads, fam.head_dim) == (5, 2, 16)
    assert (fam.window_layers, fam.window, fam.expert_layers) == (4, 32, 4)
    assert fam.window_blocks(8, 16) == 3 and fam.window_blocks(2, 16) == 2


@pytest.mark.parametrize("option", [
    {"sp": 2}, {"spec_k": 4}, {"kv_dtype": "int8"}])
def test_what_the_family_has_no_path_for_is_refused_at_construction(
        served, option):
    with pytest.raises(ValueError, match="native K/V dtype alone"):
        ContinuousGPTEngine(served["cfg"], served["variables"],
                            auto_start=False, **option)


def test_a_gpt_answers_the_seam_as_its_fields_did():
    cfg = GPTConfig.tiny(positions="learned")
    fam = cfg.serving_family()
    assert isinstance(fam.module, GPTLMHeadModel)
    assert (fam.layers, fam.kv_heads, fam.head_dim, fam.max_positions) == (
        2, 2, 16, 64)
    assert not (fam.window_layers or fam.expert_layers or fam.paged_only)
    assert GPTConfig.tiny().serving_family().max_positions is None
    with pytest.raises(ValueError, match="learned position table"):
        ContinuousGPTEngine(cfg, None, max_len=128, auto_start=False)
