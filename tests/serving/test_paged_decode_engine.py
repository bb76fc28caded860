"""``ContinuousGPTEngine`` over a family whose one-token step reads K and V
in the pool (``ops/paged_decode.py``, ISSUE 35): a tiny ``olmo_hybrid``
configuration with three heads of 128, (ISSUE 37) a tiny ``mimo_v2_flash``
with four query heads over two K/V heads, keys of 64 on an axis of 128 under
values of 128 on one of 256, and (ISSUE 43) a tiny ``lfm2_moe`` with eight
query heads over two K/V heads of 64, K and V each on an axis of 128 (a value
head of half a lane tile), so that the rule by which the kernel
is taken holds on the CPU (under the Pallas interpreter). Greedy tokens are
those of the same engine with the rule forced false (the gather and the
merged-axis attention, what every configuration ran before); the step's
``kv_cols_read`` counts what the kernel fetches, each riding row's depth
rounded up to whole blocks; the families the rule leaves alone count what
they counted."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparkdl_tpu.models import afmoe, mimo_v2_flash, olmo_hybrid
from sparkdl_tpu.models.afmoe import AfmoeConfig, AfmoeLMHeadModel
from sparkdl_tpu.models.gpt import GPTConfig, GPTLMHeadModel
from sparkdl_tpu.models.lfm2_moe import Lfm2MoeConfig, Lfm2MoeLMHeadModel
from sparkdl_tpu.models.mimo_v2_flash import (
    MimoV2FlashConfig,
    MimoV2FlashLMHeadModel,
)
from sparkdl_tpu.models.olmo_hybrid import (
    OlmoHybridConfig,
    OlmoHybridLMHeadModel,
)
from sparkdl_tpu.observability import tracing
from sparkdl_tpu.ops import paged_decode
from sparkdl_tpu.serving import ContinuousGPTEngine

N_OUT, SLOTS, BS = 4, 2, 16
#: four prompts on two slots: under a block, on a block's edge, across
#: blocks; every slot serves a second request
LENGTHS = (5, 16, 33, 12)


def _serve(cfg, model, prompts):
    """The prompts through one hand-driven engine: tokens, the decode
    steps' span arguments, the snapshot."""
    variables = model(cfg).init(
        jax.random.PRNGKey(3), jnp.zeros((1, 8), jnp.int32))
    tracing.enable_tracing()
    tracing.clear_trace()
    try:
        eng = ContinuousGPTEngine(
            cfg, variables, n_slots=SLOTS, max_len=64, kv_block_size=BS,
            prefill_chunk=16, auto_start=False)
        try:
            futs = [eng.submit(p, N_OUT) for p in prompts]
            deadline = time.monotonic() + 600
            while not all(f.done() for f in futs):
                assert time.monotonic() < deadline, "engine did not finish"
                eng.tick()
            snap = eng.snapshot()
        finally:
            eng.close()
        steps = [e["args"] for e in tracing.trace_events()
                 if e["name"] == "serving.decode_step"]
    finally:
        tracing.disable_tracing()
    return {"tokens": [np.asarray(f.result(timeout=0)) for f in futs],
            "steps": steps, "snap": snap}


def _prompts(vocab):
    rng = np.random.default_rng(11)
    return [rng.integers(0, vocab, n).astype(np.int32) for n in LENGTHS]


def _olmo():
    return OlmoHybridConfig.tiny(
        num_heads=3, head_dim=128,
        layer_types=(olmo_hybrid.LINEAR, olmo_hybrid.FULL)
    ), OlmoHybridLMHeadModel


def _mimo():
    # a full dense layer, a window layer (a ring of 16 a slot, which the
    # kernel is not for) and a full expert layer
    return MimoV2FlashConfig.tiny(
        num_heads=4, num_kv_heads=2, swa_num_kv_heads=2, head_dim=64,
        v_head_dim=128, partial_rotary_factor=0.5,
        hybrid_layer_pattern=(mimo_v2_flash.FULL, mimo_v2_flash.WINDOW,
                              mimo_v2_flash.FULL),
        moe_layer_freq=(0, 1, 1)), MimoV2FlashLMHeadModel


def _lfm2():
    # the tiny pattern (a dense convolution, an attention layer, three
    # expert convolutions) with heads of 64: an axis of 2 x 64 = 128
    return Lfm2MoeConfig.tiny(head_dim=64), Lfm2MoeLMHeadModel


@pytest.fixture(scope="module", params=[_olmo, _mimo, _lfm2],
                ids=["olmo_hybrid", "mimo_v2_flash", "lfm2_moe"])
def hybrid(request):
    cfg, model = request.param()
    assert cfg.serving_family().decode_reads_in_place
    prompts = _prompts(cfg.vocab_size)
    in_place = _serve(cfg, model, prompts)
    with pytest.MonkeyPatch.context() as mp:
        # the rule forced false HERE: the module takes the gather and the
        # merged-axis attention, the engine counts as it did
        mp.setattr(paged_decode, "reads_in_place", lambda *a: False)
        assert not cfg.serving_family().decode_reads_in_place
        gathered = _serve(cfg, model, prompts)
    return in_place, gathered


@pytest.mark.parametrize("i", range(len(LENGTHS)))
def test_greedy_tokens_are_those_of_the_gather_and_the_merged_axis_attention(
        hybrid, i):
    in_place, gathered = hybrid
    assert len(in_place["tokens"][i]) == N_OUT
    assert in_place["tokens"][i].tolist() == gathered["tokens"][i].tolist()


def _depths(steps):
    """Each step's riding rows' depths, from the spans alone: a request
    rides its first step at its prompt's length and is one deeper every
    step after (the engines here chain nothing)."""
    ridden, by_request = {}, {}
    out = []
    for a in steps:
        assert a["chain"] == 1
        row = []
        for rid in a["links"]:
            if rid not in by_request:
                by_request[rid] = LENGTHS[len(by_request)]
            row.append(by_request[rid] + ridden.get(rid, 0))
            ridden[rid] = ridden.get(rid, 0) + 1
        out.append(row)
    return out


def test_kv_cols_read_is_each_riding_rows_depth_rounded_up_to_its_blocks(
        hybrid):
    in_place, gathered = hybrid
    assert in_place["steps"]
    # requests are admitted in the order they were submitted, and the
    # depths read back from the spans add up to what the engine counted
    for a, depths in zip(in_place["steps"], _depths(in_place["steps"])):
        assert a["kv_cols_live"] == sum(depths)
        assert a["kv_cols_read"] == sum(-(-d // BS) * BS for d in depths)
        assert a["kv_cols_read"] < a["kv_cols_live"] + BS * len(depths)
        assert a["state_rows"] == a["slots"]
    kv = in_place["snap"]
    assert kv["kv_cols_read"] == sum(
        a["kv_cols_read"] for a in in_place["steps"])
    # with the rule forced false every slot pays for the deepest row's nb
    for a in gathered["steps"]:
        assert a["kv_cols_read"] == SLOTS * a["nb"] * BS
    # what a tick NEEDS did not move: the same live columns, step for step
    assert [a["kv_cols_live"] for a in in_place["steps"]] == [
        a["kv_cols_live"] for a in gathered["steps"]]
    assert kv["kv_cols_live"] == gathered["snap"]["kv_cols_live"]
    assert kv["kv_cols_read"] < gathered["snap"]["kv_cols_read"]


@pytest.mark.parametrize("cfg, model", [
    pytest.param(GPTConfig.tiny(), GPTLMHeadModel, id="gpt"),
    pytest.param(AfmoeConfig.tiny(layer_types=(afmoe.SLIDING, afmoe.FULL)),
                 AfmoeLMHeadModel, id="afmoe"),
    # 2 K/V heads of 8: an axis of 16, padded to a lane tile
    pytest.param(Lfm2MoeConfig.tiny(), Lfm2MoeLMHeadModel, id="lfm2_moe"),
])
def test_a_family_the_rule_leaves_alone_counts_what_it_counted(cfg, model):
    fam = cfg.serving_family()
    assert not fam.decode_reads_in_place
    served = _serve(cfg, model, _prompts(cfg.vocab_size))
    assert served["steps"]
    for a in served["steps"]:
        read = SLOTS * a["nb"] * BS * a["chain"]
        assert a["kv_cols_read"] == read
        if fam.window_layers:
            wb = fam.window_blocks(a["nb"], BS)
            assert a["kv_cols_read_window"] == (
                read // a["nb"] * wb * fam.window_layers)
            assert a["kv_cols_read_full"] == read * (
                fam.pool_layers - fam.window_layers)
    assert served["snap"]["kv_cols_read"] == sum(
        a["kv_cols_read"] for a in served["steps"])
