"""The ``serve_lfm2_moe`` runner kind and what it brings (CPU only): the
configuration holds the catalog's keys but the cut; the mix is the issue's; a
broken timed path comes out not correct; each control of the plain reference
is over the rehearsal's limits; ``needs_lfm2_moe`` counts the bytes that the
seeded weights have; the seven ``.conv`` readers' arithmetic on spans built
by hand, and None on a silent run; a convolution's operations are known by
their shapes."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import manifest as mf
from benchmark import needs_lfm2_moe as needs_l
from benchmark import readers_lfm2_moe as readers_l
from benchmark import reference_lfm2_moe as ref
from benchmark import traffic
from benchmark.harness import Run
from benchmark.runners import serve_lfm2_moe

ROOT = mf.repo_root()
CELL = "lfm2-concurrent-chat-backlog"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def rehearsal_run(seed: int = 5) -> Run:
    return Run(cell=mf.resolve_cell(CELL, ROOT), seed=seed, seconds=1.0,
               trace=False, rehearse=True, t_process=0.0)


def published_hf() -> dict:
    return serve_lfm2_moe.hf_config(mf.resolve_cell(CELL, ROOT).config)


def test_the_configuration_holds_the_catalogs_keys_but_the_cut():
    cfg = mf.resolve_cell(CELL, ROOT).config
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types"]
    # no width differs from the source
    assert (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["conv_L_cache"],
            cfg["num_experts"], cfg["num_experts_per_tok"],
            cfg["vocab_size"]) == (2048, 11776, 1536, 32, 8, 3, 64, 4, 65536)
    # the cut: published layers 0-9, both dense layers and two whole periods
    assert cfg["num_hidden_layers"] == 10 and cfg["num_dense_layers"] == 2
    assert cfg["layer_types"] == ["conv", "conv"] + [
        "full_attention", "conv", "conv", "conv"] * 2
    assert cfg["published"]["num_hidden_layers"] == 40
    assert "one chip holds each layer WHOLE" in cfg["deployment"]
    assert "pipeline stages" in cfg["deployment"]
    for item in ("tied head", "last norm", "norm placement",
                 "the split's order", "the rotation's pairing",
                 "the router's epsilon", "n_slots", "max_len", "weights"):
        assert item in cfg["assumed"], item
    assert cfg["engine"] == {"n_slots": 64, "max_len": 4096}
    assert cfg["dtype"] == "bfloat16"
    assert cfg["entry_point"].endswith("ContinuousGPTEngine")
    assert set(serve_lfm2_moe.HF_KEYS) <= set(published_hf())
    entry = next(c for c in mf.load_manifest(ROOT)["configs"]
                 if c["name"] == "lfm2-24b-a2b-serve")
    assert (entry["source"], entry["reduced"]) == (cfg["source"],
                                                   cfg["reduced"])


def test_every_key_of_the_catalogs_row_is_in_the_file_unchanged_or_reduced():
    try:
        rows = [json.loads(ln) for ln in open(CATALOG)]
    except OSError:
        pytest.skip("the catalog is not on this machine")
    (row,) = [r for r in rows if r["name"] == "LFM2-24B-A2B"]
    cfg = mf.resolve_cell(CELL, ROOT).config
    assert cfg["source"] == row["source_url"]
    assert set(row["config"]) == set(serve_lfm2_moe.HF_KEYS)
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg[key] != value, key
        else:
            assert cfg[key] == value, key
    assert cfg["layer_types"] == row["config"]["layer_types"][:10]


def test_the_mix_is_the_issues_and_its_sizes_are_what_the_cell_says():
    mix = mf.resolve_cell(CELL, ROOT).mix
    assert (mix["runner"], mix["loop"], mix["clients"], mix["block"],
            mix["pair_seed"], mix["pool"], mix["lead_in_s"],
            mix["check_requests"]) == (
                "serve_lfm2_moe", "closed", 64, 16, 4, 2048, 20, 4)
    assert mix["prompt"] == {"dist": "lognormal", "median": 512,
                             "sigma": 0.9, "min": 64, "max": 4096}
    assert mix["output"] == {"dist": "lognormal", "median": 256,
                             "sigma": 0.5, "min": 32, "max": 1024}
    sizes = traffic.request_sizes(mix, 1, 0)
    assert sorted(sizes[:, 0]) == [96, 156, 206, 255, 304, 356, 414, 477,
                                   549, 634, 735, 862, 1030, 1271, 1677, 2737]
    assert (sizes[:, 1].min(), sizes[:, 1].max()) == (101, 650)
    assert sizes.sum(1).max() == 3003 < 4096
    assert 2.5 < sizes[:, 0].sum() / sizes[:, 1].sum() < 2.7
    # the deepest bucket a step gathers: 256 blocks of 16
    assert 1 << (-(-3003 // 16) - 1).bit_length() == 256


def test_a_broken_timed_path_comes_out_not_correct(monkeypatch, capsys):
    """A whole rehearsal in this process with the engine's answers altered
    where they are handed out: the last token of every completion is
    another token."""
    from concurrent.futures import Future

    from benchmark import harness
    from sparkdl_tpu.serving.continuous import ContinuousGPTEngine

    real_submit = ContinuousGPTEngine.submit

    def altered(self, prompt_ids, max_new_tokens, **kw):
        inner = real_submit(self, prompt_ids, max_new_tokens, **kw)
        outer: Future = Future()

        def relay(f):
            if f.exception() is not None:
                outer.set_exception(f.exception())
                return
            toks = np.array(f.result())
            toks[-1] = (toks[-1] + 1) % self.config.vocab_size
            outer.set_result(toks)

        inner.add_done_callback(relay)
        return outer

    monkeypatch.setattr(ContinuousGPTEngine, "submit", altered)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    rc = harness.main(["--workload", CELL, "--seed", str(2**31 + 9),
                       "--seconds", "1.5", "--trace", "0", "--rehearse"])
    out = capsys.readouterr().out
    line = json.loads([ln for ln in out.splitlines() if ln.strip()][-1])
    assert rc == 0 and line["correct"] is False
    assert "NOT CORRECT" in out
    assert not line["compared"]["token_gap_max_over_logit_std"]["ok"]


@pytest.fixture(scope="module")
def greedy():
    """Four rows of prompts of 20, 33, 41 and 48 tokens and 16 tokens the
    float32 reference decodes greedily after each (a sound program's
    stand-in: every gap 0), at the rehearsal size."""
    seed = 2**31 + 4
    hf = serve_lfm2_moe.hf_config(rehearsal_run().config())
    lens = (20, 33, 41, 48)
    seqs = np.array(traffic.rng_for(seed, 0).integers(1, 512, (4, 64)),
                    np.int32)
    for r, n in enumerate(lens):
        seqs[r, n:] = 0
    rows = np.arange(4)
    with jax.default_matmul_precision("highest"):
        top = ref.top_weights(seed, hf, "float32")
        for j in range(16):
            at = np.array(lens) - 1 + j
            x, _ = ref.lfm2_hidden(seed, hf, seqs, "float32")
            seqs[rows, at + 1] = np.asarray(jnp.argmax(
                ref.lfm2_logits_at(top, hf, x[rows, at]), -1))
    return seed, hf, seqs, [(n - 1, n - 1 + 16) for n in lens]


def test_the_references_own_greedy_tokens_read_zero(greedy):
    seed, hf, seqs, spans = greedy
    gaps, std = ref.lfm2_token_gaps(seed, hf, seqs, spans, "float32")
    assert gaps.shape == (4 * 16,) and std > 0.5
    # the seeded kernels give the rehearsal's hidden 64 the gains of the
    # published 2048: a request's tokens are many, not one repeated
    assert len(set(seqs[0, 20:36].tolist())) > 8
    assert (gaps / std).max() <= serve_lfm2_moe.TOKEN_GAP_MAX_LIMIT["cpu"]
    assert (gaps / std).mean() <= serve_lfm2_moe.TOKEN_GAP_MEAN_LIMIT["cpu"]


#: the faulty controls as the rehearsal shows them. The tail is zeroed at
#: every 16-token boundary here (a context of 64 tokens never reaches the
#: published chunk of 256: ``tail_zeroed_256`` reads 0 at this size and is
#: the chip's probe's to show)
SHOWN_HERE = ("int8", "float8", "weakest_dropped", "bias_left_out",
              "tail_zeroed_16", "gate_left_out", "qk_norm_left_out")


def test_the_controls_are_the_issues_and_the_rehearsal_shows_each_fault(
        greedy):
    assert ref.CONTROLS == (
        "f32", "bfloat16", "int8", "float8", "weakest_dropped",
        "bias_left_out", "tail_zeroed_256", "gate_left_out",
        "qk_norm_left_out")
    assert {c.replace("_16", "_256") for c in SHOWN_HERE} == set(
        ref.CONTROLS) - {"f32", "bfloat16"}
    with pytest.raises(ValueError, match="unknown control"):
        ref.lfm2_hidden(1, published_hf(), np.zeros((1, 8), np.int32),
                        control="tail_zeroed_0")
    seed, hf, seqs, spans = greedy
    gaps, _ = ref.lfm2_token_gaps(seed, hf, seqs, spans, "float32",
                                  "tail_zeroed_256")
    assert gaps.max() == 0


@pytest.mark.parametrize("control", SHOWN_HERE)
def test_a_control_is_over_the_rehearsals_limits_three_times(greedy, control):
    """The reference with one thing wrong, judged at the served positions by
    the float32 reference: each is over both limits by three times and more
    here (PERF.md section 2 says which are caught on the chip, at bfloat16's
    own distance from float32)."""
    seed, hf, seqs, spans = greedy
    gaps, std = ref.lfm2_token_gaps(seed, hf, seqs, spans, "float32", control)
    assert (gaps / std).max() > 3 * serve_lfm2_moe.TOKEN_GAP_MAX_LIMIT["cpu"]
    assert (gaps / std).mean() > 3 * serve_lfm2_moe.TOKEN_GAP_MEAN_LIMIT[
        "cpu"]


def test_the_stated_precision_lies_nearer_than_the_ones_below(greedy):
    seed, hf, seqs, spans = greedy
    mean = {c: float(ref.lfm2_token_gaps(seed, hf, seqs, spans, "float32",
                                         c)[0].mean())
            for c in ("bfloat16", "int8", "float8")}
    assert 0 <= mean["bfloat16"] < mean["int8"] < mean["float8"]


def test_the_weakest_expert_is_dropped_and_the_bias_moves_the_selection():
    hf = serve_lfm2_moe.hf_config(rehearsal_run().config())
    hf_json = json.dumps(hf, sort_keys=True)
    w = ref.layer_weights(11, 2, hf, "float32")
    h = jax.random.normal(jax.random.PRNGKey(2), (64, 64))

    def route(control):
        return tuple(np.asarray(a) for a in ref._mlp_programs(
            hf_json, control)[0](h, w["moe.router"], w["moe.expert_bias"]))

    sel, wt = route("f32")
    sel2, wt2 = route("weakest_dropped")
    assert (sel == sel2).all()
    dropped = (wt2 == 0) & (wt > 0)
    assert (dropped.sum(1) == 1).all()
    assert (wt[dropped] == wt.min(1)).all()
    # without the bias other experts are selected for some tokens, and the
    # weights are the unbiased scores in both
    sel3, wt3 = route("bias_left_out")
    assert (np.sort(sel3, 1) != np.sort(sel, 1)).any()
    np.testing.assert_allclose(wt3.sum(1), wt.sum(1), atol=1e-5)


def test_needs_count_the_bytes_the_seeded_weights_have():
    """At the rehearsal size against the arrays themselves; at the published
    widths against the issue's hand count (5,267,090,176 parameters)."""
    hf = serve_lfm2_moe.hf_config(rehearsal_run().config())
    for dtype, dense in (("bfloat16", 2), ("float32", 4)):
        arrays = [ref.top_weights(3, hf, dtype)] + [
            ref.layer_weights(3, i, hf, dtype) for i in range(5)]
        have = sum(a.nbytes for t in arrays for a in t.values())
        assert needs_l.lfm2_param_bytes(hf, dense) == have
    big = published_hf()
    assert ref.seeded_parameters(big) == 5_267_090_176
    total = needs_l.lfm2_param_bytes(big)
    assert 10.534e9 < total < 10.538e9
    assert needs_l.layer_counts(big) == (8, 2, 8)
    expert = 3 * 2048 * 1536 * 2
    assert needs_l.lfm2_expert_bytes(big) == expert == 18874368
    # all but the experts' kernels: the embedding is the head, read whole
    assert needs_l.lfm2_fixed_bytes(big) == total - 8 * 64 * expert
    assert 0.86e9 < needs_l.lfm2_fixed_bytes(big) < 0.88e9
    assert needs_l.lfm2_kv_bytes_per_token_layer(big) == 2048
    assert needs_l.lfm2_tail_bytes_per_row_layer(big) == 8192
    # 64 rows at depth 1,500, 62.7 experts hit a layer, 256 pairs
    need = needs_l.lfm2_call_bytes(big, 64, 256, 62.7, 64 * 1500)
    kv = 2 * 2048 * (64 * 1500 + 64)
    tails = 8 * 2 * 64 * 8192
    products = 8 * (62.7 * expert + 2 * 256 * 2048 * 2)
    assert need == pytest.approx(
        needs_l.lfm2_fixed_bytes(big) + 64 * 2048 * 2 + products + kv + tails)
    assert 0.86 < products / need < 0.90
    assert 0.03 < kv / need < 0.04 and tails / need < 0.001
    assert needs_l.lfm2_expert_product_bytes(big, 256, 60) == (
        60 * expert + 2 * 256 * 2048 * 2)
    # 2 a weight a row over the dense kernels, the routers and the tied head
    attn = 2048 * 2 * 64 * (32 + 8)
    conv = 4 * 2048 * 2048 + 3 * 2048
    per_row = (2 * attn + 8 * conv + 2 * 3 * 2048 * 11776 + 8 * 2048 * 64
               + 2048 * 65536)
    assert needs_l.lfm2_call_flops(big, 1, 0, 0) == 2 * per_row
    # a pair costs an expert's three products in each expert layer
    assert (needs_l.lfm2_call_flops(big, 1, 1, 0) - 2 * per_row
            == 8 * 2 * 3 * 2048 * 1536)
    # a (query, key) pair costs 2 x 32 x (64 + 64) in each attention layer
    assert (needs_l.lfm2_call_flops(big, 1, 0, 100) - 2 * per_row
            == 2 * 2 * 32 * 128 * 100)


def _silent_run():
    run = rehearsal_run()
    run.window = (0.0, 10.0)
    run.raw = {"hf_config": published_hf(), "n_slots": 64}
    return run


READERS = ("decode_roofline_share", "expert_device_ms",
           "expert_product_roofline_share", "experts_hit_share",
           "expert_rows_max_over_mean", "short_conv_device_ms",
           "kv_cols_read_over_live")


@pytest.mark.parametrize("reader", READERS)
def test_a_reader_is_silent_where_its_source_is(reader):
    """On a run with no spans and no trace, and on the parent's program
    (no family, so no cell: a run of it has no span at all)."""
    run = _silent_run()
    assert getattr(readers_l, reader)(run) is None
    run.traced_window = (0.5, 2.5)
    assert getattr(readers_l, reader)(run) is None


def test_the_seven_readers_arithmetic_on_spans_built_by_hand():
    run = _silent_run()
    hf = run.raw["hf_config"]

    def tick(t, rows, live, nb, hit, top):
        return {"name": "serving.decode_step", "t0": t, "t1": t + 0.01,
                "args": {"slots": rows, "chain": 1, "nb": nb,
                         "kv_cols_read": 64 * nb * 16, "kv_cols_live": live,
                         "state_rows": rows, "state_bytes": rows * 131072,
                         "experts_hit": hit, "expert_rows": 256.0,
                         "expert_rows_max": top, "expert_pairs": 256}}

    run.spans = [tick(1.0, 64, 90000, 256, 62.0, 10),
                 tick(2.0, 60, 70000, 256, 63.0, 12),
                 tick(5.0, 64, 40000, 128, 61.0, 9)]
    assert readers_l.kv_cols_read_over_live(run) == pytest.approx(
        64 * 16 * (256 + 256 + 128) / 200000)
    assert readers_l.experts_hit_share(run) == pytest.approx(100 * 62.0 / 64)
    assert readers_l.expert_rows_max_over_mean(run) == pytest.approx(
        (10 / (256 / 62) + 12 / (256 / 63) + 9 / (256 / 61)) / 3)
    run.traced_window = (0.5, 2.5)
    run.device_kind = "TPU v5 lite"
    run.trace_summary = {"whole_programs": {
        "jit__paged_step(1)": {"seconds": 0.040, "count": 2}}}
    need = readers_l._tick_needs(run)
    assert need == {"rows": 62, "pairs": 256.0, "experts_hit": 62.5,
                    "tokens_full": 80000}
    want = needs_l.lfm2_call_bytes(hf, 62, 256.0, 62.5, 80000)
    assert readers_l.decode_roofline_share(run) == pytest.approx(
        100 * want / 819e9 / 0.020)
    assert readers_l.decode_roofline_share(run) < 100
    # the device seconds inside the kernels come from the run's own trace;
    # here they are put where the readers keep them
    run.raw["_expert_device"] = (0.030, 2)
    assert readers_l.expert_device_ms(run) == pytest.approx(15.0)
    product = 8 * needs_l.lfm2_expert_product_bytes(hf, 256.0, 62.5)
    assert readers_l.expert_product_roofline_share(run) == pytest.approx(
        100 * product / 819e9 / 0.015)
    assert readers_l.expert_product_roofline_share(run) < 100
    run.raw["_op_device:is_conv_op_of_run:paged_step"] = (0.001, 2)
    assert readers_l.short_conv_device_ms(run) == pytest.approx(0.5)


def test_a_convolutions_operations_are_known_by_their_shapes():
    """Event names as the compiled step for the described v5e has them
    (``tests/serving/test_paged_step_chip_compile.py`` compiles it)."""
    hf = published_hf()
    for name in (
            "%fusion.44 = bf16[8,64,2,2048]{3,1,2,0:T(8,128)(2,1)} fusion("
            "bf16[8,64,2,2048]{3,1,2,0} %p, bf16[64,2,2048]{2,0,1} %g), "
            "kind=kLoop",
            "%copy.3 = bf16[8,64,2,2048]{3,2,1,0:T(2,128)(2,1)} copy(%x)",
            "%fusion.9 = bf16[64,2,2048]{2,0,1:T(8,128)(2,1)S(1)} fusion("
            "%a, %b)",
            "%slice-done.1 = bf16[2,64,2,2048]{3,1,2,0} slice-done(%s)",
            "%convolution.5 = bf16[64,6144]{1,0:T(8,128)(2,1)} convolution("
            "bf16[64,2048]{1,0} %h, bf16[2048,6144]{1,0} %w)",
            "%fusion.12 = bf16[64,2048]{1,0} fusion(f32[64,2,2048]{2,0,1} "
            "%t, bf16[64,6144]{1,0} %bcu)",
            "%fusion.13 = bf16[64,1,6144]{2,1,0} fusion(%x)"):
        assert readers_l.is_conv_op(name, hf, 64), name
    for name in (
            "%fusion.3 = bf16[64,4096,512]{2,1,0:T(8,128)(2,1)} fusion(%g)",
            "%fusion.4 = f32[64,32,4096]{2,1,0} fusion(%q, %k)",
            "%gmm.3 = bf16[256,1536]{1,0} custom-call(%a, %b, %c)",
            "%fusion.5 = bf16[64,2048]{1,0} fusion(%o)",
            "%convolution.7 = bf16[64,2048]{1,0} convolution(%y, %w)",
            "%fusion.6 = bf16[32,2,2048]{2,1,0} fusion(%x)",
            "%fusion.8 = f32[64,65536]{1,0} fusion(%x, %e)"):
        assert not readers_l.is_conv_op(name, hf, 64), name
