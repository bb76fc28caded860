"""The ``serve_mimo_v2_flash`` runner kind and what it brings (CPU only): the
configuration holds the catalog's keys but the cut; a broken timed path comes
out not correct; each control of the plain reference is over the rehearsal's
limits; ``needs_mimo_v2_flash`` counts the bytes that the seeded weights
have; the six ``.swa`` readers' arithmetic on spans built by hand, and None
on a silent run; a ring's operations are known by their shapes."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import manifest as mf
from benchmark import needs_mimo_v2_flash as needs_m
from benchmark import readers_mimo_v2_flash as readers_m
from benchmark import reference_mimo_v2_flash as ref
from benchmark import traffic
from benchmark.harness import Run
from benchmark.runners import serve_mimo_v2_flash

ROOT = mf.repo_root()
CELL = "mimo-flash-reasoning-backlog"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def rehearsal_run(seed: int = 5) -> Run:
    return Run(cell=mf.resolve_cell(CELL, ROOT), seed=seed, seconds=1.0,
               trace=False, rehearse=True, t_process=0.0)


def published_hf() -> dict:
    return serve_mimo_v2_flash.hf_config(mf.resolve_cell(CELL, ROOT).config)


def test_the_configuration_holds_the_catalogs_keys_but_the_cut():
    cfg = mf.resolve_cell(CELL, ROOT).config
    assert cfg["reduced"] == [
        "num_hidden_layers", "hybrid_layer_pattern", "moe_layer_freq",
        "n_routed_experts", "vocab_size"]
    # no width differs from the source
    assert (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"], cfg["num_attention_heads"],
            cfg["head_dim"], cfg["v_head_dim"], cfg["num_key_value_heads"],
            cfg["swa_num_key_value_heads"], cfg["sliding_window"],
            cfg["num_experts_per_tok"], cfg["router_experts"]) == (
                4096, 16384, 2048, 64, 192, 128, 4, 8, 128, 8, 256)
    # the cut: layers 0 and 6-11, 16 experts held, an eighth of the rows
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["first_expert"], cfg["vocab_size"]) == (7, 16, 0, 19072)
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"] == 152576
    assert cfg["hybrid_layer_pattern"] == [0, 1, 1, 1, 1, 1, 0]
    assert cfg["moe_layer_freq"] == [0, 1, 1, 1, 1, 1, 1]
    assert (cfg["published"]["num_hidden_layers"],
            cfg["published"]["n_routed_experts"]) == (48, 256)
    assert "16 chips share each layer" in cfg["deployment"]
    for item in ("norm placement", "layer kinds", "sink", "value scale",
                 "rotary", "attention_chunk_size", "router",
                 "multi-token prediction", "weights"):
        assert item in cfg["assumed"], item
    assert cfg["engine"] == {"n_slots": 32, "max_len": 16384}
    # what the reference and the program are given: the router's width, and
    # the share beside it
    hf = published_hf()
    assert (hf["n_routed_experts"], hf["experts_held"],
            hf["first_expert"]) == (256, 16, 0)
    assert set(serve_mimo_v2_flash.HF_KEYS) <= set(hf)


def test_every_key_of_the_catalogs_row_is_in_the_file_unchanged_or_reduced():
    try:
        rows = [json.loads(ln) for ln in open(CATALOG)]
    except OSError:
        pytest.skip("the catalog is not on this machine")
    (row,) = [r for r in rows if r["name"] == "MiMo-V2-Flash"]
    cfg = mf.resolve_cell(CELL, ROOT).config
    assert cfg["source"] == row["source_url"]
    assert set(row["config"]) == set(serve_mimo_v2_flash.HF_KEYS)
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg[key] != value, key
        else:
            assert cfg[key] == value, key
    # the kept layers are the published 0 and 6-11
    kept = [0, 6, 7, 8, 9, 10, 11]
    assert cfg["hybrid_layer_pattern"] == [
        row["config"]["hybrid_layer_pattern"][i] for i in kept]
    assert cfg["moe_layer_freq"] == [
        row["config"]["moe_layer_freq"][i] for i in kept]


def test_the_mix_is_the_issues_and_its_sizes_are_what_the_cell_says():
    mix = mf.resolve_cell(CELL, ROOT).mix
    assert (mix["runner"], mix["loop"], mix["clients"], mix["block"],
            mix["pair_seed"], mix["pool"], mix["lead_in_s"],
            mix["check_requests"]) == (
                "serve_mimo_v2_flash", "closed", 32, 16, 3, 1024, 20, 4)
    assert mix["prompt"] == {"dist": "lognormal", "median": 1024,
                             "sigma": 1.0, "min": 128, "max": 12288}
    assert mix["output"] == {"dist": "lognormal", "median": 640,
                             "sigma": 0.6, "min": 128, "max": 2048}
    sizes = traffic.request_sizes(mix, 1, 0)
    assert (sizes[:, 0].min(), sizes[:, 0].max()) == (159, 6596)
    assert (sizes[:, 1].min(), sizes[:, 1].max()) == (209, 1957)
    assert sizes.sum(1).max() == 6998 < 16384
    assert 2.0 < sizes[:, 0].sum() / sizes[:, 1].sum() < 2.3
    # ids are drawn from this chip's slice of the vocabulary
    reqs = traffic.serve_requests(mix, 19072, 16, 7)
    assert max(int(r.prompt.max()) for r in reqs) < 19072


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
    """Four rows of prompts of 58, 75, 90 and 96 tokens (past three windows
    of 16) and 24 tokens the float32 reference decodes greedily after each
    (a sound program's stand-in: every gap 0), at the rehearsal size."""
    seed = 2**31 + 4
    hf = serve_mimo_v2_flash.hf_config(rehearsal_run().config())
    lens = (58, 75, 90, 96)
    seqs = np.array(traffic.rng_for(seed, 0).integers(1, 512, (4, 128)),
                    np.int32)
    for r, n in enumerate(lens):
        seqs[r, n:] = 0
    rows = np.arange(4)
    with jax.default_matmul_precision("highest"):
        top = ref.top_weights(seed, hf, "float32")
        for j in range(24):
            at = np.array(lens) - 1 + j
            x, _ = ref.mimo_hidden(seed, hf, seqs, "float32")
            seqs[rows, at + 1] = np.asarray(jnp.argmax(
                ref.mimo_logits_at(top, hf, x[rows, at]), -1))
    return seed, hf, seqs, [(n - 1, n - 1 + 24) for n in lens]


def test_the_references_own_greedy_tokens_read_zero(greedy):
    seed, hf, seqs, spans = greedy
    gaps, std = ref.mimo_token_gaps(seed, hf, seqs, spans, "float32")
    assert gaps.shape == (4 * 24,) and std > 0.1
    assert (gaps / std).max() <= serve_mimo_v2_flash.TOKEN_GAP_MAX_LIMIT["cpu"]
    assert (gaps / std).mean() <= serve_mimo_v2_flash.TOKEN_GAP_MEAN_LIMIT[
        "cpu"]


#: the controls that 96 tokens at the rehearsal's widths can show. Not among
#: them: int8 operands (one scale a vector of 64 values rounds by 1e-4 of the
#: logits' spread here) and the whole head rotated (at hidden 64 a score has
#: a spread of 0.03, so attention is nearly uniform whatever is rotated); the
#: chip's probe reads both at the published widths (PERF.md section 2)
SHOWN_HERE = ("float8", "weakest_held_dropped", "window_ignored",
              "sink_left_out", "v_scale_left_out", "window_127")


def test_the_controls_are_the_issues_and_the_rehearsal_shows_six():
    assert ref.CONTROLS == (
        "f32", "bfloat16", "int8", "float8", "weakest_held_dropped",
        "window_ignored", "sink_left_out", "v_scale_left_out",
        "whole_head_rotated", "window_127")
    assert set(SHOWN_HERE) < set(ref.CONTROLS)
    with pytest.raises(ValueError, match="unknown control"):
        ref.mimo_hidden(1, published_hf(), np.zeros((1, 8), np.int32),
                        control="window_126")


@pytest.mark.parametrize("control", SHOWN_HERE)
def test_a_control_is_over_the_rehearsals_limits_three_times(greedy, control):
    """The reference with one thing wrong, judged at the served positions by
    the float32 reference: each of these is over both limits by three times
    and more here (PERF.md section 2 says which are caught on the chip, at
    bfloat16's own distance from float32)."""
    seed, hf, seqs, spans = greedy
    gaps, std = ref.mimo_token_gaps(seed, hf, seqs, spans, "float32", control)
    assert (gaps / std).max() > 3 * serve_mimo_v2_flash.TOKEN_GAP_MAX_LIMIT[
        "cpu"]
    assert (gaps / std).mean() > 3 * serve_mimo_v2_flash.TOKEN_GAP_MEAN_LIMIT[
        "cpu"]


def test_the_stated_precision_lies_nearer_than_the_one_below(greedy):
    seed, hf, seqs, spans = greedy
    mean = {c: float(ref.mimo_token_gaps(seed, hf, seqs, spans, "float32",
                                         c)[0].mean())
            for c in ("bfloat16", "int8", "float8")}
    # (at hidden 64 bfloat16's and int8's rounding move hardly an argmax of
    # 96; float8's does)
    assert 0 <= mean["bfloat16"] <= mean["int8"] < mean["float8"]


def test_the_weakest_held_expert_is_dropped_and_no_other():
    hf = dict(serve_mimo_v2_flash.hf_config(rehearsal_run().config()),
              experts_held=4, first_expert=2)
    hf_json = json.dumps(hf, sort_keys=True)
    w = ref.layer_weights(11, 1, hf, "float32")
    h = jax.random.normal(jax.random.PRNGKey(2), (64, 64))
    sel, wt = (np.asarray(a) for a in ref._mlp_programs(hf_json, "f32")[0](
        h, w["moe.router"], w["moe.expert_bias"]))
    sel2, wt2 = (np.asarray(a) for a in ref._mlp_programs(
        hf_json, "weakest_held_dropped")[0](
            h, w["moe.router"], w["moe.expert_bias"]))
    assert (sel == sel2).all()
    here = (sel >= 2) & (sel < 6)
    dropped = (wt2 == 0) & (wt > 0)
    assert (dropped <= here).all()
    # exactly one a token that has a held expert, and it is the weakest held
    assert (dropped.sum(1) == here.any(1)).all()
    both = here.all(1)
    assert both.any()
    assert (wt[both][dropped[both]] == wt[both].min(1)).all()


def test_needs_count_the_bytes_the_seeded_weights_have():
    """At the rehearsal size against the arrays themselves; at the published
    widths against the issue's hand count (3.43 B parameters, 6.87 GB)."""
    hf = serve_mimo_v2_flash.hf_config(rehearsal_run().config())
    for dtype, dense in (("bfloat16", 2), ("float32", 4)):
        arrays = [ref.top_weights(3, hf, dtype)] + [
            ref.layer_weights(3, i, hf, dtype) for i in range(7)]
        have = sum(a.nbytes for t in arrays for a in t.values())
        assert needs_m.mimo_param_bytes(hf, dense) == have
    big = published_hf()
    total = needs_m.mimo_param_bytes(big)
    assert 6.86e9 < total < 6.89e9
    assert needs_m.layer_counts(big) == (5, 2, 6)
    expert = 3 * 4096 * 2048 * 2
    assert needs_m.mimo_expert_bytes(big) == expert == 50331648
    embed = 19072 * 4096 * 2
    assert needs_m.mimo_fixed_bytes(big) == total - embed - 6 * 16 * expert
    assert 1.85e9 < needs_m.mimo_fixed_bytes(big) < 1.95e9
    # a full layer's column of the pool, a window layer's column of its ring
    assert needs_m.mimo_kv_bytes_per_token_layer(big, ref.FULL) == 2560
    assert needs_m.mimo_kv_bytes_per_token_layer(big, ref.WINDOW) == 5120
    # 32 rows at depth 2,500, 10.2 held experts hit a layer, full windows
    need = needs_m.mimo_call_bytes(big, 32, 6 * 10.2, 32 * 2500, 32 * 128)
    kv = 2 * 2560 * (32 * 2500 + 32)
    rings = 5 * 5120 * (32 * 128 + 32)
    assert need == pytest.approx(
        needs_m.mimo_fixed_bytes(big) + 32 * 4096 * 2 + 61.2 * expert + kv
        + rings)
    assert 0.55 < 61.2 * expert / need < 0.60
    assert 0.07 < kv / need < 0.08 and 0.015 < rings / need < 0.025
    # 2 a weight a row over the dense kernels, routers and the head's slice
    full = 4096 * (64 * 320 + 4 * 320)
    window = 4096 * (64 * 320 + 8 * 320)
    per_row = (2 * full + 5 * window + 3 * 4096 * 16384 + 6 * 4096 * 256
               + 4096 * 19072)
    assert needs_m.mimo_call_flops(big, 1, 0, 0, 0) == 2 * per_row
    # a pair routed here costs an expert's three products in each layer
    assert (needs_m.mimo_call_flops(big, 1, 1, 0, 0) - 2 * per_row
            == 6 * 2 * 3 * 4096 * 2048)
    # a (query, key) pair costs 2 x 64 x (192 + 128) in a layer of its kind
    assert (needs_m.mimo_call_flops(big, 1, 0, 100, 0) - 2 * per_row
            == 2 * 2 * 64 * 320 * 100)
    assert (needs_m.mimo_call_flops(big, 1, 0, 0, 100) - 2 * per_row
            == 5 * 2 * 64 * 320 * 100)
    assert needs_m.mimo_expert_product_bytes(big, 16, 10) == (
        10 * expert + 2 * 16 * 4096 * 2)


def _silent_run():
    run = rehearsal_run()
    run.window = (0.0, 10.0)
    run.raw = {"hf_config": published_hf(), "n_slots": 32}
    return run


READERS = ("decode_roofline_share", "expert_device_ms",
           "expert_product_roofline_share", "experts_hit_share",
           "window_attn_device_ms", "kv_cols_read_over_live")


@pytest.mark.parametrize("reader", READERS)
def test_a_reader_is_silent_where_its_source_is(reader):
    run = _silent_run()
    assert getattr(readers_m, reader)(run) is None
    # Trinity's ticks: expert counters, no ring counters on them
    run.spans = [{"name": "serving.decode_step", "t0": 1.0, "t1": 1.1,
                  "args": {"slots": 8, "chain": 1, "kv_cols_read": 100,
                           "kv_cols_live": 50, "experts_hit": 100.0,
                           "expert_rows": 256.0, "expert_rows_max": 7}}]
    run.traced_window = (0.5, 2.5)
    run.raw["_expert_device"] = (0.004, 2)
    assert getattr(readers_m, reader)(run) is None


def test_the_six_readers_arithmetic_on_spans_built_by_hand():
    run = _silent_run()
    hf = run.raw["hf_config"]

    def tick(t, rows, live, nb, hit, pairs):
        return {"name": "serving.decode_step", "t0": t, "t1": t + 0.01,
                "args": {"slots": rows, "chain": 1, "nb": nb,
                         "kv_cols_read": 32 * nb * 16, "kv_cols_live": live,
                         "win_cols_read": 32 * 128,
                         "win_cols_live": rows * 128,
                         "state_rows": rows, "state_bytes": 0,
                         "experts_hit": hit, "expert_rows": pairs,
                         "expert_rows_max": 3, "expert_pairs": 256}}

    run.spans = [tick(1.0, 32, 90000, 512, 10.0, 16.0),
                 tick(2.0, 30, 70000, 512, 11.0, 18.0),
                 tick(5.0, 32, 40000, 256, 9.0, 14.0)]
    assert readers_m.kv_cols_read_over_live(run) == pytest.approx(
        32 * 16 * (512 + 512 + 256) / 200000)
    assert readers_m.experts_hit_share(run) == pytest.approx(
        100 * 10.0 / 16)
    run.traced_window = (0.5, 2.5)
    run.device_kind = "TPU v5 lite"
    run.trace_summary = {"whole_programs": {
        "jit__paged_step(1)": {"seconds": 0.028, "count": 2}}}
    need = readers_m._tick_needs(run)
    assert need == {"rows": 31, "pairs": 17.0, "experts_hit": 10.5,
                    "tokens_full": 80000, "tokens_window": 31 * 128}
    want = needs_m.mimo_call_bytes(hf, 31, 6 * 10.5, 80000, 31 * 128)
    assert readers_m.decode_roofline_share(run) == pytest.approx(
        100 * want / 819e9 / 0.014)
    assert readers_m.decode_roofline_share(run) < 100
    # the device seconds inside the kernels come from the run's own trace;
    # here they are put where the readers keep them
    run.raw["_expert_device"] = (0.010, 2)
    assert readers_m.expert_device_ms(run) == pytest.approx(5.0)
    product = 6 * needs_m.mimo_expert_product_bytes(hf, 17.0, 10.5)
    assert readers_m.expert_product_roofline_share(run) == pytest.approx(
        100 * product / 819e9 / 0.005)
    assert readers_m.expert_product_roofline_share(run) < 100
    run.raw["_op_device:is_ring_op_of_run:paged_step"] = (0.001, 2)
    assert readers_m.window_attn_device_ms(run) == pytest.approx(0.5)


def test_a_rings_operations_are_known_by_their_shapes():
    hf = published_hf()
    for name in (
            "%fusion.44 = bf16[32,128,1536]{2,1,0:T(8,128)(2,1)} fusion("
            "bf16[32,128,1536]{2,1,0} %bitcast.271, s32[32]{0} %g), "
            "kind=kCustom",
            "%copy-start.2 = (bf16[5,32,128,1536]{3,2,1,0:T(8,128)(2,1)S(1)}, "
            "bf16[5,32,128,1536]{3,2,1,0}, u32[]{:S(2)}) copy-start(%p)",
            "%fusion.9 = bf16[5,32,128,1024]{3,2,1,0} fusion(%a, %b)",
            "%fusion.584 = (f32[32,64]{1,0}, f32[32,64,128]{2,1,0}) fusion("
            "bf16[1,32,128,1536]{3,2,1,0} %p0, bf16[32,64,1536]{2,1,0} %p1)",
            "%fusion.12 = f32[32,64,128]{2,1,0:T(8,128)} fusion(%x)"):
        assert readers_m.is_ring_op(name, hf, 32), name
    for name in (
            "%fusion.3 = bf16[32,8192,768]{2,1,0:T(8,128)(2,1)} fusion(%g)",
            "%fusion.4 = f32[32,64,8192]{2,1,0} fusion(%q, %k)",
            "%gmm.3 = bf16[256,2048]{1,0} custom-call(%a, %b, %c)",
            "%fusion.5 = bf16[32,8192]{1,0} fusion(%o)",
            "%copy.7 = bf16[4,8,64,128]{3,2,1,0} copy(%w)",
            "%fusion.6 = f32[16,64,128]{2,1,0} fusion(%x)"):
        assert not readers_m.is_ring_op(name, hf, 32), name
