"""The ``serve_glm_moe_dsa`` runner kind and what it brings (CPU only): the
configuration holds the catalog's keys but the cut; the mix is the issue's; a
broken timed path comes out not correct; each control of the plain reference
is over the rehearsal's limits where float32 can show it; ``needs_glm_moe_dsa``
counts the bytes that the seeded weights have and never a padded column; the
ten ``.dsa`` readers' arithmetic on spans built by hand, and None on a silent
run; the two stages' operations are known by their shapes."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import manifest as mf
from benchmark import needs_glm_moe_dsa as needs_g
from benchmark import readers_glm_moe_dsa as readers_g
from benchmark import reference_glm_moe_dsa as ref
from benchmark import traffic
from benchmark.harness import Run
from benchmark.runners import serve_glm_moe_dsa

ROOT = mf.repo_root()
CELL = "glm52-sparse-agent-backlog"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_hidden_layers", "first_k_dense_replace", "mlp_layer_types",
           "indexer_types", "n_routed_experts", "vocab_size"]


def rehearsal_run(seed: int = 5) -> Run:
    return Run(cell=mf.resolve_cell(CELL, ROOT), seed=seed, seconds=1.0,
               trace=False, rehearse=True, t_process=0.0)


def published_hf() -> dict:
    return serve_glm_moe_dsa.hf_config(mf.resolve_cell(CELL, ROOT).config)


def test_the_configuration_holds_the_catalogs_keys_but_the_cut():
    cfg = mf.resolve_cell(CELL, ROOT).config
    assert cfg["reduced"] == REDUCED
    # no width differs from the source
    assert (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"], cfg["num_attention_heads"],
            cfg["q_lora_rank"], cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"], cfg["index_n_heads"],
            cfg["index_head_dim"], cfg["index_topk"],
            cfg["num_experts_per_tok"]) == (
                6144, 12288, 2048, 64, 2048, 512, 192, 64, 256, 32, 128,
                2048, 8)
    # the cut: published layers 2-6, the share of 16 chips a layer
    assert cfg["num_hidden_layers"] == 5 and cfg["first_k_dense_replace"] == 1
    assert cfg["indexer_types"] == ["full", "shared", "shared", "shared",
                                    "full"]
    assert cfg["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    assert (cfg["n_routed_experts"], cfg["router_experts"],
            cfg["first_expert"], cfg["vocab_size"]) == (16, 256, 0, 19456)
    assert cfg["vocab_size"] % 128 == 0 and cfg["vocab_size"] * 8 >= 154880
    assert cfg["published"]["num_hidden_layers"] == 78
    assert "16 chips share each layer" in cfg["deployment"]
    assert "pipeline stages" in cfg["deployment"]
    for item in ("n_slots", "max_len", "parameters", "weights",
                 "norm placement", "latent attention", "rotary", "indexer",
                 "index sharing", "router", "multi-token prediction"):
        assert item in cfg["assumed"], item
    assert "0.076" in cfg["assumed"]["weights"]
    assert ref.Q_KERNEL_STD == 0.076
    assert cfg["engine"] == {"n_slots": 32, "max_len": 16384}
    assert cfg["dtype"] == "bfloat16"
    assert cfg["entry_point"].endswith("ContinuousGPTEngine")
    hf = published_hf()
    assert set(serve_glm_moe_dsa.HF_KEYS) <= set(hf)
    assert (hf["n_routed_experts"], hf["experts_held"]) == (256, 16)
    entry = next(c for c in mf.load_manifest(ROOT)["configs"]
                 if c["name"] == "glm-5.2-serve")
    assert (entry["source"], entry["reduced"]) == (cfg["source"],
                                                   cfg["reduced"])


def test_every_key_of_the_catalogs_row_is_in_the_file_unchanged_or_reduced():
    try:
        rows = [json.loads(ln) for ln in open(CATALOG)]
    except OSError:
        pytest.skip("the catalog is not on this machine")
    (row,) = [r for r in rows if r["name"] == "GLM-5.2"]
    cfg = mf.resolve_cell(CELL, ROOT).config
    assert cfg["source"] == row["source_url"]
    assert set(row["config"]) == set(serve_glm_moe_dsa.HF_KEYS)
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg[key] != value, key
        else:
            assert cfg[key] == value, key
    # published layers 2-6 of both lists
    assert cfg["indexer_types"] == row["config"]["indexer_types"][2:7]
    assert cfg["mlp_layer_types"] == row["config"]["mlp_layer_types"][2:7]


def test_the_mix_is_the_issues_fallback_and_its_sizes_are_what_the_cell_says():
    """The issue named a fallback before any run: if six untraced runs over
    three seeds spread by more than 3% of their median, the prompt becomes
    median 2048 clipped 512-8192 and nothing else moves. They did (4.1%:
    PERF.md section 6), so this is the mix."""
    mix = mf.resolve_cell(CELL, ROOT).mix
    assert (mix["runner"], mix["loop"], mix["clients"], mix["block"],
            mix["pair_seed"], mix["pool"], mix["lead_in_s"],
            mix["check_requests"]) == (
                "serve_glm_moe_dsa", "closed", 32, 16, 5, 1024, 30, 4)
    assert mix["prompt"] == {"dist": "lognormal", "median": 2048,
                             "sigma": 0.6, "min": 512, "max": 8192}
    assert mix["output"] == {"dist": "lognormal", "median": 768,
                             "sigma": 0.5, "min": 128, "max": 2048}
    sizes = traffic.request_sizes(mix, 1, 0)
    assert sorted(sizes[:, 0]) == [
        670, 929, 1117, 1285, 1447, 1609, 1776, 1954, 2147, 2361, 2607,
        2899, 3263, 3754, 4516, 6262]
    assert (sizes[:, 1].min(), sizes[:, 1].max()) == (303, 1949)
    assert round(sizes[:, 0].mean()) == 2412
    assert round(sizes[:, 1].mean()) == 861
    assert sizes.sum(1).max() == 6659 < 16384
    assert 2.7 < sizes[:, 0].sum() / sizes[:, 1].sum() < 2.9
    # with every slot taken some row is past the selection's 2,048 columns
    # in every tick: a block's contexts end at 1.7k-6.7k, 3,273 on average
    assert (sizes.sum(1) > 2048).sum() == 14
    cell = mf.resolve_cell(CELL, ROOT)
    assert cell.chips == 1 and len(cell.why) <= 200


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
    """Four rows of prompts of 20, 33, 41 and 48 tokens (all past the
    rehearsal's selection of 16 columns) and 16 tokens the float32 reference
    decodes greedily after each (a sound program's stand-in: every gap 0),
    at the rehearsal size."""
    seed = 2**31 + 4
    hf = serve_glm_moe_dsa.hf_config(rehearsal_run().config())
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
            x, _ = ref.glm_hidden(seed, hf, seqs, "float32")
            seqs[rows, at + 1] = np.asarray(jnp.argmax(
                ref.glm_logits_at(top, hf, x[rows, at]), -1))
    return seed, hf, seqs, [(n - 1, n - 1 + 16) for n in lens]


def test_the_references_own_greedy_tokens_read_zero(greedy):
    seed, hf, seqs, spans = greedy
    gaps, std = ref.glm_token_gaps(seed, hf, seqs, spans, "float32")
    assert gaps.shape == (4 * 16,) and std > 0.05
    assert (gaps / std).max() <= serve_glm_moe_dsa.TOKEN_GAP_MAX_LIMIT["cpu"]
    assert (gaps / std).mean() <= serve_glm_moe_dsa.TOKEN_GAP_MEAN_LIMIT[
        "cpu"]


#: the faulty controls, all of which float32 shows at the rehearsal's size
#: (contexts of 20-64 tokens under a selection of 16)
SHOWN_HERE = ("int8", "float8", "all_columns", "shared_last", "no_relu",
              "weakest_held_dropped")


def test_the_controls_are_the_issues():
    assert ref.CONTROLS == ("f32", "bfloat16") + SHOWN_HERE
    with pytest.raises(ValueError, match="unknown control"):
        ref.glm_hidden(1, published_hf(), np.zeros((1, 8), np.int32),
                       control="first_columns")


@pytest.mark.parametrize("control", SHOWN_HERE)
def test_a_control_is_over_the_rehearsals_limits_three_times(greedy, control):
    """The reference with one thing wrong, judged at the served positions by
    the float32 reference: each is over both limits by three times and more
    here (PERF.md section 2 says which are caught on the chip, at bfloat16's
    own distance from float32)."""
    seed, hf, seqs, spans = greedy
    gaps, std = ref.glm_token_gaps(seed, hf, seqs, spans, "float32", control)
    assert (gaps / std).max() > 3 * serve_glm_moe_dsa.TOKEN_GAP_MAX_LIMIT[
        "cpu"]
    assert (gaps / std).mean() > 3 * serve_glm_moe_dsa.TOKEN_GAP_MEAN_LIMIT[
        "cpu"]


def test_the_stated_precision_lies_nearer_than_the_ones_below(greedy):
    seed, hf, seqs, spans = greedy
    mean = {c: float(ref.glm_token_gaps(seed, hf, seqs, spans, "float32",
                                        c)[0].mean())
            for c in ("bfloat16", "int8", "float8")}
    assert 0 <= mean["bfloat16"] < mean["int8"] < mean["float8"]


def test_needs_count_the_bytes_the_seeded_weights_have():
    """At the rehearsal size against the arrays themselves; at the published
    widths against the issue's hand count (3,882.7 M parameters)."""
    hf = serve_glm_moe_dsa.hf_config(rehearsal_run().config())
    for dtype, dense in (("bfloat16", 2), ("float32", 4)):
        arrays = [ref.top_weights(3, hf, dtype)] + [
            ref.layer_weights(3, i, hf, dtype)
            for i in range(hf["num_hidden_layers"])]
        have = sum(a.nbytes for t in arrays for a in t.values())
        assert needs_g.glm_param_bytes(hf, dense) == have
    big = published_hf()
    assert ref.seeded_parameters(big) == 3_882_696_704
    total = needs_g.glm_param_bytes(big)
    assert 7.77e9 < total < 7.80e9
    assert needs_g.layer_counts(big) == (5, 2, 4)
    expert = 3 * 6144 * 2048 * 2
    assert needs_g.glm_expert_bytes(big) == expert == 75497472
    # all but the held experts' kernels and the embedding
    assert needs_g.glm_fixed_bytes(big) == (
        total - 4 * 16 * expert - 19456 * 6144 * 2)
    # a selected column is 576 values, NEVER the pool's padded 640; an
    # indexer's key 128
    assert needs_g.latent_bytes_per_column(big) == 1152
    assert needs_g.index_bytes_per_column(big) == 256
    assert needs_g.sparse_attn_bytes(big, 65536) == 65536 * 1152
    assert needs_g.sparse_attn_flops(big, 1) == 2 * 64 * (576 + 512)
    proj = (2048 * 4096 + 6144 * 128 + 6144 * 32) * 2
    assert needs_g.indexer_projection_bytes(big) == proj
    assert needs_g.indexer_bytes(big, 1000) == 1000 * 256 + proj
    assert needs_g.indexer_flops(big, 0, 1) == 2 * 32 * 128
    # 32 rows, 2,048 attended columns each, 166,400 columns to score, 12
    # held experts hit a layer, 16 pairs
    need = needs_g.glm_call_bytes(big, 32, 4 * 12.0, 65536, 166400)
    cols = 5 * 1152 * (65536 + 32) + 2 * 256 * (166400 + 32)
    assert need == pytest.approx(
        needs_g.glm_fixed_bytes(big) + 32 * 6144 * 2 + 48 * expert + cols)
    assert 0.05 < cols / need < 0.09
    assert needs_g.glm_expert_product_bytes(big, 16, 12) == (
        12 * expert + 2 * 16 * 6144 * 2)
    # a pair costs an expert's three products in each expert layer
    base = needs_g.glm_call_flops(big, 1, 0, 0, 0)
    assert (needs_g.glm_call_flops(big, 1, 1, 0, 0) - base
            == 4 * 2 * 3 * 6144 * 2048)
    # an attended column costs a score over 576 and a mix over 512 a head a
    # layer; a scored column a product of 128 an index head a full layer
    assert (needs_g.glm_call_flops(big, 1, 0, 100, 0) - base
            == 5 * 2 * 64 * (576 + 512) * 100)
    assert (needs_g.glm_call_flops(big, 1, 0, 0, 100) - base
            == 2 * 2 * 32 * 128 * 100)


def _silent_run():
    run = rehearsal_run()
    run.window = (0.0, 10.0)
    run.raw = {"hf_config": published_hf(), "n_slots": 32}
    return run


READERS = ("decode_roofline_share", "expert_device_ms",
           "expert_product_roofline_share", "experts_hit_share",
           "indexer_device_ms", "indexer_roofline_share",
           "sparse_attn_device_ms", "sparse_attn_roofline_share",
           "selected_cols_share", "kv_cols_read_over_live")


@pytest.mark.parametrize("reader", READERS)
def test_a_reader_is_silent_where_its_source_is(reader):
    """On a run with no spans and no trace, and on the parent's program
    (no family, so no cell: a run of it has no span at all)."""
    run = _silent_run()
    assert getattr(readers_g, reader)(run) is None
    run.traced_window = (0.5, 2.5)
    assert getattr(readers_g, reader)(run) is None
    # every reader has its file under layer_metrics/, and the manifest lists
    # it for this cell alone
    entry = next(p for p in mf.load_manifest(ROOT)["per_layer"]
                 if p["name"] == reader + ".dsa")
    assert entry["workloads"] == [CELL] and entry["moves"] == "tokens_per_s"
    assert mf.reader_file(mf.load_manifest(ROOT), ROOT,
                          reader + ".dsa").endswith(reader + ".dsa.py")


def test_the_ten_readers_arithmetic_on_spans_built_by_hand():
    run = _silent_run()
    hf = run.raw["hf_config"]

    def tick(t, rows, live, sel, index, hit):
        return {"name": "serving.decode_step", "t0": t, "t1": t + 0.01,
                "args": {"slots": rows, "chain": 1, "nb": 1024,
                         "kv_cols_read": 32 * 2048, "kv_cols_live": live,
                         "sel_cols": sel, "index_cols": index,
                         "experts_hit": hit, "expert_rows": 16.0,
                         "expert_rows_max": 4, "expert_pairs": 256}}

    run.spans = [tick(1.0, 32, 170000, 62000, 165000, 10.0),
                 tick(2.0, 30, 150000, 60000, 148000, 12.0),
                 tick(5.0, 32, 80000, 50000, 70000, 11.0)]
    assert readers_g.kv_cols_read_over_live(run) == pytest.approx(
        3 * 32 * 2048 / 400000)
    assert readers_g.kv_cols_read_over_live(run) < 1
    assert readers_g.selected_cols_share(run) == pytest.approx(
        100 * 172000 / 400000)
    assert readers_g.experts_hit_share(run) == pytest.approx(100 * 11.0 / 16)
    run.traced_window = (0.5, 2.5)
    run.device_kind = "TPU v5 lite"
    run.trace_summary = {"whole_programs": {
        "jit__paged_step(1)": {"seconds": 0.060, "count": 2}}}
    need = readers_g._tick_needs(run)
    assert need == {"rows": 31, "pairs": 16.0, "experts_hit": 11.0,
                    "sel_cols": 61000, "index_cols": 156500}
    want = needs_g.glm_call_bytes(hf, 31, 4 * 11.0, 61000, 156500)
    assert readers_g.decode_roofline_share(run) == pytest.approx(
        100 * want / 819e9 / 0.030)
    assert readers_g.decode_roofline_share(run) < 100
    # the device seconds inside the kernels and the stages come from the
    # run's own trace; here they are put where the readers keep them
    run.raw["_expert_device"] = (0.010, 2)
    assert readers_g.expert_device_ms(run) == pytest.approx(5.0)
    product = 4 * needs_g.glm_expert_product_bytes(hf, 16.0, 11.0)
    assert readers_g.expert_product_roofline_share(run) == pytest.approx(
        100 * product / 819e9 / 0.005)
    assert readers_g.expert_product_roofline_share(run) < 100
    run.raw["_op_device:is_indexer_op:paged_step"] = (0.004, 2)
    assert readers_g.indexer_device_ms(run) == pytest.approx(2.0)
    assert readers_g.indexer_roofline_share(run) == pytest.approx(
        100 * 2 * needs_g.indexer_bytes(hf, 156500) / 819e9 / 0.002)
    run.raw["_op_device:is_sparse_attn_op:paged_step"] = (0.020, 2)
    assert readers_g.sparse_attn_device_ms(run) == pytest.approx(10.0)
    assert readers_g.sparse_attn_roofline_share(run) == pytest.approx(
        100 * 5 * 61000 * 1152 / 819e9 / 0.010)
    assert readers_g.sparse_attn_roofline_share(run) < 100
