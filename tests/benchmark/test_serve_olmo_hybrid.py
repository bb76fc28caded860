"""The ``serve_olmo_hybrid`` runner kind and what it brings (CPU only): a
broken timed path comes out not correct; each control of the plain reference
is over the rehearsal's limits; ``needs_olmo_hybrid`` counts the bytes that
the seeded weights have; the six readers' arithmetic on spans built by hand,
and None on a silent run."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import manifest as mf
from benchmark import needs_olmo_hybrid as needs_h
from benchmark import readers_olmo_hybrid as readers_h
from benchmark import reference_olmo_hybrid as ref
from benchmark import traffic
from benchmark.harness import Run
from benchmark.runners import serve_olmo_hybrid

ROOT = mf.repo_root()
CELL = "olmo-hybrid-long-backlog"


def rehearsal_run(seed: int = 5) -> Run:
    return Run(cell=mf.resolve_cell(CELL, ROOT), seed=seed, seconds=1.0,
               trace=False, rehearse=True, t_process=0.0)


def published_hf() -> dict:
    return serve_olmo_hybrid.hf_config(mf.resolve_cell(CELL, ROOT).config)


def test_the_configuration_holds_the_catalogs_keys_but_the_cut():
    cfg = mf.resolve_cell(CELL, ROOT).config
    hf = published_hf()
    assert set(hf) == set(serve_olmo_hybrid.HF_KEYS)
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types"]
    assert (hf["num_hidden_layers"], cfg["published"]["num_hidden_layers"]
            ) == (8, 32)
    assert hf["layer_types"] == ["linear_attention"] * 3 + [
        "full_attention"] + ["linear_attention"] * 3 + ["full_attention"]
    # no width differs from the source
    assert (hf["hidden_size"], hf["intermediate_size"], hf["vocab_size"],
            hf["num_attention_heads"], hf["num_key_value_heads"]) == (
                3840, 11008, 100352, 30, 30)
    assert (hf["linear_num_key_heads"], hf["linear_num_value_heads"],
            hf["linear_key_head_dim"], hf["linear_value_head_dim"],
            hf["linear_conv_kernel_dim"], hf["linear_allow_neg_eigval"]) == (
                30, 30, 96, 192, 4, True)
    assert hf["rope_parameters"] == {"rope_theta": None}
    for item in ("norm placement", "q/k norm", "no rotation", "head_dim",
                 "beta", "convolutions", "output gate",
                 "state and decay arithmetic"):
        assert item in cfg["assumed"], item
    mix = mf.resolve_cell(CELL, ROOT).mix
    assert (mix["clients"], mix["pair_seed"], mix["pool"], mix["lead_in_s"],
            mix["check_requests"]) == (16, 2, 1024, 12, 4)
    sizes = traffic.request_sizes(mix, 1, 0)
    assert sorted(sizes[:, 0])[:3] == [318, 548, 746]
    assert sizes[:, 0].max() == 7168 and sizes.sum(1).max() == 7463
    assert (sizes[:, 1].min(), sizes[:, 1].max()) == (84, 768)


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
    """Four rows of prompts of 58, 75, 90 and 96 tokens (last chunks of 26,
    11, 26 and 32 real tokens at a prefill chunk of 32: pads of 6, 5, 6 and
    none) and 24 tokens the float32 reference decodes greedily after each
    (a sound program's stand-in: every gap 0), at the rehearsal size."""
    seed = 2**31 + 4
    hf = serve_olmo_hybrid.hf_config(rehearsal_run().config())
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
            x = ref.hybrid_hidden(seed, hf, seqs, "float32")
            seqs[rows, at + 1] = np.asarray(jnp.argmax(
                ref.hybrid_logits_at(top, hf, x[rows, at]), -1))
    return seed, hf, seqs, [(n - 1, n - 1 + 24) for n in lens]


def test_the_references_own_greedy_tokens_read_zero(greedy):
    seed, hf, seqs, spans = greedy
    gaps, std = ref.hybrid_token_gaps(seed, hf, seqs, spans, "float32")
    assert gaps.shape == (4 * 24,) and std > 0.1
    assert (gaps / std).max() <= serve_olmo_hybrid.TOKEN_GAP_MAX_LIMIT["cpu"]
    assert (gaps / std).mean() <= serve_olmo_hybrid.TOKEN_GAP_MEAN_LIMIT["cpu"]


@pytest.mark.parametrize("control", [
    "int8", "float8", "state_bf16", "no_decay", "beta_single",
    "pad_unmasked"])
def test_a_control_is_over_the_rehearsals_limits_three_times(greedy, control):
    """The reference with one thing wrong, judged at the served positions by
    the float32 reference: every control is over both limits by three times
    and more here (PERF.md section 2 says which are caught on the chip, at
    bfloat16's own distance from float32)."""
    seed, hf, seqs, spans = greedy
    gaps, std = ref.hybrid_token_gaps(seed, hf, seqs, spans, "float32",
                                      control, chunk=32)
    assert (gaps / std).max() > 3 * serve_olmo_hybrid.TOKEN_GAP_MAX_LIMIT[
        "cpu"]
    assert (gaps / std).mean() > 3 * serve_olmo_hybrid.TOKEN_GAP_MEAN_LIMIT[
        "cpu"]


def test_the_stated_precision_lies_nearer_than_the_one_below(greedy):
    seed, hf, seqs, spans = greedy
    mean = {c: float(ref.hybrid_token_gaps(seed, hf, seqs, spans, "float32",
                                           c)[0].mean())
            for c in ("bfloat16", "int8", "float8")}
    assert 0 < mean["bfloat16"] < mean["int8"] < mean["float8"]


def test_the_pad_control_pads_as_the_engine_buckets_a_last_chunk():
    # chunks of 256, the last one's real count to a power of two from 8
    assert [ref.chunk_pad(n, 256) for n in (318, 548, 7168, 256, 3, 250)] == [
        2, 28, 0, 0, 5, 6]
    seqs = np.arange(1, 25, dtype=np.int32).reshape(2, 12)
    out, moved, seen = ref.with_pad_tokens(seqs, [(4, 8), (8, 10)], 8)
    # row 0: a prompt of 5 padded to 8; row 1: 9 = 8 + 1, its last chunk of
    # one token padded to 8
    assert moved == [(4, 8, 3), (8, 10, 7)]
    assert out[0, :11].tolist() == [1, 2, 3, 4, 5, 0, 0, 0, 6, 7, 8]
    assert seen[0, :11].tolist() == [True] * 5 + [False] * 3 + [True] * 3
    assert out[1, 8:17].tolist() == [21] + [0] * 7 + [22]
    assert not seen[1, 9:16].any() and seen[1, 16]


def test_needs_count_the_bytes_the_seeded_weights_have():
    """At the rehearsal size against the arrays themselves; at the published
    widths against the issue's hand count (2.436 B parameters, 4.87 GB)."""
    hf = serve_olmo_hybrid.hf_config(rehearsal_run().config())
    for dtype, dense in (("bfloat16", 2), ("float32", 4)):
        arrays = [ref.top_weights(3, hf, dtype)] + [
            ref.layer_weights(3, i, hf, dtype) for i in range(8)]
        have = sum(a.nbytes for t in arrays for a in t.values())
        assert needs_h.hybrid_param_bytes(hf, dense) == have
    big = published_hf()
    total = needs_h.hybrid_param_bytes(big)
    assert 4.87e9 < total < 4.88e9
    embed = 100352 * 3840 * 2
    assert needs_h.hybrid_fixed_bytes(big) == total - embed
    assert needs_h.hybrid_kv_bytes_per_token_layer(big) == 2 * 3840 * 2
    state = 30 * 96 * 192 * 4 + 3 * 11520 * 2
    assert needs_h.hybrid_state_bytes_per_row_layer(big) == state == 2280960
    # 16 rows at depth 2,800: weights 69%, K/V of TWO layers 23%, state 7%
    need = needs_h.hybrid_call_bytes(big, 16, 16 * 2800)
    kv = 2 * 15360 * (16 * 2800 + 16)
    assert need == (needs_h.hybrid_fixed_bytes(big) + 16 * 3840 * 2 + kv
                    + 2 * 16 * 6 * state)
    assert 0.20 < kv / need < 0.26 and 0.06 < 2 * 16 * 6 * state / need < 0.08
    # 2 a weight a row over the cut's kernels and the head, and the rule
    linear = 3840 * (2 * 2880 + 3 * 5760 + 60) + 3 * 3840 * 11008
    full = 4 * 3840 * 3840 + 3 * 3840 * 11008
    rule = 7 * 30 * 96 * 192
    assert needs_h.hybrid_call_flops(big, 1, 0) == (
        2 * (6 * linear + 2 * full + 3840 * 100352) + 6 * rule)
    assert (needs_h.hybrid_call_flops(big, 1, 100)
            - needs_h.hybrid_call_flops(big, 1, 0)) == 4 * 30 * 128 * 2 * 100
    # the scan of one chunk of 256: the state in and out once, 34.6 KB a
    # token a layer of q, k, v and o
    assert needs_h.hybrid_delta_scan_bytes(big, 256, 1) == 6 * (
        2 * state + 256 * 2 * 30 * 576)
    assert needs_h.hybrid_delta_scan_flops(big, 256) == 256 * 6 * rule
    assert needs_h.hybrid_delta_step_flops(big, 16) == 16 * 6 * rule


def _silent_run():
    run = rehearsal_run()
    run.window = (0.0, 10.0)
    run.raw = {"hf_config": published_hf(), "n_slots": 16}
    return run


READERS = ("decode_roofline_share", "delta_step_device_ms",
           "delta_step_roofline_share", "delta_scan_device_ms",
           "delta_scan_roofline_share", "kv_cols_read_over_live")


@pytest.mark.parametrize("reader", READERS)
def test_a_reader_is_silent_where_its_source_is(reader):
    run = _silent_run()
    assert getattr(readers_h, reader)(run) is None
    # a GPT's ticks and chunks: no state counters on them
    run.spans = [{"name": "serving.decode_step", "t0": 1.0, "t1": 1.1,
                  "args": {"slots": 8, "chain": 1, "kv_cols_read": 100,
                           "kv_cols_live": 50}},
                 {"name": "serving.prefill_chunk", "t0": 1.0, "t1": 1.1,
                  "args": {"tokens": 256, "width": 256}}]
    run.traced_window = (0.5, 2.5)
    assert getattr(readers_h, reader)(run) is None


def test_the_six_readers_arithmetic_on_spans_built_by_hand():
    run = _silent_run()
    hf = run.raw["hf_config"]
    per_row = 2 * 6 * needs_h.hybrid_state_bytes_per_row_layer(hf)

    def tick(t, rows, live, nb):
        return {"name": "serving.decode_step", "t0": t, "t1": t + 0.01,
                "args": {"slots": rows, "chain": 1, "nb": nb,
                         "kv_cols_read": 16 * nb * 16, "kv_cols_live": live,
                         "state_rows": rows, "state_bytes": rows * per_row}}

    def chunk(t, tokens, width):
        return {"name": "serving.prefill_chunk", "t0": t, "t1": t + 0.01,
                "args": {"tokens": tokens, "width": width,
                         "scan_tokens": tokens, "pad_tokens": width - tokens}}

    run.spans = [tick(1.0, 16, 50000, 512), tick(2.0, 14, 30000, 256),
                 tick(5.0, 16, 20000, 512),
                 chunk(1.5, 256, 256), chunk(2.2, 62, 64), chunk(6.0, 256, 256)]
    assert readers_h.kv_cols_read_over_live(run) == pytest.approx(
        16 * 16 * (512 + 256 + 512) / 100000)
    run.traced_window = (0.5, 2.5)
    run.device_kind = "TPU v5 lite"
    run.trace_summary = {"whole_programs": {
        "jit__paged_step(1)": {"seconds": 0.050, "count": 2}}}
    need = readers_h._tick_needs(run)
    assert need == {"rows": 15, "tokens_full": 40000,
                    "state_bytes": 15 * per_row}
    want = needs_h.hybrid_call_bytes(hf, 15, 40000)
    assert readers_h.decode_roofline_share(run) == pytest.approx(
        100 * want / 819e9 / 0.025)
    assert readers_h.decode_roofline_share(run) < 100
    # the device seconds inside the recurrence come from the run's own
    # trace; here they are put where the readers keep them
    run.raw["_op_device:is_step_op:paged_step"] = (0.004, 2)
    assert readers_h.delta_step_device_ms(run) == pytest.approx(2.0)
    assert readers_h.delta_step_roofline_share(run) == pytest.approx(
        100 * 15 * per_row / 819e9 / 0.002)
    run.raw["_op_device:is_scan_op:_chunk_"] = (0.006, 2)
    assert readers_h.delta_scan_device_ms(run) == pytest.approx(3.0)
    scan = needs_h.hybrid_delta_scan_bytes(hf, 256 + 62, 2)
    assert readers_h.delta_scan_roofline_share(run) == pytest.approx(
        100 * scan / 819e9 / 2 / 0.003)
    assert readers_h.delta_scan_roofline_share(run) < 100


def test_the_recurrences_operations_are_known_by_their_shapes():
    hf = published_hf()
    step = ("%add_select_fusion = f32[6,16,30,96,192]{4,3,2,1,0:T(8,128)} "
            "fusion(f32[6,16,30,96,192]{4,3,2,1,0:T(8,128)} %p, f32[16,30,96]"
            "{2,1,0} %k), kind=kLoop")
    assert readers_h.is_step_op(step, hf)
    assert not readers_h.is_step_op(
        "%fusion.3 = bf16[16,8192,3840]{2,1,0:T(8,128)(2,1)} fusion(...)", hf)
    for name in (
            "%copy.1 = f32[30,4,64]{2,1,0:T(8,128)} copy(f32[4,30,64] %x)",
            "%fusion.2 = f32[4,1,30,64,96]{4,3,2,1,0} fusion(...)",
            "%fusion.9 = f32[30,64,192]{2,1,0} fusion(...)",
            "%custom-call.2 = f32[1,30,4,1,64,64]{4,5,3,2,1,0:T(8,128)S(1)} "
            "custom-call(f32[1,30,4,1,64,64]{5,4,3,2,1,0} %f), custom_call_"
            "target=\"InvertDiagBlocksLowerTriangular\"",
            "%fusion.4 = f32[1,30,4,64,288]{4,3,2,1,0} fusion(...)",
            "%fusion.5 = f32[1,30,96,192]{3,2,1,0} fusion(...)",
            "%t = (f32[1,30,1,64,96]{4,3,2,1,0}, f32[30]{0}) fusion(...)"):
        assert readers_h.is_scan_op(name, hf), name
    for name in (
            "%fusion.7 = f32[1,64,30,128]{3,2,1,0} fusion(...)",     # q heads
            "%fusion.8 = f32[1,30,1,64,8192]{4,3,2,1,0} fusion(...)",  # scores
            "%fusion.1 = bf16[1,256,30,192]{3,2,1,0} fusion(...)",
            "%convolution.3 = bf16[256,11008]{1,0} convolution(...)",
            "%fusion.6 = f32[256,30]{1,0} fusion(...)"):
        assert not readers_h.is_scan_op(name, hf), name
