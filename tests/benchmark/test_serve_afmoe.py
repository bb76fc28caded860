"""The ``serve_afmoe`` runner kind and what it brings (CPU only): a broken
timed path comes out not correct; each control of the plain reference is
over the rehearsal's limits; ``needs_afmoe`` counts the bytes that the seeded
weights have; the new readers' arithmetic on spans built by hand."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import manifest as mf
from benchmark import needs_afmoe, readers_afmoe
from benchmark import reference_afmoe as ref
from benchmark import traffic
from benchmark.harness import Run
from benchmark.runners import serve_afmoe

ROOT = mf.repo_root()
CELL = "trinity-mixed-backlog"


def rehearsal_run(seed: int = 5) -> Run:
    return Run(cell=mf.resolve_cell(CELL, ROOT), seed=seed, seconds=1.0,
               trace=False, rehearse=True, t_process=0.0)


def published_hf() -> dict:
    return serve_afmoe.hf_config(mf.resolve_cell(CELL, ROOT).config)


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
    """Four rows of 96 prompt tokens and 24 tokens the float32 reference
    decodes greedily (a sound program's stand-in: every gap 0), at the
    rehearsal size; contexts reach 120, past the window of 32."""
    seed = 2**31 + 4
    hf = serve_afmoe.hf_config(rehearsal_run().config())
    seqs = np.array(traffic.rng_for(seed, 0).integers(0, 512, (4, 128)),
                    np.int32)
    with jax.default_matmul_precision("highest"):
        top = ref.top_weights(seed, hf, "float32")
        for t in range(95, 119):
            x, _ = ref.afmoe_hidden(seed, hf, seqs, "float32")
            seqs[:, t + 1] = np.asarray(jnp.argmax(
                ref.afmoe_logits_at(top, hf, x[:, t]), -1))
    return seed, hf, seqs, [(95, 119)] * 4


def test_the_references_own_greedy_tokens_read_zero(greedy):
    seed, hf, seqs, spans = greedy
    gaps, std, sels = ref.afmoe_token_gaps(seed, hf, seqs, spans, "float32")
    assert gaps.shape == (4 * 24,) and std > 0.1
    assert (gaps / std).max() <= serve_afmoe.TOKEN_GAP_MAX_LIMIT["cpu"]
    assert (gaps / std).mean() <= serve_afmoe.TOKEN_GAP_MEAN_LIMIT["cpu"]
    # one dense layer, then four that select 2 of 8 experts a token
    assert [None if s is None else s.shape for s in sels] == [
        None] + [(4 * 128, 2)] * 4
    assert serve_afmoe.routed_otherwise(sels, sels, spans) == 0.0


@pytest.mark.parametrize("control", ["float8", "window_ignored",
                                     "weakest_dropped", "int8"])
def test_a_control_is_over_the_rehearsals_limits(greedy, control):
    """The reference with one thing wrong, judged at the served positions by
    the float32 reference: every control is far over both limits here
    (PERF.md section 2 says which are caught on the chip, at bfloat16's
    own distance from float32)."""
    seed, hf, seqs, spans = greedy
    gaps, std, sels = ref.afmoe_token_gaps(seed, hf, seqs, spans, "float32",
                                           control)
    assert (gaps / std).max() > 3 * serve_afmoe.TOKEN_GAP_MAX_LIMIT["cpu"]
    assert (gaps / std).mean() > 3 * serve_afmoe.TOKEN_GAP_MEAN_LIMIT["cpu"]
    if control == "int8":
        # it rounds less than float8 does
        low, _, _ = ref.afmoe_token_gaps(seed, hf, seqs, spans, "float32",
                                         "float8")
        assert gaps.mean() < low.mean()
        _, _, exact = ref.afmoe_token_gaps(seed, hf, seqs, spans, "float32")
        assert 0 < serve_afmoe.routed_otherwise(exact, sels, spans) < 0.5


def test_needs_count_the_bytes_the_seeded_weights_have():
    """At the rehearsal size against the arrays themselves; at the published
    widths against the issue's hand count (4.24 B parameters, 8.5 GB)."""
    hf = serve_afmoe.hf_config(rehearsal_run().config())
    for dtype, dense in (("bfloat16", 2), ("float32", 4)):
        arrays = [ref.top_weights(3, hf, dtype)] + [
            ref.layer_weights(3, i, hf, dtype) for i in range(5)]
        have = sum(a.nbytes for t in arrays for a in t.values())
        assert needs_afmoe.afmoe_param_bytes(hf, dense) == have
    big = published_hf()
    total = needs_afmoe.afmoe_param_bytes(big)
    expert = needs_afmoe.afmoe_expert_bytes(big)
    assert expert == 3 * 2048 * 1024 * 2
    embed = 200192 * 2048 * 2
    assert needs_afmoe.afmoe_fixed_bytes(big) == (
        total - embed - 4 * 128 * expert)
    assert 8.48e9 < total < 8.50e9
    assert needs_afmoe.afmoe_kv_bytes_per_token_layer(big) == 2048
    # a decode tick of 32 rows at depth 4,096 that hits 112 experts a layer:
    # expert kernels are three quarters of what it needs
    need = needs_afmoe.afmoe_call_bytes(big, 32, 4 * 112, 32 * 2048,
                                        32 * 4096)
    assert 0.70 < 4 * 112 * expert / need < 0.80
    assert needs_afmoe.afmoe_call_bytes(big, 32, 0, 0, 0) == (
        needs_afmoe.afmoe_fixed_bytes(big) + 32 * 2048 * 2
        + 32 * 5 * 2048)
    # 2 a weight a row over the ACTIVE weights of the cut: five attentions
    # (q, gate and o of 32 heads, k and v of 4), the dense MLP, four expert
    # layers (router, shared and 8 routed experts) and the head: 0.81 B
    active = (5 * 2048 * 128 * (3 * 32 + 2 * 4) + 3 * 2048 * 6144
              + 4 * (2048 * 128 + 9 * 3 * 2048 * 1024) + 2048 * 200192)
    assert needs_afmoe.afmoe_call_flops(big, 1, 0, 0) == 2 * active
    # attention: 4 x 32 heads x 128 a (query, key) pair inside a layer's reach
    assert (needs_afmoe.afmoe_call_flops(big, 1, 10, 100) - 2 * active
            == 4 * 32 * 128 * (4 * 10 + 100))


def test_the_expert_readers_arithmetic_on_spans_built_by_hand():
    run = rehearsal_run()
    run.window = (0.0, 10.0)
    run.raw = {"hf_config": published_hf(), "n_slots": 32}
    assert readers_afmoe.experts_hit_share(run) is None
    assert readers_afmoe.kv_cols_read_over_live(run) is None
    assert readers_afmoe.expert_device_ms(run) is None

    def tick(t, hit, most, live, live_window, read_window, read_full):
        return {"name": "serving.decode_step", "t0": t, "t1": t + 0.01,
                "args": {"slots": 30, "chain": 1, "nb": 256,
                         "expert_rows": 256.0, "experts_hit": hit,
                         "expert_rows_max": most, "kv_cols_live": live,
                         "kv_cols_live_window": live_window,
                         "kv_cols_read_window": read_window,
                         "kv_cols_read_full": read_full}}

    run.spans = [tick(1.0, 112.0, 6, 60000, 40000, 4 * 32 * 129 * 16,
                      32 * 256 * 16),
                 tick(2.0, 96.0, 8, 30000, 30000, 4 * 32 * 129 * 16,
                      32 * 256 * 16),
                 {"name": "serving.decode_step", "t0": 3.0, "t1": 3.1,
                  "args": {"slots": 8, "chain": 1}}]   # a GPT's tick
    assert readers_afmoe.experts_hit_share(run) == pytest.approx(
        100 * (112 + 96) / 2 / 128)
    assert readers_afmoe.expert_rows_max_over_mean(run) == pytest.approx(
        (6 / (256 / 112) + 8 / (256 / 96)) / 2)
    read = 2 * (4 * 32 * 129 * 16 + 32 * 256 * 16)
    live = 4 * (40000 + 30000) + (60000 + 30000)
    assert readers_afmoe.kv_cols_read_over_live(run) == pytest.approx(
        read / live)
    # the roofline shares need a device trace
    assert readers_afmoe.decode_roofline_share(run) is None
    assert readers_afmoe.expert_product_roofline_share(run) is None
    run.traced_window = (0.5, 2.5)
    run.device_kind = "TPU v5 lite"
    run.trace_summary = {"whole_programs": {
        "jit__paged_step(1)": {"seconds": 0.030, "count": 2}}}
    need = readers_afmoe._tick_needs(run)
    assert need["rows"] == 30 and need["experts_hit"] == 104
    hf = run.raw["hf_config"]
    want = needs_afmoe.afmoe_call_bytes(hf, 30, 4 * 104, 35000, 45000)
    assert readers_afmoe.decode_roofline_share(run) == pytest.approx(
        100 * want / 819e9 / 0.015)
    run.raw["_expert_device"] = (0.016, 2)
    assert readers_afmoe.expert_device_ms(run) == pytest.approx(8.0)
    product = 4 * needs_afmoe.afmoe_expert_product_bytes(hf, 256, 104)
    assert readers_afmoe.expert_product_roofline_share(run) == pytest.approx(
        100 * product / 819e9 / 0.008)
    assert readers_afmoe.expert_product_roofline_share(run) < 100
