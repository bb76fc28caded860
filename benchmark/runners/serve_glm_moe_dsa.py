"""Cells that serve a ``glm_moe_dsa`` model (zai-org GLM-5.2) through ``ContinuousGPTEngine.submit``.

The timed path, the load loop, ``tokens_per_s`` and the teardown are
``runners/serve.py``'s, the set-up's shape ``runners/serve_afmoe.py``'s: the
engine is the same engine. What is this family's: the model and its seeded
weights, made ONE LAYER AT A TIME from ``(seed, layer)``
(``benchmark/reference_glm_moe_dsa.py``), as ONE CHIP'S SHARE of a stated
deployment (the configuration's ``n_routed_experts`` experts held of the
router's ``router_experts``, from ``first_expert``; a slice of the
vocabulary, which the traffic draws its ids from), and the check: the float32
reference in its expanded form, given the same share, over prompt + served
tokens of ``check_requests`` of the requests the window finished, the served
token's gap below the reference's best in units of the reference logits'
standard deviation.
"""

from __future__ import annotations

import gc
import time

import numpy as np

# a checkout whose program lacks the family fails HERE, at once and before
# any device is touched (the parent of the PR that added this cell)
import sparkdl_tpu.models.glm_moe_dsa  # noqa: F401  isort: skip

from benchmark import reference_glm_moe_dsa as ref
from benchmark import traffic
from benchmark.harness import Comparison, Run, memory_peak, say
from benchmark.runners import serve
from benchmark.runners.serve import (  # noqa: F401  (the runner's surface)
    State,
    _probe_run,
    close_engine,
    end_to_end,
    n_requests,
    pick_checked,
    window,
)

#: Limits of the comparison with the float32 reference, in units of the
#: reference logits' standard deviation (PERF.md, section 2, gives the
#: readings each was set from, ALL ON THE CELL'S OWN MIX: the sound runs'
#: largest over eleven readings and the controls' smallest at the same
#: served positions, on the chip). Two selections that bfloat16 rounds
#: otherwise set a sound run's range here (the indexer's: the columns at
#: the 2,048th place of a row swap with their neighbours; the router's: a
#: sixteenth of the pairs are computed here), under a softmax drawn sharp so
#: that the selection shows at all: a sound run's MEAN gap is 0.014-0.042
#: (the bfloat16-operand reference, which has no fault, 0.014) and its
#: LARGEST 1.2-2.4. The mean separates the faulty controls it can (the
#: smallest 0.091, int8 operands; then 0.44-0.61: float8 operands, the ReLU
#: left out, every column attended); the largest gap catches the last three
#: (3.8, 5.0, 6.2). A shared layer given the LAST 2,048 columns reads 0.025
#: at these contexts (to 6.7 k), INSIDE the sound range: no limit can catch
#: it here, and PERF.md says so. "cpu" is the float32 rehearsal.
TOKEN_GAP_MAX_LIMIT = {"tpu": 3.3, "cpu": 1e-3}
TOKEN_GAP_MEAN_LIMIT = {"tpu": 0.065, "cpu": 1e-5}

#: the keys of the published ``config.json`` that the configuration's file
#: holds at its top level (the catalog's ``config``)
HF_KEYS = (
    "attention_bias", "ep_size", "first_k_dense_replace", "head_dim",
    "hidden_act", "hidden_size", "index_head_dim", "index_n_heads",
    "index_share_for_mtp_iteration", "index_skip_topk_offset", "index_topk",
    "index_topk_freq", "index_topk_pattern", "indexer_rope_interleave",
    "indexer_types", "intermediate_size", "kv_lora_rank",
    "max_position_embeddings", "mlp_layer_types", "model_type",
    "moe_intermediate_size", "moe_layer_freq", "n_group", "n_routed_experts",
    "n_shared_experts", "norm_topk_prob", "num_attention_heads",
    "num_experts_per_tok", "num_hidden_layers", "num_key_value_heads",
    "num_nextn_predict_layers", "q_lora_rank", "qk_head_dim",
    "qk_nope_head_dim", "qk_rope_head_dim", "rms_norm_eps", "rope_interleave",
    "rope_parameters", "routed_scaling_factor", "scoring_func",
    "tie_word_embeddings", "topk_group", "topk_method", "v_head_dim",
    "vocab_size")


def hf_config(cfg: dict) -> dict:
    """The model's own keys out of the configuration's file, as the
    reference takes them: ``n_routed_experts`` is the ROUTER's width (the
    file's ``router_experts``: the published count), and the experts held
    here (the file's ``n_routed_experts``, which ``reduced`` lists) go
    beside it as ``experts_held`` from ``first_expert``."""
    hf = {k: cfg[k] for k in HF_KEYS if k in cfg}
    hf["experts_held"] = int(cfg["n_routed_experts"])
    hf["n_routed_experts"] = int(cfg["router_experts"])
    hf["first_expert"] = int(cfg.get("first_expert", 0))
    ref.glm_sizes(hf)     # the lists, the depth and the share agree
    return hf


def program_variables(model, hf: dict, dtype: str, seed: int) -> dict:
    """The seeded weights of ``benchmark.reference_glm_moe_dsa`` laid into
    the program's own variables tree, each layer made on the device in one
    jitted call of its own (as ``serve_afmoe`` lays its family's)."""
    import jax
    import jax.numpy as jnp

    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]

    def nest(flat: dict, want: dict, where: str) -> dict:
        tree: dict = {}
        for name, a in flat.items():
            node, spec = tree, want
            *path, leaf = name.split(".")
            for p in path:
                node, spec = node.setdefault(p, {}), spec[p]
            if (a.shape, a.dtype) != (spec[leaf].shape, spec[leaf].dtype):
                raise ValueError(
                    f"{where}.{name}: seeded {a.shape} {a.dtype}, program "
                    f"wants {spec[leaf].shape} {spec[leaf].dtype}")
            node[leaf] = a
        if jax.tree.structure(tree) != jax.tree.structure(want):
            raise ValueError(f"{where}: the seeded leaves are not the "
                             "program's")
        return tree

    top = ref.top_weights(seed, hf, dtype)
    params = nest(top, {k: shapes[k] for k in top}, "top")
    for i in range(ref.glm_sizes(hf)["layers"]):
        params[f"layers_{i}"] = nest(
            jax.block_until_ready(ref.layer_weights(seed, i, hf, dtype)),
            shapes[f"layers_{i}"], f"layers_{i}")
    if set(params) != set(shapes):
        raise ValueError(f"program wants {sorted(shapes)}, seeded "
                         f"{sorted(params)}")
    return {"params": params}


def build(run: Run):
    """``(config, model)`` of the cell's configuration as run: the
    published keys through ``config_from_hf_glm_moe_dsa``, told the share
    this chip holds."""
    import jax.numpy as jnp

    from sparkdl_tpu.models.glm_moe_dsa import (
        GlmMoeDsaLMHeadModel,
        config_from_hf_glm_moe_dsa,
    )

    cfg = run.config()
    hf = hf_config(cfg)
    mcfg = config_from_hf_glm_moe_dsa(
        {k: hf[k] for k in HF_KEYS if k in hf},
        first_expert=hf["first_expert"], experts_held=hf["experts_held"],
        dtype=jnp.dtype(cfg["dtype"]))
    return mcfg, GlmMoeDsaLMHeadModel(mcfg)


def setup(run: Run) -> State:
    """Weights on the device from the seed, a layer at a time; the engine as
    the configuration builds it; every program the mix's lengths reach
    warmed (each prompt length once, alone, as ``runners/serve.py`` does)."""
    import jax

    from sparkdl_tpu.serving.continuous import ContinuousGPTEngine

    mix, cfg = run.sizes(), run.config()
    hf, dtype = hf_config(cfg), cfg["dtype"]
    mcfg, model = build(run)
    t0 = time.monotonic()
    variables = jax.block_until_ready(
        program_variables(model, hf, dtype, run.seed))
    n_params = sum(a.size for a in jax.tree.leaves(variables))
    n_bytes = sum(a.nbytes for a in jax.tree.leaves(variables))
    say(f"{n_params:,} seeded parameters ({n_params / 1e6:.1f} M, "
        f"{n_bytes / 1e9:.3f} GB; {ref.seeded_parameters(hf):,} and "
        f"{ref.seeded_weight_bytes(hf, dtype) / 1e9:.3f} GB by the "
        f"reference's tables), experts {hf['first_expert']}.."
        f"{hf['first_expert'] + hf['experts_held'] - 1} of the router's "
        f"{hf['n_routed_experts']} and {hf['vocab_size']} rows of the "
        f"vocabulary, on the device in {time.monotonic() - t0:.1f} s, a layer "
        f"at a time; device peak so far {memory_peak()} bytes")
    eng = ContinuousGPTEngine(mcfg, variables, **cfg["engine"])
    del variables
    kv = eng.snapshot()["kv"]
    # the pool's arrays as the DEVICE holds them, under the family's names
    arrays = "; ".join(
        f"{name} {a.dtype}{list(a.shape)} {a.nbytes / 1e9:.3f} GB, device "
        f"layout {getattr(a, 'format', None) and a.format.layout}"
        for name, a in sorted(eng._pool_kv.items()))
    say(f"pool {arrays}: {kv['bytes_per_token']} bytes a token over "
        f"{kv['blocks_total']} blocks of {kv['block_size']} "
        f"({kv['blocks_total'] * kv['block_size'] * kv['bytes_per_token'] / 1e9:.3f}"
        f" GB), no array by slot; device peak so far {memory_peak()} bytes")
    max_len = int(cfg["engine"]["max_len"])
    requests = traffic.serve_requests(mix, int(hf["vocab_size"]),
                                      n_requests(mix, run.seconds), run.seed)
    too_long = [r for r in requests if len(r.prompt) + r.n_out > max_len]
    if too_long:
        raise ValueError(f"{len(too_long)} requests of the mix exceed the "
                         f"engine's max_len {max_len}")
    state = State(eng, hf, dtype, max_len, requests)

    lens = sorted({len(r.prompt) for r in requests})
    kv_block = int(kv["block_size"])

    def depth(tokens: int) -> int:
        return 1 << (-(-tokens // kv_block) - 1).bit_length()

    warmed = {depth(n + k) for n in lens for k in (1, 2)}
    reached = {d for r in requests
               for d in (depth(len(r.prompt) + k) for k in range(1, r.n_out + 1))}
    if reached - warmed:
        raise ValueError(f"decode depths {sorted(reached - warmed)} (blocks) "
                         "are reached by the mix's contexts and by none of "
                         "its prompts: they would compile inside the window")
    rng = traffic.rng_for(run.seed, 7)
    t0 = time.monotonic()
    for n_tok in lens:
        ids = rng.integers(0, int(hf["vocab_size"]), n_tok, np.int32)
        out = eng.submit(ids, 2).result(timeout=1200)
        state.submitted_ok += 1
        if len(out) != 2:
            raise RuntimeError(f"warm-up request of {n_tok} tokens gave "
                               f"{len(out)} tokens, not 2")
    say(f"warmed {len(lens)} prompt lengths {lens[0]}..{lens[-1]} one at a "
        f"time in {time.monotonic() - t0:.1f} s; device peak so far "
        f"{memory_peak()} bytes")
    # What set-up leaves on the host (the traces and lowered modules of the
    # programs) lives as long as the process, and a full pass of Python's
    # collector walks every object with the interpreter held: every thread
    # stops (0.24 s measured in the lfm2_moe cell, once in 140 s, where two
    # runs of one seed read 5.8% apart before its runner did this: PERF.md
    # section 6, PR 42). A server does this once its programs are warm.
    t0 = time.monotonic()
    gc.collect()
    gc.freeze()
    say(f"collected, then froze {gc.get_freeze_count():,} objects out of the "
        f"collector's reach in {time.monotonic() - t0:.2f} s")
    return state


def teardown(state: State) -> None:
    serve.teardown(state)
    gc.unfreeze()


def checked_sequences(state: State, picked: "list[dict]"):
    """``(seqs [rows, width], spans)``: each picked request's prompt and
    served tokens, right-padded to one width (a multiple of the reference's
    query block), and the positions whose next token was served."""
    longest = max(len(state.requests[r["i"] % len(state.requests)].prompt)
                  + r["n_out"] for r in picked)
    block = ref.Q_BLOCK
    width = -(-longest // block) * block if longest > block else longest
    seqs = np.zeros((len(picked), width), np.int32)
    spans = []
    for row, r in enumerate(picked):
        p = state.requests[r["i"] % len(state.requests)].prompt
        seqs[row, :len(p)] = p
        seqs[row, len(p):len(p) + r["n_out"]] = r["tokens"]
        spans.append((len(p) - 1, len(p) - 1 + r["n_out"]))
    return seqs, spans


def token_gaps(state: State, picked: "list[dict]", seed: int,
               control: str = "f32", reference_hidden=None):
    """The reference's verdict on the served tokens of ``picked`` (or, with
    a ``control``, on the tokens that forward puts first at the served
    positions), in units of the reference logits' standard deviation."""
    seqs, spans = checked_sequences(state, picked)
    gaps, std = ref.glm_token_gaps(seed, state.hf, seqs, spans,
                                    state.dtype, control, reference_hidden)
    return gaps / std, std


def check(run: Run, state: State) -> "list[Comparison]":
    platform = "cpu" if run.rehearse else "tpu"
    snap = close_engine(state)
    unreconciled = (abs(snap["submitted"] - state.submitted_ok)
                    + abs(snap["completed"] + snap["failed"]
                          - state.submitted_ok))
    picked = pick_checked(state, run.seed, int(run.sizes()["check_requests"]))
    out = [
        Comparison("requests_failed_or_wrong_length", run.failed, 0),
        Comparison("snapshot_unreconciled_requests", unreconciled, 0),
    ]
    if not picked:
        return out + [Comparison("requests_compared", 0, 1,
                                 higher_is_worse=False)]
    gaps, std = token_gaps(state, picked, run.seed)
    say(f"compared {gaps.size} served tokens of {len(picked)} requests with "
        f"the float32 reference of the same share, expanded form, a layer at "
        f"a time (logit "
        f"std {std:.3f}); the served token was the reference's best at "
        f"{100.0 * float((gaps <= 0).mean()):.1f}% of positions")
    return out + [
        Comparison("token_gap_max_over_logit_std", float(gaps.max()),
                   TOKEN_GAP_MAX_LIMIT[platform]),
        Comparison("token_gap_mean_over_logit_std", float(gaps.mean()),
                   TOKEN_GAP_MEAN_LIMIT[platform]),
    ]


# -- readings for the limits (python -m benchmark.probe) --------------------------

def probe(cells, seeds, control_seeds, seconds, rehearse) -> None:
    """The readings the limits are set from, one JSON line a seed: a short
    window of the cell's own load on the engine as configured, then, with
    the engine closed, the float32 reference's verdict on what it served and
    on each control of ``reference_glm_moe_dsa.CONTROLS`` at the SAME
    positions, each beside the limits; a control that no limit fails is
    said to be so. ``control_seeds`` is unused: this family's controls are
    the reference's, not an engine option."""
    import json

    import jax

    del control_seeds
    platform = "cpu" if rehearse else "tpu"
    limits = (TOKEN_GAP_MAX_LIMIT[platform], TOKEN_GAP_MEAN_LIMIT[platform])
    for seed in seeds:
        for cell in cells:
            run = _probe_run(cell, seed, seconds, rehearse)
            state = setup(run)
            try:
                window(run, state)
                picked = pick_checked(state, seed,
                                      int(run.sizes()["check_requests"]))
                e2e = end_to_end(run, state)
                peak = memory_peak()
                close_engine(state)
            finally:
                teardown(state)
            line = {"reading": "correctness", "cell": cell.name,
                    "seed": seed, "requests": len(state.sample),
                    "failed": run.failed, "memory_peak_bytes": peak,
                    "limit_gap_max": limits[0], "limit_gap_mean": limits[1],
                    **e2e}
            # the float32 forward over the checked sequences, once: every
            # control is judged by it at the same positions
            with jax.default_matmul_precision("highest"):
                hidden, _ = ref.glm_hidden(
                    seed, state.hf, checked_sequences(state, picked)[0],
                    state.dtype)
            for control in ref.CONTROLS:
                t0 = time.monotonic()
                gaps, std = token_gaps(state, picked, seed, control, hidden)
                name = "program" if control == "f32" else control
                failed = [which for which, value, limit in (
                    ("max", float(gaps.max()), limits[0]),
                    ("mean", float(gaps.mean()), limits[1])) if value > limit]
                line.update({
                    name + "_gap_max": float(gaps.max()),
                    name + "_gap_mean": float(gaps.mean()),
                    name + "_not_best_share": float((gaps > 0).mean()),
                    name + "_fails": failed,
                    name + "_check_s": round(time.monotonic() - t0, 1)})
                if control == "f32":
                    line.update(tokens_compared=int(gaps.size),
                                logit_std=std)
                else:
                    say(f"control {control}: max {gaps.max():.4g} (limit "
                        f"{limits[0]}), mean {gaps.mean():.4g} (limit "
                        f"{limits[1]}): " + (
                            "fails " + " and ".join(failed) if failed
                            else "NO LIMIT FAILS IT"))
            print(json.dumps(line), flush=True)
