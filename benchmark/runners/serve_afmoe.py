"""Cells that serve an ``afmoe`` model (Arcee Trinity) through ``ContinuousGPTEngine.submit``.

The timed path, the load loop, ``tokens_per_s`` and the teardown are
``runners/serve.py``'s: the engine is the same engine. What is this
family's: the model and its seeded weights, made ONE LAYER AT A TIME from
``(seed, layer)`` (``benchmark/reference_afmoe.py``), and the check: the
float32 reference over prompt + served tokens of ``check_requests`` of the
requests the window finished, one layer made and let go at a time, the
served token's gap below the reference's best in units of the reference
logits' standard deviation.
"""

from __future__ import annotations

import time

import numpy as np

# a checkout whose program lacks the family fails HERE, at once and before
# any device is touched (the parent of the PR that added this cell)
import sparkdl_tpu.models.afmoe  # noqa: F401  isort: skip

from benchmark import reference_afmoe as ref
from benchmark import traffic
from benchmark.harness import Comparison, Run, memory_peak, say
from benchmark.runners.serve import (  # noqa: F401  (the runner's surface)
    State,
    _probe_run,
    close_engine,
    end_to_end,
    n_requests,
    pick_checked,
    teardown,
    window,
)

#: Limits of the comparison with the float32 reference, in units of the
#: reference logits' standard deviation (PERF.md, section 2, gives the
#: readings each was set from). A bfloat16 forward sends a tenth of the
#: (token, expert layer) pairs to another 8th expert than float32 does, so
#: a sound run's LARGEST gap is of the order of the logits' spread; the MEAN
#: is what separates the controls. "cpu" is the float32 rehearsal.
TOKEN_GAP_MAX_LIMIT = {"tpu": 3.0, "cpu": 1e-3}
TOKEN_GAP_MEAN_LIMIT = {"tpu": 0.05, "cpu": 1e-5}

#: the keys of the published ``config.json`` that the configuration's file
#: holds at its top level (the catalog's ``config``)
HF_KEYS = (
    "global_attn_every_n_layers", "head_dim", "hidden_act", "hidden_size",
    "intermediate_size", "layer_types", "max_position_embeddings",
    "model_type", "moe_intermediate_size", "mup_enabled", "n_group",
    "num_attention_heads", "num_dense_layers", "num_experts",
    "num_experts_per_tok", "num_hidden_layers", "num_key_value_heads",
    "num_shared_experts", "rms_norm_eps", "rope_scaling", "rope_theta",
    "route_norm", "route_scale", "score_func", "sliding_window",
    "tie_word_embeddings", "topk_group", "vocab_size")


def hf_config(cfg: dict) -> dict:
    """The model's own keys out of the configuration's file."""
    hf = {k: cfg[k] for k in HF_KEYS if k in cfg}
    if len(hf["layer_types"]) != int(hf["num_hidden_layers"]):
        raise ValueError("layer_types and num_hidden_layers disagree")
    return hf


def program_variables(model, hf: dict, dtype: str, seed: int) -> dict:
    """The seeded weights of ``benchmark.reference_afmoe`` laid into the
    program's own variables tree, each layer made on the device in one
    jitted call of its own: nothing is ever held in float32 beside it."""
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

    n_layers = ref.afmoe_sizes(hf)["layers"]
    top = ref.top_weights(seed, hf, dtype)
    params = nest(top, {k: shapes[k] for k in top}, "top")
    for i in range(n_layers):
        params[f"layers_{i}"] = nest(
            jax.block_until_ready(ref.layer_weights(seed, i, hf, dtype)),
            shapes[f"layers_{i}"], f"layers_{i}")
    if set(params) != set(shapes):
        raise ValueError(f"program wants {sorted(shapes)}, seeded "
                         f"{sorted(params)}")
    return {"params": params}


def build(run: Run):
    """``(config, model)`` of the cell's configuration as run."""
    import jax.numpy as jnp

    from sparkdl_tpu.models.afmoe import (
        AfmoeLMHeadModel,
        config_from_hf_afmoe,
    )

    cfg = run.config()
    acfg = config_from_hf_afmoe(hf_config(cfg), dtype=jnp.dtype(cfg["dtype"]))
    return acfg, AfmoeLMHeadModel(acfg)


def setup(run: Run) -> State:
    """Weights on the device from the seed, a layer at a time; the engine as
    the configuration builds it; every program the mix's lengths reach
    warmed (each prompt length once, alone, as ``runners/serve.py`` does)."""
    import jax

    from sparkdl_tpu.serving.continuous import ContinuousGPTEngine

    mix, cfg = run.sizes(), run.config()
    hf, dtype = hf_config(cfg), cfg["dtype"]
    acfg, model = build(run)
    t0 = time.monotonic()
    variables = jax.block_until_ready(
        program_variables(model, hf, dtype, run.seed))
    n_params = sum(a.size for a in jax.tree.leaves(variables))
    n_bytes = sum(a.nbytes for a in jax.tree.leaves(variables))
    say(f"{n_params / 1e9:.3f} B seeded parameters ({n_bytes / 1e9:.3f} GB) "
        f"on the device in {time.monotonic() - t0:.1f} s, a layer at a time; "
        f"device peak so far {memory_peak()} bytes")
    eng = ContinuousGPTEngine(acfg, variables, **cfg["engine"])
    del variables
    max_len = int(cfg["engine"]["max_len"])
    requests = traffic.serve_requests(mix, int(hf["vocab_size"]),
                                      n_requests(mix, run.seconds), run.seed)
    too_long = [r for r in requests if len(r.prompt) + r.n_out > max_len]
    if too_long:
        raise ValueError(f"{len(too_long)} requests of the mix exceed the "
                         f"engine's max_len {max_len}")
    state = State(eng, hf, dtype, max_len, requests)

    lens = sorted({len(r.prompt) for r in requests})
    kv_block = int(eng.snapshot()["kv"]["block_size"])

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
    return state


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
               control: str = "f32"):
    """The reference's verdict on the served tokens of ``picked`` (or, with
    a ``control``, on the tokens that forward puts first at the served
    positions), in units of the reference logits' standard deviation."""
    seqs, spans = checked_sequences(state, picked)
    gaps, std, sels = ref.afmoe_token_gaps(seed, state.hf, seqs, spans,
                                           state.dtype, control)
    return gaps / std, std, sels


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
    gaps, std, _ = token_gaps(state, picked, run.seed)
    say(f"compared {gaps.size} served tokens of {len(picked)} requests with "
        f"the float32 reference, a layer at a time (logit std {std:.3f}); "
        f"the served token was the reference's best at "
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
    on each control of ``reference_afmoe.CONTROLS`` at the SAME positions,
    and the share of checked (token, layer) pairs whose selected experts
    differ between the float32 reference and the bfloat16-operand forward.
    ``control_seeds`` is unused: this family's controls are the
    reference's, not an engine option."""
    import json

    del control_seeds
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
                    "failed": run.failed, "memory_peak_bytes": peak, **e2e}
            _, spans = checked_sequences(state, picked)
            ref_sels = None
            for control in ref.CONTROLS:
                t0 = time.monotonic()
                gaps, std, sels = token_gaps(state, picked, seed, control)
                name = "program" if control == "f32" else control
                line.update({
                    name + "_gap_max": float(gaps.max()),
                    name + "_gap_mean": float(gaps.mean()),
                    name + "_not_best_share": float((gaps > 0).mean()),
                    name + "_check_s": round(time.monotonic() - t0, 1)})
                if control == "f32":
                    ref_sels = sels
                    line.update(tokens_compared=int(gaps.size),
                                logit_std=std)
                elif control == "bfloat16":
                    line["bfloat16_routes_otherwise_share"] = (
                        routed_otherwise(ref_sels, sels, spans))
            print(json.dumps(line), flush=True)


def routed_otherwise(a, b, spans) -> float:
    """Share of the served positions' (token, expert layer) pairs whose SET
    of selected experts differs between two forwards' selections
    (``afmoe_hidden``'s ``sels``)."""
    differ = total = 0
    for la, lb in zip(a, b):
        if la is None:
            continue
        width = la.shape[0] // len(spans)
        for row, (s0, s1) in enumerate(spans):
            xa = np.sort(la[row * width + s0:row * width + s1], axis=-1)
            xb = np.sort(lb[row * width + s0:row * width + s1], axis=-1)
            differ += int((xa != xb).any(-1).sum())
            total += s1 - s0
    return differ / max(total, 1)
