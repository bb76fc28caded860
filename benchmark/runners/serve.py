"""Cells that serve a GPT through ``ContinuousGPTEngine.submit``.

The timed path is the live engine on its own thread, fed by one load
generator (this process's main thread): a closed loop of ``clients`` callers
or an open loop on a seeded schedule. Requests are timed from the instant
they were DUE to the instant their future resolved (stamped in the future's
callback). A lead-in of the same traffic runs before the window so that it
opens in steady state; the run drains what is outstanding after it.

``tokens_per_s`` is the output tokens GENERATED inside the window over its
length. The engine hands back whole completions, so a request that straddles
an edge of the window is credited the share of its tokens that its time
inside the window is of its time in the engine (submit to resolution). A
closed loop therefore runs as many clients as the engine has slots: a request
is then admitted at the tick after it is sent and its tokens come evenly
over its life. (Crediting whole completions at their end instead swings by a
tenth from run to run when a window finishes some thirty requests and eight
are under way at each edge.)
"""

from __future__ import annotations

import dataclasses
import gc
import queue
import time
import types

import numpy as np

from benchmark import reference, traffic
from benchmark.harness import Comparison, Run, memory_peak, say

#: Limits of the comparison with the float32 reference, in units of the
#: reference logits' standard deviation (PERF.md, section 2, gives the
#: readings each was set from). "cpu" is the float32 rehearsal.
TOKEN_GAP_MAX_LIMIT = {"tpu": 0.12, "cpu": 1e-3}
TOKEN_GAP_MEAN_LIMIT = {"tpu": 0.002, "cpu": 1e-5}


@dataclasses.dataclass
class State:
    eng: object
    hf: dict
    dtype: str
    max_len: int
    requests: "list[traffic.Request]"
    records: "list[dict]" = dataclasses.field(default_factory=list)
    sample: "list[dict]" = dataclasses.field(default_factory=list)
    submitted_ok: int = 0
    window_end: float = float("inf")
    closed: bool = False


def program_variables(model, hf: dict, dtype: str, seed: int):
    """The seeded weights of ``benchmark.reference`` laid into the program's
    own variables tree, made on the device in one jitted call."""
    import jax
    import jax.numpy as jnp

    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    make = reference.gpt2_weights_fn(hf, dtype)

    def fill(canon):
        def leaf(path, want):
            keys = [k.key for k in path if hasattr(k, "key")]
            if keys[0] != "params":
                raise KeyError(f"unexpected collection {keys[0]!r}")
            if keys[1].startswith("h_"):
                got = canon["blocks"][".".join(keys[2:])][int(keys[1][2:])]
            elif keys[1] == "ln_f":
                got = canon["ln_f"][keys[2]]
            else:
                got = canon[keys[1]]
            if got.shape != want.shape or got.dtype != want.dtype:
                raise ValueError(f"{keys}: seeded {got.shape} {got.dtype}, "
                                 f"program wants {want.shape} {want.dtype}")
            return got

        return jax.tree_util.tree_map_with_path(leaf, shapes)

    return jax.jit(lambda key: fill(make(key)))(reference.seed_key(seed))


def n_requests(mix: dict, seconds: float) -> int:
    """Requests to generate: an open loop's arrivals through lead-in and
    window and a block to spare; a closed loop's pool."""
    if mix["loop"] == "open":
        return int(float(mix["arrivals"]["rate_per_s"])
                   * (float(mix["lead_in_s"]) + seconds)) + int(mix["block"])
    return int(mix["pool"])


def setup(run: Run, engine_overrides: "dict | None" = None) -> State:
    """Weights on the device from the seed, the engine as the configuration
    builds it (``engine_overrides`` is the probe's: the control's lower
    precision), every program the mix's lengths reach warmed."""
    import jax
    import jax.numpy as jnp

    from sparkdl_tpu.models.gpt import GPTLMHeadModel, config_from_hf_gpt2
    from sparkdl_tpu.serving.continuous import ContinuousGPTEngine

    mix, cfg = run.sizes(), run.config()
    hf, dtype = cfg["hf_config"], cfg["dtype"]
    gcfg = dataclasses.replace(
        config_from_hf_gpt2(types.SimpleNamespace(**hf)),
        dtype=jnp.dtype(dtype))
    model = GPTLMHeadModel(gcfg)
    t0 = time.monotonic()
    variables = jax.block_until_ready(
        program_variables(model, hf, dtype, run.seed))
    n_params = sum(a.size for a in jax.tree.leaves(variables))
    say(f"{n_params / 1e9:.3f} B seeded parameters on the device in "
        f"{time.monotonic() - t0:.1f} s")
    eng = ContinuousGPTEngine(gcfg, variables,
                              **{**cfg["engine"], **(engine_overrides or {})})
    del variables
    max_len = int(cfg["engine"]["max_len"])
    requests = traffic.serve_requests(mix, int(hf["vocab_size"]),
                                      n_requests(mix, run.seconds), run.seed)
    too_long = [r for r in requests if len(r.prompt) + r.n_out > max_len]
    if too_long:
        raise ValueError(f"{len(too_long)} requests of the mix exceed the "
                         f"engine's max_len {max_len}")
    state = State(eng, hf, dtype, max_len, requests)

    # the shapes this cell's traffic uses and no others: sizes are
    # stratified, so a mix sends a fixed set of prompt lengths. Each is sent
    # once, alone, which loads its prefill programs and, between them, the
    # decode program of every depth the contexts reach (the engine compiles
    # one per power-of-two count of K/V blocks under the deepest live row)
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
        f"time in {time.monotonic() - t0:.1f} s")
    return state


def _submit(state: State, i: int, t_due: float, done: "queue.SimpleQueue"):
    r = state.requests[i]
    rec = {"i": i, "t_due": t_due, "n_out": r.n_out, "t_done": None,
           "error": None, "tokens": None}
    rec["t_submit"] = time.monotonic()
    try:
        fut = state.eng.submit(r.prompt, r.n_out)
    except Exception as e:  # refused at admission: a failed request
        rec["error"] = repr(e)
        rec["t_done"] = time.monotonic()
        state.records.append(rec)
        done.put(rec)
        return

    def resolved(f, rec=rec):
        rec["t_done"] = time.monotonic()
        exc = f.exception()
        if exc is not None:
            rec["error"] = repr(exc)
        else:
            rec["tokens"] = np.asarray(f.result())
        done.put(rec)

    state.submitted_ok += 1
    state.records.append(rec)
    fut.add_done_callback(resolved)


def window(run: Run, state: State) -> None:
    mix = run.sizes()
    lead_in, secs = float(mix["lead_in_s"]), run.seconds
    done: "queue.SimpleQueue" = queue.SimpleQueue()
    t_start = time.monotonic()
    t0_plan = t_start + lead_in
    t0 = None
    nxt = 0

    def poll(now):
        nonlocal t0
        if t0 is None and now >= t0_plan:
            t0 = run.open_window()

    if mix["loop"] == "closed":
        for _ in range(int(mix["clients"])):
            _submit(state, nxt, time.monotonic(), done)
            nxt += 1
        while True:
            now = time.monotonic()
            poll(now)
            if now >= t0_plan + secs:
                break
            try:
                done.get(timeout=0.02)
            except queue.Empty:
                continue
            _submit(state, nxt % len(state.requests), time.monotonic(), done)
            nxt += 1
    else:
        while nxt < len(state.requests):
            due = t_start + state.requests[nxt].due_s
            if due >= t0_plan + secs:
                break
            now = time.monotonic()
            poll(now)
            if now < due:
                time.sleep(min(due - now, 0.02))
                continue
            _submit(state, nxt, due, done)
            nxt += 1
        while time.monotonic() < t0_plan + secs:
            poll(time.monotonic())
            time.sleep(0.02)
    poll(time.monotonic())
    run.window = (t0, t0 + secs)
    state.window_end = t0 + secs
    deadline = time.monotonic() + 300
    while any(r["t_done"] is None for r in state.records):
        if time.monotonic() > deadline:
            break
        time.sleep(0.05)
    # the window's requests: a closed loop's are those in the engine at any
    # instant of it, an open loop's those due inside it
    in_window = ((lambda r: r["t_done"] >= t0 and r["t_submit"] <= t0 + secs)
                 if mix["loop"] == "closed"
                 else (lambda r: t0 <= r["t_due"] < t0 + secs))
    unresolved = [r for r in state.records if r["t_done"] is None]
    state.sample = [r for r in state.records
                    if r["t_done"] is None or in_window(r)]
    bad = [r for r in state.sample
           if r["error"] is not None or r["tokens"] is None
           or len(r["tokens"]) != r["n_out"]]
    run.attempted, run.failed = len(state.sample), len(bad)
    run.raw = {
        "n_slots": state.eng.n_slots, "hf_config": state.hf,
        # each of the window's requests as (sent, resolved, prompt tokens,
        # output tokens): what was in the engine at any instant
        "lives": [(r["t_submit"], r["t_done"],
                   len(state.requests[r["i"] % len(state.requests)].prompt),
                   r["n_out"]) for r in state.sample
                  if r["t_done"] is not None],
        "late_s": [r["t_submit"] - r["t_due"] for r in state.sample],
        "latency_s": [r["t_done"] - r["t_due"] for r in state.sample
                      if r["t_done"] is not None],
        "unresolved": len(unresolved),
    }


def end_to_end(run: Run, state: State) -> dict:
    """Every end-to-end number this kind of cell can give; the harness
    reports the ones the cell lists. A failed or refused request counts as
    the window's worst latency."""
    w0, w1 = run.window
    ok = [r for r in state.sample if r["error"] is None
          and r["tokens"] is not None and len(r["tokens"]) == r["n_out"]]
    inside = sum(
        r["n_out"] * max(0.0, min(r["t_done"], w1) - max(r["t_submit"], w0))
        / max(r["t_done"] - r["t_submit"], 1e-9) for r in ok)
    out = {"tokens_per_s": inside / (w1 - w0)}
    if ok:
        lat = [r["t_done"] - r["t_due"] for r in ok]
        per_tok = [1e3 * (r["t_done"] - r["t_due"]) / r["n_out"] for r in ok]
        n_bad = len(state.sample) - len(ok)
        lat += [max(lat)] * n_bad
        per_tok += [max(per_tok)] * n_bad
        out["request_p95_s"] = traffic.percentile(lat, 95)
        out["latency_per_token_p50_ms"] = traffic.percentile(per_tok, 50)
    return out


def pick_checked(state: State, seed: int, k: int) -> "list[dict]":
    """``k`` of the requests the window finished (of those it only began,
    where it finished none), drawn from the seed, with the longest among
    them."""
    finished = [r for r in state.sample if r["tokens"] is not None]
    ok = ([r for r in finished if r["t_done"] <= state.window_end]
          or finished)
    if not ok:
        return []
    longest = max(ok, key=lambda r: len(state.requests[r["i"] % len(
        state.requests)].prompt) + r["n_out"])
    rest = [r for r in ok if r is not longest]
    order = traffic.rng_for(seed, 8).permutation(len(rest))
    return [longest] + [rest[j] for j in order[:k - 1]]


def token_gaps(state: State, picked: "list[dict]", seed: int,
               precision: str = "f32") -> "tuple[np.ndarray, float]":
    """The reference's verdict on the served tokens of ``picked``: for each,
    how far below the reference's best its logit lay, in units of the
    reference logits' standard deviation."""
    seqs = np.zeros((len(picked), state.max_len), np.int32)
    spans = []
    for row, r in enumerate(picked):
        p = state.requests[r["i"] % len(state.requests)].prompt
        seqs[row, :len(p)] = p
        seqs[row, len(p):len(p) + r["n_out"]] = r["tokens"]
        spans.append((len(p) - 1, len(p) - 1 + r["n_out"]))
    weights = reference.gpt2_weights(seed, state.hf, state.dtype)
    gaps, std = reference.gpt2_token_gaps(weights, seqs, state.hf, precision)
    del weights
    # program and control alike are judged at the served positions only
    served = np.concatenate([gaps[row, a:b]
                             for row, (a, b) in enumerate(spans)])
    return served / std, std


def close_engine(state: State) -> dict:
    """Drain and close the engine, free its state, return its last snapshot."""
    snap = state.eng.snapshot()
    state.eng.close(drain=True)
    state.closed = True
    state.eng = None
    gc.collect()
    return snap


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
        f"the float32 reference (logit std {std:.3f}); the served token was "
        f"the reference's best at {100.0 * float((gaps <= 0).mean()):.1f}% "
        "of positions")
    return out + [
        Comparison("token_gap_max_over_logit_std", float(gaps.max()),
                   TOKEN_GAP_MAX_LIMIT[platform]),
        Comparison("token_gap_mean_over_logit_std", float(gaps.mean()),
                   TOKEN_GAP_MEAN_LIMIT[platform]),
    ]


def teardown(state: State) -> None:
    if not state.closed and state.eng is not None:
        state.eng.close(drain=False)
        state.closed = True


# -- readings for the limits (python -m benchmark.probe) --------------------------

def _probe_run(cell, seed: int, seconds: float, rehearse: bool) -> Run:
    return Run(cell=cell, seed=seed, seconds=seconds, trace=False,
               rehearse=rehearse, t_process=time.monotonic())


def _gap_summary(prefix: str, gaps: np.ndarray) -> dict:
    return {prefix + "_gap_max": float(gaps.max()),
            prefix + "_gap_mean": float(gaps.mean()),
            prefix + "_gap_p99": float(np.percentile(gaps, 99)),
            prefix + "_not_best_share": float((gaps > 0).mean())}


def probe(cells, seeds, control_seeds, seconds, rehearse) -> None:
    """The readings the limits are set from. Two engines, one after the
    other, each with its programs compiled once and each seed's weights
    swapped into ``engine.variables`` (an argument of its programs): first
    the CONTROL, the engine with the configuration's ``control`` arguments
    (its own lower-precision path) over ``control_seeds``, then the engine
    as configured over ``seeds``; each seed serves a short window of each
    cell's own load. With both closed and freed, the float32 reference
    judges what each window served, at the served positions; beside the
    configured engine's readings stand the int8 and float8 references'
    verdicts at the same positions."""
    import json

    import jax
    import jax.numpy as jnp

    from sparkdl_tpu.models.gpt import GPTLMHeadModel, config_from_hf_gpt2

    cfg = _probe_run(cells[0], (seeds or control_seeds)[0], seconds,
                     rehearse).config()
    model = GPTLMHeadModel(dataclasses.replace(
        config_from_hf_gpt2(types.SimpleNamespace(**cfg["hf_config"])),
        dtype=jnp.dtype(cfg["dtype"])))
    vocab = int(cfg["hf_config"]["vocab_size"])

    served = []
    for engine, overrides, its_seeds in (
            ("control", cfg["control"]["engine"], control_seeds),
            ("configured", None, seeds)):
        if not its_seeds:
            continue
        state = setup(_probe_run(cells[0], its_seeds[0], seconds, rehearse),
                      overrides)
        for seed in its_seeds:
            state.eng.variables = None
            state.eng.variables = jax.block_until_ready(program_variables(
                model, cfg["hf_config"], cfg["dtype"], seed))
            for cell in cells:
                run = _probe_run(cell, seed, seconds, rehearse)
                mix = run.sizes()
                state.requests = traffic.serve_requests(
                    mix, vocab, n_requests(mix, seconds), seed)
                state.records, state.sample = [], []
                window(run, state)
                picked = pick_checked(state, seed, int(mix["check_requests"]))
                served.append((engine, cell.name, seed, run.failed,
                               len(state.sample), end_to_end(run, state),
                               picked, list(state.requests)))
        close_engine(state)
        say(f"{engine} engine closed after {len(its_seeds)} seeds; device "
            f"peak {memory_peak()} bytes")
    for engine, name, seed, failed, n, e2e, picked, requests in served:
        state.requests = requests
        gaps, std = token_gaps(state, picked, seed)
        line = {"reading": "correctness", "engine": engine, "cell": name,
                "seed": seed, "requests": n, "failed": failed,
                "tokens_compared": int(gaps.size), "logit_std": std,
                **_gap_summary("program", gaps)}
        if engine == "configured":
            for precision in ("int8", "float8"):
                low, _ = token_gaps(state, picked, seed, precision)
                line.update(_gap_summary(precision + "_reference", low))
        print(json.dumps({**line, **e2e}), flush=True)
