"""Online serving benchmark: dynamic micro-batching vs batch-of-1.

Open-loop Poisson load (requests arrive on their own clock, regardless of
completions — the honest way to load a server; closed-loop hides queueing
collapse) replayed against two ServingEngines over the SAME jitted model:

- micro:   dynamic micro-batching up to BENCH_MAX_BATCH rows/dispatch
- batch-1: max_batch=1 — every request pays its own dispatch

Prints exactly ONE JSON line
  {"metric": ..., "value": N, "unit": "req/s", "vs_baseline": N}
value is the micro engine's completed throughput; vs_baseline is the
throughput ratio micro / batch-of-1 at the same offered load, with both
engines' p50/p95 latency recorded in the metric string so the ratio
can't hide a tail blowup. TPU only (runtime/chip.py ``require_tpu``);
under an exported ``JAX_PLATFORMS=cpu`` it is the run-tests.sh contract
smoke — the sections and counts are checked, the metric is labelled as
not a device measurement, and no ``vs_baseline`` is printed.

The model is a 4-layer MLP sized (BENCH_FEATURES=768) so the batch-of-1
path sits in the weight-bound regime every real serving model lives in:
one dispatch streams the full weight matrices through the core for ONE
row, so 32 coalesced rows cost barely more than 1 — the regime where
dynamic batching pays (and the regime a GPT decode step is always in:
per-token cost is dominated by reading the weights + KV cache).

Env knobs: BENCH_REQUESTS (default 512), BENCH_MAX_BATCH (32),
BENCH_RATE (req/s; default auto = 4x the measured batch-of-1 capacity),
BENCH_FEATURES (768), BENCH_LAYERS (4), BENCH_REPLICAS (default 1:
the micro engine serves through a ReplicaPool of N executors — on a
CPU harness N virtual devices are forced so the routing/overlap is
real, "simulated replicas" in ISSUE 4's sense).

The JSON line also carries `fetch_wait_share` (host seconds blocked
collecting async D2H results / measured wall — the number the async
completion layer exists to shrink) and `replica_count` next to
`dispatch_count`/`overhead_share`.

Continuous-GPT section (ISSUE 10): a shared-prefix chat workload is
replayed through the continuous engine (block pool + prefix cache +
chunked prefill).
`BENCH_PREFIX_SHARE` (default 0.75) sets the fraction of each prompt
that is a common prefix, `BENCH_PROMPT_LEN` (96) the prompt length,
`BENCH_GPT_REQUESTS` (32; 0 disables the section). The JSON line gains
`prefix_hit_rate` / `kv_blocks_used` / `prefill_chunks` and a
`kv_paged` block (wall + prefill-time share): the prefill share drops
with the hit rate.

Speculative decoding + quantized KV section (ISSUE 12): a DECODE-HEAVY
shared-prefix workload (short prompts, `BENCH_SPEC_NEW`=96 generated
tokens) replayed at `spec_k=BENCH_SPEC_K` (default 4; 0 disables) vs
k=1 over the same weights — greedy tokens must stay bitwise — emitting
`spec.acceptance_rate`, `spec.tokens_per_dispatch`, per-mode tokens/sec
and the speedup; and the same workload over a `BENCH_KV_DTYPE`
(default int8; empty disables) pool vs fp32, emitting the
`capacity_ratio_vs_fp32` (asserted >= 2 for int8: the same pool bytes
hold 2x+ the live tokens) and the `token_agreement_vs_fp32` parity
delta the compression trades.

Multi-host fabric section (ISSUE 14): the shared-prefix chat workload
over `BENCH_HOSTS` (default 2; <2 disables) in-process GPT hosts behind
the cache-aware Router vs round-robin — seed the prefix groups, refresh
the digests, replay 3 follower rounds (medians of 3), emitting
`fabric_hosts`, `fabric_hit_rate_routed` / `fabric_hit_rate_rr` (the
headline gap: affinity routes followers to the host whose radix cache
holds their prefix), `fabric_p95_ms_routed` / `fabric_p95_ms_rr`, and
the full `fabric` block (`BENCH_FABRIC_GROUPS`=4 prefix groups,
`BENCH_FABRIC_REQUESTS`=16 followers/round).

Scaled router tier section (ISSUE 19): `BENCH_ROUTERS=N` (>=1
enables) reruns the fleet workload behind a RouterGroup at N=1 and
N=max(2, N) routers over one 2-host fleet, plus a wholesale-forced
control arm at the same refresh cadence. Emits
`router_agreement_rate` (cross-router preferred-host agreement),
`digest_delta_bytes_per_s` vs `digest_wholesale_bytes_per_s` (plus
the per-refresh ratio `delta_vs_wholesale_per_refresh`),
`router_p95_ms_n1` / `router_p95_ms_n`, `hit_rate_n_vs_1`, and the
full `router_tier` block.

Sequence-parallel long-context section (ISSUE 13): the same long
prompt (`BENCH_LONG_PROMPT_LEN`=3072) prefilled at sp=1 vs
sp=`BENCH_SP` (default 2; <2 disables) over forced CPU devices,
spatial chunks of `BENCH_SP_CHUNK`=1024 tokens, medians of 3 with
FRESH prompts per round (a repeated prompt would prefix-hit and
measure a no-op). Emits `sp_axis`, `prefill_shard_tokens`,
`sp_prefill_speedup` and the `sp_prefill` block; greedy tokens must
stay bitwise across sp. Keep the prompt long: below ~1k tokens the
per-chunk fixed costs beat the q-split and sp measures a LOSS
(PERF.md).

Elastic autoscaling section (ISSUE 15): `BENCH_AUTOSCALE=1` drives a
1-replica MLP fleet through a stepped open-loop pattern (low -> 4x the
calibrated single-replica capacity -> low) with an AutoScaler reading
queue depth and actuating the drain-safe replica scale path. Emits
`scale_events`, `replica_trajectory` (replica count at every controller
tick), `slo_burn_before_after` (rolling burn at burst end vs after
recovery, window `BENCH_AUTOSCALE_SLO_WINDOW`=3 s), and the full
`autoscale` block (`BENCH_AUTOSCALE_REQUESTS`=192 burst requests,
`BENCH_AUTOSCALE_MAX`=3 replicas).

Tiered KV parking section (ISSUE 18): `BENCH_PARK_DEPTH` (e.g.
"8,16"; empty disables) sets the idle-session counts to sweep. Each
depth runs that many turn-1 conversations through an engine whose
device pool (`BENCH_PARK_KV_BLOCKS`=20) holds ~2 live sessions while
the host tier (`BENCH_PARK_HOST_BLOCKS`=512) parks the rest, then
times every turn-2 resume (restore parked blocks + tail prefill) vs
the same transcript re-prefilled cold by an untiered engine. Emits
`turn_resume_p50_ms`, `reprefill_p50_ms`, `parked_sessions_per_chip`
and the `park` block (per-depth tier occupancy, unparks, fallbacks).
"""

import json
import os
import sys
import time

import numpy as np


def _replay(engine, arrivals):
    """Open-loop: submit request i at absolute time arrivals[i]; wait for
    everything; return (completed, duration_s, p50_ms, p95_ms)."""
    rng = np.random.default_rng(1)
    dim = int(os.environ.get("BENCH_FEATURES", "768"))
    payloads = [
        {"x": rng.standard_normal(dim).astype(np.float32)}
        for _ in range(len(arrivals))
    ]
    futs = []
    t0 = time.perf_counter()
    for t_arr, payload in zip(arrivals, payloads):
        lag = t0 + t_arr - time.perf_counter()
        if lag > 0:
            time.sleep(lag)
        futs.append(engine.submit(payload))
    for f in futs:
        f.result(timeout=120)
    duration = time.perf_counter() - t0
    snap = engine.snapshot()
    pcts = snap["latency_s"]
    return (snap["completed"], duration,
            1e3 * pcts["p50"], 1e3 * pcts["p95"],
            snap["batch_occupancy_pct"])


def _gpt_paged_section():
    """Shared-prefix chat workload through the continuous GPT engine:
    returns the `kv_paged` block
    plus the headline prefix/pool fields (None when disabled)."""
    import jax
    import jax.numpy as jnp

    from sparkdl_tpu.models.gpt import GPTConfig, GPTLMHeadModel
    from sparkdl_tpu.serving import ContinuousGPTEngine

    n_req = int(os.environ.get("BENCH_GPT_REQUESTS", "32"))
    if n_req < 1:
        return None
    share = float(os.environ.get("BENCH_PREFIX_SHARE", "0.75"))
    if not 0.0 <= share <= 1.0:
        raise ValueError(f"BENCH_PREFIX_SHARE must be in [0,1]: {share}")
    plen = int(os.environ.get("BENCH_PROMPT_LEN", "96"))
    max_new = 16
    max_len = plen + max_new
    cfg = GPTConfig(
        vocab_size=256, hidden_size=128, num_layers=3, num_heads=4,
        intermediate_size=256, max_seq_len=4 * max_len,
    )
    model = GPTLMHeadModel(cfg)
    variables = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))

    rng = np.random.default_rng(5)
    n_shared = int(round(share * plen))
    prefix = rng.integers(1, cfg.vocab_size, n_shared).tolist()
    prompts = [
        prefix + rng.integers(1, cfg.vocab_size, plen - n_shared).tolist()
        for _ in range(n_req)
    ]
    warm = rng.integers(1, cfg.vocab_size, plen).tolist()
    # same shape as a measured request (shared prefix + fresh suffix)
    # but NOT in the measured set: warms the suffix-width chunk program
    warm_suffix = (prefix
                   + rng.integers(1, cfg.vocab_size,
                                  plen - n_shared).tolist())

    def run():
        eng = ContinuousGPTEngine(
            cfg, variables, n_slots=8, max_len=max_len,
            kv_block_size=8,
            # engine-default prefill budget (256: above these prompts,
            # so a cold admission is one bucketed chunk and a
            # prefix-hit suffix is one fused dispatch); pin via
            # SPARKDL_TPU_PREFILL_CHUNK to study throttled admission
            prefill_chunk=None,
            idle_wait_s=0.0005,
        )
        # compile warmup, then seed requests from the workload:
        # steady-state shared-prompt serving is what is being measured,
        # and in steady state the shared prefix IS cached — the cold
        # first requests are warmup, like the compile. The seeds cover
        # every bucketed chunk program the replay will hit (cold-width,
        # suffix-width, full-hit-width).
        eng.submit(warm, 2).result(timeout=120)
        eng.submit(prompts[0], max_new).result(timeout=120)
        eng.submit(warm_suffix, max_new).result(timeout=120)
        eng.submit(prompts[0], max_new).result(timeout=120)
        snap0 = eng.snapshot()
        kv0 = snap0["kv"]
        t0 = time.perf_counter()
        futs = [eng.submit(p, max_new) for p in prompts]
        for f in futs:
            f.result(timeout=120)
        wall = time.perf_counter() - t0
        snap = eng.snapshot()
        kv = snap["kv"]
        eng.close()
        prefill_s = snap["prefill_seconds"] - snap0["prefill_seconds"]
        hits = kv["prefix_hits"] - kv0["prefix_hits"]
        misses = kv["prefix_misses"] - kv0["prefix_misses"]
        return {
            "wall_s": round(wall, 4),
            "req_s": round(len(prompts) / wall, 2),
            "prefill_seconds": round(prefill_s, 4),
            "prefill_share": round(prefill_s / wall, 4),
            "prefix_hit_rate": (
                round(hits / (hits + misses), 4)
                if hits + misses else None),
            "kv_blocks_used_peak": kv["blocks_used_peak"],
            "prefill_chunks": kv["prefill_chunks"],
        }

    return {
        "prefix_share": share,
        "prompt_len": plen,
        "requests": n_req,
        "paged": run(),
    }


def _gpt_sp_section():
    """Long-context prefill: the SAME long prompt prefilled through the
    continuous engine at sp=1 vs sp=BENCH_SP (sequence-parallel spatial
    chunks over forced CPU devices), medians of 3 (CPU numbers are
    bimodal — PERF.md). Greedy tokens must stay bitwise; the headline
    is prefill seconds and the sp speedup. None when BENCH_SP < 2."""
    import jax
    import jax.numpy as jnp

    from sparkdl_tpu.models.gpt import GPTConfig, GPTLMHeadModel
    from sparkdl_tpu.serving import ContinuousGPTEngine

    sp = int(os.environ.get("BENCH_SP", "2"))
    if sp < 2:
        return None
    if len(jax.devices()) < sp:
        # An ambient XLA_FLAGS device pin below sp (main() never
        # overrides a caller's pin) must not kill the whole bench —
        # the driver contract is ONE JSON line no matter what. Skip
        # the section; sp fields ride as None.
        print(
            f"bench_serving: skipping sp section (BENCH_SP={sp} needs "
            f"{sp} devices, have {len(jax.devices())}; force them with "
            "XLA_FLAGS=--xla_force_host_platform_device_count)",
            file=sys.stderr)
        return None
    plen = int(os.environ.get("BENCH_LONG_PROMPT_LEN", "3072"))
    n_req = int(os.environ.get("BENCH_SP_REQUESTS", "1"))
    max_new = 4  # prefill-dominated on purpose: decode is not the story
    max_len = plen + max_new
    # GENUINELY long context: the q-split only beats the per-chunk
    # fixed costs (staged-head gather, scatter, collectives) once the
    # O(L^2) score block dominates — at 768 tokens sp=2 measured
    # 0.85-0.95x (a LOSS; PERF.md), at 3072 it wins 2.3x. Keep the
    # prompt long and the chunks wide when studying sp.
    cfg = GPTConfig(
        vocab_size=512, hidden_size=256, num_layers=4, num_heads=8,
        intermediate_size=512, max_seq_len=4 * max_len,
    )
    model = GPTLMHeadModel(cfg)
    variables = model.init(
        jax.random.PRNGKey(2), jnp.zeros((1, 8), jnp.int32))
    rng = np.random.default_rng(17)
    # fresh prompts per measurement round: a repeated prompt would
    # full-prompt-HIT the prefix cache and measure a no-op prefill
    rounds = [[rng.integers(1, cfg.vocab_size, plen).tolist()
               for _ in range(n_req)] for _ in range(3)]
    warm = rng.integers(1, cfg.vocab_size, plen).tolist()
    chunk = int(os.environ.get("BENCH_SP_CHUNK", "1024"))

    def run(sp_axis):
        eng = ContinuousGPTEngine(
            cfg, variables, n_slots=2, max_len=max_len,
            kv_block_size=32, prefill_chunk=chunk,
            sp=(None if sp_axis < 2 else sp_axis),
            idle_wait_s=0.0005,
        )
        eng.submit(warm, 2).result(timeout=600)  # compile warmup
        walls, outs = [], []
        for prompts in rounds:  # medians of 3: CPU numbers are bimodal
            snap0 = eng.snapshot()
            futs = [eng.submit(p, max_new) for p in prompts]
            outs.extend(np.asarray(f.result(timeout=600)) for f in futs)
            walls.append(eng.snapshot()["prefill_seconds"]
                         - snap0["prefill_seconds"])
        eng.close()
        return outs, float(np.median(walls))

    outs1, pf1 = run(1)
    outs_sp, pf_sp = run(sp)
    bitwise = all(np.array_equal(a, b) for a, b in zip(outs1, outs_sp))
    return {
        "sp_axis": sp,
        "prompt_len": plen,
        "requests": n_req,
        "prefill_chunk": chunk,
        # tokens of each chunk one chip holds under sp (the shard grain)
        "prefill_shard_tokens": min(chunk, plen) // sp,
        "sp1_prefill_seconds": round(pf1, 4),
        "sp_prefill_seconds": round(pf_sp, 4),
        "sp_prefill_speedup": round(pf1 / pf_sp, 4) if pf_sp else None,
        "prefill_tokens_per_s_sp1":
            round(n_req * plen / pf1, 1) if pf1 else None,
        "prefill_tokens_per_s_sp":
            round(n_req * plen / pf_sp, 1) if pf_sp else None,
        "sp_bitwise_vs_sp1": bitwise,
    }


def _gpt_spec_section():
    """Decode-heavy workload: speculative verify (spec_k) vs plain k=1,
    then a quantized pool vs fp32 — the two raw per-request speed/memory
    levers of ISSUE 12 (None when disabled via BENCH_SPEC_K=0)."""
    import jax
    import jax.numpy as jnp

    from sparkdl_tpu.models.gpt import GPTConfig, GPTLMHeadModel
    from sparkdl_tpu.runtime.dispatch import dispatch_count
    from sparkdl_tpu.serving import ContinuousGPTEngine
    from sparkdl_tpu.serving.kv_blocks import kv_capacity_ratio

    spec_k = int(os.environ.get("BENCH_SPEC_K", "4"))
    if spec_k < 2:
        return None
    kv_dtype = os.environ.get("BENCH_KV_DTYPE", "int8")
    n_req = int(os.environ.get("BENCH_SPEC_REQUESTS", "4"))
    max_new = int(os.environ.get("BENCH_SPEC_NEW", "96"))
    plen = 16
    max_len = plen + max_new
    # sized into the WEIGHT-BOUND regime every real serving model lives
    # in (the same argument as the MLP section above): a decode step
    # streams ~50MB of weights for a handful of rows, so a width-k
    # verify costs barely more than width-1 (measured 1.17x at L=4
    # here) and every accepted draft is nearly free. A compute-bound
    # toy (hidden 128) inverts the economics — L=k FLOPs dominate —
    # and speculation rightly loses there.
    cfg = GPTConfig(
        vocab_size=512, hidden_size=512, num_layers=4, num_heads=8,
        intermediate_size=2048, max_seq_len=4 * max_len,
    )
    model = GPTLMHeadModel(cfg)
    variables = model.init(
        jax.random.PRNGKey(1), jnp.zeros((1, 8), jnp.int32))
    rng = np.random.default_rng(11)
    # acceptance-friendly decode-heavy traffic: shared prompt scaffold +
    # tiny fresh suffix, long generation (greedy decode settles into
    # repeating spans the n-gram proposer then predicts)
    prefix = rng.integers(1, cfg.vocab_size, plen - 4).tolist()
    prompts = [
        prefix + rng.integers(1, cfg.vocab_size, 4).tolist()
        for _ in range(n_req)
    ]
    warm = (rng.integers(1, cfg.vocab_size, plen - 4).tolist()
            + rng.integers(1, cfg.vocab_size, 4).tolist())

    def run(k, dtype="fp32"):
        eng = ContinuousGPTEngine(
            cfg, variables, n_slots=2, max_len=max_len,
            kv_block_size=16, prefill_chunk=None,
            spec_k=(None if k < 2 else k), kv_dtype=dtype,
            idle_wait_s=0.0005,
        )
        # warmup covers compile: the chunk widths, every verify width
        # the budget bound will shrink through, and the k=1 tail
        eng.submit(warm, max_new).result(timeout=300)
        eng.submit(prompts[0], max_new).result(timeout=300)
        d0 = dispatch_count("decode")
        t0 = time.perf_counter()
        futs = [eng.submit(p, max_new) for p in prompts]
        outs = [np.asarray(f.result(timeout=300)) for f in futs]
        wall = time.perf_counter() - t0
        dispatches = dispatch_count("decode") - d0
        snap = eng.snapshot()
        eng.close()
        tokens = int(sum(len(o) for o in outs))
        return {
            "outs": outs,
            "stats": {
                "wall_s": round(wall, 4),
                "tokens": tokens,
                "tokens_per_s": round(tokens / wall, 2),
                "decode_dispatches": dispatches,
                "spec": snap["spec"],
            },
        }

    base = run(1)
    spec = run(spec_k)
    bitwise = all(np.array_equal(a, b)
                  for a, b in zip(base["outs"], spec["outs"]))
    out = {
        "spec_k": spec_k,
        "requests": n_req,
        "max_new_tokens": max_new,
        "k1": base["stats"],
        "spec": spec["stats"],
        "spec_bitwise_vs_k1": bitwise,
        "acceptance_rate": (spec["stats"]["spec"] or {}).get(
            "acceptance_rate"),
        "tokens_per_dispatch": (spec["stats"]["spec"] or {}).get(
            "tokens_per_dispatch"),
        "tokens_per_s_speedup": round(
            spec["stats"]["tokens_per_s"]
            / base["stats"]["tokens_per_s"], 4),
    }
    if kv_dtype and kv_dtype != "fp32":
        quant = run(1, dtype=kv_dtype)
        ratio = kv_capacity_ratio(cfg, kv_dtype)
        if kv_dtype == "int8":
            # the ISSUE 12 acceptance bar, asserted where it is measured
            assert ratio >= 2.0, ratio
        agree = total = 0
        for a, b in zip(base["outs"], quant["outs"]):
            n = min(len(a), len(b))
            agree += int((a[:n] == b[:n]).sum())
            total += n
        out["kv_quant"] = {
            "dtype": kv_dtype,
            "capacity_ratio_vs_fp32": round(ratio, 4),
            "token_agreement_vs_fp32": (
                round(agree / total, 4) if total else None),
            "tokens_per_s": quant["stats"]["tokens_per_s"],
        }
    return out


def _gpt_park_section():
    """Tiered KV session parking (ISSUE 18): multi-turn chat where the
    device pool holds only a handful of live sessions, but the host
    tier parks every idle conversation's KV blocks. For each depth in
    ``BENCH_PARK_DEPTH`` (comma-separated session counts; empty
    disables): run depth turn-1 conversations, park them all, then
    time each turn-2 resume (parked path restored via one H2D install
    per block + tail prefill) against the same turn-2 served by an
    untiered engine that must re-prefill the whole transcript. Emits
    ``turn_resume_p50_ms`` vs ``reprefill_p50_ms`` per depth,
    ``parked_sessions_per_chip``, and the tier occupancy — the
    capacity story is ``parked_sessions / device_live_sessions``
    (sessions held per chip vs what device HBM alone could keep)."""
    spec = os.environ.get("BENCH_PARK_DEPTH", "").strip()
    if not spec:
        return None
    depths = [int(d) for d in spec.split(",") if d.strip()]
    if not depths:
        return None
    import jax
    import jax.numpy as jnp

    from sparkdl_tpu.models.gpt import GPTConfig, GPTLMHeadModel
    from sparkdl_tpu.serving import ContinuousGPTEngine

    plen = int(os.environ.get("BENCH_PARK_PROMPT_LEN", "320"))
    turn1_new = 8
    turn2_new = 4
    kv_bs = 32
    # device pool sized for ~2 live sessions; the host tier is where
    # the fleet actually lives
    kv_blocks = int(os.environ.get("BENCH_PARK_KV_BLOCKS", "24"))
    host_blocks = int(os.environ.get("BENCH_PARK_HOST_BLOCKS", "512"))
    max_len = plen + turn1_new + turn2_new + kv_bs
    cfg = GPTConfig(
        vocab_size=256, hidden_size=128, num_layers=3, num_heads=4,
        intermediate_size=256, max_seq_len=2 * max_len,
    )
    model = GPTLMHeadModel(cfg)
    variables = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    # worst-case blocks one session pins while decoding turn 2
    per_session = -(-(plen + turn1_new + turn2_new + 1) // kv_bs)
    device_live = kv_blocks // per_session
    kw = dict(n_slots=2, max_len=max_len,
              kv_block_size=kv_bs, idle_wait_s=0.0005)

    def pctl(xs, q):
        return round(float(np.percentile(np.asarray(xs), q)) * 1e3, 2)

    out = {
        "prompt_len": plen,
        "kv_blocks": kv_blocks,
        "kv_block_size": kv_bs,
        "host_kv_blocks": host_blocks,
        "device_live_sessions": device_live,
        "depths": [],
    }
    for depth in depths:
        rng = np.random.default_rng(18 + depth)
        prompts = [rng.integers(1, cfg.vocab_size, plen).tolist()
                   for _ in range(depth)]

        # -- resume arm: turn 1 fills the host tier, turn 2 restores
        eng = ContinuousGPTEngine(cfg, variables,
                                  kv_blocks=kv_blocks,
                                  host_kv_blocks=host_blocks, **kw)
        # warm cycle: one throwaway conversation parked and resumed so
        # the park/unpark install programs and the suffix-width chunk
        # compile OUTSIDE the measured resumes
        wp = rng.integers(1, cfg.vocab_size, plen).tolist()
        wr = eng.submit(wp, turn1_new).result(timeout=600).tolist()
        eng.park_cold()
        eng.submit(wp + wr + [5], turn2_new).result(timeout=600)
        futs = [eng.submit(p, turn1_new) for p in prompts]
        replies = [f.result(timeout=600).tolist() for f in futs]
        eng.park_cold()
        cap = eng.capacity()
        parked_sessions = cap["kv_parked_sessions"]
        parked_blocks = cap["kv_parked_blocks"]
        tiers_peak = eng._kv_snapshot()["tiers"]
        turn2 = [p + r + [5] for p, r in zip(prompts, replies)]
        lat_resume = []
        for t in turn2:
            t0 = time.perf_counter()
            eng.submit(t, turn2_new).result(timeout=600)
            lat_resume.append(time.perf_counter() - t0)
        tiers = eng._kv_snapshot()["tiers"]
        eng.close()

        # -- re-prefill arm: the same turn-2 transcripts served cold
        # by an untiered engine (what losing the session's KV costs)
        base = ContinuousGPTEngine(cfg, variables,
                                   kv_blocks=kv_blocks, **kw)
        base.submit(turn2[0][:plen], 2).result(timeout=600)  # warm
        lat_cold = []
        for t in turn2:
            t0 = time.perf_counter()
            base.submit(t, turn2_new).result(timeout=600)
            lat_cold.append(time.perf_counter() - t0)
        base.close()

        out["depths"].append({
            "depth": depth,
            "turn_resume_p50_ms": pctl(lat_resume, 50),
            "turn_resume_p95_ms": pctl(lat_resume, 95),
            "reprefill_p50_ms": pctl(lat_cold, 50),
            "reprefill_p95_ms": pctl(lat_cold, 95),
            "resume_speedup_p50": (
                round(pctl(lat_cold, 50) / pctl(lat_resume, 50), 4)
                if pctl(lat_resume, 50) else None),
            "parked_sessions": parked_sessions,
            "parked_sessions_per_chip": parked_sessions,
            "parked_blocks": parked_blocks,
            "tier_blocks": {
                "host": tiers_peak.get("host_blocks"),
                "disk": tiers_peak.get("disk_blocks"),
            },
            "unparks": tiers.get("unparks"),
            "park_fallbacks": tiers.get("park_fallbacks"),
        })
    return out


def _fabric_section():
    """Multi-host fabric (ISSUE 14): the SAME shared-prefix chat
    workload routed over BENCH_HOSTS in-process GPT hosts by the
    cache-aware router vs blind round-robin. Per policy: seed each
    prefix group once, refresh the digests, then replay 3 follower
    rounds (fresh suffixes — steady-state serving, medians of 3: CPU
    numbers are bimodal) and read the fleet prefix hit rate off the
    engines plus client-side p95. The headline is the hit-rate gap:
    affinity lands followers where their prefix blocks live, so the
    2.2-2.5x cheaper prefill (PERF.md) actually happens; round-robin
    scatters them and the fleet re-prefills what another host already
    cached. None when BENCH_HOSTS < 2."""
    import jax
    import jax.numpy as jnp

    from sparkdl_tpu.fabric import InProcessHost, Router
    from sparkdl_tpu.models.gpt import GPTConfig, GPTLMHeadModel
    from sparkdl_tpu.serving import ContinuousGPTEngine

    n_hosts = int(os.environ.get("BENCH_HOSTS", "2"))
    if n_hosts < 2:
        return None
    n_groups = int(os.environ.get("BENCH_FABRIC_GROUPS", "4"))
    per_round = int(os.environ.get("BENCH_FABRIC_REQUESTS", "16"))
    share = float(os.environ.get("BENCH_PREFIX_SHARE", "0.75"))
    plen = int(os.environ.get("BENCH_PROMPT_LEN", "96"))
    max_new = 8
    max_len = plen + max_new
    cfg = GPTConfig(
        vocab_size=256, hidden_size=128, num_layers=3, num_heads=4,
        intermediate_size=256, max_seq_len=4 * max_len,
    )
    model = GPTLMHeadModel(cfg)
    variables = model.init(
        jax.random.PRNGKey(3), jnp.zeros((1, 8), jnp.int32))
    rng = np.random.default_rng(23)
    n_shared = int(round(share * plen))
    prefixes = [rng.integers(1, cfg.vocab_size, n_shared).tolist()
                for _ in range(n_groups)]

    def fresh_followers():
        # grouped by group (an interleaved order can hand round-robin
        # accidental parity with the seed placements)
        return [
            prefixes[g]
            + rng.integers(1, cfg.vocab_size, plen - n_shared).tolist()
            for g in range(n_groups)
            for _ in range(per_round // n_groups)
        ]

    def run(policy):
        engines = [
            ContinuousGPTEngine(
                cfg, variables, n_slots=4, max_len=max_len,
                kv_block_size=8, idle_wait_s=0.0005,
                host_id=f"bench-{policy}-{i}")
            for i in range(n_hosts)
        ]
        hit_rates, p95s, walls = [], [], []
        with Router([InProcessHost(e) for e in engines],
                    policy=policy, auto_refresh=False) as router:
            # compile warmup + digest seeding: one request per group
            for g in range(n_groups):
                router.submit({
                    "prompt": prefixes[g] + rng.integers(
                        1, cfg.vocab_size, plen - n_shared).tolist(),
                    "max_new_tokens": max_new}).result(timeout=300)
            router.refresh()
            for _ in range(3):
                kv0 = [e.snapshot()["kv"] for e in engines]
                lats = []
                t0 = time.perf_counter()
                futs = []
                for p in fresh_followers():
                    t_sub = time.perf_counter()
                    fut = router.submit(
                        {"prompt": p, "max_new_tokens": max_new})
                    fut.add_done_callback(
                        lambda f, t=t_sub:
                        lats.append(time.perf_counter() - t))
                    futs.append(fut)
                for f in futs:
                    f.result(timeout=300)
                walls.append(time.perf_counter() - t0)
                # result() can return before the done-callback that
                # appends the latency has run: wait for the full sample
                # (bounded — callbacks fire microseconds later)
                deadline = time.monotonic() + 5.0
                while (len(lats) < len(futs)
                       and time.monotonic() < deadline):
                    time.sleep(0.001)
                kv1 = [e.snapshot()["kv"] for e in engines]
                hits = sum(b["prefix_hits"] - a["prefix_hits"]
                           for a, b in zip(kv0, kv1))
                miss = sum(b["prefix_misses"] - a["prefix_misses"]
                           for a, b in zip(kv0, kv1))
                hit_rates.append(hits / max(1, hits + miss))
                p95s.append(float(np.percentile(lats, 95)))
                router.refresh()  # publish blocks the round cached
            fleet = router.snapshot()
        for e in engines:
            e.close()
        return {
            "prefix_hit_rate": round(float(np.median(hit_rates)), 4),
            "p95_ms": round(1e3 * float(np.median(p95s)), 2),
            "req_s": round(per_round / float(np.median(walls)), 2),
            "routed_per_host": {
                h["host"]: h["routed"] for h in fleet["hosts"]},
        }

    routed = run("affinity")
    rr = run("round_robin")
    return {
        "hosts": n_hosts,
        "groups": n_groups,
        "requests_per_round": per_round,
        "prefix_share": share,
        "prompt_len": plen,
        "routed": routed,
        "round_robin": rr,
        "hit_rate_gain": round(
            routed["prefix_hit_rate"] - rr["prefix_hit_rate"], 4),
    }


def _router_tier_section():
    """Horizontally scaled router tier (ISSUE 19; ``BENCH_ROUTERS=N``
    with N >= 1 enables): the shared-prefix fleet workload behind a
    :class:`RouterGroup` of N routers over the SAME 2-host engine
    fleet, at N=1 and N=BENCH_ROUTERS. Three measurements ride each
    arm: client p95 + fleet prefix hit rate (the N=2 rate must stay
    within 10 percent of single-router — deterministic placement means
    more routers never scatter a conversation's followers), the
    cross-router placement agreement rate (``preferred_host`` sampled
    per follower prompt across every member — arithmetic, so ~1.0),
    and the digest refresh wire cost: bytes/s of the delta path vs a
    wholesale-forced arm (same fleet state, same refresh cadence,
    deltas disabled) — steady-state delta traffic scales with CHURN,
    wholesale with pool size x refresh rate, so the ratio is the
    scaling headroom deltas buy."""
    import jax
    import jax.numpy as jnp

    from sparkdl_tpu.fabric import InProcessHost, Router, RouterGroup
    from sparkdl_tpu.models.gpt import GPTConfig, GPTLMHeadModel
    from sparkdl_tpu.observability.registry import registry as _reg
    from sparkdl_tpu.serving import ContinuousGPTEngine

    n_routers = int(os.environ.get("BENCH_ROUTERS", "0"))
    if n_routers < 1:
        return None
    n_hosts = 2
    n_groups = int(os.environ.get("BENCH_FABRIC_GROUPS", "4"))
    per_round = int(os.environ.get("BENCH_FABRIC_REQUESTS", "16"))
    share = float(os.environ.get("BENCH_PREFIX_SHARE", "0.75"))
    # longer than the fabric section's prompts: the wholesale wire
    # cost under test scales with the CACHED state, so the workload
    # must cache enough for the comparison to mean anything
    plen = int(os.environ.get("BENCH_ROUTER_PROMPT_LEN", "160"))
    refreshes_per_round = 8  # refresh cadence > churn cadence, as prod
    max_new = 8
    max_len = plen + max_new
    cfg = GPTConfig(
        vocab_size=256, hidden_size=128, num_layers=3, num_heads=4,
        intermediate_size=256, max_seq_len=4 * max_len,
    )
    model = GPTLMHeadModel(cfg)
    variables = model.init(
        jax.random.PRNGKey(3), jnp.zeros((1, 8), jnp.int32))
    rng = np.random.default_rng(29)
    n_shared = int(round(share * plen))
    prefixes = [rng.integers(1, cfg.vocab_size, n_shared).tolist()
                for _ in range(n_groups)]

    def fresh_followers():
        return [
            prefixes[g]
            + rng.integers(1, cfg.vocab_size, plen - n_shared).tolist()
            for g in range(n_groups)
            for _ in range(per_round // n_groups)
        ]

    class _WholesaleHost(InProcessHost):
        # the control arm: no journal endpoint, every refresh re-ships
        # the full digest (the pre-delta wire cost)
        def prefix_digest_delta(self, since_version, max_entries=1024):
            return None

    def _bytes(name):
        fam = _reg().snapshot().get(name) or {}
        return float((fam.get("values") or {}).get("", 0))

    def run(n, wholesale=False):
        engines = [
            ContinuousGPTEngine(
                cfg, variables, n_slots=4, max_len=max_len,
                kv_block_size=8, kv_blocks=256, idle_wait_s=0.0005,
                host_id=f"rt-{n}{'w' if wholesale else ''}-{i}")
            for i in range(n_hosts)
        ]
        wrap = _WholesaleHost if wholesale else InProcessHost
        routers = [Router([wrap(e) for e in engines],
                          auto_refresh=False)
                   for _ in range(n)]
        group = RouterGroup(routers)
        counter = ("sparkdl_fabric_digest_wholesale_bytes_total"
                   if wholesale else
                   "sparkdl_fabric_digest_delta_bytes_total")
        try:
            for g in range(n_groups):  # compile warmup + digest seed
                group.submit({
                    "prompt": prefixes[g] + rng.integers(
                        1, cfg.vocab_size, plen - n_shared).tolist(),
                    "max_new_tokens": max_new}).result(timeout=300)
            group.refresh()  # first post-seed sync may ride either path
            hit_rates, p95s, agrees = [], [], []
            bytes0 = _bytes(counter)
            t0 = time.perf_counter()
            for _ in range(3):
                kv0 = [e.snapshot()["kv"] for e in engines]
                lats, futs = [], []
                followers = fresh_followers()
                for i, p in enumerate(followers):
                    t_sub = time.perf_counter()
                    fut = group.submit(
                        {"prompt": p, "max_new_tokens": max_new},
                        session=f"conv-{i}")
                    fut.add_done_callback(
                        lambda f, t=t_sub:
                        lats.append(time.perf_counter() - t))
                    futs.append(fut)
                for f in futs:
                    f.result(timeout=300)
                deadline = time.monotonic() + 5.0
                while (len(lats) < len(futs)
                       and time.monotonic() < deadline):
                    time.sleep(0.001)
                for _ in range(refreshes_per_round):
                    group.refresh()
                kv1 = [e.snapshot()["kv"] for e in engines]
                hits = sum(b["prefix_hits"] - a["prefix_hits"]
                           for a, b in zip(kv0, kv1))
                miss = sum(b["prefix_misses"] - a["prefix_misses"]
                           for a, b in zip(kv0, kv1))
                hit_rates.append(hits / max(1, hits + miss))
                p95s.append(float(np.percentile(lats, 95)))
                picks = [[r.preferred_host(p) for r in routers]
                         for p in followers]
                agrees.append(
                    sum(len(set(row)) == 1 for row in picks)
                    / len(picks))
            wall = time.perf_counter() - t0
            wire_bytes = _bytes(counter) - bytes0
            n_refreshes = 3 * refreshes_per_round * n * n_hosts
        finally:
            group.close(close_members=True)
            for e in engines:
                e.close()
        return {
            "routers": n,
            "wholesale_forced": wholesale,
            "prefix_hit_rate": round(float(np.median(hit_rates)), 4),
            "p95_ms": round(1e3 * float(np.median(p95s)), 2),
            "agreement_rate": round(float(np.min(agrees)), 4),
            "digest_bytes_per_s": round(wire_bytes / wall, 1),
            "digest_bytes_per_refresh": round(
                wire_bytes / n_refreshes, 1),
        }

    single = run(1)
    scaled = run(max(2, n_routers))
    wholesale = run(1, wholesale=True)
    return {
        "hosts": n_hosts,
        "groups": n_groups,
        "requests_per_round": per_round,
        "refreshes_per_round": refreshes_per_round,
        "single": single,
        "scaled": scaled,
        "wholesale": wholesale,
        "router_agreement_rate": scaled["agreement_rate"],
        "digest_delta_bytes_per_s": scaled["digest_bytes_per_s"],
        "digest_wholesale_bytes_per_s": wholesale[
            "digest_bytes_per_s"],
        "delta_vs_wholesale_per_refresh": round(
            wholesale["digest_bytes_per_refresh"]
            / max(1e-9, scaled["digest_bytes_per_refresh"]), 2),
        "hit_rate_n_vs_1": round(
            scaled["prefix_hit_rate"]
            / max(1e-9, single["prefix_hit_rate"]), 4),
    }


def _autoscale_section():
    """Elastic autoscaling under stepped open-loop load (ISSUE 15;
    ``BENCH_AUTOSCALE=1`` enables): a 1-replica MLP fleet is driven
    low -> 4x-capacity burst -> low while an :class:`AutoScaler` reads
    the engine's queue depth and resizes the ReplicaPool through the
    drain-safe actuators. Emits the scale-event count, the replica-count
    trajectory (sampled at every controller tick), and the rolling SLO
    burn at the end of the burst vs after recovery — the artifact shows
    elasticity absorbing the step, not just that ticks happened."""
    if os.environ.get("BENCH_AUTOSCALE", "0") != "1":
        return None
    import jax.numpy as jnp

    from sparkdl_tpu.autoscale import AutoScaler, AutoscalePolicy
    from sparkdl_tpu.observability.slo import SLO
    from sparkdl_tpu.serving import ServingEngine
    from sparkdl_tpu.serving.replicas import ReplicaPool

    rng = np.random.default_rng(11)
    dim = int(os.environ.get("BENCH_AUTOSCALE_FEATURES", "256"))
    max_replicas = int(os.environ.get("BENCH_AUTOSCALE_MAX", "3"))
    n_burst = int(os.environ.get("BENCH_AUTOSCALE_REQUESTS", "192"))
    window_s = float(os.environ.get("BENCH_AUTOSCALE_SLO_WINDOW", "3.0"))
    ws = [jnp.asarray(rng.standard_normal((dim, dim)), jnp.float32) / dim
          for _ in range(2)]

    def apply_fn(batch):
        h = batch["x"]
        for w in ws:
            h = jnp.tanh(h @ w)
        return h

    def max_burn(report):
        burn = 0.0
        for d in (report.get("latency"), report.get("availability")):
            if isinstance(d, dict) and d.get("burn_rate") is not None:
                burn = max(burn, float(d["burn_rate"]))
        return round(burn, 4)

    pool = ReplicaPool(apply_fn, batch_size=16, n_replicas=1)
    warm = {"x": np.zeros((16, dim), np.float32)}
    pool.warmup(warm)
    slo = SLO(name="bench_autoscale", latency_threshold_s=0.05,
              latency_target=0.95, availability_target=0.999,
              window_s=window_s)
    engine = ServingEngine(pool, max_queue_depth=max(4 * n_burst, 256),
                           max_wait_s=0.002, slo=slo)
    scaler = AutoScaler(
        pool=pool,
        signals=lambda: (float(engine.queue.depth), 0.0),
        policy=AutoscalePolicy(
            min_replicas=1, max_replicas=max_replicas, queue_high=4.0,
            queue_low=0.5, hysteresis=1, cooldown_ticks=1,
            tabu_ticks=3),
        warmup_arrays=warm,
    )
    trajectory = []

    def tick():
        scaler.tick()
        trajectory.append(len(pool.replicas))

    # calibrate the single-replica round trip -> the step sizes
    x1 = {"x": np.zeros((dim,), np.float32)}
    engine.submit(x1).result(timeout=120)
    t_cal = time.perf_counter()
    k = 20
    for _ in range(k):
        engine.submit(x1).result(timeout=120)
    per_request = (time.perf_counter() - t_cal) / k
    base_rate = 1.0 / per_request

    def replay(n, rate):
        arr = np.cumsum(rng.exponential(1.0 / rate, n))
        futs = []
        t0 = time.perf_counter()
        for i, t_arr in enumerate(arr):
            lag = t0 + t_arr - time.perf_counter()
            if lag > 0:
                time.sleep(lag)
            futs.append(engine.submit(
                {"x": rng.standard_normal(dim).astype(np.float32)}))
            if i % 4 == 3:
                tick()
        for f in futs:
            f.result(timeout=120)

    n_low = max(16, n_burst // 6)
    replay(n_low, 0.5 * base_rate)        # steady low load
    replay(n_burst, 4.0 * base_rate)      # step: 4x the 1-replica rate
    burn_before = max_burn(engine.slo_tracker.sample())
    peak_replicas = max(trajectory) if trajectory else 1
    replay(n_low, 0.5 * base_rate)        # load drops
    deadline = time.monotonic() + 10.0
    while len(pool.replicas) > 1 and time.monotonic() < deadline:
        tick()
        time.sleep(0.01)
    burn_after = max_burn(engine.slo_tracker.sample())
    ctl = scaler.snapshot()["autoscaler"]
    engine.close()
    scaler.close()
    pool.close()
    return {
        "requests": n_low + n_burst + n_low,
        "burst_rate_per_s": round(4.0 * base_rate, 1),
        "scale_events": scaler.decision_count,
        "replica_trajectory": trajectory,
        "replicas_peak": peak_replicas,
        "replicas_final": trajectory[-1] if trajectory else 1,
        "slo_burn_before_after": {
            "before": burn_before, "after": burn_after},
        "controller": ctl,
    }


def _disagg_section():
    """Disaggregated prefill/decode serving (ISSUE 16;
    ``BENCH_DISAGG=1`` enables): a ``BENCH_DISAGG_LONG_LEN``-token
    prompt (default 3072) streams in while short interactive requests
    are served — colocated (one engine shares every tick between the
    long prompt's chunked prefill and live decode) vs disaggregated
    (a PrefillWorker absorbs the long prompt, a DecodeWorker keeps the
    interactive stream; one quantized KV-block handoff per request
    crosses the tiers). Emits interactive p50/p95 per arm and their
    ratio, the measured handoff-crossing latency p50 (wire codec +
    transfer + install, max_new=1 so the Future resolves AT install),
    and fp32-vs-int8 wire bytes — the int8 pool's storage IS the wire
    format, so the crossing inherits its ~4x compression. Also emits
    ``phase_breakdown`` (ISSUE 17): per-phase median seconds (queue
    wait / prefill compute / handoff wire / decode queue / decode
    compute) read off the registry's sparkdl_request_phase_seconds
    histograms — summed, the p50s reconstruct the measured interactive
    e2e median."""
    if os.environ.get("BENCH_DISAGG", "0") != "1":
        return None
    import jax
    import jax.numpy as jnp

    from sparkdl_tpu.disagg import DecodeWorker, KVHandoff, PrefillWorker
    from sparkdl_tpu.models.gpt import GPTConfig, GPTLMHeadModel
    from sparkdl_tpu.serving import ContinuousGPTEngine

    long_len = int(os.environ.get("BENCH_DISAGG_LONG_LEN", "3072"))
    n_int = int(os.environ.get("BENCH_DISAGG_REQUESTS", "12"))
    dtype = os.environ.get("BENCH_DISAGG_KV_DTYPE", "int8")
    int_len, int_new = 16, 16
    max_len = long_len + 32
    cfg = GPTConfig(
        vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
        intermediate_size=128, max_seq_len=max_len,
    )
    model = GPTLMHeadModel(cfg)
    variables = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    rng = np.random.default_rng(16)
    long_prompt = rng.integers(1, cfg.vocab_size, long_len).tolist()
    int_prompts = [rng.integers(1, cfg.vocab_size, int_len).tolist()
                   for _ in range(n_int)]
    chunk_warm = rng.integers(1, cfg.vocab_size, 256).tolist()
    kw = dict(max_len=max_len, kv_block_size=16,
              prefill_chunk=256, kv_dtype=dtype, idle_wait_s=0.0005)

    def pctl(xs, q):
        return round(float(np.percentile(np.asarray(xs), q)) * 1e3, 2)

    # -- colocated arm: interactive decode shares every tick with the
    # long prompt's chunked prefill
    eng = ContinuousGPTEngine(cfg, variables, n_slots=4, **kw)
    eng.submit(int_prompts[0], int_new).result(timeout=600)  # warm
    eng.submit(chunk_warm, 2).result(timeout=600)  # chunk program
    long_fut = eng.submit(long_prompt, 4)
    lat_col = []
    for p in int_prompts:
        t0 = time.perf_counter()
        eng.submit(p, int_new).result(timeout=600)
        lat_col.append(time.perf_counter() - t0)
    long_fut.result(timeout=600)
    eng.close()

    # -- disaggregated arm: the long prompt stays on the prefill tier;
    # the decode tier's ticks never see a prefill chunk
    pre = PrefillWorker(cfg, variables, n_slots=2, **kw)
    dec = DecodeWorker(cfg, variables, n_slots=4, **kw)
    h0 = pre.submit(int_prompts[0], int_new).result(timeout=600)
    out_dis = np.asarray(dec.submit_handoff(h0).result(timeout=600))
    dec.submit_handoff(
        pre.submit(chunk_warm, 2).result(timeout=600)).result(timeout=600)
    long_hfut = pre.submit(long_prompt, 4)
    long_decode = []
    long_hfut.add_done_callback(
        lambda f: long_decode.append(dec.submit_handoff(f.result())))
    lat_dis = []
    for p in int_prompts:
        t0 = time.perf_counter()
        h = pre.submit(p, int_new).result(timeout=600)
        dec.submit_handoff(h).result(timeout=600)
        lat_dis.append(time.perf_counter() - t0)
    long_hfut.result(timeout=600)
    deadline = time.monotonic() + 60.0
    while not long_decode and time.monotonic() < deadline:
        time.sleep(0.001)
    long_wire_bytes = long_hfut.result().wire_bytes
    long_decode[0].result(timeout=600)
    handoffs_total = pre._handoffs
    pre.close()
    dec.close()

    # Per-request phase attribution (ISSUE 17): the decode tier observed
    # every crossing into sparkdl_request_phase_seconds{phase,tier} —
    # read the per-phase medians NOW, before the dtype microbench below
    # floods the same histograms with max_new=1 crossings. The p50s
    # telescope: summed, they reconstruct the median interactive e2e
    # latency measured client-side above.
    from sparkdl_tpu.observability.registry import registry

    _PHASE_ORDER = {("queue", "prefill"): 0, ("compute", "prefill"): 1,
                    ("wire", "handoff"): 2, ("queue", "decode"): 3,
                    ("compute", "decode"): 4}
    fam = registry().get("sparkdl_request_phase_seconds")
    phase_rows = [
        {"phase": labels.get("phase"), "tier": labels.get("tier"),
         "p50_s": round(stats["p50"], 6),
         "mean_s": round(stats["mean"], 6),
         "observations": stats["count"]}
        for labels, stats in (fam.hist_series() if fam else [])
    ]
    phase_rows.sort(key=lambda r: _PHASE_ORDER.get(
        (r["phase"], r["tier"]), 99))
    phase_breakdown = {
        "phases": phase_rows,
        "sum_p50_s": round(sum(r["p50_s"] for r in phase_rows), 6),
        "interactive_p50_s": round(float(np.median(lat_dis)), 6),
    } if phase_rows else None

    # the split must be invisible in the tokens: the first interactive
    # prompt, decoded through the tier crossing above, vs an idle
    # colocated engine (the measured colocated replies ran CONTENDED,
    # which never changes greedy tokens, but compare against the
    # cleanest oracle anyway)
    eng2 = ContinuousGPTEngine(cfg, variables, n_slots=1, **kw)
    want0 = np.asarray(
        eng2.submit(int_prompts[0], int_new).result(timeout=600))
    eng2.close()
    bitwise = bool(np.array_equal(out_dis, want0))

    # -- handoff-crossing microbench per dtype: prefill resolves the
    # handoff, then the timed span is wire-codec round trip + queue +
    # install (max_new=1 resolves the decode Future at install)
    hand = {}
    for d in ("fp32", "int8"):
        pre_d = PrefillWorker(cfg, variables, n_slots=2,
                              **{**kw, "kv_dtype": d})
        dec_d = DecodeWorker(cfg, variables, n_slots=2,
                             **{**kw, "kv_dtype": d})
        warm_h = pre_d.submit(chunk_warm, 1).result(timeout=600)
        dec_d.submit_handoff(
            KVHandoff.from_wire(warm_h.to_wire())).result(timeout=600)
        times, nbytes = [], []
        for _ in range(8):
            p = rng.integers(1, cfg.vocab_size, 256).tolist()
            h = pre_d.submit(p, 1).result(timeout=600)
            t0 = time.perf_counter()
            h2 = KVHandoff.from_wire(h.to_wire())
            dec_d.submit_handoff(h2).result(timeout=600)
            times.append(time.perf_counter() - t0)
            nbytes.append(h.wire_bytes)
        hand[d] = {"seconds_p50": round(float(np.median(times)), 6),
                   "bytes_per_handoff": int(np.mean(nbytes))}
        pre_d.close()
        dec_d.close()
    byte_ratio = (hand["fp32"]["bytes_per_handoff"]
                  / hand["int8"]["bytes_per_handoff"])

    p95_col, p95_dis = pctl(lat_col, 95), pctl(lat_dis, 95)
    return {
        "long_prompt_len": long_len,
        "interactive_requests": n_int,
        "interactive_new_tokens": int_new,
        "kv_dtype": dtype,
        "handoffs": handoffs_total,
        "long_handoff_bytes": long_wire_bytes,
        "colocated": {"interactive_p50_ms": pctl(lat_col, 50),
                      "interactive_p95_ms": p95_col},
        "disaggregated": {"interactive_p50_ms": pctl(lat_dis, 50),
                          "interactive_p95_ms": p95_dis},
        # >1: the tier split kept interactive latency out of the long
        # prompt's blast radius
        "decode_p95_colocated_vs_disagg": (
            round(p95_col / p95_dis, 4) if p95_dis else None),
        "split_bitwise_vs_colocated": bitwise,
        "handoff_seconds_p50": hand[dtype]["seconds_p50"],
        "handoff_bytes": {**hand,
                          "fp32_over_int8": round(byte_ratio, 4)},
        # per-phase latency attribution (ISSUE 17), registry-sourced:
        # median seconds in queue-wait / prefill compute / handoff wire
        # / decode queue / decode compute — summed, the p50s reconstruct
        # the interactive e2e median
        "phase_breakdown": phase_breakdown,
    }


def _tenancy_section():
    """Multi-tenant QoS isolation (ISSUE 20; ``BENCH_TENANTS>=3``
    enables): one flooding tenant offered ~10x its admission quota
    against ``BENCH_TENANTS - 1`` compliant tenants on a shared
    engine, solo (no flooder) vs storm. Per-batch service time is a
    fixed HOST-side sleep (a plain ``run_batch`` object — inside a
    jitted apply_fn the sleep would trace away) so the victims'
    latency is dominated by a DETERMINISTIC term: the isolation ratio
    then measures scheduling, not scheduler jitter, and the batch is
    sized so victims + the flooder's quota-capped residue never
    overflow it. Emits the worst victim p95 storm/solo ratio
    (the 1.10x acceptance bar), the flooder's shed share (overage
    rejected typed at the door), and a driven brownout episode's level
    trajectory (up the ladder under synthetic burn, background sheds
    counted per level, recovery back to 0)."""
    n_tenants = int(os.environ.get("BENCH_TENANTS", "0"))
    if n_tenants < 3:
        return None
    import threading

    from sparkdl_tpu.serving import (
        PRIORITY_BACKGROUND,
        BrownoutShedError,
        OverloadController,
        RequestQueue,
        ServingEngine,
        TenantRegistry,
        TenantThrottledError,
    )
    from sparkdl_tpu.serving.tenancy import set_process_overload

    victims = [f"tenant-{i}" for i in range(n_tenants - 1)]
    n_per_victim = int(os.environ.get("BENCH_TENANT_REQUESTS", "48"))
    service_s = 0.025
    flood_rate = 40.0
    row = np.ones((2,), np.float32)

    class _FixedServiceRunner:
        chunk_size = 16

        def run_batch(self, arrays):
            time.sleep(service_s)
            return arrays["x"] * 2.0 + 1.0

    def _run(flood):
        reg = TenantRegistry(latency_threshold_s=0.25, window_s=60.0)
        reg.configure("flood", rate=flood_rate, burst=2)
        runner = _FixedServiceRunner()
        lats = {t: [] for t in victims}
        shed, flood_futs, offered = [0], [], [0]
        stop = threading.Event()
        with ServingEngine(runner, max_wait_s=0.03,
                           max_queue_depth=1024, tenants=reg) as eng:
            def flooder():
                give_up = time.monotonic() + 60.0
                while (not stop.is_set()
                       and time.monotonic() < give_up):
                    offered[0] += 1
                    try:
                        flood_futs.append(
                            eng.submit({"x": row}, tenant="flood"))
                    except TenantThrottledError:
                        shed[0] += 1
                    time.sleep(0.001)

            th = threading.Thread(target=flooder, daemon=True)
            if flood:
                th.start()
            futs = []
            try:
                for _ in range(n_per_victim):
                    for tenant in victims:
                        t0 = time.perf_counter()
                        f = eng.submit({"x": row}, tenant=tenant)
                        f.add_done_callback(
                            lambda f, t=tenant, s=t0: lats[t].append(
                                time.perf_counter() - s))
                        futs.append(f)
                    time.sleep(0.01)
                for f in futs:
                    f.result(timeout=60)
            finally:
                stop.set()
                if flood:
                    th.join(timeout=10)
            for f in flood_futs:
                f.result(timeout=60)  # zero accepted lost
            deadline = time.monotonic() + 10.0
            while (any(len(lats[t]) < n_per_victim for t in victims)
                   and time.monotonic() < deadline):
                time.sleep(0.001)
        report = reg.slo_report()
        return {
            "p95_ms": {t: round(1e3 * float(np.percentile(lats[t], 95)),
                                2) for t in victims},
            "compliance": {
                t: report[t]["latency"]["compliance"] for t in victims},
            "flooder": {
                "offered": offered[0],
                "admitted": len(flood_futs),
                "shed": shed[0],
            },
        }

    solo = _run(flood=False)
    storm = _run(flood=True)
    fl = storm["flooder"]
    isolation = max(storm["p95_ms"][t] / solo["p95_ms"][t]
                    for t in victims)

    # driven brownout episode: synthetic burn walks the ladder up and
    # back while a controller-guarded queue sheds background submits
    reg = TenantRegistry()
    ctrl = OverloadController(hysteresis=1, recovery_ticks=1,
                              cooldown_ticks=0)
    prev = set_process_overload(ctrl)
    levels, sheds_per_level = [], {}
    try:
        q = RequestQueue(max_depth=64, tenants=reg)
        for _ in range(4):
            levels.append(ctrl.evaluate(burn_rate=10.0))
            try:
                q.submit("bg", tenant="batch",
                         priority=PRIORITY_BACKGROUND)
            except BrownoutShedError as e:
                sheds_per_level[str(e.level)] = (
                    sheds_per_level.get(str(e.level), 0) + 1)
        for _ in range(4):
            levels.append(
                ctrl.evaluate(burn_rate=0.0, queue_frac=0.0))
        q.close()
    finally:
        set_process_overload(prev)

    return {
        "tenants": n_tenants,
        "requests_per_victim": n_per_victim,
        "service_s": service_s,
        "flood_quota_per_s": flood_rate,
        "solo": solo,
        "storm": storm,
        "tenant_isolation_ratio": round(isolation, 4),
        "compliance_ratio": round(min(
            (storm["compliance"][t] or 1.0)
            / (solo["compliance"][t] or 1.0) for t in victims), 4),
        "shed_share": round(fl["shed"] / max(1, fl["offered"]), 4),
        "brownout_levels": levels,
        "brownout_sheds_per_level": sheds_per_level,
    }


def main() -> None:
    n_replicas = int(os.environ.get("BENCH_REPLICAS", "1"))
    n_sp = int(os.environ.get("BENCH_SP", "2"))
    n_dev = max(n_replicas, n_sp)
    if (n_dev > 1
            and "xla_force_host_platform_device_count"
            not in os.environ.get("XLA_FLAGS", "")):
        # simulated replicas / sp chips on the CPU harness: one virtual
        # device per chip, fixed before jax's first import
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={n_dev}"
        ).strip()
    import jax
    import jax.numpy as jnp

    from sparkdl_tpu.runtime.chip import (
        configure_compile_cache,
        require_tpu,
        smoke_label,
    )
    from sparkdl_tpu.serving import ServingEngine
    from sparkdl_tpu.serving.replicas import ReplicaPool
    from sparkdl_tpu.transformers._inference import BatchedRunner

    on_tpu = require_tpu(explicit_cpu_ok=True)
    configure_compile_cache()
    platform = jax.default_backend()
    n_req = int(os.environ.get("BENCH_REQUESTS", "512"))
    max_batch = int(os.environ.get("BENCH_MAX_BATCH", "32"))
    dim = int(os.environ.get("BENCH_FEATURES", "768"))
    n_layers = int(os.environ.get("BENCH_LAYERS", "4"))

    rng = np.random.default_rng(0)
    ws = [jnp.asarray(rng.standard_normal((dim, dim)), jnp.float32) / dim
          for _ in range(n_layers)]

    def apply_fn(batch):
        h = batch["x"]
        for w in ws:
            h = jnp.tanh(h @ w)
        return h

    def make_engine(batch_size, replicas=1, slo=None):
        if replicas > 1:
            pool = ReplicaPool(
                apply_fn, batch_size=batch_size,
                devices=jax.local_devices()[:replicas],
            )
            # compile every bucket on EVERY replica before measurement
            for b in pool.replicas[0].runner._buckets:
                pool.warmup({"x": np.zeros((b, dim), np.float32)})
            return ServingEngine(
                pool, max_queue_depth=max(n_req, 8), max_wait_s=0.002,
                slo=slo,
            )
        runner = BatchedRunner(apply_fn, batch_size=batch_size,
                               data_parallel=False)
        # compile every bucket BEFORE measurement: steady-state serving is
        # what's being compared, not first-request compile latency
        for b in runner._buckets:
            runner.run_batch({"x": np.zeros((b, dim), np.float32)})
        return ServingEngine(
            runner, max_queue_depth=max(n_req, 8), max_wait_s=0.002,
            slo=slo,
        )

    # calibrate: submit->result round trip of the batch-of-1 path
    calib = make_engine(1)
    x = {"x": np.zeros((dim,), np.float32)}
    calib.submit(x).result(timeout=120)
    t0 = time.perf_counter()
    k = 30
    for _ in range(k):
        calib.submit(x).result(timeout=120)
    per_request = (time.perf_counter() - t0) / k
    calib.close()

    # 6x the serialized capacity: far past batch-of-1 saturation (its
    # queue must visibly build) while a >=32-row coalescer keeps up
    rate = float(os.environ.get("BENCH_RATE", 0)) or 6.0 / per_request
    arrivals = np.cumsum(rng.exponential(1.0 / rate, n_req))

    b1 = make_engine(1)
    n_b1, dur_b1, p50_b1, p95_b1, _ = _replay(b1, arrivals)
    b1.close()

    from sparkdl_tpu.observability.slo import SLO
    from sparkdl_tpu.runtime.completion import fetch_wait_seconds

    # Declared objectives for the measured engine (ISSUE 9): the JSON
    # artifact then carries rolling compliance + error-budget burn next
    # to the throughput number. The tracker baselines its cumulative
    # sources at engine construction — i.e. AFTER the batch-of-1
    # calibration/replay above — so the slo block covers exactly the
    # micro-batch replay being reported.
    slo = SLO(
        name="bench_serving",
        latency_threshold_s=float(
            os.environ.get("BENCH_SLO_MS", "250")) / 1e3,
        latency_target=0.95, availability_target=0.999, window_s=3600.0,
    )
    micro = make_engine(max_batch, replicas=n_replicas, slo=slo)
    fetch_wait0 = fetch_wait_seconds("serving")
    n_mb, dur_mb, p50_mb, p95_mb, occ = _replay(micro, arrivals)
    fetch_wait = fetch_wait_seconds("serving") - fetch_wait0
    replica_snap = micro.snapshot()
    micro.close()
    if n_replicas > 1:
        micro.runner.close()

    tput_b1 = n_b1 / dur_b1
    tput_mb = n_mb / dur_mb
    # Stage-level attribution rides the artifact (ISSUE 2): the registry
    # snapshot covers BOTH engines' queue/latency/occupancy series, so the
    # BENCH_*.json trajectory can tell queueing from compute regressions.
    from sparkdl_tpu.observability import registry

    # Dispatch spine (ISSUE 3): run_batch records every serving dispatch
    # (count + wall) into the registry; the calibrated gap then splits
    # device-step wall into program vs dispatch overhead for the artifact.
    from sparkdl_tpu.runtime.dispatch import (
        calibrate_dispatch_gap,
        dispatch_count,
        overhead_share,
    )

    # Paged KV serving (ISSUE 10): shared-prefix chat workload, dense
    # vs paged continuous GPT — runs BEFORE the registry snapshot below
    # so the kv/prefix series ride the artifact.
    kv_paged = _gpt_paged_section()

    # Speculative decode + quantized KV (ISSUE 12): decode-heavy
    # workload, spec_k vs k=1 (bitwise) and int8 vs fp32 pools.
    spec = _gpt_spec_section()

    # Sequence-parallel long-context prefill (ISSUE 13): the same long
    # prompt at sp=1 vs sp=BENCH_SP, spatial chunks over forced CPU
    # devices, medians of 3.
    sp_prefill = _gpt_sp_section()

    # Multi-host fabric (ISSUE 14): cache-aware routing vs round-robin
    # over BENCH_HOSTS in-process hosts, medians of 3.
    fabric = _fabric_section()

    # Horizontally scaled router tier (ISSUE 19): RouterGroup at
    # N=1 vs N=BENCH_ROUTERS over one fleet, delta-vs-wholesale
    # digest wire cost, cross-router agreement (BENCH_ROUTERS>=1).
    router_tier = _router_tier_section()

    # Elastic autoscaling (ISSUE 15): stepped open-loop load over an
    # AutoScaler-driven ReplicaPool (BENCH_AUTOSCALE=1 enables).
    autoscale = _autoscale_section()

    # Disaggregated prefill/decode (ISSUE 16): long-prompt stream vs
    # interactive decode, colocated vs split tiers with a quantized
    # KV-block handoff (BENCH_DISAGG=1 enables).
    disagg = _disagg_section()

    # Tiered KV session parking (ISSUE 18): turn-2 resume from the
    # host tier vs full re-prefill at each BENCH_PARK_DEPTH (empty
    # disables).
    park = _gpt_park_section()

    # Multi-tenant QoS (ISSUE 20): hot-tenant storm vs solo baseline,
    # flooder shed share, and a driven brownout episode
    # (BENCH_TENANTS>=3 enables).
    tenancy = _tenancy_section()

    gap = calibrate_dispatch_gap()
    n_dispatches = dispatch_count("serving")
    snap_wall = registry().snapshot().get(
        "sparkdl_dispatch_seconds", {}
    ).get("values", {}).get('path="serving"', {})
    share = overhead_share(n_dispatches, snap_wall.get("sum") or 0.0, gap)

    print(json.dumps({
        "metric": (
            smoke_label(on_tpu)
            + f"online serving req/s, micro-batch<= {max_batch} vs batch-of-1 "
            f"({platform}, {n_req} req, Poisson {rate:.0f}/s, "
            f"p50/p95 ms {p50_mb:.1f}/{p95_mb:.1f} vs "
            f"{p50_b1:.1f}/{p95_b1:.1f}, occupancy {occ:.0f}%)"
        ),
        "value": round(tput_mb, 1),
        "unit": "req/s",
        **({"vs_baseline": round(tput_mb / tput_b1, 4)} if on_tpu else {}),
        "dispatch_count": n_dispatches,
        "dispatch_gap_ms": round(gap * 1e3, 4),
        "overhead_share": round(share, 4) if share is not None else None,
        # async completion (ISSUE 4): host share of the micro run's wall
        # spent blocked collecting D2H results — the overlap headroom
        "fetch_wait_share": round(min(1.0, fetch_wait / dur_mb), 4),
        "replica_count": replica_snap.get("replica_count", 1),
        "replicas": replica_snap.get("replicas"),
        # Paged KV cache (ISSUE 10): prefix reuse + block pool + chunked
        # prefill on the shared-prefix GPT workload (None when
        # BENCH_GPT_REQUESTS=0)
        "prefix_hit_rate": (kv_paged or {}).get(
            "paged", {}).get("prefix_hit_rate"),
        "kv_blocks_used": (kv_paged or {}).get(
            "paged", {}).get("kv_blocks_used_peak"),
        "prefill_chunks": (kv_paged or {}).get(
            "paged", {}).get("prefill_chunks"),
        "kv_paged": kv_paged,
        # Speculative decoding + quantized KV (ISSUE 12): acceptance,
        # dispatch amortization, and the capacity-vs-parity trade
        "spec_acceptance_rate": (spec or {}).get("acceptance_rate"),
        "spec_tokens_per_dispatch": (spec or {}).get(
            "tokens_per_dispatch"),
        "spec_speedup": (spec or {}).get("tokens_per_s_speedup"),
        "kv_capacity_ratio": (spec or {}).get("kv_quant", {}).get(
            "capacity_ratio_vs_fp32"),
        "spec_decode": spec,
        # Sequence parallelism (ISSUE 13): long-context prefill split
        # across sp chips (None when BENCH_SP<2)
        "sp_axis": (sp_prefill or {}).get("sp_axis"),
        "prefill_shard_tokens": (sp_prefill or {}).get(
            "prefill_shard_tokens"),
        "sp_prefill_speedup": (sp_prefill or {}).get(
            "sp_prefill_speedup"),
        "sp_prefill": sp_prefill,
        # Multi-host fabric (ISSUE 14): the cache-aware router's hit
        # rate vs round-robin on the same shared-prefix fleet workload
        # (None when BENCH_HOSTS<2)
        "fabric_hosts": (fabric or {}).get("hosts"),
        "fabric_hit_rate_routed": (fabric or {}).get(
            "routed", {}).get("prefix_hit_rate"),
        "fabric_hit_rate_rr": (fabric or {}).get(
            "round_robin", {}).get("prefix_hit_rate"),
        "fabric_p95_ms_routed": (fabric or {}).get(
            "routed", {}).get("p95_ms"),
        "fabric_p95_ms_rr": (fabric or {}).get(
            "round_robin", {}).get("p95_ms"),
        "fabric": fabric,
        # Scaled router tier (ISSUE 19): placement agreement across
        # routers, digest delta vs wholesale wire cost, and p95 + hit
        # rate at N routers vs one (None when BENCH_ROUTERS<1)
        "router_agreement_rate": (router_tier or {}).get(
            "router_agreement_rate"),
        "digest_delta_bytes_per_s": (router_tier or {}).get(
            "digest_delta_bytes_per_s"),
        "digest_wholesale_bytes_per_s": (router_tier or {}).get(
            "digest_wholesale_bytes_per_s"),
        "router_p95_ms_n1": (router_tier or {}).get(
            "single", {}).get("p95_ms"),
        "router_p95_ms_n": (router_tier or {}).get(
            "scaled", {}).get("p95_ms"),
        "router_tier": router_tier,
        # Elastic autoscaling (ISSUE 15): scale-event count, replica
        # trajectory, and SLO burn at burst end vs after recovery
        # (None when BENCH_AUTOSCALE != 1)
        "scale_events": (autoscale or {}).get("scale_events"),
        "replica_trajectory": (autoscale or {}).get(
            "replica_trajectory"),
        "slo_burn_before_after": (autoscale or {}).get(
            "slo_burn_before_after"),
        "autoscale": autoscale,
        # Disaggregated serving (ISSUE 16): interactive p95 colocated
        # vs split tiers under a long-prompt stream, the measured
        # handoff-crossing latency, and the int8-vs-fp32 wire bytes
        # (None when BENCH_DISAGG != 1)
        "decode_p95_colocated_vs_disagg": (disagg or {}).get(
            "decode_p95_colocated_vs_disagg"),
        "handoff_seconds_p50": (disagg or {}).get("handoff_seconds_p50"),
        "handoff_bytes": (disagg or {}).get("handoff_bytes"),
        # Per-request phase attribution (ISSUE 17): registry-sourced
        # median seconds per phase; the p50s telescope to the
        # interactive e2e median (None when BENCH_DISAGG != 1)
        "phase_breakdown": (disagg or {}).get("phase_breakdown"),
        "disagg": disagg,
        # Tiered KV cache (ISSUE 18): turn-2 resume latency from the
        # parked host tier vs re-prefilling the transcript, and the
        # idle sessions one chip's pools can hold vs device HBM alone
        # (None when BENCH_PARK_DEPTH is unset)
        "turn_resume_p50_ms": (
            (park or {}).get("depths") or [{}])[-1].get(
                "turn_resume_p50_ms"),
        "reprefill_p50_ms": (
            (park or {}).get("depths") or [{}])[-1].get(
                "reprefill_p50_ms"),
        "parked_sessions_per_chip": (
            (park or {}).get("depths") or [{}])[-1].get(
                "parked_sessions_per_chip"),
        "park": park,
        # Multi-tenant QoS (ISSUE 20): worst victim p95 storm/solo
        # ratio (the 1.10x isolation bar), the flooder's shed share,
        # and the brownout episode's level trajectory (None when
        # BENCH_TENANTS<3)
        "tenant_isolation_ratio": (tenancy or {}).get(
            "tenant_isolation_ratio"),
        "shed_share": (tenancy or {}).get("shed_share"),
        "brownout_levels": (tenancy or {}).get("brownout_levels"),
        "tenancy": tenancy,
        # SLO accounting + flight recorder (ISSUE 9): declared objective
        # with rolling burn, and the event-ring volume this run produced
        "slo": replica_snap.get("slo"),
        "flight_events_total": _flight_events_total(),
        "observability": registry().snapshot(),
    }))


def _flight_events_total() -> int:
    from sparkdl_tpu.observability.flight import flight_recorder

    return flight_recorder().events_total


if __name__ == "__main__":
    from sparkdl_tpu.observability.profiling import maybe_profile

    with maybe_profile("bench_serving"):
        main()
