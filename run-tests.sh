#!/usr/bin/env bash
# Test runner (reference parity: [U: python/run-tests.sh], SURVEY.md 2.22).
# Runs the suite on a virtual 8-device CPU mesh (conftest.py forces
# JAX_PLATFORMS=cpu + --xla_force_host_platform_device_count=8) so every
# dp/tp/sp/ep/pp collective path executes without TPU hardware.
set -euo pipefail
cd "$(dirname "$0")"

# Tier-1 gate 0 (ISSUE 11): sparkdl-lint — AST invariant checks for
# concurrency (lock discipline), donated-buffer safety, hot-loop
# blocking, metric-family drift, fault-site coverage, and the env-pin
# contract. Fails the whole run on any finding; the JSON report (incl.
# every suppression + its justification) is printed for triage.
# `./run-tests.sh --lint-only` is the fast pre-commit path.
LINT_REPORT="${LINT_REPORT:-/tmp/sparkdl-lint.json}"
if JAX_PLATFORMS=cpu python -m sparkdl_tpu.lint sparkdl_tpu/ tests/ \
    --output "$LINT_REPORT"; then
  echo "sparkdl-lint OK (report: $LINT_REPORT)"
else
  echo "sparkdl-lint FAILED — full report: $LINT_REPORT" >&2
  exit 1
fi
if [[ "${1:-}" == "--lint-only" ]]; then
  exit 0
fi

# Two lanes (VERDICT r4 #8): the default lane skips @pytest.mark.slow —
# the multi-process elastic/preemption jobs and full-size model oracles —
# and finishes under 10 minutes (355 tests in 9:42, idle host,
# 2026-07-31). `./run-tests.sh --full` runs everything (what CI and the
# driver's `pytest tests/` do).
if [[ "${1:-}" == "--full" ]]; then
  shift
  python -m pytest tests/ -q "$@"
else
  python -m pytest tests/ -q -m "not slow" "$@"
fi

# Driver-contract smoke: bench prints exactly one JSON line; graft hooks
# compile entry() and run the 6-regime multichip dryrun.
JAX_PLATFORMS=cpu BENCH_STEPS=2 BENCH_BATCH=4 python bench.py | tail -1 | python -c '
import json, sys
line = sys.stdin.readline()
rec = json.loads(line)
assert {"metric", "value", "unit"} <= rec.keys(), rec
# a CPU contract smoke is not a device measurement and must not look
# like one: no vs_baseline, no per-chip metric name
assert "vs_baseline" not in rec and "/chip" not in rec["metric"], rec
assert rec["metric"].startswith("contract smoke"), rec
# ISSUE 2: every bench artifact carries the metrics-registry snapshot
assert "sparkdl_bench_images_total" in rec["observability"], rec.keys()
# ISSUE 3: the artifact attributes dispatch amortization, not just img/s
assert rec["dispatch_count"] == 2, rec
assert 0 <= rec["overhead_share"] <= 1, rec
assert "sparkdl_dispatches_total" in rec["observability"], rec.keys()
# ISSUE 11: static-analysis drift rides the trajectory; HEAD lints clean
assert rec["lint_findings_total"] == 0, rec["lint_findings_total"]
print("bench.py contract OK")
'
# Fused-dispatch smoke (ISSUE 3): a chained BatchedRunner.run must issue
# ~K-fold fewer device dispatches than the unchained runner on the same
# stream, with bitwise-identical outputs.
JAX_PLATFORMS=cpu python -c '
import numpy as np, jax; jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
from sparkdl_tpu.runtime.dispatch import dispatch_count
from sparkdl_tpu.transformers._inference import BatchedRunner
w = jnp.asarray(np.random.default_rng(0).standard_normal((8, 8)), jnp.float32)
rows = [{"x": np.random.default_rng(i).standard_normal(8).astype(np.float32)}
        for i in range(32)]
base = list(BatchedRunner(lambda b: jnp.tanh(b["x"] @ w), batch_size=4,
                          data_parallel=False, chain_k=1).run(iter(rows)))
d0 = dispatch_count("batch")
assert d0 == 8, d0
got = list(BatchedRunner(lambda b: jnp.tanh(b["x"] @ w), batch_size=4,
                         data_parallel=False, chain_k=8).run(iter(rows)))
d1 = dispatch_count("batch") - d0
assert d1 == 1, d1  # 8 batches, one fused dispatch
for g, b in zip(got, base):
    np.testing.assert_array_equal(g, b)
print("fused-dispatch smoke OK: 8 dispatches -> 1 at K=8, bitwise equal")
'
# Async-completion smoke (ISSUE 4): the pipelined readback must keep at
# most `window` results in flight and match the blocking readback
# bitwise on a chained runner.
JAX_PLATFORMS=cpu python -c '
import numpy as np, jax; jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
from sparkdl_tpu.runtime.completion import AsyncFetcher
from sparkdl_tpu.transformers._inference import BatchedRunner

# window bound: pulls may never run more than `window` ahead of yields
pulled = 0
def source():
    global pulled
    for i in range(24):
        pulled += 1
        yield np.full((2,), float(i))
yielded = 0
for out in AsyncFetcher(window=4, path="smoke").stream(source()):
    np.testing.assert_array_equal(out, np.full((2,), float(yielded)))
    yielded += 1
    assert pulled - yielded <= 4, (pulled, yielded)
assert yielded == 24

# bitwise parity: async (default) vs blocking readback, chained K=8
w = jnp.asarray(np.random.default_rng(0).standard_normal((8, 8)), jnp.float32)
rows = [{"x": np.random.default_rng(i).standard_normal(8).astype(np.float32)}
        for i in range(32)]
base = list(BatchedRunner(lambda b: jnp.tanh(b["x"] @ w), batch_size=4,
                          data_parallel=False, chain_k=8,
                          async_fetch=False).run(iter(rows)))
got = list(BatchedRunner(lambda b: jnp.tanh(b["x"] @ w), batch_size=4,
                         data_parallel=False, chain_k=8).run(iter(rows)))
for g, b in zip(got, base):
    np.testing.assert_array_equal(g, b)
print("async-completion smoke OK: <=4 in flight, bitwise equal at K=8")
'
# Replica-pool smoke (ISSUE 4): a 2-replica CPU pool serves a burst with
# BOTH replicas receiving work, then drains to zero depth.
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=2 python -c '
import numpy as np, jax; jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
from sparkdl_tpu.serving import ReplicaPool, ServingEngine
w = jnp.asarray(np.random.default_rng(0).standard_normal((8, 8)), jnp.float32)
pool = ReplicaPool(lambda b: jnp.tanh(b["x"] @ w), batch_size=8)
assert len(pool.replicas) == 2, len(pool.replicas)
pool.warmup({"x": np.zeros((8, 8), np.float32)})
with ServingEngine(pool, max_wait_s=0.002) as eng:
    futs = [eng.submit({"x": np.full((8,), float(i), np.float32)})
            for i in range(64)]
    for i, f in enumerate(futs):
        np.testing.assert_allclose(
            f.result(timeout=60),
            np.tanh(np.full((8,), float(i), np.float32) @ np.asarray(w)),
            rtol=1e-5)
    snap = eng.snapshot()
pool.close()
assert snap["replica_count"] == 2, snap
served = [r["dispatched"] for r in snap["replicas"]]
assert all(d > 1 for d in served), served  # burst hit BOTH replicas
assert all(r["depth"] == 0 and r["in_flight"] == 0
           for r in snap["replicas"]), snap["replicas"]
print("replica-pool smoke OK: burst over 2 replicas", served,
      "drained to zero depth")
'
# Local multi-chip DP hook: same contract, batch sharded over 8 fake chips.
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  BENCH_STEPS=2 BENCH_BATCH=8 BENCH_DP_DEVICES=8 python bench.py | tail -1 | python -c '
import json, sys
rec = json.loads(sys.stdin.readline())
assert {"metric", "value", "unit"} <= rec.keys(), rec
# a CPU contract smoke is not a device measurement and must not look
# like one: no vs_baseline, no per-chip metric name
assert "vs_baseline" not in rec and "/chip" not in rec["metric"], rec
assert rec["metric"].startswith("contract smoke"), rec
assert "over 8 devices" in rec["metric"], rec
print("bench.py dp contract OK")
'
# Sequence-parallel smoke (ISSUE 13): 2 forced CPU devices, sp=2
# spatial prefill vs sp=1 — greedy tokens bitwise on a prompt spanning
# >= 3 chunks, the prefix-cache hit preserved across the sharded
# gather, and the sp.permute/sp.gather chaos contract: an injected
# collective fault mid-prefill re-queues the victim (typed flight
# event, zero lost admitted requests, still bitwise).
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=2 \
SPARKDL_TPU_FAULT_PLAN="sp.permute:OSError@2;sp.gather:OSError@2" \
python - <<'EOF'
import numpy as np
import jax; jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
from sparkdl_tpu.models.gpt import GPTConfig, GPTLMHeadModel
from sparkdl_tpu.observability.flight import flight_recorder
from sparkdl_tpu.serving import ContinuousGPTEngine

cfg = GPTConfig.tiny()
model = GPTLMHeadModel(cfg)
variables = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
rng = np.random.default_rng(9)
shared = rng.integers(1, cfg.vocab_size, 10).tolist()
cases = [
    (list(rng.integers(1, cfg.vocab_size, 19)), 5),  # >= 3 chunks at 8
    (shared + rng.integers(1, cfg.vocab_size, 3).tolist(), 5),
    (shared + rng.integers(1, cfg.vocab_size, 2).tolist(), 4),  # hit
]

def run(sp):
    eng = ContinuousGPTEngine(
        cfg, variables, n_slots=2, max_len=64, kv_block_size=4,
        prefill_chunk=8, sp=(None if sp < 2 else sp), auto_start=False)
    futs = [eng.submit(p, n) for p, n in cases]
    for _ in range(500):
        eng.tick()
        if all(f.done() for f in futs):
            break
    outs = [np.asarray(f.result(timeout=0)) for f in futs]
    snap = eng.snapshot()
    eng.close()
    return outs, snap

outs1, _ = run(1)            # fault plan hits 1: sp sites never fire
outs2, snap2 = run(2)        # hits 2: one permute + one gather injected
assert all(np.array_equal(a, b) for a, b in zip(outs1, outs2)), \
    "sp=2 diverged from sp=1"
kv = snap2["kv"]
assert kv["prefix_hits"] > 0, kv       # hit survived the sharded gather
assert kv["sp"]["axis"] == 2, kv
assert kv["sp"]["handoffs"] >= len(cases), kv
assert kv["sp"]["staging_blocks_used"] == 0, kv  # all staging released
evs = [e for e in flight_recorder().events()
       if e.get("kind") == "sp.collective_failed"]
sites = {e["site"] for e in evs}
assert {"sp.permute", "sp.gather"} <= sites, sites
assert all(e["error"] == "SpCollectiveError" for e in evs), evs
print(f"sp smoke OK: sp=2 bitwise vs sp=1 across {len(cases)} requests "
      f"(3-chunk prompt, prefix hit {kv['prefix_hits']} tokens), "
      f"injected {sorted(sites)} faults -> re-queued, zero lost")
EOF

# Multi-host fabric smoke (ISSUE 14): (a) a 2-host fleet under the
# cache-aware router must beat round-robin's prefix hit rate on the
# identical shared-prefix workload, with tokens oracle-exact under both
# policies; (b) host-kill drill — one host hard-killed under load with
# injected host.submit faults riding: ZERO lost accepted requests
# (every Future resolves with the right tokens via failover), the dead
# host quarantines, and the router's postmortem bundle carries the
# failover sequence.
JAX_PLATFORMS=cpu \
SPARKDL_TPU_FAULT_PLAN="seed=3;host.submit:OSError@5;host.drain:OSError@1" \
python - <<'EOF'
import numpy as np
import jax; jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
from sparkdl_tpu.fabric import InProcessHost, Router
from sparkdl_tpu.models.gpt import GPTConfig, GPTLMHeadModel, generate
from sparkdl_tpu.observability.flight import flight_recorder
from sparkdl_tpu.observability.registry import registry
from sparkdl_tpu.serving import ContinuousGPTEngine

flight_recorder().configure(settle_s=0.05, min_interval_s=0.0)
cfg = GPTConfig.tiny()
model = GPTLMHeadModel(cfg)
variables = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
rng = np.random.default_rng(13)
groups = [rng.integers(1, cfg.vocab_size, 8).tolist() for _ in range(2)]
seeds = [g + [int(rng.integers(1, cfg.vocab_size))] for g in groups]
followers = [g + rng.integers(1, cfg.vocab_size, 2).tolist()
             for g in groups for _ in range(3)]

def make_engine(host_id):
    return ContinuousGPTEngine(
        cfg, variables, n_slots=2, max_len=32, kv_block_size=4,
        idle_wait_s=0.001, host_id=host_id)

def hit_rate(engines):
    h = m = 0
    for e in engines:
        kv = e.snapshot()["kv"]
        h, m = h + kv["prefix_hits"], m + kv["prefix_misses"]
    return h / max(1, h + m)

def run(policy):
    engines = [make_engine(f"{policy}-{i}") for i in range(2)]
    with Router([InProcessHost(e) for e in engines],
                policy=policy, auto_refresh=False) as router:
        for p in seeds:
            router.submit({"prompt": p, "max_new_tokens": 3}).result(60)
        router.refresh()
        futs = [router.submit({"prompt": p, "max_new_tokens": 3})
                for p in followers]
        outs = [np.asarray(f.result(60)) for f in futs]
    rate = hit_rate(engines)
    for e in engines:
        e.close()
    return rate, outs

# (a) affinity beats round-robin, both oracle-exact. The fault plan's
# 5th host.submit hit injects an OSError mid-run: the failover path
# must absorb it (zero lost) while the comparison stays valid.
rr_rate, rr_outs = run("round_robin")
af_rate, af_outs = run("affinity")
assert af_rate > rr_rate, (af_rate, rr_rate)
for p, a, b in zip(followers, af_outs, rr_outs):
    want = np.asarray(generate(
        model, variables, jnp.asarray([p], jnp.int32), 3)[0, len(p):])
    np.testing.assert_array_equal(a, want)
    np.testing.assert_array_equal(b, want)

# (b) host-kill drill on a fresh 2-host fleet, plus a graceful drain
# retry through the injected host.drain fault.
registry().reset()
engines = [make_engine(f"kill-{i}") for i in range(2)]
hosts = [InProcessHost(e) for e in engines]
with Router(hosts, max_failures=3, probation_s=0.5,
            auto_refresh=False) as router:
    futs = []
    for i in range(24):
        futs.append((i, router.submit(
            {"prompt": [1 + (i % 9), 2, 3], "max_new_tokens": 2})))
        if i == 10:
            engines[0].close(drain=False, timeout_s=5)  # host dies
    for i, f in futs:
        got = np.asarray(f.result(60))  # zero lost: all resolve
        p = [1 + (i % 9), 2, 3]
        want = np.asarray(generate(
            model, variables, jnp.asarray([p], jnp.int32), 2)[0, 3:])
        np.testing.assert_array_equal(got, want)
    assert router._hosts["kill-0"].quarantined
    moved = router.drain_host("kill-1")  # retries the injected fault
    assert moved == 0  # nothing queued: traffic already drained

def bundle_ok():
    b = flight_recorder().last_bundle
    if b is None:
        return False
    kinds = [e.get("kind") for e in b["events"]]
    return ("fabric.host_quarantined" in kinds
            and "fabric.failover" in kinds)

import time
deadline = time.monotonic() + 10.0
while not bundle_ok():
    assert time.monotonic() < deadline, "postmortem bundle never settled"
    time.sleep(0.02)
snap = registry().snapshot()
inj = snap["sparkdl_faults_injected_total"]["values"]
assert inj.get('site="host.drain"', 0) >= 1, inj
ret = snap["sparkdl_retries_total"]["values"]
assert ret.get('site="host.drain",outcome="recovered"', 0) >= 1, ret
for e in engines:
    e.close(drain=False)
print(f"fabric smoke OK: affinity hit-rate {af_rate:.2f} > "
      f"round-robin {rr_rate:.2f} (oracle-exact both), host-kill -> "
      "zero lost + quarantine + postmortem, drain fault recovered")
EOF

# Router-tier smoke (ISSUE 19): a RouterGroup of 2 routers over one
# 2-host fleet. An injected router.route fault tears one member's
# placement mid-stream AND one router is hard-killed under load:
# every accepted request still resolves oracle-exact (zero lost —
# the group walks to the surviving member), and the steady-state
# digest refreshes ride the DELTA wire, not wholesale.
JAX_PLATFORMS=cpu \
SPARKDL_TPU_FAULT_PLAN="seed=7;router.route:OSError@5" \
python - <<'EOF'
import numpy as np
import jax; jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
from sparkdl_tpu.fabric import InProcessHost, Router, RouterGroup
from sparkdl_tpu.models.gpt import GPTConfig, GPTLMHeadModel, generate
from sparkdl_tpu.observability.registry import registry
from sparkdl_tpu.serving import ContinuousGPTEngine

cfg = GPTConfig.tiny()
model = GPTLMHeadModel(cfg)
variables = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
engines = [ContinuousGPTEngine(
    cfg, variables, n_slots=2, max_len=32, kv_block_size=4,
    idle_wait_s=0.001, host_id=f"rt-{i}") for i in range(2)]
routers = [Router([InProcessHost(e) for e in engines],
                  auto_refresh=False) for _ in range(2)]
group = RouterGroup(routers)
# seed, then refresh twice: the second sync must ride the journal
group.submit({"prompt": [7, 3, 9, 1, 5], "max_new_tokens": 2}).result(60)
group.refresh()
group.refresh()
snap = registry().snapshot()
delta_bytes = snap["sparkdl_fabric_digest_delta_bytes_total"][
    "values"].get("", 0)
assert delta_bytes > 0, "steady-state refresh never used the delta wire"
# 24 requests; the fault plan tears placement #5, router 0 dies at #10
futs = []
for i in range(24):
    futs.append((i, group.submit(
        {"prompt": [1 + (i % 9), 2, 3], "max_new_tokens": 2},
        session=f"conv-{i % 6}")))
    if i == 10:
        routers[0].close()   # router killed holding accepted work
for i, f in futs:
    got = np.asarray(f.result(60))  # zero lost: every Future resolves
    p = [1 + (i % 9), 2, 3]
    want = np.asarray(generate(
        model, variables, jnp.asarray([p], jnp.int32), 2)[0, 3:])
    np.testing.assert_array_equal(got, want)
assert routers[0].closed and not routers[1].closed
snap = registry().snapshot()
inj = snap["sparkdl_faults_injected_total"]["values"]
assert inj.get('site="router.route"', 0) >= 1, inj
disp = snap["sparkdl_fabric_router_dispatch_total"]["values"]
assert sum(disp.values()) >= 25, disp
group.close(close_members=True)
for e in engines:
    e.close(drain=False)
print(f"router-tier smoke OK: 24/24 oracle-exact through a torn "
      f"placement + a router kill (dispatch {dict(disp)}), "
      f"{delta_bytes:.0f}B of digest sync on the delta wire")
EOF

# Migration smoke (ISSUE 19): drain a host holding parked sessions ->
# the sessions re-park on the survivor through the handoff wire codec,
# and every turn-2 resume there (a) matches a never-migrated engine
# bitwise and (b) beats re-prefilling the transcript cold (the
# pre-migration cost) on wall clock.
JAX_PLATFORMS=cpu python - <<'EOF'
import time
import numpy as np
import jax; jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
from sparkdl_tpu.fabric import InProcessHost, Router
from sparkdl_tpu.models.gpt import GPTConfig, GPTLMHeadModel
from sparkdl_tpu.observability.registry import registry
from sparkdl_tpu.serving import ContinuousGPTEngine

cfg = GPTConfig(vocab_size=256, hidden_size=128, num_layers=3,
                num_heads=4, intermediate_size=256, max_seq_len=1024)
model = GPTLMHeadModel(cfg)
variables = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
kw = dict(n_slots=2, max_len=352, kv_block_size=32, kv_blocks=24,
          host_kv_blocks=512, disk_kv_blocks=16, idle_wait_s=0.0005)
PLEN, NEW = 320, 8
rng = np.random.default_rng(19)
prompts = [rng.integers(1, cfg.vocab_size, PLEN).tolist() for _ in range(3)]
a = ContinuousGPTEngine(cfg, variables, host_id="mig-a", **kw)
b = ContinuousGPTEngine(cfg, variables, host_id="mig-b", **kw)
cold = ContinuousGPTEngine(cfg, variables, host_id="mig-cold", **kw)

def warm_conv(eng, park):
    p = rng.integers(1, cfg.vocab_size, PLEN).tolist()
    r = eng.submit(p, NEW).result(timeout=300).tolist()
    if park is None:
        return
    if park:
        eng.park_cold()
    eng.submit(p + r + [5], NEW).result(timeout=300)

warm_conv(a, None)          # compile A's prefill bucket
warm_conv(b, True)          # compile B's resume path (install + tail)
warm_conv(cold, False)      # compile the cold arm's full re-prefill
replies = [a.submit(p, NEW).result(timeout=300).tolist() for p in prompts]
a.park_cold()
with Router([InProcessHost(a), InProcessHost(b)],
            auto_refresh=False) as router:
    router.drain_host("mig-a")   # exports A's parked fleet onto B
mig = registry().snapshot()["sparkdl_kv_migrations_total"]["values"]
assert mig.get('outcome="exported"', 0) >= 3, mig
assert mig.get('outcome="imported"', 0) >= 3, mig
assert b.capacity()["kv_parked_sessions"] >= 3, b.capacity()

def timed(eng):
    outs, lats = [], []
    for p, r in zip(prompts, replies):
        t0 = time.perf_counter()
        outs.append(eng.submit(p + r + [5], NEW)
                    .result(timeout=300).tolist())
        lats.append(time.perf_counter() - t0)
    return outs, 1e3 * float(np.median(lats))

out_b, resume_p50 = timed(b)       # migrated resume: unpark + tail
out_cold, reprefill_p50 = timed(cold)  # never saw the transcripts
assert out_b == out_cold, "migrated resume diverged from cold oracle"
assert b._kv_snapshot()["tiers"]["unparks"] > 0, "resume re-prefilled"
assert resume_p50 < reprefill_p50, (resume_p50, reprefill_p50)
for e in (a, b, cold):
    e.close(drain=False)
print(f"migration smoke OK: 3 parked sessions drained mig-a -> mig-b "
      f"over the wire codec; resume p50 {resume_p50:.1f}ms beats cold "
      f"re-prefill {reprefill_p50:.1f}ms, tokens bitwise")
EOF

# Elastic-autoscale smoke (ISSUE 15): a 1-replica pool + engine under
# manual controller ticks. (a) load step -> scale-up within a bounded
# tick count; (b) load drop -> drain-based scale-down with ZERO lost
# accepted requests (every Future resolves correctly); (c) an injected
# replica.scale_down fault defers the scale event (healthz degraded,
# nothing moves, nothing lost) and the retry lands clean; (d) every
# decision is visible in the flight ring and /healthz recovers to ok.
JAX_PLATFORMS=cpu \
SPARKDL_TPU_FAULT_PLAN="seed=7;autoscale.decide:RuntimeError@4;replica.scale_down:OSError@1;kv_pool.resize:OSError@9" \
python - <<'EOF'
import threading
import time
import numpy as np
import jax; jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
from sparkdl_tpu.autoscale import AutoScaler, AutoscalePolicy
from sparkdl_tpu.observability.flight import flight_recorder, healthz_report
from sparkdl_tpu.serving import ReplicaPool, ServingEngine
from sparkdl_tpu.serving.kv_blocks import KVBlockPool

DIM = 8
W = jnp.asarray(np.random.default_rng(0).standard_normal((DIM, DIM)),
                jnp.float32) / DIM

def apply_fn(b):
    return jnp.tanh(b["x"] @ W)

pool = ReplicaPool(apply_fn, batch_size=8, n_replicas=1)
warm = {"x": np.zeros((8, DIM), np.float32)}
pool.warmup(warm)
engine = ServingEngine(pool, max_queue_depth=4096, max_wait_s=0.002)
kv = KVBlockPool(64, 4)
depth = [0.0]
scaler = AutoScaler(pool=pool, kv_pool=kv, kv_lock=threading.Lock(),
                    signals=lambda: (depth[0], 0.0),
                    policy=AutoscalePolicy(max_replicas=2, hysteresis=2,
                                           cooldown_ticks=1, tabu_ticks=2,
                                           kv_step_blocks=8),
                    warmup_arrays=warm)
futs = [engine.submit({"x": np.full((DIM,), float(i % 5), np.float32)})
        for i in range(64)]
# (a) load step: scale-up within N ticks (hysteresis 2 -> 2 ticks)
depth[0] = 40.0
ticks_to_scale = 0
for _ in range(6):
    scaler.tick(); ticks_to_scale += 1
    if len(pool.replicas) == 2:
        break
assert len(pool.replicas) == 2, "no scale-up under load step"
assert ticks_to_scale <= 3, f"scale-up took {ticks_to_scale} ticks"
# (b)+(c) load drop: the kv tier shrinks first (one step per cooldown
# window), then the FIRST replica scale-down attempt hits the injected
# replica.scale_down@1 fault -> the decision defers (nothing moves) and
# the retry lands clean; autoscale.decide@4 also defers one whole pass
# mid-sequence. Drive ticks until the pool is back to 1.
depth[0] = 0.0
saw_deferred = saw_degraded = False
deadline = time.monotonic() + 30.0
while len(pool.replicas) > 1 and time.monotonic() < deadline:
    scaler.tick()
    if scaler.state == "deferred":
        saw_deferred = True
        saw_degraded |= healthz_report()["status"] == "degraded"
    time.sleep(0.005)
assert len(pool.replicas) == 1, "no drain-based scale-down"
assert saw_deferred, "injected fault never deferred a scale decision"
assert saw_degraded, "deferred scale event did not degrade /healthz"
# ZERO lost: every accepted request resolves with the right answer
expect = {v: np.tanh(np.full((DIM,), float(v)) @ np.asarray(W))
          for v in range(5)}
for i, f in enumerate(futs):
    np.testing.assert_allclose(np.asarray(f.result(timeout=60)),
                               expect[i % 5], rtol=1e-5)
snap = engine.snapshot()
assert snap["completed"] == 64 and snap["failed"] == 0, snap
# (d) decisions visible; healthz recovered
kinds = [str(e.get("kind")) for e in flight_recorder().events()]
assert "autoscale.decision" in kinds
assert "autoscale.deferred" in kinds
assert "pool.scale_up" in kinds and "pool.scale_down" in kinds
for _ in range(4):
    scaler.tick()
assert scaler.state == "ok"
assert healthz_report()["status"] == "ok", healthz_report()
a = healthz_report()["autoscalers"]
assert a and a[0]["state"] == "ok", a
engine.close(); scaler.close(); pool.close()
print("autoscale smoke OK: step -> scale-up in "
      f"{ticks_to_scale} ticks, drop -> drain-based scale-down, "
      "injected scale_down/decide faults deferred (healthz degraded "
      "-> ok), 64/64 requests exact, decisions in flight ring")
EOF

# Disaggregated-serving smoke (ISSUE 16): (a) greedy tokens through a
# PrefillWorker -> int8 KVHandoff -> DecodeWorker chain are BITWISE
# identical to the colocated engine (prefix hits included, on both
# sides of the tier boundary); (b) a PhaseRouter stream under injected
# handoff.export AND handoff.install faults loses ZERO accepted
# requests — victims re-queue at the prefill tier's head and the
# counters reconcile exactly.
JAX_PLATFORMS=cpu python - <<'EOF'
import numpy as np
import jax; jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
from sparkdl_tpu.disagg import DecodeWorker, PhaseRouter, PrefillWorker
from sparkdl_tpu.fabric.host import InProcessHost
from sparkdl_tpu.models.gpt import GPTConfig, GPTLMHeadModel
from sparkdl_tpu.reliability.faults import inject
from sparkdl_tpu.serving import ContinuousGPTEngine

cfg = GPTConfig.tiny()
model = GPTLMHeadModel(cfg)
variables = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
KW = dict(n_slots=2, max_len=48, kv_block_size=4, prefill_chunk=8,
          kv_dtype="int8")
rng = np.random.RandomState(3)
base = rng.randint(1, 50, size=12).tolist()
cases = [(base + rng.randint(1, 50, size=rng.randint(2, 6)).tolist(),
          int(rng.randint(2, 8))) for _ in range(8)]

# (a) bitwise across the split, int8 wire, shared-prefix workload
col = ContinuousGPTEngine(cfg, variables, **KW)
want = [np.asarray(col.submit(p, m).result(timeout=120))
        for p, m in cases]
col.close()
pre = PrefillWorker(cfg, variables, **KW)
dec = DecodeWorker(cfg, variables, **KW)
got, wire_bytes = [], 0
for p, m in cases:
    h = pre.submit(p, m).result(timeout=120)
    wire_bytes += h.wire_bytes
    got.append(np.asarray(dec.submit_handoff(h).result(timeout=120)))
assert all(np.array_equal(w, g) for w, g in zip(want, got)), \
    "tier split changed greedy tokens"
assert pre._prefix.hit_tokens > 0 and dec._prefix.hit_tokens > 0, \
    "prefix cache never hit across the boundary"
pre.close(); dec.close()

# (b) zero loss under both handoff fault sites + counters reconcile
pres = [PrefillWorker(cfg, variables, host_id=f"p{i}", **KW)
        for i in range(2)]
decs = [DecodeWorker(cfg, variables, host_id=f"d{i}", **KW)
        for i in range(2)]
pr = PhaseRouter([InProcessHost(e, host_id=e.host_id) for e in pres],
                 [InProcessHost(e, host_id=e.host_id) for e in decs],
                 auto_refresh=False, max_handoff_retries=4)
with inject("handoff.install%0.25;handoff.export@3;seed=11"):
    futs = [(pr.submit(p, m), m) for p, m in cases * 3]
    outs = [np.asarray(f.result(timeout=120)) for f, _ in futs]
for (f, m), out in zip(futs, outs):
    assert len(out) == m, (len(out), m)
snap = pr.snapshot()["disagg"]
assert snap["submitted"] == len(futs), snap
assert snap["completed"] == len(futs) and snap["failed"] == 0, snap
assert snap["requeues"] >= 1, "install faults never exercised requeue"
aborts = sum(e._export_aborts for e in pres)
pr.close()
for e in pres + decs:
    e.close()
print(f"disagg smoke OK: {len(cases)}/8 bitwise across the int8 split "
      f"({wire_bytes} wire bytes, prefix hits both tiers), "
      f"{len(futs)}/{len(futs)} under chaos (requeues={snap['requeues']}, "
      f"export aborts={aborts}, zero lost, counters reconcile)")
EOF

# Cross-host trace-stitching smoke (ISSUE 17): the split request over
# the REAL HTTP transport — a prefill-tier HostServer and a decode-tier
# HostServer on separate ports behind a PhaseRouter of HttpHostHandles.
# The serialized SpanContext rides the submit body and the KVHandoff
# wire dict, so fleet_trace(request_id) resolves to ONE stitched trace:
# BOTH tiers' spans, exactly one handoff.wire crossing, clock offsets
# estimated for both hosts, and a five-phase breakdown telescoping to
# the measured end-to-end latency.
JAX_PLATFORMS=cpu python - <<'EOF'
import time
import numpy as np
import jax; jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
from sparkdl_tpu.disagg import DecodeWorker, PhaseRouter, PrefillWorker
from sparkdl_tpu.fabric.http import HostServer, HttpHostHandle
from sparkdl_tpu.models.gpt import GPTConfig, GPTLMHeadModel
from sparkdl_tpu.observability import tracing
from sparkdl_tpu.observability.fleet import PHASES, FleetScraper

tracing.clear_trace()
tracing.enable_tracing()
cfg = GPTConfig.tiny()
model = GPTLMHeadModel(cfg)
variables = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
KW = dict(n_slots=2, max_len=48, kv_block_size=4, prefill_chunk=8,
          kv_dtype="int8")
pre = PrefillWorker(cfg, variables, host_id="pre-0", **KW)
dec = DecodeWorker(cfg, variables, host_id="dec-0", **KW)
srv_p = HostServer(pre)
srv_d = HostServer(dec)
pr = PhaseRouter(
    [HttpHostHandle(f"http://127.0.0.1:{srv_p.port}", host_id="pre-0")],
    [HttpHostHandle(f"http://127.0.0.1:{srv_d.port}", host_id="dec-0")],
    auto_refresh=False)
try:
    t0 = time.monotonic()
    out = np.asarray(pr.submit(list(range(1, 11)), 4).result(timeout=120))
    e2e = time.monotonic() - t0
    assert len(out) == 4, out

    wire = [e for e in tracing.trace_events()
            if e["name"] == "handoff.wire"]
    assert len(wire) == 1, sorted(
        {e["name"] for e in tracing.trace_events()})
    rid = wire[0]["args"]["request_id"]

    scraper = FleetScraper.from_phase_router(pr)
    assert scraper.tier_of("pre-0") == "prefill"
    assert scraper.tier_of("dec-0") == "decode"
    stitched = scraper.fleet_trace(rid)
    names = [e["name"] for e in stitched["spans"]]
    assert names.count("handoff.wire") == 1, names
    assert "disagg.handoff_export" in names, names   # prefill tier ran
    assert "disagg.handoff_install" in names, names  # decode tier ran
    assert names.index("disagg.handoff_export") \
        < names.index("handoff.wire"), names
    # both hosts answered the offset probes; one process, so ~zero skew
    offs = scraper.clock_offsets()
    assert set(offs) == {"pre-0", "dec-0"}, offs
    assert all(abs(o) < 1e6 for o in offs.values()), offs
    phases = stitched["phases"]
    assert [(p["phase"], p["tier"]) for p in phases] == list(PHASES), \
        phases
    total = sum(p["seconds"] for p in phases)
    assert total > 0, phases
    assert abs(total - e2e) < 0.25 * e2e + 0.1, (total, e2e)
finally:
    pr.close()
    srv_p.close(); srv_d.close()
    pre.close(); dec.close()
    tracing.disable_tracing(); tracing.clear_trace()
print(f"disagg-trace smoke OK: split request over HTTP stitched to ONE "
      f"trace ({len(names)} spans, 1 handoff.wire crossing), phases "
      f"{total:.3f}s vs e2e {e2e:.3f}s")
EOF

# Online serving bench: same one-JSON-line contract; vs_baseline is the
# micro-batch / batch-of-1 throughput ratio under open-loop Poisson load.
# BENCH_SPEC_K/BENCH_KV_DTYPE are pinned: the contract below asserts the
# spec/quant sections, so the ambient environment must not disable them.
# BENCH_AUTOSCALE=1: the elastic-autoscaling section must emit scale
# events and the replica trajectory for the contract below.
# BENCH_DISAGG=1: the disaggregated-serving section must show the
# 3072-token prompt stream NOT moving interactive p95 past the
# colocated stall, and the int8 handoff moving >=3.5x fewer bytes.
# BENCH_PARK_DEPTH: the tiered-KV section must show turn-2 resume
# beating re-prefill at both depths with >=4x device-only sessions
# parked per chip.
# BENCH_ROUTERS=2: the scaled-router-tier section must show N=2
# placement agreement ~1, digest deltas >=10x smaller than wholesale
# per refresh, and the N=2 hit rate within 10% of single-router.
# BENCH_TENANTS=3: the multi-tenant QoS section must show the worst
# victim p95 within 10% of its flooder-free baseline while the
# flooder's ~10x overage sheds typed, and a driven brownout episode
# walking the ladder up and back to 0.
JAX_PLATFORMS=cpu BENCH_REQUESTS=64 BENCH_SPEC_K=4 BENCH_KV_DTYPE=int8 \
  BENCH_AUTOSCALE=1 BENCH_DISAGG=1 BENCH_PARK_DEPTH=8,16 \
  BENCH_ROUTERS=2 BENCH_TENANTS=3 \
  python bench_serving.py | tail -1 | python -c '
import json, os, sys
rec = json.loads(sys.stdin.readline())
assert {"metric", "value", "unit"} <= rec.keys(), rec
# a CPU contract smoke is not a device measurement and must not look
# like one: no vs_baseline, no per-chip metric name
assert "vs_baseline" not in rec and "/chip" not in rec["metric"], rec
assert rec["metric"].startswith("contract smoke"), rec
assert "micro-batch" in rec["metric"], rec
# the serving spine must attribute the run: admission, latency, occupancy
obs = rec["observability"]
for key in ("sparkdl_queue_submitted_total", "sparkdl_serving_requests_total",
            "sparkdl_serving_latency_seconds",
            "sparkdl_serving_batch_occupancy_pct"):
    assert key in obs, (key, sorted(obs))
# ISSUE 3: serving dispatches counted + overhead share attributed
assert rec["dispatch_count"] > 0, rec
assert "sparkdl_dispatch_seconds" in obs, sorted(obs)
# ISSUE 4: async-completion + replica fields ride the artifact
assert 0 <= rec["fetch_wait_share"] <= 1, rec["fetch_wait_share"]
assert rec["replica_count"] == 1, rec["replica_count"]
assert "sparkdl_fetch_wait_seconds" in obs, sorted(obs)
# ISSUE 9: declared SLO (objective + rolling burn) and flight-ring volume
slo = rec["slo"]
assert slo["latency"]["threshold_s"] > 0, slo
assert 0 < slo["latency"]["target"] < 1, slo
assert slo["latency"]["burn_rate"] is not None, slo
assert slo["availability"]["burn_rate"] is not None, slo
assert isinstance(rec["flight_events_total"], int), rec["flight_events_total"]
assert rec["flight_events_total"] > 0, "flight ring saw no events"
# ISSUE 10: paged-KV section — shared-prefix hit rate, block accounting,
# chunked prefill
assert rec["prefix_hit_rate"] > 0.5, rec["prefix_hit_rate"]
assert rec["kv_blocks_used"] > 0, rec["kv_blocks_used"]
assert rec["prefill_chunks"] > 0, rec["prefill_chunks"]
assert "sparkdl_kv_blocks_used" in obs, sorted(obs)
assert "sparkdl_prefix_hits_total" in obs, sorted(obs)
# ISSUE 12: speculative decode + quantized KV — acceptance/dispatch
# amortization and the capacity ratio embedded in the JSON line, spec
# tokens bitwise vs k=1, strictly fewer decode dispatches
sd = rec["spec_decode"]
assert sd["spec_bitwise_vs_k1"] is True, sd
assert 0 <= rec["spec_acceptance_rate"] <= 1, rec["spec_acceptance_rate"]
assert rec["spec_tokens_per_dispatch"] > 1, rec["spec_tokens_per_dispatch"]
assert sd["spec"]["decode_dispatches"] < sd["k1"]["decode_dispatches"], sd
assert rec["kv_capacity_ratio"] >= 2.0, rec["kv_capacity_ratio"]
assert 0 <= sd["kv_quant"]["token_agreement_vs_fp32"] <= 1, sd
assert "sparkdl_spec_proposed_total" in obs, sorted(obs)
assert "sparkdl_spec_accepted_total" in obs, sorted(obs)
assert "sparkdl_kv_pool_dtype" in obs, sorted(obs)
# ISSUE 13: sequence-parallel long-context prefill — sp axis, shard
# grain, measured speedup (the acceptance bar: sp=2 prefill seconds
# <= 0.75x sp=1, i.e. speedup >= 1.333), bitwise verdict, sp metrics
spf = rec["sp_prefill"]
assert rec["sp_axis"] == 2, rec["sp_axis"]
assert rec["prefill_shard_tokens"] > 0, rec
assert spf["sp_bitwise_vs_sp1"] is True, spf
# the wall-clock bar needs real parallelism: two sp shards on a
# single-core harness just interleave (the PERF.md load-sensitivity
# note), so the 1.333x floor only applies when >=2 CPUs are visible
if (os.cpu_count() or 1) >= 2:
    assert rec["sp_prefill_speedup"] >= 1.333, spf
else:
    assert rec["sp_prefill_speedup"] > 0, spf
assert "sparkdl_sp_ring_steps_total" in obs, sorted(obs)
assert "sparkdl_sp_permute_bytes_total" in obs, sorted(obs)
assert "sparkdl_sp_shard_imbalance" in obs, sorted(obs)
# ISSUE 14: multi-host fabric — the cache-aware router must beat
# round-robin prefix hit rate on the shared-prefix fleet workload,
# with p95s measured for both, and the fabric metric families live
fb = rec["fabric"]
assert rec["fabric_hosts"] == 2, rec["fabric_hosts"]
assert rec["fabric_hit_rate_routed"] > rec["fabric_hit_rate_rr"], fb
assert rec["fabric_hit_rate_routed"] > 0.5, fb
assert rec["fabric_p95_ms_routed"] > 0, fb
assert rec["fabric_p95_ms_rr"] > 0, fb
assert sum(fb["routed"]["routed_per_host"].values()) >= \
    fb["requests_per_round"], fb
assert "sparkdl_fabric_routed_total" in obs, sorted(obs)
assert "sparkdl_fabric_affinity_hits_total" in obs, sorted(obs)
assert "sparkdl_fabric_digest_blocks" in obs, sorted(obs)
# ISSUE 15: elastic autoscaling — the stepped load must produce scale
# events with a visible replica trajectory (up during the burst, back
# down after), SLO burn sampled before/after, and the autoscale metric
# families live on the spine
au = rec["autoscale"]
assert rec["scale_events"] >= 2, rec["scale_events"]
traj = rec["replica_trajectory"]
assert max(traj) >= 2, traj          # the burst scaled the pool up
assert au["replicas_final"] == 1, au  # and the drop scaled it back
sba = rec["slo_burn_before_after"]
assert sba["before"] is not None and sba["after"] is not None, sba
assert au["controller"]["state"] == "ok", au["controller"]
assert "sparkdl_autoscale_decisions_total" in obs, sorted(obs)
assert "sparkdl_autoscale_replicas" in obs, sorted(obs)
assert "sparkdl_autoscale_ticks_total" in obs, sorted(obs)
# ISSUE 16: disaggregated serving — the long-prompt stream must not
# move interactive p95 past the colocated stall (ratio >= 1), the
# split stays bitwise, the int8 handoff moves >= 3.5x fewer bytes
# than fp32, and the handoff metric families are live on the spine
dg = rec["disagg"]
assert dg["long_prompt_len"] >= 3072, dg
assert rec["decode_p95_colocated_vs_disagg"] >= 1.0, dg
assert dg["split_bitwise_vs_colocated"] is True, dg
assert rec["handoff_seconds_p50"] > 0, dg
assert rec["handoff_bytes"]["fp32_over_int8"] >= 3.5, dg
assert dg["handoffs"] >= dg["interactive_requests"], dg
assert "sparkdl_disagg_handoffs_total" in obs, sorted(obs)
assert "sparkdl_disagg_handoff_bytes_total" in obs, sorted(obs)
assert "sparkdl_disagg_handoff_seconds" in obs, sorted(obs)
# ISSUE 17: per-phase latency attribution — all five phases observed
# with non-zero medians, registry-sourced, and the p50s telescope to
# the measured interactive e2e median (generous bound: histogram
# percentiles are bucket-interpolated and the warmup/long-prompt
# crossings ride the same series)
pb = rec["phase_breakdown"]
assert pb is not None, "phase_breakdown missing from disagg artifact"
assert [(r["phase"], r["tier"]) for r in pb["phases"]] == [
    ("queue", "prefill"), ("compute", "prefill"), ("wire", "handoff"),
    ("queue", "decode"), ("compute", "decode")], pb
assert all(r["observations"] > 0 for r in pb["phases"]), pb
assert all(r["p50_s"] > 0 for r in pb["phases"]), pb
assert pb["interactive_p50_s"] > 0, pb
assert abs(pb["sum_p50_s"] - pb["interactive_p50_s"]) <= \
    0.5 * pb["interactive_p50_s"] + 0.05, pb
assert "sparkdl_request_phase_seconds" in obs, sorted(obs)
# ISSUE 18: tiered KV parking — resuming a parked conversation must
# beat re-prefilling its transcript at EVERY swept depth, the host
# tier must hold >= 4x the sessions device HBM alone keeps live, no
# park fell back, and the tier metric families ride the spine
pk = rec["park"]
assert len(pk["depths"]) >= 2, pk
for d in pk["depths"]:
    assert d["turn_resume_p50_ms"] < d["reprefill_p50_ms"], d
    assert d["parked_sessions_per_chip"] >= \
        4 * pk["device_live_sessions"], d
    assert d["tier_blocks"]["host"] > 0, d
    assert d["unparks"] > 0, d
    assert d["park_fallbacks"] == 0, d
assert rec["turn_resume_p50_ms"] < rec["reprefill_p50_ms"], rec
assert rec["parked_sessions_per_chip"] >= \
    4 * pk["device_live_sessions"], rec
assert "sparkdl_kv_tier_blocks" in obs, sorted(obs)
assert "sparkdl_kv_parks_total" in obs, sorted(obs)
assert "sparkdl_kv_unparks_total" in obs, sorted(obs)
# ISSUE 19: scaled router tier — cross-router placement agreement is
# arithmetic (~1.0), steady-state digest deltas move >=10x fewer
# bytes per refresh than the wholesale-forced control at the same
# cadence, N=2 prefix hit rate stays within 10% of single-router,
# p95 measured at both N, and the new families ride the spine
rt = rec["router_tier"]
assert rec["router_agreement_rate"] >= 0.99, rt
assert rec["digest_delta_bytes_per_s"] > 0, rt
assert rec["digest_wholesale_bytes_per_s"] > 0, rt
assert rt["delta_vs_wholesale_per_refresh"] >= 10.0, rt
assert rt["hit_rate_n_vs_1"] >= 0.9, rt
assert rec["router_p95_ms_n1"] > 0, rt
assert rec["router_p95_ms_n"] > 0, rt
assert rt["scaled"]["routers"] >= 2, rt
assert "sparkdl_fabric_digest_delta_bytes_total" in obs, sorted(obs)
assert "sparkdl_fabric_digest_delta_applied_total" in obs, sorted(obs)
assert "sparkdl_fabric_router_dispatch_total" in obs, sorted(obs)
# ISSUE 20: multi-tenant QoS — the worst victim p95 must stay within
# 10% of its flooder-free baseline (and compliance within 10%) while
# the flooder is offered >=3x what its quota admits and its overage
# sheds typed at the door; the driven brownout episode must step the
# ladder to at least shed_background (shedding background submits at
# every raised level) and recover to 0; tenant + overload metric
# families live on the spine
tn = rec["tenancy"]
assert rec["tenant_isolation_ratio"] <= 1.10, tn
assert tn["compliance_ratio"] >= 0.90, tn
fl = tn["storm"]["flooder"]
assert fl["offered"] >= 3 * max(1, fl["admitted"]), fl
assert fl["shed"] > 0, fl
assert 0 < rec["shed_share"] < 1, rec["shed_share"]
assert max(rec["brownout_levels"]) >= 1, rec["brownout_levels"]
assert rec["brownout_levels"][-1] == 0, rec["brownout_levels"]
assert sum(tn["brownout_sheds_per_level"].values()) >= 1, tn
assert "sparkdl_tenant_admitted_total" in obs, sorted(obs)
assert "sparkdl_tenant_shed_total" in obs, sorted(obs)
assert "sparkdl_tenant_latency_seconds" in obs, sorted(obs)
assert "sparkdl_overload_level" in obs, sorted(obs)
assert "sparkdl_overload_transitions_total" in obs, sorted(obs)
assert "sparkdl_overload_shed_total" in obs, sorted(obs)
print("bench_serving contract OK (snapshot + slo + flight + kv + spec "
      "+ sp + fabric + autoscale + disagg + phases + park + router "
      "tier + tenancy embedded)")
'

# Paged-KV smoke (ISSUE 10): (a) a shared-prefix workload through the
# engine must hit the prefix cache on >50% of prompt tokens and
# stay BITWISE identical to generate; (b) with a fault plan
# injecting kv.alloc exhaustion, admissions DEFER (no request fails),
# /healthz degrades while the streak lasts, and the flight recorder
# auto-writes a postmortem whose engine context carries the block-pool
# state; (c) peak block usage stays token-bound, far under every slot
# at max_len.
FLIGHT_DIR=$(mktemp -d)
JAX_PLATFORMS=cpu \
SPARKDL_TPU_FAULT_PLAN="kv.alloc:RuntimeError@3*6" \
SPARKDL_TPU_FLIGHT_DIR="$FLIGHT_DIR" python - "$FLIGHT_DIR" <<'EOF'
import glob, json, sys, time
import numpy as np
import jax; jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
from sparkdl_tpu.models.gpt import GPTConfig, GPTLMHeadModel, generate
from sparkdl_tpu.observability.flight import flight_recorder, healthz_report
from sparkdl_tpu.serving import ContinuousGPTEngine

flight_recorder().configure(settle_s=0.05, min_interval_s=0.0)
cfg = GPTConfig.tiny()
model = GPTLMHeadModel(cfg)
variables = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
rng = np.random.default_rng(7)
shared = rng.integers(1, cfg.vocab_size, 8).tolist()
cases = [(shared + rng.integers(1, cfg.vocab_size, 3).tolist(), 5)
         for _ in range(8)]

def run():
    eng = ContinuousGPTEngine(
        cfg, variables, n_slots=2, max_len=32,
        kv_block_size=4, prefill_chunk=8, idle_wait_s=0.001)
    futs = [eng.submit(p, n) for p, n in cases]
    outs = [np.asarray(f.result(timeout=60)) for f in futs]
    snap = eng.snapshot()
    eng.close()
    return outs, snap

# (b) first, the fault plan: the 3rd+ allocations fail 6 times -> the
# run below defers (streak >= 3 triggers the postmortem) yet
# every request completes
outs, snap = run()
for (p, n), got in zip(cases, outs):
    want = generate(model, variables, jnp.asarray([p], jnp.int32), n)
    assert np.array_equal(got, np.asarray(want[0, len(p):])), \
        "engine diverged from generate"
kv = snap["kv"]
hits, misses = kv["prefix_hits"], kv["prefix_misses"]
hit_rate = hits / (hits + misses)
assert hit_rate > 0.5, (hits, misses)
assert kv["deferrals_total"] >= 3, kv
# (c) token-bound memory: every slot at max_len is n_slots * max_len
# columns; the peak is the live requests' worst case
all_slots_full = 2 * (32 // 4)
assert kv["blocks_used"] < all_slots_full, kv
# healthz while a streak is LIVE (deterministic manual ticks: a 2-block
# pool, one request holding both, a second deferring): degraded — never
# unhealthy, it self-recovers as the blocker retires
eng = ContinuousGPTEngine(
    cfg, variables, n_slots=2, max_len=32, kv_block_size=16,
    kv_blocks=2, auto_start=False)
blocker = eng.submit([5, 3, 9], 14)  # 17 tokens: the whole pool
eng.tick()
starved = eng.submit([1, 4], 4)
eng.tick(); eng.tick()
assert healthz_report()["status"] == "degraded", healthz_report()
while not (blocker.done() and starved.done()):
    eng.tick()
eng.close()
assert healthz_report()["status"] == "ok", healthz_report()
# (a+b) postmortem written by the exhaustion streak, carrying pool state
time.sleep(0.3)
bundles = glob.glob(sys.argv[1] + "/flight-*.json")
assert bundles, "no postmortem bundle written"
# the FIRST bundle is the fault-plan streak's, written while the
# serving engine was live (later ones may come from the manual-tick
# healthz demo above, whose engine closes before its settle expires)
bundle = json.load(open(sorted(bundles)[0]))
assert bundle["reason"] == "kv.pool_exhausted", bundle["reason"]
ctx_pools = [c.get("kv_pool") for c in bundle["context"].values()
             if isinstance(c, dict) and c.get("kv_pool")]
assert ctx_pools, "bundle context lacks block-pool state"
assert ctx_pools[0]["blocks_total"] > 0, ctx_pools
evs = [e for e in bundle["events"] if e["kind"] == "kv.admission_deferred"]
assert evs, "deferral events missing from the bundle ring"
print(f"paged-KV smoke OK: hit_rate {hit_rate:.2f} > 0.5, bitwise vs "
      f"generate, {kv['deferrals_total']} deferrals -> postmortem with pool "
      f"state, healthz degraded during streak")
EOF
rm -rf "$FLIGHT_DIR"

# Spec-decode smoke (ISSUE 12): (a) k=4 speculative decode must stay
# BITWISE identical to the spec-free engine — including while the env
# fault plan kills two verify dispatches mid-run (spec.verify site:
# the engine falls back to plain decode for those ticks, zero lost
# requests); (b) an injected kv.quantize fault fails the compressed-
# pool build loudly while fp32 builds never hit the site; (c) the int8
# layout fits >= 2x fp32's live tokens in the same pool bytes.
JAX_PLATFORMS=cpu \
SPARKDL_TPU_FAULT_PLAN="spec.verify:RuntimeError@2*2" python - <<'EOF'
import numpy as np
import jax; jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
from sparkdl_tpu.models.gpt import GPTConfig, GPTLMHeadModel
from sparkdl_tpu.reliability.faults import inject
from sparkdl_tpu.serving import ContinuousGPTEngine
from sparkdl_tpu.serving.kv_blocks import kv_capacity_ratio

cfg = GPTConfig.tiny()
model = GPTLMHeadModel(cfg)
variables = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
cases = [([5, 3, 9, 2, 7], 9), ([6, 8, 6, 1, 6, 8, 6, 1], 10), ([1, 4], 7)]

def run(**kw):
    eng = ContinuousGPTEngine(cfg, variables, n_slots=2, max_len=32,
                              kv_block_size=4, prefill_chunk=8,
                              auto_start=False, **kw)
    futs = [eng.submit(p, n) for p, n in cases]
    while not all(f.done() for f in futs):
        eng.tick()
    eng.close()
    return [np.asarray(f.result(timeout=0)) for f in futs], eng

# the env plan arms spec.verify@2*2: the 2nd and 3rd verify attempts
# fail, those ticks serve plain decode, and the stream must STILL be
# bitwise vs the spec-free engine (which never hits the site)
base, _ = run()
spec, eng = run(spec_k=4)
for a, b in zip(base, spec):
    np.testing.assert_array_equal(a, b)
assert eng._spec_fallbacks == 2, eng._spec_fallbacks
assert eng._spec_dispatches >= 1
assert eng._spec_accepted > 0
with inject("kv.quantize:RuntimeError@1"):
    try:
        run(kv_dtype="int8")
        raise SystemExit("kv.quantize fault did not fail the build")
    except RuntimeError as e:
        assert "kv.quantize" in str(e), e
    run()  # fp32 build never hits the armed site
q, _ = run(kv_dtype="int8")  # compressed pool serves end to end
assert all(len(o) >= 1 for o in q)
assert kv_capacity_ratio(cfg, "int8") >= 2.0
print("spec-decode smoke OK: k=4 bitwise vs k=1 through 2 injected "
      "verify failures (zero lost requests), kv.quantize fails the "
      f"int8 build loudly, int8 fits {kv_capacity_ratio(cfg, 'int8'):.1f}x "
      "fp32 tokens per byte")
EOF

# Tiered-KV park smoke (ISSUE 18): (a) 8 sessions squeezed through a
# device pool holding ~2 live sessions park to the host tier under
# admission pressure (plus a park_cold flush), and every turn-2 resume
# stays BITWISE vs an engine that never parked; (b) the same soak with
# kv.park faults injected mid-run falls back to plain eviction — ZERO
# lost requests, tokens still bitwise, the failures on the flight ring.
JAX_PLATFORMS=cpu python - <<'EOF'
import numpy as np
import jax; jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
from sparkdl_tpu.models.gpt import GPTConfig, GPTLMHeadModel
from sparkdl_tpu.observability.flight import flight_recorder
from sparkdl_tpu.reliability.faults import inject
from sparkdl_tpu.serving import ContinuousGPTEngine

cfg = GPTConfig.tiny()
model = GPTLMHeadModel(cfg)
variables = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
kw = dict(n_slots=2, max_len=32, kv_block_size=4,
          idle_wait_s=0.0005)
rng = np.random.default_rng(18)
prompts = [rng.integers(1, cfg.vocab_size, 9).tolist() for _ in range(8)]

def two_turns(eng, park):
    replies = [eng.submit(p, 4).result(timeout=120).tolist()
               for p in prompts]
    if park:
        eng.park_cold()
    outs = [eng.submit(p + r + [5], 4).result(timeout=120).tolist()
            for p, r in zip(prompts, replies)]
    return replies, outs

# (a) pressure-parked sessions resume bitwise vs a roomy untiered pool
eng = ContinuousGPTEngine(cfg, variables, kv_blocks=10,
                          host_kv_blocks=64, **kw)
r_park, o_park = two_turns(eng, park=True)
tiers = eng._kv_snapshot()["tiers"]
assert tiers["parks"] > 0, tiers
assert tiers["unparks"] > 0, tiers
assert tiers["park_fallbacks"] == 0, tiers
eng.close()
ref = ContinuousGPTEngine(cfg, variables, kv_blocks=64, **kw)
r_ref, o_ref = two_turns(ref, park=False)
ref.close()
assert r_park == r_ref and o_park == o_ref, "parked-resume diverged"

# (b) torn parks mid-soak: eviction fallback, zero lost, still bitwise
base = flight_recorder().events_total
eng = ContinuousGPTEngine(cfg, variables, kv_blocks=10,
                          host_kv_blocks=64, **kw)
with inject("kv.park:RuntimeError@2*2"):
    r_chaos, o_chaos = two_turns(eng, park=True)
fb = eng._kv_snapshot()["tiers"]["park_fallbacks"]
assert fb >= 1, fb
eng.close()
assert r_chaos == r_ref and o_chaos == o_ref, "chaos soak diverged"
evs = [e for e in flight_recorder().events()
       if e["kind"] == "kv.park_failed" and e["seq"] > base]
assert evs, "kv.park failure missing from the flight ring"
print(f"tiered-KV park smoke OK: {tiers['parks']} parks / "
      f"{tiers['unparks']} unparks bitwise across 8 sessions on a "
      f"10-block device pool; {fb} torn parks fell back to eviction "
      "with zero lost requests")
EOF

# Multi-tenant QoS smoke (ISSUE 20): one engine under (a) a flooding
# tenant offered ~10x its admission quota — the overage sheds TYPED at
# the door (TenantThrottledError, never a timeout) while every accepted
# request completes; (b) an env-plan tenant.preempt fault on the first
# preemption attempt — the victim still re-queues (zero lost, tokens
# bitwise) and the SECOND attempt preempts clean; (c) a driven brownout
# ladder — level up under synthetic burn (healthz degraded, background
# shed), then recovery back to level 0 with healthz ok.
JAX_PLATFORMS=cpu \
SPARKDL_TPU_FAULT_PLAN="tenant.preempt:RuntimeError@1" python - <<'EOF'
import numpy as np
import jax; jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
from sparkdl_tpu.models.gpt import GPTConfig, GPTLMHeadModel, generate
from sparkdl_tpu.observability.flight import flight_recorder, healthz_report
from sparkdl_tpu.serving import ContinuousGPTEngine
from sparkdl_tpu.serving.tenancy import (
    PRIORITY_BACKGROUND, BrownoutShedError, OverloadController,
    TenantRegistry, TenantThrottledError, set_process_overload)

cfg = GPTConfig.tiny()
model = GPTLMHeadModel(cfg)
variables = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
reg = TenantRegistry(latency_threshold_s=5.0)
reg.configure("offline", priority=PRIORITY_BACKGROUND)
reg.configure("flood", rate=5.0, burst=2)
eng = ContinuousGPTEngine(
    cfg, variables, n_slots=1, max_len=32, auto_start=False,
    kv_block_size=4, prefill_chunk=4, tenants=reg)
rng = np.random.default_rng(20)

def oracle(p, n):
    return np.asarray(generate(
        model, variables, jnp.asarray([p], jnp.int32), n)[0, len(p):])

def drain(futs):
    for _ in range(2000):
        eng.tick()
        if all(f.done() for f in futs):
            return
    raise SystemExit("engine never drained")

# (b) two preemption rounds: the env plan tears attempt #1 (victim
# re-queues anyway), attempt #2 preempts clean
base = flight_recorder().events_total
for _ in range(2):
    bg = rng.integers(1, cfg.vocab_size, 12).tolist()
    fg = rng.integers(1, cfg.vocab_size, 6).tolist()
    f_bg = eng.submit(bg, 4, tenant="offline")
    eng.tick()  # first chunk only: mid-prefill, the sole slot held
    f_fg = eng.submit(fg, 4, tenant="acme")
    drain([f_bg, f_fg])  # zero lost, both bitwise
    np.testing.assert_array_equal(f_fg.result(timeout=0), oracle(fg, 4))
    np.testing.assert_array_equal(f_bg.result(timeout=0), oracle(bg, 4))
kinds = [e["kind"] for e in flight_recorder().events()
         if e["seq"] > base and e["kind"].startswith("tenant.")]
assert "tenant.preempt_failed" in kinds, kinds   # round 1: torn
assert "tenant.preempted" in kinds, kinds        # round 2: clean

# (a) flooder storm: 40 offered against a burst-2 bucket; overage shed
# typed, every ACCEPTED request still completes with real tokens
p = rng.integers(1, cfg.vocab_size, 4).tolist()
accepted, shed = [], 0
for _ in range(40):
    try:
        accepted.append(eng.submit(p, 2, tenant="flood"))
    except TenantThrottledError:
        shed += 1
assert shed >= 30, f"flooder only shed {shed}/40"
drain(accepted)
for f in accepted:
    np.testing.assert_array_equal(f.result(timeout=0), oracle(p, 2))
snap = reg.snapshot()["flood"]
assert snap["shed"] == shed and snap["admitted"] == len(accepted), snap

# (c) brownout ladder: hot ticks step it up (healthz degraded,
# background shed at admission), quiet ticks walk it back to 0
ctrl = OverloadController(hysteresis=1, recovery_ticks=1,
                          cooldown_ticks=0)
prev = set_process_overload(ctrl)
try:
    ctrl.evaluate(burn_rate=10.0)
    assert ctrl.level >= 1
    assert healthz_report()["status"] == "degraded", healthz_report()
    try:
        eng.submit(p, 2, tenant="offline")
        raise SystemExit("brownout never shed the background submit")
    except BrownoutShedError as e:
        assert e.level == ctrl.level
    f_ok = eng.submit(p, 2, tenant="acme")  # interactive still admitted
    drain([f_ok])
    ctrl.evaluate(burn_rate=0.0, queue_frac=0.0)
    assert ctrl.level == 0
    assert healthz_report()["status"] == "ok", healthz_report()
finally:
    set_process_overload(prev)
eng.close()
print(f"tenant QoS smoke OK: torn preempt re-queued + clean preempt "
      f"(bitwise both rounds), flooder shed {shed}/40 typed with "
      f"{len(accepted)} accepted all exact, brownout stepped to "
      f"level>=1 (healthz degraded, background shed) and recovered")
EOF

# Fault-injection smoke (ISSUE 5): resumable_finetune survives an
# injected crash at step k and its per-step loss trajectory matches the
# uninterrupted run BITWISE; the disarmed fault_point must stay
# invisible next to a device dispatch (bench-guarded: per-call cost and
# its share of one measured BatchedRunner.run dispatch).
JAX_PLATFORMS=cpu python -c '
import tempfile, time
import numpy as np, jax; jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
from sparkdl_tpu.reliability import RetryPolicy, resumable_finetune
from sparkdl_tpu.reliability.faults import fault_point, inject
from sparkdl_tpu.train.finetune import batches_from_arrays, finetune_classifier
from sparkdl_tpu.transformers._inference import BatchedRunner

rng = np.random.default_rng(0)
params = {"w": jnp.asarray(rng.standard_normal((8, 3)) * 0.1, jnp.float32)}
data = {"x": rng.standard_normal((64, 8)).astype(np.float32),
        "labels": rng.integers(0, 3, 64).astype(np.int32)}
mk = lambda: batches_from_arrays(data, batch_size=16, epochs=2, seed=3)
_, base = finetune_classifier(lambda p, x: x @ p["w"], params, mk(),
                              learning_rate=0.1)
with tempfile.TemporaryDirectory() as d, inject("dispatch:RuntimeError@5"):
    _, got = resumable_finetune(
        lambda p, x: x @ p["w"], params, mk, checkpoint_dir=d,
        checkpoint_every=2, learning_rate=0.1,
        retry=RetryPolicy(max_attempts=3, base_delay_s=0.0,
                          sleep=lambda s: None))
assert [(h["step"], h["loss"], h["accuracy"]) for h in got] == \
    [(h["step"], h["loss"], h["accuracy"]) for h in base]  # bitwise
print("fault-injection smoke OK: crash@5 recovered, trajectory bitwise")

# disarmed overhead guard: per-call cost ~a global load + None test
n = 200_000
t0 = time.perf_counter()
for _ in range(n):
    fault_point("dispatch")
per_call = (time.perf_counter() - t0) / n
assert per_call < 2e-6, f"disarmed fault_point {per_call*1e9:.0f}ns/call"
w = jnp.asarray(rng.standard_normal((8, 8)), jnp.float32)
r = BatchedRunner(lambda b: jnp.tanh(b["x"] @ w), batch_size=8,
                  data_parallel=False)
rows = [{"x": rng.standard_normal(8).astype(np.float32)}
        for _ in range(64)]
list(r.run(iter(rows)))  # warm the jit cache
t0 = time.perf_counter()
list(r.run(iter({"x": row["x"]} for row in rows)))
per_dispatch = (time.perf_counter() - t0) / 8
assert per_call / per_dispatch < 0.01, (per_call, per_dispatch)
print(f"fault_point overhead OK: {per_call*1e9:.0f}ns/call disarmed, "
      f"{100*per_call/per_dispatch:.3f}% of one BatchedRunner dispatch")
'
# Quarantine-reintegration smoke (ISSUE 5): a BENCH_REPLICAS=2 pool
# loses one executor mid-load — its riders are re-routed (zero errors),
# the replica is quarantined, and after the executor "restarts" a
# probation probe reintegrates it.
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=2 \
  BENCH_REPLICAS=2 python -c '
import os, threading, time
import numpy as np, jax; jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
from sparkdl_tpu.observability import registry
from sparkdl_tpu.serving import ReplicaPool, ServingEngine
from sparkdl_tpu.transformers._inference import BatchedRunner

n_replicas = int(os.environ["BENCH_REPLICAS"])
w = jnp.asarray(np.random.default_rng(0).standard_normal((8, 8)),
                jnp.float32)
down = threading.Event()

class Killable:
    def __init__(self, inner, killable):
        self._inner, self._killable = inner, killable
        self.chunk_size = inner.chunk_size
    def run_batch(self, arrays):
        if self._killable and down.is_set():
            raise RuntimeError("executor down")
        return self._inner.run_batch(arrays)

made = []
def make_runner(device):
    r = Killable(BatchedRunner(lambda b: jnp.tanh(b["x"] @ w),
                               batch_size=8, data_parallel=False,
                               device=device), killable=not made)
    made.append(r)
    return r

pool = ReplicaPool(make_runner=make_runner, n_replicas=n_replicas,
                   max_failures=2, probation_s=0.05, probation_max_s=1.0)
pool.warmup({"x": np.zeros((8, 8), np.float32)})
with ServingEngine(pool, max_wait_s=0.002) as eng:
    down.set()  # kill replica 0 mid-load
    futs = [eng.submit({"x": np.full((8,), float(i), np.float32)})
            for i in range(48)]
    for i, f in enumerate(futs):  # every rider re-routed, zero errors
        np.testing.assert_allclose(
            f.result(timeout=60),
            np.tanh(np.full((8,), float(i), np.float32) @ np.asarray(w)),
            rtol=1e-5)
    assert pool.snapshot()["healthy_count"] == n_replicas - 1
    down.clear()  # "restart" the executor; probation probes rejoin it
    deadline = time.monotonic() + 20.0
    while (pool.snapshot()["healthy_count"] < n_replicas
           and time.monotonic() < deadline):
        eng.submit({"x": np.zeros((8,), np.float32)}).result(timeout=60)
        time.sleep(0.02)
    snap = pool.snapshot()
pool.close()
assert snap["healthy_count"] == n_replicas, snap
reint = registry().get("sparkdl_replica_reintegrated_total")
assert reint is not None and reint.snapshot_values().get("", 0) >= 1
print(f"quarantine-reintegration smoke OK: {n_replicas}-replica pool "
      "lost one executor, riders re-routed, replica rejoined via "
      "probation probe")
'

# Flight-recorder chaos smoke (ISSUE 9 acceptance): a fault-plan-injected
# replica failure under load must (a) cost no client a result (re-route),
# (b) quarantine the victim replica, and (c) auto-dump a postmortem
# bundle whose event ring holds the fault injection + the quarantine
# transition and whose trace section holds the re-routed request's FULL
# trace (queue wait, failed replica dispatch, re-routed dispatch,
# terminal request span).
FLIGHT_DIR=$(mktemp -d)
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=2 \
  SPARKDL_TPU_TRACE=1 SPARKDL_TPU_FLIGHT_DIR="$FLIGHT_DIR" \
  SPARKDL_TPU_FAULT_PLAN="replica.execute:RuntimeError@3" python -c '
import glob, json, os, time
import numpy as np, jax; jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
from sparkdl_tpu.observability.flight import flight_recorder
from sparkdl_tpu.serving import ReplicaPool, ServingEngine

flight_recorder().configure(settle_s=0.3, min_interval_s=0.0)
w = jnp.asarray(np.random.default_rng(0).standard_normal((8, 8)),
                jnp.float32)
# probation off: the quarantine must be a stable end state to assert on
pool = ReplicaPool(lambda b: jnp.tanh(b["x"] @ w), batch_size=8,
                   max_failures=1, probation_s=None)
pool.warmup({"x": np.zeros((8, 8), np.float32)})  # site hits 1 and 2
with ServingEngine(pool, max_wait_s=0.002) as eng:
    futs = [eng.submit({"x": np.full((8,), float(i), np.float32)})
            for i in range(48)]
    for i, f in enumerate(futs):  # hit 3 injects; its riders re-route
        np.testing.assert_allclose(
            f.result(timeout=60),
            np.tanh(np.full((8,), float(i), np.float32) @ np.asarray(w)),
            rtol=1e-5)
    assert pool.snapshot()["healthy_count"] == 1, pool.snapshot()
    victim = None
    for f in futs:
        spans = eng.trace(f.request_id)
        failed = [s for s in spans if s["name"] == "serving.replica_batch"
                  and "error" in s["args"]]
        if failed:
            victim = (f.request_id, spans)
            break
    assert victim, "no request trace crossed the injected failure"
    rid, spans = victim
    names = {s["name"] for s in spans}
    assert {"serving.queue_wait", "serving.replica_batch",
            "serving.request"} <= names, names
    # the re-route shows as a SECOND replica dispatch in the same trace
    assert len([s for s in spans
                if s["name"] == "serving.replica_batch"]) >= 2, names
    deadline = time.monotonic() + 15.0
    paths = []
    while not paths and time.monotonic() < deadline:
        paths = glob.glob(os.path.join(
            os.environ["SPARKDL_TPU_FLIGHT_DIR"], "flight-*.json"))
        time.sleep(0.05)
    assert paths, "no postmortem bundle written"
    bundle = json.load(open(sorted(paths)[-1]))
pool.close()
assert bundle["reason"] == "replica_quarantined", bundle["reason"]
events = bundle["events"]
assert any(e["kind"] == "fault.injected"
           and e.get("site") == "replica.execute" for e in events), \
    sorted({e["kind"] for e in events})
assert any(e["kind"] == "replica.quarantined" for e in events)
bundle_spans = {e["args"]["span_id"] for e in bundle["trace_events"]}
missing = [s["name"] for s in spans
           if s["args"]["span_id"] not in bundle_spans]
assert not missing, f"victim trace spans missing from bundle: {missing}"
assert any(p.get("healthy_count") == 1
           for p in bundle["context"].values()
           if isinstance(p, dict) and "healthy_count" in p), \
    "bundle lacks the pool quarantine state"
print(f"flight-recorder chaos smoke OK: injected replica fault -> "
      f"quarantine + postmortem bundle with {len(events)} events, "
      f"victim request {rid} trace ({len(spans)} spans) fully captured")
'
rm -rf "$FLIGHT_DIR"
# Disabled-path overhead guard (ISSUE 9 acceptance): flight-recorder
# append + per-request trace-ID plumbing (tracing OFF) must together
# stay under 1% of one BatchedRunner dispatch.
JAX_PLATFORMS=cpu python -c '
import time
import numpy as np, jax; jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
from sparkdl_tpu.observability import tracing
from sparkdl_tpu.observability.flight import flight_recorder
from sparkdl_tpu.transformers._inference import BatchedRunner

assert not tracing.tracing_enabled()
rec = flight_recorder()
n = 200_000
t0 = time.perf_counter()
for _ in range(n):
    rec.record("overhead.guard", site="x")
per_append = (time.perf_counter() - t0) / n
assert per_append < 2e-6, f"flight append {per_append*1e9:.0f}ns/event"
t0 = time.perf_counter()
for _ in range(n):
    rid = tracing.next_request_id()
    tracing.request_context(rid)  # None with tracing off: id is the cost
per_rid = (time.perf_counter() - t0) / n
assert per_rid < 2e-6, f"trace-ID plumbing {per_rid*1e9:.0f}ns/request"
rng = np.random.default_rng(0)
w = jnp.asarray(rng.standard_normal((8, 8)), jnp.float32)
r = BatchedRunner(lambda b: jnp.tanh(b["x"] @ w), batch_size=8,
                  data_parallel=False)
rows = [{"x": rng.standard_normal(8).astype(np.float32)}
        for _ in range(64)]
list(r.run(iter(rows)))  # warm the jit cache
t0 = time.perf_counter()
list(r.run(iter({"x": row["x"]} for row in rows)))
per_dispatch = (time.perf_counter() - t0) / 8
share = (per_append + per_rid) / per_dispatch
assert share < 0.01, (per_append, per_rid, per_dispatch)
print(f"flight/trace disabled-path overhead OK: append "
      f"{per_append*1e9:.0f}ns + request-id {per_rid*1e9:.0f}ns = "
      f"{100*share:.3f}% of one BatchedRunner dispatch")
'

# Partitioner/ZeRO smoke (ISSUE 6): an fsdp=2 finetune on 2 forced
# virtual CPU devices must (a) measure per-chip optimizer-state bytes
# BELOW the replicated dp baseline (registry gauge
# sparkdl_opt_state_bytes{axis}) and (b) keep the per-step loss
# trajectory at parity with the dp run.
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=2 python -c '
import numpy as np, jax; jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
from sparkdl_tpu.observability import registry
from sparkdl_tpu.partition import DataParallelPartitioner, make_mesh
from sparkdl_tpu.train.finetune import batches_from_arrays, finetune_classifier

rng = np.random.default_rng(0)
params = {"w": jnp.asarray(rng.standard_normal((8, 4)) * 0.1, jnp.float32),
          "b": jnp.zeros((4,), jnp.float32)}
data = {"x": rng.standard_normal((64, 8)).astype(np.float32),
        "labels": rng.integers(0, 4, 64).astype(np.int32)}
mk = lambda: batches_from_arrays(data, batch_size=16, epochs=2, seed=3)
apply_fn = lambda p, x: x @ p["w"] + p["b"]

_, base = finetune_classifier(apply_fn, params, mk(), learning_rate=0.1)
zero = DataParallelPartitioner(make_mesh(dp=1, fsdp=2), zero_axis="fsdp")
_, got = finetune_classifier(apply_fn, params, mk(), learning_rate=0.1,
                             partitioner=zero)
bytes_by_axis = registry().get(
    "sparkdl_opt_state_bytes").labelled_values("axis")
assert bytes_by_axis["fsdp"] < bytes_by_axis["replicated"], bytes_by_axis
np.testing.assert_allclose([h["loss"] for h in got],
                           [h["loss"] for h in base], rtol=2e-4)
assert [h["step"] for h in got] == [h["step"] for h in base]
b_sharded, b_repl = bytes_by_axis["fsdp"], bytes_by_axis["replicated"]
print(f"partitioner ZeRO smoke OK: opt-state {b_sharded:.0f}B/chip sharded "
      f"vs {b_repl:.0f}B replicated, fsdp=2 trajectory at parity with dp")
'
# Metrics-endpoint smoke (ISSUE 2): start the exporter the way production
# does (SPARKDL_TPU_METRICS_PORT -> maybe_start_metrics_server), scrape
# once, assert well-formed Prometheus exposition text.
JAX_PLATFORMS=cpu SPARKDL_TPU_METRICS_PORT=0 python -c '
import json, urllib.request
from sparkdl_tpu.observability import maybe_start_metrics_server, registry
from sparkdl_tpu.observability import flight, slo
registry().counter("sparkdl_smoke_total", "endpoint smoke").inc(3)
srv = maybe_start_metrics_server()
assert srv is not None, "SPARKDL_TPU_METRICS_PORT=0 must start the server"
assert maybe_start_metrics_server() is srv, "must be idempotent"
body = urllib.request.urlopen(
    f"http://127.0.0.1:{srv.port}/metrics", timeout=5).read().decode()
assert "# TYPE sparkdl_smoke_total counter" in body, body
assert "sparkdl_smoke_total 3" in body, body
# ISSUE 9 endpoints: /slo.json lists registered trackers, /healthz
# aggregates reliability state, /debug/flight serves a live bundle
tracker = slo.register(slo.SLOTracker(slo.SLO(
    name="smoke", latency_threshold_s=0.1)))
doc = json.loads(urllib.request.urlopen(
    f"http://127.0.0.1:{srv.port}/slo.json", timeout=5).read())
assert any(s.get("slo") == "smoke" for s in doc["slos"]), doc
slo.unregister(tracker)
hz = json.loads(urllib.request.urlopen(
    f"http://127.0.0.1:{srv.port}/healthz", timeout=5).read())
assert hz["status"] == "ok" and "retry_budget" in hz, hz
flight.record_event("endpoint.smoke")
fl = json.loads(urllib.request.urlopen(
    f"http://127.0.0.1:{srv.port}/debug/flight", timeout=5).read())
assert any(e["kind"] == "endpoint.smoke"
           for e in fl["bundle"]["events"]), fl["bundle"]["events"][-3:]
srv.close()
print("metrics endpoint smoke OK (/metrics /slo.json /healthz /debug/flight)")
'
# Autotune smoke (ISSUE 8): a deliberately slow synthetic producer under
# the tuner must reach the throughput of the best hand-picked setting
# within a bounded number of decisions, and a fully pinned run must make
# ZERO tuning decisions.
JAX_PLATFORMS=cpu python -c '
import time
from sparkdl_tpu.ingest import AutoTuner, Pipeline

def slow_fn(x):
    time.sleep(0.003)  # the synthetic bottleneck: 3 ms of host work/item
    return x

def run(parallelism, depth, tuner=None, n=400, tail=120):
    pipe = (Pipeline(range(n), name="smoke")
            .map(slow_fn, parallelism=parallelism, max_parallelism=4,
                 name="work")
            .prefetch(depth, transfer=lambda x: x))
    if tuner is not None:
        pipe.autotune(tuner)
        tuner.start()
    tail_t0 = None
    for i, _ in enumerate(pipe):
        if i == n - tail - 1:
            tail_t0 = time.perf_counter()
    rate = tail / (time.perf_counter() - tail_t0)
    if tuner is not None:
        tuner.stop()
    return rate

# best hand-picked setting: parallelism 4 (the map stage is the
# bottleneck; 4 workers x 3ms ≈ 1333 items/s vs 333 at parallelism 1)
hand = run(parallelism=4, depth=2)

tuned_tuner = AutoTuner(interval_s=0.05, hysteresis=2, cooldown_ticks=1)
tuned = run(parallelism=None, depth=None, tuner=tuned_tuner)
assert tuned_tuner.decision_count >= 1, "tuner never acted on starvation"
assert tuned_tuner.decision_count <= 12, tuned_tuner.decision_count
assert tuned >= 0.6 * hand, (
    f"autotuned steady-state {tuned:.0f}/s < 0.6x hand-tuned {hand:.0f}/s "
    f"after {tuned_tuner.decision_count} decisions")

pinned_tuner = AutoTuner(interval_s=0.05, hysteresis=2, cooldown_ticks=1)
run(parallelism=4, depth=2, tuner=pinned_tuner)  # everything pinned
assert pinned_tuner.decision_count == 0, (
    "pinned knobs moved", pinned_tuner.decision_count)
print(f"autotune smoke OK: hand-tuned {hand:.0f}/s, autotuned "
      f"{tuned:.0f}/s steady-state in {tuned_tuner.decision_count} "
      "decisions; fully pinned run made 0 decisions")
'
# Secondary benches keep the same one-JSON-line contract (values are
# CPU-smoke only; the real numbers come from the chip — PERF.md).
# ISSUE 8: both now embed the autotuner decision count + steady-state
# knob values (registry-sourced) next to the registry snapshot.
for b in bench_tf_ingest.py bench_hostfed.py; do
  JAX_PLATFORMS=cpu BENCH_IMAGES=64 BENCH_BATCH=16 python "$b" | tail -1 | python -c '
import json, sys
rec = json.loads(sys.stdin.readline())
assert {"metric", "value", "unit"} <= rec.keys(), rec
# a CPU contract smoke is not a device measurement and must not look
# like one: no vs_baseline, no per-chip metric name
assert "vs_baseline" not in rec and "/chip" not in rec["metric"], rec
assert rec["metric"].startswith("contract smoke"), rec
at = rec["autotune"]
assert isinstance(at["decisions"], int), at
assert isinstance(at["knobs"], dict) and at["knobs"], at
assert "sparkdl_autotune_knob" in rec["observability"], sorted(
    rec["observability"])
print("contract OK:", rec["metric"][:60],
      "autotune:", at["decisions"], "decisions,",
      len(at["knobs"]), "knobs")
'
done

# The driver's EXACT call form: import the module, call dryrun_multichip(8)
# with however many devices this host exposes (1 here — JAX_PLATFORMS=cpu
# without a forced device count), so the self-provisioning re-exec path is
# what gets tested, not an env-prepared shortcut. SPARKDL_TPU_CHAIN_K=2
# pins K=2 for every auto-mode chainer (ISSUE 3): the regimes must all
# still pass with fused dispatch enabled wherever it auto-applies.
JAX_PLATFORMS=cpu SPARKDL_TPU_CHAIN_K=2 python -c 'import __graft_entry__ as g; g.dryrun_multichip(8)'
SDL_SKIP_DRYRUN=1 python __graft_entry__.py
