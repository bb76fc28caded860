"""Headline benchmark: InceptionV3 featurization throughput (images/sec/chip).

Prints exactly ONE JSON line
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}
vs_baseline is against the 10,000 images/sec/chip target from BASELINE.md
(the reference publishes no numbers of its own).

Runs on the TPU and nowhere else: no TPU backend is an error
(runtime/chip.py ``require_tpu``). The one exception is the contract
smoke in run-tests.sh, which exports ``JAX_PLATFORMS=cpu``; that run
checks the shape of the JSON line at a tiny size and prints neither a
per-chip metric name nor a ``vs_baseline``. Measures the steady-state
jitted hot loop — on-device uint8 -> preprocess -> bf16 InceptionV3
features — with the batch device-resident; bench_hostfed.py measures the
host-fed path.
"""

import json
import os
import time

import numpy as np


def main() -> None:
    import jax
    import jax.numpy as jnp

    from sparkdl_tpu.models.registry import build_flax_model
    from sparkdl_tpu.ops.preprocess import PREPROCESSORS
    from sparkdl_tpu.runtime.chip import (
        configure_compile_cache,
        require_tpu,
        smoke_label,
    )

    on_accel = require_tpu(explicit_cpu_ok=True)
    configure_compile_cache()
    platform = jax.default_backend()
    batch = int(os.environ.get("BENCH_BATCH", 128 if on_accel else 8))
    steps = int(os.environ.get("BENCH_STEPS", 50 if on_accel else 3))
    # The benched unit chains K batches per dispatch (every image still
    # processed exactly once per step; PERF.md "scan-K" has the
    # earlier-installation measurements). Since ISSUE 8 the chaining
    # runs through the PRODUCTION ScanChainer (runtime/dispatch), not a
    # hand-rolled scan, so the measured gap is the real dispatch path's.
    # SPARKDL_TPU_CHAIN_K (the production pin) takes precedence over
    # BENCH_SCAN_K — the chainer fails loud on conflicting pins.
    scan_k = int(os.environ.get("SPARKDL_TPU_CHAIN_K")
                 or os.environ.get("BENCH_SCAN_K")
                 or (32 if on_accel else 1))
    size = 299 if on_accel else 128  # contract smoke: sane compile time

    dtype = jnp.bfloat16 if on_accel else jnp.float32
    module, variables = build_flax_model(
        "InceptionV3", weights=None, include_top=False, dtype=dtype
    )
    # 'tf' preprocessing folded into the stem weights (exact — see
    # ops/fold.py + tests/ops/test_fold.py): the program eats raw pixels,
    # saving one full-image elementwise pass per batch. On accelerators
    # the branch-merged eval forward (models/inception_fused.py,
    # oracle-tested identical) reads each mixed-block input once instead
    # of once per 1x1 head (+1.9% measured on the v5e).
    from sparkdl_tpu.models.inception_fused import (
        fused_inception_v3_features,
    )
    from sparkdl_tpu.ops.fold import fold_tf_preprocess

    variables = fold_tf_preprocess(variables)
    preprocess = PREPROCESSORS["identity"]

    if on_accel:
        def featurize_one(x):
            return fused_inception_v3_features(variables, x, dtype=dtype)
    else:
        def featurize_one(x):
            feats, _ = module.apply(
                variables, preprocess(x.astype(dtype)), train=False
            )
            return feats.astype(jnp.float32)

    # The production fused-dispatch layer (ISSUE 3 / PERF.md open
    # re-measure (a)): ScanChainer stacks the K staged batches and runs
    # one jitted lax.scan per dispatch — the exact path BatchedRunner
    # and finetune dispatch through, so the measured vs_baseline gap is
    # the real dispatch path's, not a bench-local harness's.
    from sparkdl_tpu.runtime.dispatch import ScanChainer

    chainer = ScanChainer(featurize_one, path="bench", chain_k=scan_k)

    rng = np.random.default_rng(0)
    xs_host = [
        rng.integers(0, 256, (batch, size, size, 3), dtype=np.uint8)
        for _ in range(scan_k)
    ]

    # Local multi-chip DP (SURVEY.md 2.11a / transformers/_inference.py):
    # BENCH_DP_DEVICES=n shards the batch dim over an n-device dp mesh —
    # the committed input sharding makes jit compile the forward SPMD,
    # exactly how BatchedRunner feeds a multi-chip host. Default 1 keeps
    # the single-chip driver contract unchanged.
    dp = int(os.environ.get("BENCH_DP_DEVICES", "1"))
    if dp > 1:
        from sparkdl_tpu.runtime.mesh import data_parallel_mesh

        if dp > len(jax.devices()):
            raise SystemExit(
                f"BENCH_DP_DEVICES={dp} but only {len(jax.devices())} "
                "devices available"
            )
        if batch % dp:
            raise SystemExit(f"BENCH_BATCH {batch} not divisible by {dp}")
        mesh = data_parallel_mesh(jax.devices()[:dp])
        sharding = jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec("dp"))
        xs = [jax.device_put(x, sharding) for x in xs_host]
    else:
        xs = [jax.device_put(x) for x in xs_host]

    def stream(n_steps):
        # each timed "step" feeds the K staged batches once; with
        # chain_k pinned to K, map_stream fuses them into ONE dispatch
        for _ in range(n_steps):
            yield from xs

    # warmup / compile: one full chained dispatch
    last = None
    for last in chainer.map_stream(stream(1)):
        pass
    float(last.sum())

    from sparkdl_tpu.runtime.dispatch import dispatch_count

    d_before = dispatch_count("bench")
    t0 = time.perf_counter()
    for last in chainer.map_stream(stream(steps)):
        pass
    # Forced 4-byte read: the dependency chain pins all steps behind it.
    float(last.sum())
    dt = time.perf_counter() - t0

    images_per_sec = scan_k * batch * steps / dt
    target = 10_000.0
    # The hot loop stays uninstrumented (device-resident, no framework
    # staging on purpose); record the aggregate AFTER timing so the
    # artifact still carries the spine's view of the run.
    from sparkdl_tpu.observability import registry
    from sparkdl_tpu.observability.tracing import observe_stage
    from sparkdl_tpu.runtime.dispatch import (
        calibrate_dispatch_gap,
        overhead_share,
    )

    registry().counter(
        "sparkdl_bench_images_total", "images processed by bench.py"
    ).inc(scan_k * batch * steps)
    observe_stage("bench.featurize_step", dt / steps)
    # Dispatch spine (ISSUE 3 -> 8): the chainer records every dispatch
    # itself now (path="bench"); the timed delta is the real dispatch
    # count of the measured window, and the calibrated gap turns it into
    # the overhead share of the wall, so the trajectory captures
    # amortization, not just img/s.
    gap = calibrate_dispatch_gap()
    n_dispatches = dispatch_count("bench") - d_before
    # Static-analysis drift tracker (ISSUE 11): the artifact embeds the
    # linter's finding count over the package, so a rule regression shows
    # up in the bench trajectory like any perf regression (run after
    # timing; ~1-2s of host work, PERF.md "sparkdl-lint wall time").
    import sparkdl_tpu
    from sparkdl_tpu.lint import lint_paths

    pkg_dir = os.path.dirname(os.path.abspath(sparkdl_tpu.__file__))
    repo_root = os.path.dirname(pkg_dir)
    lint_targets = [pkg_dir] + [
        p for p in (os.path.join(repo_root, "tests"),)
        if os.path.isdir(p)  # fault plans live in the test tree
    ]
    lint_findings_total = len(
        lint_paths(lint_targets, root=repo_root).findings)
    # dp>1 reports AGGREGATE throughput; vs_baseline stays per-chip so the
    # number remains comparable to the single-chip target. A CPU contract
    # smoke carries neither a per-chip name nor a vs_baseline: its value
    # is XLA:CPU's, not a device metric.
    per_chip = on_accel and dp == 1
    shape = (f"({platform}, {size}px, batch {batch}"
             + (f", scan {scan_k}" if scan_k > 1 else "") + ")")
    print(
        json.dumps(
            {
                "metric": smoke_label(on_accel)
                          + "InceptionV3 featurization images/sec"
                          + ("/chip " if per_chip else
                             f" over {dp} devices " if dp > 1 else " ")
                          + shape,
                "value": round(images_per_sec, 1),
                "unit": "images/sec" + ("/chip" if per_chip else ""),
                **({"vs_baseline": round(images_per_sec / dp / target, 4)}
                   if on_accel else {}),
                "chain_k": scan_k,
                "dispatch_count": n_dispatches,
                "dispatch_gap_ms": round(gap * 1e3, 4),
                "overhead_share": round(
                    overhead_share(n_dispatches, dt, gap) or 0.0, 4
                ),
                "lint_findings_total": lint_findings_total,
                "observability": registry().snapshot(),
            }
        )
    )


if __name__ == "__main__":
    # SPARKDL_TPU_PROFILE=1: sample host thread stacks for the whole run
    # and drop a collapsed-stack file (flamegraph/speedscope) — ISSUE 9
    from sparkdl_tpu.observability.profiling import maybe_profile

    with maybe_profile("bench"):
        main()
