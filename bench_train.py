"""Secondary benchmark: ResNet50 training MFU (BASELINE.md north-star 2).

Prints one JSON line like bench.py. Measures the steady-state jitted
train step — bf16 ResNet50, SGD+momentum, device-resident batch — and
reports MFU via the framework's own StepMeter/compiled_flops meters
(observability.metrics), against the >=50% target from BASELINE.md.
TPU only (runtime/chip.py ``require_tpu``); under an exported
``JAX_PLATFORMS=cpu`` it is a contract smoke that prints no MFU, no
per-chip rate and no ``vs_baseline``.
"""

import json
import os
import time

import numpy as np


def main() -> None:
    import jax
    import jax.numpy as jnp
    import optax

    from sparkdl_tpu.models.resnet import ResNet50
    from sparkdl_tpu.observability.metrics import StepMeter, compiled_flops
    from sparkdl_tpu.train.vision import (
        make_resnet50_fused_train_step,
        make_vision_train_step,
    )

    from sparkdl_tpu.runtime.chip import (
        configure_compile_cache,
        require_tpu,
        smoke_label,
    )

    on_accel = require_tpu(explicit_cpu_ok=True)
    configure_compile_cache()
    platform = jax.default_backend()
    batch = int(os.environ.get("BENCH_BATCH", 256 if on_accel else 8))
    steps = int(os.environ.get("BENCH_STEPS", 10 if on_accel else 2))
    repeats = int(os.environ.get("BENCH_REPEATS", 3 if on_accel else 1))
    # BENCH_FUSED=1 runs the Pallas BN-epilogue step; NOT the default —
    # measured round 3, kernel islands inside the XLA conv program pay a
    # layout-conversion tax that outweighs the fused passes (PERF.md
    # "Round 3"). Default = the XLA lowering, the faster program today.
    fused = os.environ.get("BENCH_FUSED", "0") == "1"
    size = 224 if on_accel else 32
    dtype = jnp.bfloat16 if on_accel else jnp.float32

    model = ResNet50(num_classes=1000, include_top=True, dtype=dtype)
    variables = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, size, size, 3))
    )
    params, batch_stats = variables["params"], variables["batch_stats"]
    tx = optax.sgd(0.1, momentum=0.9)
    opt_state = tx.init(params)

    # Partitioner layer (ISSUE 6): the bench artifact carries the
    # partition geometry, the registry-sourced rule hit-counts, and the
    # measured per-chip optimizer-state bytes — so the ZeRO memory win
    # (BENCH_FSDP=N shards the momentum along fsdp) is a number in the
    # bench trajectory, not a claim.
    from sparkdl_tpu.partition import (
        DataParallelPartitioner,
        SingleDevicePartitioner,
        make_mesh,
        rule_hit_counts,
    )

    fsdp = int(os.environ.get("BENCH_FSDP", "1"))
    if fsdp > 1:
        # the benched loop runs the ZeRO layout for real: params
        # replicated, momentum sharded, update math sharded by XLA
        partitioner = DataParallelPartitioner(
            make_mesh(dp=-1, fsdp=fsdp, devices=jax.local_devices()),
            zero_axis="fsdp",
        )
        params = partitioner.shard_params(params)
        batch_stats = partitioner.shard_replicated(batch_stats)
        opt_state = partitioner.shard_opt_state(opt_state)
    else:
        # nothing committed: the bench stays the exact single-chip
        # program of the pre-partitioner trajectory, and the JSON line
        # honestly reports no partition axes
        partitioner = SingleDevicePartitioner()
    opt_state_bytes = partitioner.export_opt_state_bytes(opt_state)
    train_step = (
        make_resnet50_fused_train_step(
            tx, num_classes=1000, dtype=dtype, donate=False
        )
        if fused else make_vision_train_step(model, tx, donate=False)
    )
    # FLOPs are ALWAYS counted on the unfused (pure-XLA) step:
    # cost_analysis reports Pallas custom calls as 0 FLOPs, which would
    # silently understate the fused path's MFU — the same semantic
    # program must yield the same denominator either way.
    flops_step = make_vision_train_step(model, tx, donate=False)

    rng = np.random.default_rng(0)
    x = jax.device_put(rng.random((batch, size, size, 3), np.float32))
    y = jax.device_put(rng.integers(0, 1000, batch).astype(np.int32))

    flops_per_step = compiled_flops(
        flops_step, params, batch_stats, opt_state, x, y
    )
    if flops_per_step is None:
        raise SystemExit(
            "compiled_flops returned None (XLA gave no cost analysis for "
            "the train step): no MFU can be reported")
    meter = StepMeter(flops_per_step=flops_per_step, n_chips=1)

    # The benched unit chains `steps` train steps inside one jit via
    # lax.scan (state-carried, so iterations can't collapse), so the
    # per-dispatch overhead and the trailing read are amortized over
    # `steps`. State is donated per dispatch — the steady-state
    # production shape.
    from jax import lax

    def _step(carry, batch):
        p, bs, o = carry
        p, bs, o, loss = train_step(p, bs, o, *batch)  # inlines under jit
        return (p, bs, o), loss

    if fsdp > 1:
        # pin the carried state to its ZeRO layout from inside the trace
        # (partitioner.wrap_step): without the constraint XLA may pick a
        # replicated sharding for the scan carry, and the loop would not
        # run the sharded layout the JSON line reports
        carry_shardings = jax.tree_util.tree_map(
            lambda a: a.sharding, (params, batch_stats, opt_state)
        )
        _step = partitioner.wrap_step(_step, carry_shardings)

    def scanned(params, batch_stats, opt_state, x, y):
        def body(carry, _):
            return _step(carry, (x, y))

        (params, batch_stats, opt_state), losses = lax.scan(
            body, (params, batch_stats, opt_state), None, length=steps
        )
        return params, batch_stats, opt_state, losses[-1]

    scanned = jax.jit(scanned, donate_argnums=(0, 1, 2))

    # warmup / compile; the forced scalar read drains the queue before
    # timing starts.
    params, batch_stats, opt_state, loss = scanned(
        params, batch_stats, opt_state, x, y
    )
    float(loss)

    t0 = time.perf_counter()
    for _ in range(repeats):
        params, batch_stats, opt_state, loss = scanned(
            params, batch_stats, opt_state, x, y
        )
    float(loss)  # forced read: the dependency chain pins all steps behind it
    step_time = (time.perf_counter() - t0) / (steps * repeats)
    for _ in range(steps * repeats):
        meter.record(step_time, examples=batch)

    s = meter.summary()
    mfu = s.get("mfu")
    target = 0.50
    # Dispatch spine (ISSUE 3): each timed repeat was ONE dispatch fusing
    # `steps` scan-chained train steps; report the amortization the JSON
    # trajectory would otherwise lose.
    from sparkdl_tpu.runtime.dispatch import (
        calibrate_dispatch_gap,
        dispatch_count,
        overhead_share,
        record_dispatch,
    )

    total_wall = step_time * steps * repeats
    for _ in range(repeats):
        record_dispatch("train_bench", steps, total_wall / repeats)
    gap = calibrate_dispatch_gap()
    n_dispatches = dispatch_count("train_bench")
    if on_accel:
        # a TPU always has a peak (an unknown device_kind raised in the
        # meter), so the MFU is a number here
        headline = {
            "metric": f"ResNet50 train MFU ({platform}, {size}px, "
                      f"batch {batch})",
            "value": round(mfu, 4),
            "unit": "MFU",
            "vs_baseline": round(mfu / target, 4),
            "examples_per_sec_per_chip": s.get("examples_per_sec_per_chip"),
        }
    else:
        headline = {
            "metric": smoke_label(False) + "ResNet50 train step seconds "
                      f"({platform}, {size}px, batch {batch})",
            "value": round(step_time, 4),
            "unit": "s/step",
        }
    print(
        json.dumps(
            {
                **headline,
                "dispatch_count": n_dispatches,
                "dispatch_gap_ms": round(gap * 1e3, 4),
                "overhead_share": round(
                    overhead_share(n_dispatches, total_wall, gap) or 0.0, 4
                ),
                "opt_state_bytes_per_chip": opt_state_bytes,
                "partition_axes": partitioner.describe()["axes"],
                "partition_rule_hits": rule_hit_counts(),
            }
        )
    )


if __name__ == "__main__":
    main()
