"""TF-ingestion hardware smoke (SURVEY.md §7 hard part 1; VERDICT #8).

Builds a tiny MLP as a frozen TF-v1 GraphDef, ingests it through
``TFInputGraph``/``GraphFunction.to_jax`` (the jax2tf.call_tf lowering),
jits it on the TPU, and asserts the device result matches the TF session
oracle. Prints ONE JSON line like bench.py.

This is the proof that the reference's "run an arbitrary frozen TF graph"
path executes ON TPU, not just in the CPU suite — so no TPU backend is an
error (runtime/chip.py ``require_tpu``); under an exported
``JAX_PLATFORMS=cpu`` it is the run-tests.sh contract smoke and prints no
``vs_baseline``.
"""

from __future__ import annotations

import json
import time

import numpy as np


def main() -> None:
    import os

    import jax
    import tensorflow as tf

    from sparkdl_tpu.graph.builder import IsolatedSession
    from sparkdl_tpu.graph.input import TFInputGraph
    from sparkdl_tpu.runtime.chip import (
        configure_compile_cache,
        require_tpu,
        smoke_label,
    )

    on_tpu = require_tpu(explicit_cpu_ok=True)
    configure_compile_cache()

    rows = int(os.environ.get("BENCH_BATCH", 256))
    rng = np.random.default_rng(0)
    w1 = rng.standard_normal((16, 64)).astype(np.float32) * 0.3
    w2 = rng.standard_normal((64, 8)).astype(np.float32) * 0.3

    with IsolatedSession() as sess:
        x = tf.compat.v1.placeholder(tf.float32, [None, 16], name="x")
        h = tf.nn.relu(tf.matmul(x, tf.constant(w1)))
        y = tf.nn.softmax(tf.matmul(h, tf.constant(w2)), name="y")
        gfn = sess.asGraphFunction([x], [y])
        batch = rng.standard_normal((rows, 16)).astype(np.float32)
        oracle = sess.run(y, feed_dict={x: batch})

    tig = TFInputGraph.fromGraphDef(gfn.graph_def, ["x:0"], ["y:0"])
    to_jax = tig.to_jax()
    fn = jax.jit(lambda a: to_jax(a)[0])

    xb = jax.device_put(batch)
    out = np.asarray(fn(xb))
    ok = np.allclose(out, oracle, atol=1e-5)

    t0 = time.perf_counter()
    steps = 50
    last = None
    for _ in range(steps):
        last = fn(xb)
    float(last.sum())  # forced scalar read pins the chain
    dt = time.perf_counter() - t0
    device_resident_rps = batch.shape[0] * steps / dt

    # -- autotuned streaming ingest (ISSUE 8): the same ingested graph,
    # -- host-fed row by row through the sparkdl_tpu/ingest pipeline
    # -- (bucketing batch -> staging ring/prefetch -> fused dispatch)
    # -- with every unpinned knob under the tuner. The headline value is
    # -- THIS path — the zero-config throughput the autotuner delivers.
    from sparkdl_tpu import ingest
    from sparkdl_tpu.observability import registry
    from sparkdl_tpu.transformers._inference import BatchedRunner

    tuner = ingest.default_tuner()
    tuner.interval_s = float(os.environ.get("BENCH_AUTOTUNE_INTERVAL", 0.2))
    runner = BatchedRunner(
        lambda b: to_jax(b["x"])[0], batch_size=rows, autotune=True)
    n_stream = int(os.environ.get("BENCH_STREAM_ROWS", rows * 40))
    feats = rng.standard_normal((n_stream, 16)).astype(np.float32)

    # warmup: compile every bucket the stream will see
    list(runner.run(iter([{"x": feats[0]}] * rows)))
    t0 = time.perf_counter()
    n_out = sum(1 for _ in runner.run(
        {"x": feats[i]} for i in range(n_stream)))
    stream_dt = time.perf_counter() - t0
    assert n_out == n_stream, (n_out, n_stream)
    streamed_rps = n_stream / stream_dt

    platform = jax.default_backend()
    print(json.dumps({
        "metric": smoke_label(on_tpu)
                  + f"TFInputGraph.to_jax ingested-MLP autotuned streaming "
                  f"ingest ({platform})",
        "value": round(streamed_rps, 1),
        "unit": "rows/sec",
        **({"vs_baseline": 1.0 if ok else 0.0} if on_tpu else {}),
        "allclose_vs_tf_session": bool(ok),
        "device_resident_rows_per_sec": round(device_resident_rps, 1),
        # ISSUE 8: decision count + steady-state knobs, registry-sourced
        "autotune": ingest.autotune_telemetry(),
        "observability": registry().snapshot(),
    }))
    tuner.stop()
    if not ok:
        raise SystemExit("ingested graph result diverged from TF oracle")


if __name__ == "__main__":
    main()
