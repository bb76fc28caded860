"""Host-fed featurization benchmark: decode -> pack -> stage -> device ->
features (VERDICT round-1 next-step #4 / weak #8).

Measures the FULL ingest path the native bridge exists for: JPEG bytes on
the host, native C++ threaded decode+resize, native pack into the staging
ring, double-buffered device transfer (DeviceFeeder via BatchedRunner),
jitted InceptionV3 features back to host. Reports img/s plus the ring
telemetry and infeed-starvation %, as ONE JSON line.

TPU only (runtime/chip.py ``require_tpu``). Under an exported
``JAX_PLATFORMS=cpu`` it is the run-tests.sh contract smoke: every
host-side stage plus a real device_put at a tiny size, printing no
``vs_baseline`` — its rate is the host's, not a device metric.
"""

from __future__ import annotations

import io
import json
import os
import time

import numpy as np


def main() -> None:
    import jax
    import jax.numpy as jnp

    from sparkdl_tpu.models.registry import build_flax_model, get_entry
    from sparkdl_tpu.native import bridge
    from sparkdl_tpu.native import decode as native_decode
    from sparkdl_tpu.observability.metrics import StepMeter, compiled_flops
    from sparkdl_tpu.ops.preprocess import PREPROCESSORS
    from sparkdl_tpu.transformers._inference import BatchedRunner

    from sparkdl_tpu.runtime.chip import (
        configure_compile_cache,
        require_tpu,
        smoke_label,
    )

    on_accel = require_tpu(explicit_cpu_ok=True)
    configure_compile_cache()
    platform = jax.default_backend()
    n_images = int(os.environ.get("BENCH_IMAGES", 2048 if on_accel else 256))
    batch = int(os.environ.get("BENCH_BATCH", 128 if on_accel else 32))
    size = 299 if on_accel else 128

    # -- synthesize a JPEG corpus (the host-side input of SURVEY.md 3.1) --
    from PIL import Image

    rng = np.random.default_rng(0)
    jpegs = []
    for i in range(64):
        arr = (rng.random((size + 21, size + 40, 3)) * 255).astype(np.uint8)
        buf = io.BytesIO()
        Image.fromarray(arr).save(buf, "JPEG", quality=85)
        jpegs.append(buf.getvalue())

    entry = get_entry("InceptionV3")
    dtype = jnp.bfloat16 if on_accel else jnp.float32
    module, variables = build_flax_model(
        "InceptionV3", weights=None, include_top=False, dtype=dtype
    )
    preprocess = PREPROCESSORS[entry.preprocess]

    def apply_fn(b):
        feats, _ = module.apply(
            variables, preprocess(b["image"].astype(dtype)), train=False
        )
        return feats.astype(jnp.float32)

    # Autotuned ingest (ISSUE 8): the bench runs the SAME pipeline a
    # zero-config user gets — decode parallelism, staging depth, chain K
    # and packer threads all start at their defaults and the tuner
    # resizes them from the measured starvation / producer-blocked
    # shares. Env pins (SPARKDL_TPU_PREFETCH, SPARKDL_TPU_CHAIN_K,
    # BENCH_DECODE_PAR) exclude a knob from tuning.
    from sparkdl_tpu import ingest

    tuner = ingest.default_tuner()
    tuner.interval_s = float(os.environ.get("BENCH_AUTOTUNE_INTERVAL", 0.2))
    runner = BatchedRunner(apply_fn, batch_size=batch, autotune=True)
    flops_per_img = compiled_flops(
        apply_fn,
        {"image": jax.ShapeDtypeStruct((1, size, size, 3), jnp.uint8)},
    )
    meter = StepMeter(
        flops_per_example=flops_per_img, n_chips=1, warmup_steps=0,
    )

    use_native_decode = native_decode.available()

    def decode_one(raw):
        if use_native_decode:
            arr = native_decode.decode_resize(raw, size, size)
        else:
            arr = np.asarray(
                Image.open(io.BytesIO(raw)).resize((size, size)))
        return {"image": arr}

    def rows():
        # decode rides an ingest map stage whose parallelism is a live
        # tuner knob: when the feed starves the device, more decode
        # threads spin up — the tf.data AUTOTUNE win on the real decode
        # hot path. BENCH_DECODE_PAR pins it.
        pipe = ingest.Pipeline(
            (jpegs[i % len(jpegs)] for i in range(n_images)),
            name="hostfed",
        ).map(decode_one, max_parallelism=4, env_var="BENCH_DECODE_PAR",
              name="decode")
        pipe.autotune(True)
        return iter(pipe)

    from sparkdl_tpu.observability import registry

    def _series(snap, name, field="value"):
        fam = snap.get(name) or {}
        vals = fam.get("values") or {}
        series = vals.get("") or {}
        if isinstance(series, dict):
            return float(series.get("sum") or 0.0)
        return float(series or 0.0)

    def ring_telemetry(snap):
        """Ring counters straight off the observability registry (ISSUE
        4 satellite): the SAME series `/metrics` exposes, not bench-local
        bookkeeping — slot waits (transfer/compute behind) and consumer
        waits (infeed starvation) next to batches/bytes."""
        return {
            "batches": _series(snap, "sparkdl_ring_batches_total"),
            "bytes": _series(snap, "sparkdl_ring_bytes_total"),
            "slot_wait_s": _series(
                snap, "sparkdl_ring_slot_wait_seconds_total"),
            "consumer_wait_s": _series(
                snap, "sparkdl_ring_consumer_wait_seconds"),
            "prefetch_consumer_wait_s": _series(
                snap, "sparkdl_prefetch_consumer_wait_seconds"),
        }

    # warmup (compile every bucket it will see)
    list(runner.run({"image": np.zeros((size, size, 3), np.uint8)}
                    for _ in range(batch)))
    ring0 = ring_telemetry(registry().snapshot())

    t0 = time.perf_counter()
    n_out = 0
    with meter.step(examples=n_images):
        for _ in runner.run(rows()):
            n_out += 1
    dt = time.perf_counter() - t0
    assert n_out == n_images

    ring1 = ring_telemetry(registry().snapshot())
    ring = {k: ring1[k] - ring0[k] for k in ring1}
    ring_batches = int(ring["batches"])
    ring_mb = ring["bytes"] / 2**20
    # starvation share of this run's wall: how long the consumer sat
    # waiting on the feed (ring or Python prefetch, whichever path ran)
    starve_s = ring["consumer_wait_s"] + ring["prefetch_consumer_wait_s"]
    summary = meter.summary()

    # -- text variant: BERT featurization through the struct-of-tensors
    # -- ring (input_ids + attention_mask share one slot; VERDICT r2 #4)
    from sparkdl_tpu.models.bert import BertConfig, BertModel

    tcfg = BertConfig.tiny(vocab_size=1024) if not on_accel else BertConfig(
        vocab_size=30522, hidden_size=256, num_hidden_layers=4,
        num_attention_heads=4, intermediate_size=1024,
        max_position_embeddings=128,
    )
    tmodel = BertModel(tcfg)
    max_len = 128 if on_accel else 16
    tvars = tmodel.init(
        jax.random.PRNGKey(0),
        jnp.zeros((1, max_len), jnp.int32), jnp.ones((1, max_len), jnp.int32),
    )

    def text_apply(b):
        seq, _ = tmodel.apply(tvars, b["input_ids"], b["attention_mask"])
        m = b["attention_mask"][:, :, None].astype(jnp.float32)
        return (seq.astype(jnp.float32) * m).sum(1) / jnp.maximum(
            m.sum(1), 1.0)

    n_texts = n_images
    trunner = BatchedRunner(text_apply, batch_size=batch)

    def text_rows():
        for i in range(n_texts):
            n = int(rng.integers(4, max_len))
            ids = np.zeros(max_len, np.int32)
            ids[:n] = rng.integers(1, tcfg.vocab_size, n)
            yield {"input_ids": ids,
                   "attention_mask": (np.arange(max_len) < n)
                   .astype(np.int32)}

    list(trunner.run(
        {"input_ids": np.zeros(max_len, np.int32),
         "attention_mask": np.ones(max_len, np.int32)}
        for _ in range(batch)))
    tstats0 = dict(bridge.FEED_STATS)
    t0 = time.perf_counter()
    t_out = sum(1 for _ in trunner.run(text_rows()))
    t_dt = time.perf_counter() - t0
    assert t_out == n_texts
    text_ring = bridge.FEED_STATS["ring_streams"] - tstats0["ring_streams"]

    print(json.dumps({
        "metric": smoke_label(on_accel)
                  + f"host-fed InceptionV3 featurization "
                  f"(decode->pack->ring->device->features, {platform}, "
                  f"{size}px, batch {batch})",
        "value": round(n_images / dt, 1),
        "unit": "images/sec",
        **({"vs_baseline": round(n_images / dt / 10_000.0, 4)}
           if on_accel else {}),
        "native_decode": use_native_decode,
        "ring_batches": ring_batches,
        "ring_mb": round(ring_mb, 1),
        # registry-sourced (ISSUE 4): the same series /metrics scrapes
        "ring_slot_wait_s": round(ring["slot_wait_s"], 4),
        "ring_consumer_wait_s": round(ring["consumer_wait_s"], 4),
        "infeed_starvation_share": round(min(1.0, starve_s / dt), 4),
        "mfu": summary.get("mfu"),
        "infeed_starvation_pct": summary.get("infeed_starvation_pct"),
        "text_variant": {
            "texts_per_sec": round(n_texts / t_dt, 1),
            "rode_ring": bool(text_ring),
        },
        # ISSUE 8: every tuning decision visible, steady-state knobs
        # embedded (registry-sourced, like dispatch_gap_ms elsewhere)
        "autotune": ingest.autotune_telemetry(),
        "observability": registry().snapshot(),
    }))
    tuner.stop()


if __name__ == "__main__":
    main()
