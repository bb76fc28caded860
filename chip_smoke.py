"""First-run proof on the chip: featurize, serve, train, kernel — one process.

    python chip_smoke.py                 # on the TPU; anything else fails
    python chip_smoke.py --rehearse      # tiny sizes on CPU, for debugging
    python chip_smoke.py --rehearse 4    # same, four virtual CPU devices

Drives the three production entry points end to end at real sizes through
the calls a user makes, with seeded random weights and inputs generated in
the run (no network), and asserts what comes out:

  device     backend is a TPU; native libraries built; compile cache placed;
             dispatch gap, host read and host-to-device rate printed
  featurize  PNGs -> readImagesWithCustomFn -> DeepImageFeaturizer
             (InceptionV3, batch 128, 299 px): two full batches, a ragged
             tail, one corrupt file; rows checked against the module applied
             directly; the native staging ring traversed
  serve      live ContinuousGPTEngine over GPT-2 XL widths (hidden 1600,
             25 heads of 64, vocab 50257, learned positions): concurrent
             ragged submits, a shared prefix, tokens checked against
             unbatched ``generate`` and a float32 jax.numpy forward
  train      make_vision_train_step on ResNet50 (224 px, bf16, batch 256,
             donated state): finite loss, moved params, an MFU
  kernel     ops/flash_attention compiled by Mosaic (interpret=False),
             forward and backward, against the naive path
  four_chip  with >= 4 devices: every dry-run regime on the real devices,
             data-parallel featurize, four one-chip replicas

Every phase asserts; any failure makes the exit code non-zero. The last
line of stdout on success is one JSON object naming the device as jax
reports it. Without ``--rehearse`` there is no CPU path: no TPU backend
is exit code 2 before anything compiles. One process holds the chip for
the whole run; nothing is spawned that needs it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
import traceback
import types

PHASES = ("device", "featurize", "serve", "train", "kernel", "four_chip")

#: GPT-2 XL as published (huggingface.co/openai-community/gpt2-xl
#: config.json) — what ``config_from_hf_gpt2`` reads off an HF config.
GPT2_XL = types.SimpleNamespace(
    vocab_size=50257, n_embd=1600, n_layer=48, n_head=25, n_inner=None,
    n_positions=1024, layer_norm_epsilon=1e-5,
    activation_function="gelu_new",
)

#: Real sizes (the chip) and rehearsal sizes (CPU, Pallas interpreter).
#: Widths are never cut on the chip; ``gpt_layers`` is the one depth knob.
REAL = types.SimpleNamespace(
    h2d_mib=256, feat_batch=128, feat_images=2 * 128 + 37,
    gpt=GPT2_XL, gpt_layers=48, gpt_dtype="bfloat16",
    prompt_lens=(24, 72, 200), shared_prefix=192, new_tokens=32,
    train_batch=256, train_px=224, train_dtype="bfloat16", train_steps=5,
    flash_lens=(1024, 1000), flash_heads=8,
)
REHEARSAL = types.SimpleNamespace(
    h2d_mib=8, feat_batch=4, feat_images=2 * 4 + 1,
    gpt=types.SimpleNamespace(**{**vars(GPT2_XL), "vocab_size": 512,
                                 "n_embd": 64, "n_head": 4}),
    gpt_layers=2, gpt_dtype="float32",
    prompt_lens=(6, 11, 40), shared_prefix=32, new_tokens=8,
    train_batch=4, train_px=32, train_dtype="float32", train_steps=2,
    flash_lens=(128, 100), flash_heads=2,
)


def say(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def check(cond: bool, what: str) -> None:
    """An assertion that survives ``python -O``."""
    if not cond:
        raise AssertionError(what)


def hbm(phase: str) -> None:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        say(phase, f"device memory: in use "
                   f"{stats['bytes_in_use'] / 2**30:.2f} GiB, peak so far "
                   f"{stats['peak_bytes_in_use'] / 2**30:.2f} GiB")
    else:
        say(phase, "device memory: not reported by this backend")


# -- device --------------------------------------------------------------------

def phase_device(sz, ctx) -> None:
    import importlib.metadata as md

    import jax
    import jax.numpy as jnp
    import numpy as np

    from sparkdl_tpu.native import _lib
    from sparkdl_tpu.runtime.chip import cache_entry_count
    from sparkdl_tpu.runtime.dispatch import calibrate_dispatch_gap

    d = jax.devices()[0]
    versions = {p: md.version(p) for p in ("jax", "jaxlib")}
    try:
        versions["libtpu"] = md.version("libtpu")
    except md.PackageNotFoundError:
        versions["libtpu"] = "absent"
    say("device", f"platform={d.platform} device_kind={d.device_kind!r} "
                  f"count={len(jax.devices())} versions={versions}")

    # the native staging ring and decoder are on the featurize path; a
    # silent drop to the pure-Python fallbacks would hide them
    report = _lib.build_report()
    for name, r in report.items():
        say("device", f"native {name}: loaded={r['loaded']} "
                      f"built_in_this_run={r['built_here']} "
                      f"file={os.path.basename(r['path'] or '-')}")
    check(all(r["loaded"] for r in report.values()),
          f"native library missing (pure-Python fallback): {report}")

    say("device", f"compile cache: dir={ctx.cache_dir} entries_before="
                  f"{cache_entry_count(ctx.cache_dir)}")

    gap = calibrate_dispatch_gap()
    say("device", f"dispatch gap (trivial jitted program, median of 30): "
                  f"{gap * 1e6:.1f} us")

    # a fresh array per read: jax keeps the host copy of one it has read
    ready = [jax.block_until_ready(jnp.float32(i) + 1) for i in range(20)]
    reads = []
    for x in ready:
        t0 = time.perf_counter()
        float(x)
        reads.append(time.perf_counter() - t0)
    reads.sort()
    say("device", f"host read of a ready 4-byte array (median of 20): "
                  f"{reads[10] * 1e6:.1f} us")

    host = np.random.default_rng(0).integers(
        0, 256, sz.h2d_mib * 2**20, dtype=np.uint8)
    rates = []
    for _ in range(3):
        t0 = time.perf_counter()
        dev = jax.block_until_ready(jax.device_put(host))
        rates.append(host.nbytes / (time.perf_counter() - t0) / 1e9)
        del dev
    say("device", f"device_put of {sz.h2d_mib} MiB, three times: "
                  + ", ".join(f"{r:.2f}" for r in rates) + " GB/s")
    check(gap > 0 and reads[10] > 0 and min(rates) > 0, "non-positive timing")


# -- featurize -----------------------------------------------------------------

def phase_featurize(sz, ctx) -> None:
    import jax
    import numpy as np
    from PIL import Image

    from sparkdl_tpu.image import imageIO
    from sparkdl_tpu.image.schema import UNDEFINED_MODE
    from sparkdl_tpu.models.registry import build_flax_model, get_entry
    from sparkdl_tpu.native import bridge
    from sparkdl_tpu.ops.preprocess import PREPROCESSORS
    from sparkdl_tpu.transformers.named_image import DeepImageFeaturizer

    rng = np.random.default_rng(21)
    n, px = sz.feat_images, 299
    images = rng.integers(0, 256, (n, px, px, 3), dtype=np.uint8)
    before = dict(bridge.FEED_STATS)
    with tempfile.TemporaryDirectory(prefix="chip_smoke_img_") as d:
        for i, arr in enumerate(images):
            Image.fromarray(arr).save(os.path.join(d, f"img_{i:04d}.png"))
        with open(os.path.join(d, "zz_corrupt.png"), "wb") as f:
            f.write(b"\x89PNG\r\n\x1a\nthis is not an image")
        df = imageIO.readImagesWithCustomFn(
            d, decode_f=imageIO.native_decode_bytes, numPartition=1)
        feat = DeepImageFeaturizer(
            modelName="InceptionV3", inputCol="image", outputCol="features",
            weights="random", batchSize=sz.feat_batch)
        t0 = time.perf_counter()
        rows = feat.transform(df).collect()
        wall = time.perf_counter() - t0
    check(len(rows) == n + 1, f"{len(rows)} rows out, {n + 1} files in")
    bad = rows[-1]
    check(bad["image"]["mode"] == UNDEFINED_MODE and bad["features"] is None,
          "corrupt file did not come back as an UNDEFINED_MODE row")
    got = np.stack([r["features"] for r in rows[:n]])
    check(got.shape == (n, 2048) and bool(np.isfinite(got).all()),
          f"features shape {got.shape} / non-finite values")
    say("featurize", f"{n} images + 1 corrupt through batch "
                     f"{sz.feat_batch} in {wall:.1f} s (compiles included)")

    ring = {k: bridge.FEED_STATS[k] - before[k] for k in before}
    say("featurize", f"native staging ring: {ring}")
    check(ring["ring_streams"] >= 1 and ring["ring_batches"] >= 3
          and ring["fallback_streams"] == 0,
          f"feed did not ride the native ring: {ring}")

    # the same module applied directly, rows from the first batch, the
    # batch boundary, and the ragged tail: a recycled staging slot that
    # had not landed would show up as a wrong row here
    module, variables = build_flax_model(
        "InceptionV3", weights="random", include_top=False)
    preprocess = PREPROCESSORS[get_entry("InceptionV3").preprocess]
    b = sz.feat_batch
    pick = sorted({0, 1, b - 1, b, 2 * b - 1, 2 * b, n - 1})
    want, _ = jax.jit(lambda x: module.apply(
        variables, preprocess(x), train=False))(
            images[pick].astype(np.float32))
    want = np.asarray(want)
    err = float(np.abs(got[pick] - want).max() / np.abs(want).max())
    # float32 modules: exact to rounding on CPU; on the TPU both programs
    # run their convolutions in single-pass bf16 (default precision) at
    # different batch shapes, so agreement is to bf16 rounding
    tol = 1e-4 if ctx.rehearse else 5e-2
    say("featurize", f"rows {pick} vs direct module.apply: max abs err / "
                     f"max abs = {err:.2e} (tolerance {tol:g})")
    check(err <= tol, f"featurizer rows differ from the direct forward: {err}")
    ctx.featurize_devices = _runner_output_devices(feat, images[:b])
    say("featurize", f"runner output shards on devices "
                     f"{ctx.featurize_devices}")


def _runner_output_devices(feat, batch_u8) -> "list[int]":
    """Devices one staged batch's OUTPUT lands on, through the runner the
    transformer just used (reaches into its cached BatchedRunner: the
    public surface only ever hands back host arrays)."""
    import numpy as np

    from sparkdl_tpu.runtime.mesh import shard_device_ids
    from sparkdl_tpu.transformers import named_image as ni

    runner = ni._named_model_runner(
        feat.getModelName(), feat.getOrDefault("weights"), False,
        "features", feat.getBatchSize(),
        ni._weights_token(feat.getOrDefault("weights")))
    staged = next(runner._device_feed(
        iter([{"img": batch_u8.astype(np.float32)}])))
    return shard_device_ids(runner._jitted(staged))


# -- serve ---------------------------------------------------------------------

def gpt2_reference_logits(params, ids, n_layers, n_heads, eps):
    """GPT-2 forward in plain float32 jax.numpy: no kernels, no cache, no
    batching tricks (pre-LN blocks, learned positions, tanh-gelu, tied
    head). The reference the smoke holds the engine's tokens against."""
    import jax
    import jax.numpy as jnp

    def f32(a):
        return jnp.asarray(a, jnp.float32)

    def ln(x, p):
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + eps) * f32(p["scale"]) + f32(p["bias"])

    def dense(x, p):
        return x @ f32(p["kernel"]) + f32(p["bias"])

    b, l = ids.shape
    wte = f32(params["wte"]["embedding"])
    x = wte[ids] + f32(params["wpe"]["embedding"])[jnp.arange(l)][None]
    causal = jnp.tril(jnp.ones((l, l), bool))
    for i in range(n_layers):
        blk = params[f"h_{i}"]
        h = ln(x, blk["ln_1"])
        q, k, v = (dense(h, blk["attn"][n]).reshape(b, l, n_heads, -1)
                   for n in ("q_proj", "k_proj", "v_proj"))
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(q.shape[-1])
        p = jax.nn.softmax(jnp.where(causal, s, -1e30), axis=-1)
        ctx = jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(b, l, -1)
        x = x + dense(ctx, blk["attn"]["out_proj"])
        h = ln(x, blk["ln_2"])
        x = x + dense(jax.nn.gelu(dense(h, blk["up"]), approximate=True),
                      blk["down"])
    return ln(x, params["ln_f"]) @ wte.T


def phase_serve(sz, ctx) -> None:
    import dataclasses

    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import numpy as np

    from sparkdl_tpu.models.gpt import (
        GPTLMHeadModel,
        config_from_hf_gpt2,
        generate,
    )
    from sparkdl_tpu.serving.continuous import ContinuousGPTEngine

    full = config_from_hf_gpt2(sz.gpt)
    cfg = dataclasses.replace(full, num_layers=sz.gpt_layers,
                              dtype=jnp.dtype(sz.gpt_dtype))
    say("serve", f"{'rehearsal' if ctx.rehearse else 'GPT-2 XL'} widths: "
                 f"hidden {cfg.hidden_size}, "
                 f"{cfg.num_heads} heads of "
                 f"{cfg.hidden_size // cfg.num_heads}, vocab "
                 f"{cfg.vocab_size}, positions {cfg.positions}, compute "
                 f"{cfg.dtype.name}; depth {cfg.num_layers} of "
                 f"{full.num_layers} layers"
                 + ("" if cfg.num_layers == full.num_layers
                    else " (DEPTH CUT: see PERF.md, bring-up section)"))
    model = GPTLMHeadModel(cfg)
    t0 = time.perf_counter()
    variables = jax.block_until_ready(
        model.init(jax.random.PRNGKey(21), jnp.zeros((1, 8), jnp.int32)))
    n_params = sum(a.size for a in jax.tree.leaves(variables))
    say("serve", f"seeded random weights: {n_params / 1e9:.3f} B params "
                 f"in {time.perf_counter() - t0:.1f} s")

    rng = np.random.default_rng(21)
    shared = rng.integers(0, cfg.vocab_size, sz.shared_prefix)
    short, mid, long_ = sz.prompt_lens

    def with_prefix():
        tail = rng.integers(0, cfg.vocab_size, long_ - sz.shared_prefix)
        return np.concatenate([shared, tail]).astype(np.int32)

    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (short, mid, short, mid, short, mid)]
    prompts[2:2] = [with_prefix(), with_prefix()]  # two share the prefix
    late = with_prefix()  # a third, after the first two finished

    eng = ContinuousGPTEngine(cfg, variables)
    try:
        t0 = time.perf_counter()
        futs = [eng.submit(p, sz.new_tokens) for p in prompts]
        outs = [f.result(timeout=900) for f in futs]
        outs.append(eng.submit(late, sz.new_tokens).result(timeout=900))
        prompts.append(late)
        wall = time.perf_counter() - t0
        snap = eng.snapshot()
    finally:
        eng.close(drain=True)
    del eng
    check(all(len(o) == sz.new_tokens for o in outs),
          f"short outputs: {[len(o) for o in outs]}")
    check(snap["submitted"] == snap["completed"] == len(prompts)
          and snap["failed"] == 0,
          f"snapshot does not reconcile: submitted {snap['submitted']} "
          f"completed {snap['completed']} failed {snap['failed']}")
    kv = snap["kv"]
    check(kv["prefix_hits"] > 0, f"no prefix hits: {kv}")
    say("serve", f"{len(prompts)} requests (prompt lengths "
                 f"{[len(p) for p in prompts]}, {sz.new_tokens} new tokens "
                 f"each) in {wall:.1f} s, compiles included; prefix hits "
                 f"{kv['prefix_hits']} tokens, KV blocks peak "
                 f"{kv['blocks_used_peak']}/{kv['blocks_total']}; "
                 "close(drain=True) returned")
    hbm("serve")

    # 1. the repository's central contract: greedy tokens bitwise equal to
    #    unbatched generate()
    same = []
    for p, o in zip(prompts, outs):
        ref = np.asarray(generate(model, variables, jnp.asarray(p)[None],
                                  sz.new_tokens))[0, len(p):]
        same.append(int((ref == o).sum()))
    n_tok = len(prompts) * sz.new_tokens
    exact = [s == sz.new_tokens for s in same]
    say("serve", f"greedy tokens vs unbatched generate(): "
                 f"{sum(same)}/{n_tok} tokens equal, {sum(exact)}/"
                 f"{len(prompts)} requests bitwise")

    # 2. against the float32 jax.numpy forward, teacher-forced on the
    #    engine's own sequences (right-padded: causal attention makes the
    #    padding invisible to the real positions)
    width = max(len(p) for p in prompts) + sz.new_tokens
    seqs = np.zeros((len(prompts), width), np.int32)
    for i, (p, o) in enumerate(zip(prompts, outs)):
        seqs[i, :len(p)], seqs[i, len(p):len(p) + len(o)] = p, o
    params = nn.meta.unbox(variables)["params"]
    with jax.default_matmul_precision("highest"):
        ref_logits = np.asarray(jax.jit(
            gpt2_reference_logits, static_argnums=(2, 3, 4))(
                params, seqs, cfg.num_layers, cfg.num_heads,
                cfg.layer_norm_eps))
    sys_logits = np.asarray(jax.jit(
        lambda v, x: model.apply(v, x)[0])(variables, seqs))
    agree, margin, errs = 0, 0.0, []
    for i, (p, o) in enumerate(zip(prompts, outs)):
        rows = slice(len(p) - 1, len(p) - 1 + len(o))  # rows that chose o
        ref = ref_logits[i, rows]
        agree += int((ref.argmax(-1) == o).sum())
        margin = max(margin, float(
            (ref.max(-1) - ref[np.arange(len(o)), o]).max()))
        errs.append(sys_logits[i, rows] - ref)
    errs = np.concatenate(errs)
    err_max, err_rms = float(np.abs(errs).max()), float(errs.std())
    spread = float(ref_logits.std())
    say("serve", f"vs float32 reference: engine token == reference argmax "
                 f"on {agree}/{n_tok} tokens; worst reference-logit margin "
                 f"of an engine token {margin:.4f}; teacher-forced "
                 f"{cfg.dtype.name} logits err max {err_max:.4f} rms "
                 f"{err_rms:.4f} (reference logit std {spread:.3f})")
    # What holds on the chip, where the bitwise contract does not (PERF.md,
    # bring-up section): the system's logits track the reference to the
    # compute dtype's precision, and a token the engine chose is the
    # reference's best or within that rounding of it — the disagreements
    # are near-ties, not errors. float32 on the CPU: ~1e-4 of the logit
    # spread. bf16 (8 mantissa bits) through the residual stack measured
    # 0.16 max on the v5e at 48 layers; the bound leaves 2x on that and
    # would still fail an 8-bit float by an order of magnitude.
    tol = (1e-3 if cfg.dtype == jnp.float32 else 0.3) * spread
    check(err_max <= tol and margin <= 2 * tol,
          f"engine disagrees with the float32 reference beyond {tol:.4f}: "
          f"logit err max {err_max}, token margin {margin}")
    check(agree >= 0.9 * n_tok,
          f"engine tokens match the reference argmax on only {agree}/{n_tok}")
    if ctx.rehearse:
        check(all(exact), f"greedy parity broke on CPU float32: {same}")


# -- train ---------------------------------------------------------------------

def phase_train(sz, ctx) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from sparkdl_tpu.models.resnet import ResNet50
    from sparkdl_tpu.observability.metrics import StepMeter, compiled_flops
    from sparkdl_tpu.train.vision import make_vision_train_step

    dtype = jnp.dtype(sz.train_dtype)
    model = ResNet50(num_classes=1000, include_top=True, dtype=dtype)
    variables = model.init(jax.random.PRNGKey(21),
                           jnp.zeros((1, sz.train_px, sz.train_px, 3)))
    params, stats = variables["params"], variables["batch_stats"]
    tx = optax.sgd(0.01, momentum=0.9)
    opt = tx.init(params)
    step = make_vision_train_step(model, tx, donate=True)
    rng = np.random.default_rng(21)
    x = jax.device_put(rng.random(
        (sz.train_batch, sz.train_px, sz.train_px, 3), np.float32))
    y = jax.device_put(rng.integers(0, 1000, sz.train_batch).astype(np.int32))

    shapes = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                          (params, stats, opt, x, y))
    flops = compiled_flops(step, *shapes)
    check(flops is not None, "compiled_flops returned None: XLA gave no "
                             "cost analysis for the train step")
    say("train", f"ResNet50 {sz.train_px} px {dtype.name} batch "
                 f"{sz.train_batch}: {flops / 1e12:.3f} TFLOP per step "
                 "(XLA cost analysis)")

    # host copy BEFORE the first step: the state is donated, its device
    # buffers are gone afterwards
    leaf0 = np.asarray(jax.tree.leaves(params)[0]).copy()
    meter = StepMeter(flops_per_step=flops, n_chips=1)
    losses = []
    for _ in range(1 + sz.train_steps):  # the meter drops the compile step
        with meter.step(examples=sz.train_batch):
            params, stats, opt, loss = step(params, stats, opt, x, y)
            losses.append(float(loss))  # forced read: the step is done
    check(bool(np.isfinite(losses).all()), f"non-finite loss: {losses}")
    moved = float(np.abs(np.asarray(jax.tree.leaves(params)[0])
                         - leaf0).max())
    check(moved > 0, "parameters did not change")
    s = meter.summary()
    say("train", f"losses {[round(v, 4) for v in losses]}; first param "
                 f"leaf moved by {moved:.3e}; step "
                 f"{s['step_time_mean_s'] * 1e3:.1f} ms over "
                 f"{s['steps']} steps")
    if ctx.rehearse:
        say("train", "MFU: not measured (a CPU has no row in the peak table)")
    else:
        check(isinstance(s["mfu"], float) and 0 < s["mfu"] < 1,
              f"MFU is not a number in (0, 1): {s['mfu']}")
        say("train", f"StepMeter MFU {s['mfu']:.4f} "
                     f"({s['examples_per_sec_per_chip']:.0f} img/s/chip)")
    hbm("train")


# -- kernel --------------------------------------------------------------------

def phase_kernel(sz, ctx) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from sparkdl_tpu.ops.flash_attention import flash_attention

    # passed explicitly, never left to a default: compiled by Mosaic on
    # the chip, the Pallas interpreter only in a rehearsal
    interpret = ctx.rehearse

    def naive(q, k, v, q_offset):
        s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                       k.astype(jnp.float32)) / np.sqrt(q.shape[-1])
        q_pos = q_offset + jnp.arange(q.shape[1])[:, None]
        s = jnp.where(jnp.arange(k.shape[1])[None, :] <= q_pos, s, -1e30)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1),
                          v.astype(jnp.float32))

    cases = [(L, L, 0) for L in sz.flash_lens]
    lk = sz.flash_lens[0]
    cases.append((lk // 4, lk, lk - lk // 4))  # cached prefill: q_offset
    for lq, lk, off in cases:
        rng = np.random.default_rng(lq + lk)
        q, k, v = (jnp.asarray(rng.standard_normal(
            (2, n, sz.flash_heads, 64)), jnp.bfloat16) for n in (lq, lk, lk))

        def flash_loss(q, k, v):
            o = flash_attention(q, k, v, causal=True, q_offset=off,
                                interpret=interpret)
            return jnp.sum(o.astype(jnp.float32) ** 2), o

        def naive_loss(q, k, v):
            o = naive(q, k, v, off)
            return jnp.sum(o ** 2), o

        t0 = time.perf_counter()
        (gf, of), (gn, on) = (
            jax.jit(jax.grad(f, argnums=(0, 1, 2), has_aux=True))(q, k, v)
            for f in (flash_loss, naive_loss))
        fwd = float(jnp.abs(of.astype(jnp.float32) - on).max())
        bwd = max(float(jnp.abs(a.astype(jnp.float32)
                                - b.astype(jnp.float32)).max())
                  for a, b in zip(gf, gn))
        say("kernel", f"flash_attention Lq={lq} Lk={lk} q_offset={off} "
                      f"heads of 64, interpret={interpret}: fwd max err "
                      f"{fwd:.4f}, bwd max err {bwd:.4f} "
                      f"({time.perf_counter() - t0:.1f} s with compiles)")
        # bench_attention.py's tolerances: bf16 inputs, f32 accumulation
        check(fwd < 0.05 and bwd < 0.5 + 1e-4 * lk,
              f"flash kernel diverged from the naive path at Lq={lq}")


# -- four chips ----------------------------------------------------------------

def phase_four_chip(sz, ctx) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    import __graft_entry__ as graft
    from sparkdl_tpu.serving import ReplicaPool, ServingEngine

    placements = graft._run_all_regimes(4)
    for regime, devs in placements.items():
        say("four_chip", f"regime {regime}: output shards on devices {devs}")
        check(len(devs) == 4, f"regime {regime} used devices {devs}, not 4")

    if ctx.featurize_devices is None:  # not run as a phase of its own
        phase_featurize(sz, ctx)
        ctx.ran.add("featurize")
    say("four_chip", f"featurize through BatchedRunner's automatic data "
                     f"parallelism: devices {ctx.featurize_devices}")
    check(len(ctx.featurize_devices) == 4,
          f"featurize used devices {ctx.featurize_devices}, not 4")

    w = jnp.asarray(np.random.default_rng(21).standard_normal((64, 64)),
                    jnp.float32)
    pool = ReplicaPool(lambda b: jnp.tanh(b["x"] @ w), batch_size=8,
                       devices=jax.devices()[:4])
    try:
        pool.warmup({"x": np.zeros((8, 64), np.float32)})
        with ServingEngine(pool, max_wait_s=0.002) as eng:
            futs = [eng.submit({"x": np.full((64,), i / 64, np.float32)})
                    for i in range(64)]
            got = np.stack([f.result(timeout=120) for f in futs])
            snap = eng.snapshot()
    finally:
        pool.close()
    # the same program on the default device, all rows in one batch: a
    # float32 matmul runs at the TPU's default (bf16-pass) precision on
    # both sides, so they agree to batch-shape rounding
    want = np.asarray(jnp.tanh(
        jnp.arange(64, dtype=jnp.float32)[:, None] / 64 * jnp.ones((1, 64))
        @ w))
    err = float(np.abs(got - want).max())
    check(err < 1e-3, f"replica outputs differ from the direct forward: {err}")
    served = {r["device"]: r["dispatched"] for r in snap["replicas"]}
    say("four_chip", f"ReplicaPool of four one-chip replicas, dispatches "
                     f"per device: {served}")
    check(len(served) == 4 and all(n > 0 for n in served.values()),
          f"not every replica served: {served}")


PHASE_FNS = {
    "device": phase_device, "featurize": phase_featurize,
    "serve": phase_serve, "train": phase_train, "kernel": phase_kernel,
    "four_chip": phase_four_chip,
}


def main(argv: "list[str]") -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", nargs="?", const=1, type=int, default=None,
                    metavar="N_DEVICES",
                    help="tiny sizes on N virtual CPU devices (default 1), "
                         "Pallas in interpret mode; NOT a chip result")
    ap.add_argument("--phases", default=",".join(PHASES),
                    help=f"comma-separated subset of {','.join(PHASES)}")
    args = ap.parse_args(argv)
    phases = [p for p in args.phases.split(",") if p]
    unknown = sorted(set(phases) - set(PHASES))
    if unknown:
        ap.error(f"unknown phase(s) {unknown}")

    rehearse = args.rehearse is not None
    if rehearse:
        # must precede the first jax import: the platform and the device
        # count are fixed when the backend initialises
        from sparkdl_tpu.runner.backends import virtual_cpu_overrides

        os.environ.update(virtual_cpu_overrides(
            args.rehearse, os.environ.get("XLA_FLAGS", "")))
        print("=== REHEARSAL on CPU at tiny sizes: checks control flow "
              "only, nothing below is a chip result ===", flush=True)

    from sparkdl_tpu.reliability.faults import fault_point
    from sparkdl_tpu.runtime.chip import (
        NoAcceleratorError,
        cache_entry_count,
        configure_compile_cache,
        require_tpu,
    )

    try:
        require_tpu(explicit_cpu_ok=rehearse)
    except NoAcceleratorError as e:
        print(f"chip_smoke: {e}", file=sys.stderr)
        return 2
    import jax

    ctx = types.SimpleNamespace(
        rehearse=rehearse, cache_dir=configure_compile_cache(),
        featurize_devices=None, ran=set())
    sz = REHEARSAL if rehearse else REAL

    failed, t_start = [], time.perf_counter()
    for name in phases:
        if name == "four_chip" and len(jax.devices()) < 4:
            say(name, f"skipped: {len(jax.devices())} device")
            continue
        t0 = time.perf_counter()
        try:
            fault_point(f"smoke.{name}")
            PHASE_FNS[name](sz, ctx)
        except Exception:
            traceback.print_exc()
            sys.stderr.flush()
            failed.append(name)
            say(name, f"FAILED after {time.perf_counter() - t0:.1f} s")
        else:
            say(name, f"ok in {time.perf_counter() - t0:.1f} s")
        ctx.ran.add(name)
    for name in PHASES:
        if name not in phases and name not in ctx.ran:
            say(name, "skipped: not in --phases")
    say("smoke", f"compile cache entries after: "
                 f"{cache_entry_count(ctx.cache_dir)}; total wall "
                 f"{time.perf_counter() - t_start:.1f} s")

    d = jax.devices()[0]
    result = {"ok": not failed,
              "device": {"platform": d.platform, "kind": d.device_kind,
                         "count": len(jax.devices())}}
    if failed:
        result["failed"] = failed
    if rehearse:
        result["rehearsal"] = True
    print(json.dumps(result), flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
