"""Per-fusion ceiling analysis of the InceptionV3 featurization program.

Traces the exact program bench.py runs (merged-head InceptionV3, batch
128, preprocess fold), then for every TPU op >= 50us/step computes its
bandwidth-bound minimum time on this chip (peaks from
``observability.metrics.DEVICE_PEAKS``) from the HLO buffer shapes, and
prints the table PERF.md needs: measured vs bound per fusion, summed
ceiling vs measured program.
"""
import os
import re
import sys
import tempfile
from collections import defaultdict

import numpy as np

_SHAPE_RE = re.compile(r"(bf16|f32|s32|u8|pred|s8)\[([0-9,]*)\]")
_BYTES = {"bf16": 2, "f32": 4, "s32": 4, "u8": 1, "pred": 1, "s8": 1}


def op_bytes(name: str) -> int:
    """Sum buffer bytes of every shape literal in the HLO long name."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(name):
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _BYTES[dt]
    return total


def main():
    import jax
    import jax.numpy as jnp

    from sparkdl_tpu.observability.metrics import device_peak
    from sparkdl_tpu.runtime.chip import require_tpu

    require_tpu()
    peak_bw = device_peak().hbm_bytes_per_s

    from sparkdl_tpu.models.inception_fused import (
        fused_inception_v3_features,
    )
    from sparkdl_tpu.models.registry import build_flax_model
    from sparkdl_tpu.ops.fold import fold_tf_preprocess

    batch = 128
    size = 299
    _, variables = build_flax_model(
        "InceptionV3", weights=None, include_top=False, dtype=jnp.bfloat16
    )
    variables = fold_tf_preprocess(variables)

    @jax.jit
    def featurize(x):
        return fused_inception_v3_features(variables, x,
                                           dtype=jnp.bfloat16)

    rng = np.random.default_rng(0)
    x = jax.device_put(
        rng.integers(0, 256, (batch, size, size, 3), dtype=np.uint8))
    out = featurize(x)
    float(jnp.sum(out.astype(jnp.float32)))

    steps = 10
    tmp = tempfile.mkdtemp(prefix="jaxprof_inf_")
    with jax.profiler.trace(tmp):
        for _ in range(steps):
            out = featurize(x)
        float(jnp.sum(out.astype(jnp.float32)))

    paths = []
    for root, _, files in os.walk(tmp):
        paths += [os.path.join(root, f) for f in files
                  if f.endswith(".xplane.pb")]
    pd = jax.profiler.ProfileData.from_file(paths[0])
    for plane in pd.planes:
        if "TPU" not in plane.name:
            continue
        per_op = defaultdict(float)
        for line in plane.lines:
            for ev in line.events:
                per_op[ev.name] += ev.duration_ns
        rows = []
        prog_total = 0.0
        for nm, d in per_op.items():
            if not nm.startswith("%"):
                continue
            ms = d / steps / 1e6
            prog_total += ms
            if ms < 0.05:
                continue
            b = op_bytes(nm)
            bw_ms = b / peak_bw * 1e3
            rows.append((ms, bw_ms, nm))
        rows.sort(reverse=True)
        print(f"== plane {plane.name}: program ops sum "
              f"{prog_total:.2f} ms/step ==")
        print(f"{'meas ms':>8} {'bw-min ms':>10} {'eff':>5}  op")
        ceil = 0.0
        small = prog_total
        for ms, bw_ms, nm in rows:
            eff = bw_ms / ms if ms else 0
            ceil += bw_ms
            small -= ms
            kind = nm.split(" = ")[0][:60]
            print(f"{ms:8.3f} {bw_ms:10.3f} {eff:5.1%}  {kind}")
        print(f"(+ {small:.2f} ms in ops under 50us each)")
        print(f"bandwidth-floor of listed ops: {ceil:.2f} ms; "
              f"measured listed: {prog_total - small:.2f} ms")


if __name__ == "__main__":
    main()
