"""Device-trace the FUSED ResNet50 train step; print top ops by device time."""
import os
import sys
import tempfile
from collections import defaultdict

import numpy as np


def main():
    import jax
    import jax.numpy as jnp
    import optax

    from sparkdl_tpu.models.resnet import ResNet50
    from sparkdl_tpu.runtime.chip import require_tpu
    from sparkdl_tpu.train.vision import make_resnet50_fused_train_step

    require_tpu()

    batch = int(sys.argv[1]) if len(sys.argv) > 1 else 256
    size = 224
    dtype = jnp.bfloat16
    model = ResNet50(num_classes=1000, include_top=True, dtype=dtype)
    variables = model.init(jax.random.PRNGKey(0), jnp.zeros((1, size, size, 3)))
    params, batch_stats = variables["params"], variables["batch_stats"]
    tx = optax.sgd(0.1, momentum=0.9)
    opt_state = tx.init(params)
    step = make_resnet50_fused_train_step(
        tx, num_classes=1000, dtype=dtype, donate=True)

    rng = np.random.default_rng(0)
    x = jax.device_put(rng.random((batch, size, size, 3), np.float32))
    y = jax.device_put(rng.integers(0, 1000, batch).astype(np.int32))

    params, batch_stats, opt_state, loss = step(params, batch_stats, opt_state, x, y)
    float(loss)

    tmp = tempfile.mkdtemp(prefix="jaxprof_train_")
    with jax.profiler.trace(tmp):
        for _ in range(5):
            params, batch_stats, opt_state, loss = step(
                params, batch_stats, opt_state, x, y)
        float(loss)

    paths = []
    for root, _, files in os.walk(tmp):
        paths += [os.path.join(root, f) for f in files if f.endswith(".xplane.pb")]
    pd = jax.profiler.ProfileData.from_file(paths[0])
    for plane in pd.planes:
        if "TPU" not in plane.name:
            continue
        per_op = defaultdict(float)
        for line in plane.lines:
            for ev in line.events:
                per_op[ev.name] += ev.duration_ns
        total = sum(per_op.values())
        print(f"== plane {plane.name}: sum {total/1e6:.1f} ms over 5 steps ==")
        for nm, d in sorted(per_op.items(), key=lambda kv: -kv[1])[:40]:
            print(f"  {d/1e6:9.3f} ms  {nm[:120]}")


if __name__ == "__main__":
    main()
