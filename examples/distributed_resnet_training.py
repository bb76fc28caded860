"""Distributed ResNet50 data-parallel training (BASELINE.json config 4).

The HorovodRunner-parity workload: ``TPURunner(np).run(train_fn)`` launches
one process per host, bootstraps the global JAX runtime (coordinator
rendezvous replacing MPI), and inside ``train_fn`` the step is jitted over
a data-parallel mesh — gradient sync is an XLA ``psum`` over ICI, not an
NCCL ring. ``np=-2`` here runs two local processes with fake CPU devices
(HorovodRunner's documented local debug mode); on a real pod the same
script runs with ``np=<hosts>`` under Spark barrier mode.

Run: python examples/distributed_resnet_training.py [--steps N]
"""

from __future__ import annotations

import argparse


def train_fn(steps: int = 3, batch_per_device: int = 2, size: int = 32):
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from sparkdl_tpu.models.resnet import ResNet50
    from sparkdl_tpu.runtime.mesh import data_parallel_mesh
    from sparkdl_tpu.train.vision import make_vision_train_step

    mesh = data_parallel_mesh()  # every device across every process on dp
    n_dev = jax.device_count()
    batch = batch_per_device * n_dev

    model = ResNet50(num_classes=10, include_top=True)
    variables = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, size, size, 3))
    )
    params, batch_stats = variables["params"], variables["batch_stats"]
    tx = optax.sgd(1e-2, momentum=0.9)
    train_step = make_vision_train_step(model, tx)

    rng = np.random.default_rng(jax.process_index())
    data = NamedSharding(mesh, P(("dp", "fsdp")))
    repl = NamedSharding(mesh, P())
    with jax.set_mesh(mesh):
        params = jax.device_put(params, repl)
        batch_stats = jax.device_put(batch_stats, repl)
        opt_state = jax.device_put(tx.init(params), repl)
        history = []
        for i in range(steps):
            # Global batch assembled from per-process local shards, as the
            # infeed bridge does in production.
            x = jax.make_array_from_process_local_data(
                data, rng.random((batch, size, size, 3), np.float32)
            )
            y = jax.make_array_from_process_local_data(
                data, rng.integers(0, 10, batch).astype(np.int32)
            )
            t0 = time.perf_counter()
            params, batch_stats, opt_state, loss = train_step(
                params, batch_stats, opt_state, x, y
            )
            loss = float(loss)  # sync point
            dt = time.perf_counter() - t0
            history.append(
                {"step": i, "loss": loss,
                 "img_per_sec": batch / dt if i else 0.0}  # step 0 = compile
            )
    return {
        "devices": n_dev,
        "processes": jax.process_count(),
        "history": history,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--np", type=int, default=-2,
                    help="<0: |np| local processes; >0: cluster hosts")
    ap.add_argument("--steps", type=int, default=3)
    args = ap.parse_args()

    from sparkdl_tpu import TPURunner

    out = TPURunner(np=args.np, devices_per_process=2).run(
        train_fn, steps=args.steps
    )
    print(f"trained on {out['devices']} devices across "
          f"{out['processes']} processes")
    for h in out["history"]:
        print(f"  step {h['step']}: loss={h['loss']:.4f} "
              f"img/s={h['img_per_sec']:.1f}")


if __name__ == "__main__":
    main()
