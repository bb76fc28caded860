"""Data-parallel classification fine-tuning (BERT-base config and friends).

The step is plain jit-over-mesh SPMD: batch sharded on the data axes,
params replicated (or tp-sharded when the model's kernels carry tp
metadata), gradient psum inserted by XLA from the shardings — the
HorovodRunner `hvd.DistributedOptimizer` allreduce (SURVEY.md 3.4) with no
user-space ring. Drop the returned ``train_fn`` into ``TPURunner.run`` for
the multi-host form.
"""

from __future__ import annotations

import time
from typing import Any, Callable, Iterator

import flax.struct
import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh

from sparkdl_tpu.observability.registry import registry
from sparkdl_tpu.observability.tracing import span
from sparkdl_tpu.partition import DataParallelPartitioner, Partitioner
from sparkdl_tpu.reliability.faults import fault_point
from sparkdl_tpu.runtime.completion import AsyncFetcher
from sparkdl_tpu.runtime.dispatch import (
    ChainPolicy,
    chain_carry,
    record_dispatch,
    shape_key,
)

_M_STEPS = registry().counter(
    "sparkdl_train_steps_total", "optimizer steps taken")
_M_EXAMPLES = registry().counter(
    "sparkdl_train_examples_total", "examples consumed by training")
_M_STEP_TIME = registry().histogram(
    "sparkdl_train_step_seconds", "train step wall time (dispatch + sync)")


@flax.struct.dataclass
class TrainState:
    """Pytree train state (params/opt_state/step cross the jit boundary)."""

    params: Any
    opt_state: Any
    step: jax.Array


def softmax_cross_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    return optax.softmax_cross_entropy_with_integer_labels(
        logits.astype(jnp.float32), labels
    ).mean()


def classification_train_step(
    apply_fn: Callable[..., jax.Array],
    tx: optax.GradientTransformation,
) -> Callable:
    """Jittable (state, batch) -> (state, metrics) step.

    ``apply_fn(params, **batch_inputs) -> logits``; batch is a dict with
    ``labels`` plus whatever apply_fn consumes.
    """

    def step(state: TrainState, batch: dict) -> tuple[TrainState, dict]:
        labels = batch["labels"]
        inputs = {k: v for k, v in batch.items() if k != "labels"}

        def loss_fn(params):
            logits = apply_fn(params, **inputs)
            return softmax_cross_entropy(logits, labels), logits

        (loss, logits), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            state.params
        )
        updates, opt_state = tx.update(grads, state.opt_state, state.params)
        params = optax.apply_updates(state.params, updates)
        acc = jnp.mean((jnp.argmax(logits, -1) == labels).astype(jnp.float32))
        return (
            state.replace(params=params, opt_state=opt_state, step=state.step + 1),
            {"loss": loss, "accuracy": acc},
        )

    return step


def finetune_classifier(
    apply_fn: Callable[..., jax.Array],
    params: Any,
    batches: Iterator[dict] | list[dict],
    *,
    learning_rate: float = 2e-5,
    weight_decay: float = 0.01,
    tx: "optax.GradientTransformation | None" = None,
    mesh: Mesh | None = None,
    partitioner: "Partitioner | None" = None,
    metrics_cb: Callable[[dict], None] | None = None,
    checkpoint_dir: "str | None" = None,
    checkpoint_every: int = 100,
    keep_checkpoints: int = 3,
    chain_steps: "int | None" = 1,
    input_prefetch: "int | None" = None,
    autotune: "bool | None" = None,
) -> tuple[Any, list[dict]]:
    """Run the fine-tune loop over ``batches``; returns (params, history).

    Each batch dict's arrays are placed batch-sharded over the mesh's data
    axes before the jitted step — under TPURunner each process feeds its
    local shard of the global batch.

    ``tx`` overrides the default ``adamw(learning_rate, weight_decay)``
    optimizer — pass any optax chain (warmup/cosine schedules,
    ``optax.MultiSteps`` gradient accumulation, clipping, ...) without
    forking the loop.

    ``partitioner`` owns every placement decision (partition/): batch
    sharding, param/optimizer-state layout, and the step's sharding
    constraints. Default: :class:`~sparkdl_tpu.partition.
    DataParallelPartitioner` over ``mesh`` (or all local devices) — the
    exact historical dp behavior. Pass
    ``DataParallelPartitioner(make_mesh(dp=4, fsdp=2), zero_axis="fsdp")``
    for ZeRO-sharded optimizer state (per-chip opt memory ~1/fsdp,
    measured into ``sparkdl_opt_state_bytes{axis}``), or an
    :class:`~sparkdl_tpu.partition.SPMDPartitioner` for rule-placed
    tp/fsdp params. The loss trajectory is invariant across
    partitioners up to float reduction order.

    ``chain_steps`` fuses K optimizer steps into ONE device dispatch
    (``lax.scan`` with the TrainState donated — runtime/dispatch.py),
    amortizing the per-dispatch gap over K short steps. The loss/accuracy trajectory in ``history`` stays
    per-step and numerically identical — the scan collects every step's
    metrics — but host-side work (metrics_cb, checkpoint saves, registry
    updates) happens once per K steps. None = auto-calibrate K from
    measured step time vs the dispatch gap; 1 (default) = one dispatch
    per step, the exact pre-chaining behavior.

    Host-metric reads are asynchronous (runtime/completion.py): each
    dispatch's metric values start their device→host copy immediately
    and are folded into ``history``/``metrics_cb`` one dispatch later,
    behind the next dispatch — same values, same order, no blocking
    device read on the hot path (checkpoint cadence stays at dispatch
    boundaries, driven by a host-side step counter).

    With ``checkpoint_dir`` set, the full train state is async-saved every
    ``checkpoint_every`` steps plus once at the end, and an existing
    checkpoint in that directory is resumed from (already-trained steps are
    skipped) — the barrier-retry resume story from SURVEY.md §5.

    ``input_prefetch`` is the input iterator's host-side readahead depth
    (sparkdl_tpu/ingest): a background producer keeps that many batches
    staged ahead of the dispatch loop, so a slow ``batches`` source
    (decode, augmentation, a remote read) overlaps the device step
    instead of serializing with it. The batch stream — order, values,
    resume replay — is exactly the pre-pipeline iterator's (parity
    pinned by tests/ingest/test_ported_parity.py). None = auto
    (``SPARKDL_TPU_PREFETCH`` pin, else 2; a live autotuner knob when
    ``autotune`` resolves on); 0 disables readahead (the strictly
    consumer-pulled pre-pipeline behavior); an explicit depth pins.
    """
    if chain_steps is not None and chain_steps < 1:
        raise ValueError(f"chain_steps must be >= 1, got {chain_steps}")
    if partitioner is None:
        # mesh= keeps its historical meaning: dp over that mesh's data
        # axes. Anything richer (ZeRO opt-state sharding, rule-placed
        # tp/fsdp params) is spelled as a Partitioner.
        partitioner = DataParallelPartitioner(mesh=mesh)
    elif mesh is not None and partitioner.mesh is not mesh:
        raise ValueError(
            "pass either mesh= or partitioner= (the partitioner owns "
            "its mesh), not both"
        )
    if tx is None:
        tx = optax.adamw(learning_rate, weight_decay=weight_decay)
    # one tree convention inside the loop: flax Partitioned boxes are
    # sharding METADATA, and the partitioner is now the object that owns
    # placement — unbox up front so params, grads, and optimizer state
    # all flatten identically (a boxed tx.init against unboxed grads is
    # a tree-structure mismatch deep inside optax)
    from sparkdl_tpu.partition.partitioner import _unbox

    params = _unbox(params)
    step_fn = classification_train_step(apply_fn, tx)
    policy = ChainPolicy(
        max_chain=chain_steps if chain_steps is not None else 32
    )
    if chain_steps is None:
        policy.gap()  # auto mode: calibrate before the loop, not inside

    data_sharding = partitioner.batch_sharding()
    # the stacked [K, batch, ...] chain feed: K is the scanned dim,
    # batch stays sharded over the data axes exactly as the single step
    chain_sharding = partitioner.chain_batch_sharding()
    ckpt = None
    if checkpoint_dir is not None:
        from sparkdl_tpu.checkpoint import CheckpointManager

        ckpt = CheckpointManager(
            checkpoint_dir, keep=keep_checkpoints,
            save_interval_steps=checkpoint_every,
        )
    # Input pipeline (sparkdl_tpu/ingest): host-side readahead between
    # the batch source and the dispatch loop. transfer=identity — device
    # placement stays in run_single/run_chain where the shardings live.
    from sparkdl_tpu import ingest
    from sparkdl_tpu.ingest.pipeline import resolve_pin

    feed_depth, feed_pinned, _ = resolve_pin(
        input_prefetch, "SPARKDL_TPU_PREFETCH", 2, what="input_prefetch")
    input_pipe: "ingest.Pipeline | None" = None
    if feed_depth > 0:
        input_pipe = ingest.Pipeline(batches, name="finetune").prefetch(
            feed_depth, transfer=lambda b: b, pinned=feed_pinned)
        if ingest.autotune_enabled(autotune):
            input_pipe.autotune(True)
        batches = input_pipe
    try:
        with partitioner.mesh_context():
            state = TrainState(
                params=partitioner.shard_params(params),
                opt_state=partitioner.shard_opt_state(tx.init(params)),
                # commit the scalar too: an uncommitted device-0 step next
                # to 8-device params is a mixed-device error under jit on
                # runtimes without an ambient-mesh auto-commit
                step=partitioner.shard_replicated(
                    jnp.zeros((), jnp.int32)),
            )
            # the ZeRO memory win (or its absence) is a measured number:
            # sparkdl_opt_state_bytes{axis} per chip, set once at init
            partitioner.export_opt_state_bytes(state.opt_state)
            # pin the output state to the input layout from INSIDE the
            # trace — survives jit, chain_carry's scan, and donation, so
            # sharded optimizer state stays sharded across every step
            state_shardings = jax.tree_util.tree_map(
                lambda a: a.sharding, state)
            wrapped_step = partitioner.wrap_step(step_fn, state_shardings)
            step = jax.jit(wrapped_step)
            chained_step = (chain_carry(wrapped_step, donate=True)
                            if chain_steps != 1 else None)
            resume_step = 0
            if ckpt is not None and ckpt.latest_step() is not None:
                state = ckpt.restore(template=state)
                resume_step = int(state.step)
            history: list[dict] = []
            last_saved = resume_step
            #: host-tracked mirror of state.step — reading the device
            #: scalar back per dispatch would block on the exact path
            #: the async pipeline is hiding
            host_step = resume_step
            # Async host-metric reads (runtime/completion.py): the D2H
            # copy of each dispatch's metrics starts as soon as the
            # dispatch lands and is COLLECTED one window later, behind
            # the following dispatch — the history/metrics_cb trajectory
            # stays per-step, in order, and numerically identical; only
            # the host-side collection point moves.
            fetcher = AsyncFetcher(window=2, path="train")
            #: (ticket, wall_s, k, base_step, n_examples) awaiting emit
            deferred: "list[tuple]" = []

            def emit(entries: "list[dict]") -> None:
                for m in entries:
                    _M_STEPS.inc()
                    _M_EXAMPLES.inc(m.pop("_examples"))
                    _M_STEP_TIME.observe(m["step_time_s"])
                    history.append(m)
                    if metrics_cb is not None:
                        metrics_cb(m)

            def collect(limit: int) -> None:
                # resolve deferred metric reads down to ``limit`` in
                # flight (submission order — the trajectory never
                # reorders)
                while len(deferred) > limit:
                    ticket, wall, k, base, n_ex = deferred.pop(0)
                    ms = ticket.result()
                    emit([
                        {
                            **{key: float(np.asarray(v).reshape(-1)[j])
                               for key, v in ms.items()},
                            "step_time_s": wall / k,
                            "step": base + j + 1,
                            "_examples": n_ex,
                        }
                        for j in range(k)
                    ])

            def maybe_checkpoint() -> None:
                # checkpoint cadence stays AT the dispatch boundary (the
                # state is current here); only metric reads are deferred
                nonlocal last_saved
                if ckpt is None:
                    return
                if ckpt.save(host_step, state):
                    last_saved = host_step
                elif host_step - last_saved >= checkpoint_every:
                    # chain boundaries (step = K, 2K, ...) may never
                    # align with the manager's step-modulo policy:
                    # force whenever a full interval has passed since
                    # the last landed save, so chaining can thin the
                    # cadence but never silently disable it
                    if ckpt.save(host_step, state, force=True):
                        last_saved = host_step

            def run_single(batch: dict) -> None:
                nonlocal state, host_step
                fault_point("dispatch")
                n_examples = len(next(iter(batch.values())))
                with span("train.step", step=host_step,
                          examples=n_examples):
                    staged = {
                        k: jax.device_put(jnp.asarray(v), data_sharding)
                        for k, v in batch.items()
                    }
                    t0 = time.perf_counter()
                    state, metrics = step(state, staged)
                    # sync on the step scalar (not the metric values):
                    # the wall stays an honest device time for the
                    # ChainPolicy while the metric payload is still in
                    # async flight
                    jax.block_until_ready(state.step)
                    wall = time.perf_counter() - t0
                record_dispatch("train", 1, wall)
                policy.record(wall, 1)
                deferred.append(
                    (fetcher.submit(metrics), wall, 1, host_step,
                     n_examples)
                )
                host_step += 1
                maybe_checkpoint()
                collect(fetcher.window - 1)

            def run_chain(group: "list[dict]") -> None:
                # K steps, ONE dispatch: stack on host, scan on device
                # with the TrainState donated; per-step metrics come back
                # stacked so the recorded trajectory stays exact.
                nonlocal state, host_step
                fault_point("dispatch")
                k = len(group)
                n_examples = len(next(iter(group[0].values())))
                with span("dispatch.chain", path="train", k=k,
                          examples=k * n_examples):
                    xs = {
                        key: jax.device_put(
                            np.stack([np.asarray(b[key]) for b in group]),
                            chain_sharding,
                        )
                        for key in group[0]
                    }
                    t0 = time.perf_counter()
                    state, ms = chained_step(state, xs)
                    jax.block_until_ready(state.step)
                    wall = time.perf_counter() - t0
                record_dispatch("train", k, wall)
                policy.record(wall, k)
                deferred.append(
                    (fetcher.submit(ms), wall, k, host_step, n_examples)
                )
                host_step += k
                maybe_checkpoint()
                collect(fetcher.window - 1)

            pending: "list[dict]" = []
            pending_key = None
            try:
                for i, batch in enumerate(batches):
                    if i < resume_step:  # deterministic replay on resume
                        continue
                    if chained_step is None:
                        run_single(batch)
                        continue
                    key = shape_key(batch)
                    if pending and key != pending_key:
                        # ragged boundary (epoch-tail batch): the scan
                        # can't stack mixed shapes — flush unchained
                        for b in pending:
                            run_single(b)
                        pending = []
                    pending.append(batch)
                    pending_key = key
                    k_target = (chain_steps if chain_steps is not None
                                else policy.chain_len())
                    if len(pending) >= k_target:
                        if len(pending) > 1:
                            run_chain(pending)
                        else:
                            run_single(pending[0])
                        pending = []
                for b in pending:  # stream tail: no one-off-K compile
                    run_single(b)
            except BaseException:
                # A crashed step must not strand the metrics of steps
                # whose dispatches already LANDED: a checkpoint may cover
                # those steps, so a resume will never re-run them — the
                # crash-time drain is what keeps the recovered history
                # (reliability/supervisor.py) bitwise-complete. Best
                # effort: if the device itself died, the drain fails too
                # and those steps are re-run from the checkpoint anyway.
                try:
                    collect(0)
                except BaseException:
                    pass
                raise
            collect(0)  # drain the async metric window: history complete
            if (
                ckpt is not None
                and host_step > resume_step
                and last_saved != host_step
            ):
                # final state always lands regardless of the interval policy
                ckpt.save(host_step, state, force=True)
            return state.params, history
    finally:
        if input_pipe is not None:
            # a crash mid-loop must not leak the readahead producer
            input_pipe.close()
        if ckpt is not None:
            ckpt.close()


def batches_from_arrays(
    arrays: dict[str, np.ndarray], batch_size: int, *, epochs: int = 1,
    seed: int = 0, drop_remainder: bool = True,
) -> Iterator[dict]:
    """Shuffled minibatch iterator over same-length arrays (tiny-data path,
    the KerasImageFileEstimator-style in-memory fit)."""
    n = len(next(iter(arrays.values())))
    rng = np.random.default_rng(seed)
    for _ in range(epochs):
        order = rng.permutation(n)
        end = n - n % batch_size if drop_remainder else n
        for i in range(0, end, batch_size):
            idx = order[i:i + batch_size]
            yield {k: v[idx] for k, v in arrays.items()}
