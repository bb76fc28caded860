"""Shared batched-inference engine for all transformers.

The TPU-native replacement for the reference's per-partition
``Session.run`` hot loop (SURVEY.md 3.1/3.2): a jitted apply function mapped
over bucketed, padded batches with double-buffered host→device prefetch.
jit's shape-keyed cache means each bucket size compiles exactly once.
"""

from __future__ import annotations

import dataclasses
import logging
import threading
import time
import weakref
from concurrent.futures import TimeoutError as FuturesTimeoutError
from typing import Any, Callable, Iterator

import jax
import numpy as np

from sparkdl_tpu.observability import tracing
from sparkdl_tpu.observability.tracing import span
from sparkdl_tpu.reliability.faults import fault_point
from sparkdl_tpu.runtime.batching import (
    default_buckets,
    pad_to_bucket,
)
from sparkdl_tpu.runtime.completion import (
    AsyncFetcher,
    FetchTicket,
    start_fetch,
)
from sparkdl_tpu.runtime.dispatch import (
    ChainPolicy,
    ScanChainer,
    record_dispatch,
)


@dataclasses.dataclass
class BatchedRunner:
    """Maps ``apply_fn(batch_dict) -> output array(s)`` over row streams.

    apply_fn must be shape-polymorphic only across the bucket set (it is
    jitted; one compile per bucket). Outputs follow the batch leading dim.

    Host->device staging: every uniform-row feed rides the native C++
    staging ring (:class:`~sparkdl_tpu.native.bridge.DeviceFeeder`):
    packer thread -> stable slot -> transfer thread -> device,
    double-buffered so the chip computes batch i while batch i+1 is on
    the wire and i+2 is packing — the TensorFrames-block-feed equivalent
    (SURVEY.md 2.15) on the actual hot path. Multi-tensor feeds (text's
    input_ids+attention_mask, multi-input graphs) pack as a
    struct-of-tensors slot with a fixed byte segment per key. Ragged
    feeds and hosts without the .so use the pure-Python prefetcher with
    the same overlap semantics.

    ``ragged_rows=True`` declares that row shapes vary across batches
    (e.g. un-resized images into a dynamic-spatial graph): ring slots are
    fixed-size, so such feeds must keep to the Python path.

    Local multi-chip data parallelism (SURVEY.md 2.11a: the reference
    scales inference DP over DataFrame partitions ACROSS hosts; chips
    WITHIN a host are this class's job): with ``data_parallel`` left at
    auto and >1 local device, batches land sharded over a 1-axis ``dp``
    mesh of the local devices (``jax.device_put`` with a
    ``NamedSharding`` in the transfer hook), and jit compiles the apply
    SPMD from the committed input sharding — a 4-chip host featurizes 4x
    without any Spark-side change. Bucket sizes are rounded up to
    multiples of the device count so the batch dim always divides the
    mesh; single-device hosts keep the exact single-chip behavior.
    """

    apply_fn: Callable[[dict[str, Any]], Any]
    batch_size: int = 64
    #: Staging-pipeline depth (batches in flight ahead of the device).
    #: None = auto: ``SPARKDL_TPU_PREFETCH`` env pin if set, else 2 —
    #: and the depth is a live autotuner knob when :attr:`autotune` is
    #: on. An explicit int (or the env var) PINS the depth and excludes
    #: it from tuning; both set and disagreeing fails loud.
    prefetch: "int | None" = None
    ragged_rows: bool = False
    #: None = auto (shard over local devices when there is more than one);
    #: False forces single-device; True demands >1 local device.
    data_parallel: "bool | None" = None
    #: Fused multi-step dispatch (runtime/dispatch.py): chain this many
    #: same-bucket batches per device dispatch in :meth:`run`. None =
    #: auto (``SPARKDL_TPU_CHAIN_K`` env, else the ChainPolicy picks K
    #: from measured program time vs the calibrated dispatch gap); 1
    #: disables chaining. Outputs are bitwise-identical either way —
    #: chaining is a dispatch decision, never a numeric one. Memory:
    #: chaining holds up to K staged batches (auto caps K at 8) plus a
    #: stacked [K, ...] copy inside the fused program — workloads whose
    #: per-batch inputs already sit near the HBM limit should pass
    #: ``chain_k=1`` (the chain buys nothing there anyway: big batches
    #: mean long programs, where the policy degrades to K=1 itself).
    chain_k: "int | None" = None
    #: Async completion (runtime/completion.py): start each result's
    #: device->host copy as soon as its dispatch lands and collect it
    #: while the NEXT dispatch runs, instead of the blocking
    #: ``np.asarray`` that serialized readback with dispatch. True
    #: (default) pipelines :meth:`run` readback ``fetch_window`` deep;
    #: False restores the strictly blocking readback (the parity
    #: reference — outputs are bitwise identical either way).
    async_fetch: bool = True
    #: Results in flight for the async readback window. None = auto:
    #: prefetch depth x resolved chain length (the same pipeline depth
    #: the input side already runs at), so device memory holds at most
    #: that many result buffers.
    fetch_window: "int | None" = None
    #: Pin every dispatch of this runner to ONE device (a ReplicaPool
    #: executor). Implies no local data-parallel sharding — the pool
    #: scales across devices by replication, not by splitting batches.
    #: Sugar for ``partitioner=SingleDevicePartitioner(device)``.
    device: Any = None
    #: The placement owner (sparkdl_tpu/partition): every staged batch
    #: goes through ``partitioner.shard_batch``. None = auto —
    #: :class:`~sparkdl_tpu.partition.SingleDevicePartitioner` (pinned
    #: or default device), or a
    #: :class:`~sparkdl_tpu.partition.DataParallelPartitioner` over the
    #: local devices when ``data_parallel`` resolves on. Pass one
    #: explicitly to run this runner over a custom data-parallel mesh
    #: layout (the chunk/bucket sizes round to its data-axis size).
    partitioner: Any = None
    #: Online autotuning of the ingest knobs (sparkdl_tpu/ingest): the
    #: staging depth, the dispatch chain K, and the native packer
    #: parallelism become live knobs on the process
    #: :func:`~sparkdl_tpu.ingest.default_tuner`, resized from the
    #: measured starvation / producer-blocked shares. None = defer to
    #: ``SPARKDL_TPU_AUTOTUNE`` (default off). Explicitly pinned knobs
    #: (``prefetch=``, ``chain_k=``, their env pins) are registered for
    #: visibility but never moved.
    autotune: "bool | None" = None

    def __post_init__(self):
        from sparkdl_tpu.ingest.pipeline import resolve_pin, unique_name

        self._prefetch_depth, self._prefetch_pinned, _ = resolve_pin(
            self.prefetch, "SPARKDL_TPU_PREFETCH", 2, what="prefetch")
        self._prefetch_depth = max(1, self._prefetch_depth)
        # knob prefix: unique per RUNNER so concurrent autotuned runners
        # never collide in the tuner's name-keyed registry, while one
        # runner's successive streams (warmup, then the real run) keep
        # one stable set of names (identity-checked unregistration
        # handles the rare same-runner-concurrent-streams case)
        self._pipe_name = unique_name("batch")
        self._chainer = ScanChainer(
            self.apply_fn, path="batch", chain_k=self.chain_k,
            # auto mode holds K staged batches for the chain on top of
            # the prefetch queue: cap auto-K at 8 so peak input memory
            # stays bounded on unchanged caller code (PERF.md: K=8
            # captures most of the measured dispatch win; an explicit
            # chain_k raises the ceiling deliberately)
            policy=ChainPolicy(max_chain=8),
        )
        # run_batch and the unchained run path share this executable
        self._jitted = self._chainer.jit_single
        self._chunk = self.batch_size
        self._buckets = default_buckets(self.batch_size)
        if self.fetch_window is not None and self.fetch_window < 1:
            raise ValueError(
                f"fetch_window must be >= 1, got {self.fetch_window}"
            )
        # Placement routes through ONE object (sparkdl_tpu/partition):
        # the partitioner decides where every staged batch lands, and
        # the chunk/bucket geometry follows its data-axis size.
        from sparkdl_tpu.partition import (
            DataParallelPartitioner,
            SingleDevicePartitioner,
        )

        if self.device is not None:
            if self.data_parallel is True:
                raise ValueError(
                    "device= pins this runner to one chip; data_parallel "
                    "scaling is the ReplicaPool's job (one runner per "
                    "device), not this runner's"
                )
            if self.partitioner is not None:
                raise ValueError(
                    "device= is sugar for partitioner="
                    "SingleDevicePartitioner(device); pass one or the "
                    "other, not both"
                )
            self._partitioner = SingleDevicePartitioner(self.device)
            return
        if self.partitioner is not None:
            if self.data_parallel is True:
                raise ValueError(
                    "partitioner= owns placement; an explicit "
                    "data_parallel=True would be silently overridden — "
                    "leave it at None and encode dp in the partitioner's "
                    "mesh instead"
                )
            self._partitioner = self.partitioner
            self._round_to_data_axes(self._partitioner.data_axis_size)
            return
        n_local = jax.local_device_count()
        if self.data_parallel is True and n_local == 1:
            raise ValueError(
                "data_parallel=True but only one local device; use "
                "data_parallel=None for auto fallback"
            )
        self._partitioner = SingleDevicePartitioner()
        if self.data_parallel is not False and n_local > 1:
            from sparkdl_tpu.runtime.mesh import data_parallel_mesh

            # never spread a batch thinner than one row per device
            n_use = max(1, min(n_local, self.batch_size))
            if n_use == 1:
                if self.data_parallel is True:
                    raise ValueError(
                        "data_parallel=True but batch_size=1 leaves "
                        "nothing to shard"
                    )
            else:
                self._partitioner = DataParallelPartitioner(
                    data_parallel_mesh(jax.local_devices()[:n_use])
                )
                self._round_to_data_axes(n_use)

    def _round_to_data_axes(self, n_use: int) -> None:
        """Round the dispatch chunk DOWN and the buckets UP to multiples
        of the partitioner's data-axis size, so the batch dim always
        divides the mesh (never above the caller's memory ask — the
        caller-supplied ``batch_size`` field stays untouched; the
        rounded value is the private dispatch chunk)."""
        if n_use <= 1:
            return
        if self.batch_size < n_use:
            # only reachable with an explicit partitioner= (the auto-dp
            # path clamps its device count to batch_size); rounding UP
            # would dispatch more rows than the caller's memory ask
            raise ValueError(
                f"batch_size={self.batch_size} is smaller than the "
                f"partitioner's {n_use}-way data axes — every dispatch "
                f"needs at least one row per data-axis device; raise "
                f"batch_size or use a smaller mesh"
            )
        self._chunk = self.batch_size // n_use * n_use
        if self._chunk != self.batch_size:
            logging.getLogger(__name__).debug(
                "batch_size %d rounded to %d-way data-axis chunk %d "
                "(configured value preserved on .batch_size)",
                self.batch_size, n_use, self._chunk,
            )
        self._buckets = tuple(sorted({
            -(-b // n_use) * n_use
            for b in default_buckets(self._chunk)
        }))

    @property
    def _sharding(self):
        """Introspection shim: the batch ``NamedSharding`` when this
        runner splits batches over a mesh, else None. Derived from the
        partitioner — placement has exactly one owner."""
        if getattr(self._partitioner, "mesh", None) is None:
            return None
        return self._partitioner.batch_sharding()

    @property
    def chunk_size(self) -> int:
        """Rows per device dispatch: ``batch_size`` rounded down to a
        multiple of the dp device count (equal to ``batch_size`` on
        single-device hosts)."""
        return self._chunk

    @property
    def max_inflight_batches(self) -> int:
        """How many ``run_batch_async`` dispatches a caller (the
        micro-batcher) should keep in flight against this runner: one
        resolving while one runs. A :class:`~sparkdl_tpu.serving.replicas.
        ReplicaPool` overrides this with its healthy replica count."""
        return 2 if self.async_fetch else 1

    def _fetch_window(self) -> int:
        """Async readback window: prefetch depth x resolved chain length
        (a K-chain hands back K results per dispatch, so the window must
        cover ``prefetch`` dispatches' worth of outputs to keep the
        pipeline full). This holds up to that many RESULT buffers on the
        device — workloads with outputs as large as their inputs should
        pin ``fetch_window`` lower."""
        if self.fetch_window is not None:
            return self.fetch_window
        chain = self._chainer.chain_k or self._chainer.policy.max_chain
        return max(2, self._prefetch_depth) * max(1, chain)

    def run(self, rows: Iterator[dict[str, np.ndarray]]) -> Iterator[np.ndarray]:
        """Yield one output per input row, in order.

        Single-array apply_fns yield arrays; tuple-valued apply_fns (e.g.
        multi-output ingested graphs) yield per-row tuples.

        The feed is one composable ingest pipeline (sparkdl_tpu/ingest):
        ``rows -> batch(bucketing) -> to_device(ring | prefetch)`` — the
        stage chain replaces the hand-wired rebatch/_device_feed pair
        and, with :attr:`autotune` on, exports its depth plus this
        runner's chain-K and the native packer parallelism as live
        tuner knobs. Outputs are bitwise-identical to the pre-pipeline
        path (parity pinned by tests/ingest/test_ported_parity.py).
        """
        from sparkdl_tpu import ingest

        # keep (n_valid) alongside the device computation
        metas: list[int] = []
        tuning = ingest.autotune_enabled(self.autotune)
        pname = self._pipe_name
        pipe = (
            ingest.Pipeline(rows, name=pname)
            .batch(self._chunk, self._buckets)
            .tap(lambda b: metas.append(b.n_valid))
            .apply(lambda b: b.arrays)
            .to_device(
                transfer=self._transfer,
                depth=self._feed_depth(),
                ragged=self.ragged_rows,
                max_bucket=max(self._buckets),
                pinned=self._prefetch_pinned,
                # the staging depth may never shrink below the chain
                # ceiling: a K-chain consumes K staged batches per
                # dispatch, so depth < K turns chain assembly into the
                # serialization point (_feed_depth's invariant, kept
                # under tuning by the knob floor)
                lo=self._chain_floor(),
            )
        )
        if tuning:
            pipe.autotune(True, extra_knobs=self._tuning_knobs(pname))
        results = iter(pipe)
        # Fused dispatch: runs of same-bucket staged batches are chained
        # K-per-dispatch (lax.scan inside one jit) behind the prefetch
        # buffer; ragged tail buckets flush unchained. Output order and
        # values are identical to the one-dispatch-per-batch loop.
        # NOTE: the device step now lands in the chainer's
        # ``dispatch.chain`` span (path="batch"); the old per-batch
        # ``batch.device_step`` span would only time the host-side
        # conversion of an already-materialized output here, so it is
        # gone rather than left lying about where the time went.
        outputs = self._chainer.map_stream(results)
        if self.async_fetch:
            # Async completion: each output's D2H copy starts the moment
            # its dispatch lands and is collected while the following
            # dispatches run — readback hides behind compute instead of
            # serializing with it. Bitwise-identical to the blocking
            # path; a device error still surfaces on ITS batch.
            outputs = AsyncFetcher(
                window=self._fetch_window(), path="batch"
            ).stream(outputs)
        for i, out in enumerate(outputs):
            n = metas[i]
            if isinstance(out, (tuple, list)):
                arrays: Any = [np.asarray(o) for o in out]
            else:
                arrays = np.asarray(out)
            if isinstance(arrays, list):
                for j in range(n):
                    yield tuple(a[j] for a in arrays)
            else:
                yield from arrays[:n]

    def _chain_floor(self) -> int:
        """The chain ceiling the staging depth must cover: the RESOLVED
        chain_k (env override included), or the policy ceiling in auto
        mode since K can ramp there after the first measured dispatch."""
        return self._chainer.chain_k or self._chainer.policy.max_chain

    def _feed_depth(self) -> int:
        """Staging depth: a K-chain consumes K staged batches per
        dispatch, so the pipeline must run at least that far ahead or
        the chain assembly itself becomes the serialization point."""
        return max(self._prefetch_depth, self._chain_floor())

    def _tuning_knobs(self, prefix: str) -> "list[Any]":
        """This runner's non-stage knobs for the autotuner: the dispatch
        chain K (inverted — it grows when the CONSUMER side lags, i.e.
        producer-blocked, to amortize per-dispatch overhead) and the
        native packer parallelism. Pinned chain lengths (explicit
        ``chain_k=`` or ``SPARKDL_TPU_CHAIN_K``) register pinned so the
        gauge still exports them but the tuner never moves them."""
        from sparkdl_tpu.ingest.autotune import Knob
        from sparkdl_tpu.native import bridge

        ch = self._chainer

        def get_k(ch=ch) -> int:
            return int(ch.chain_k if ch.chain_k is not None
                       else ch.policy.chain_len())

        def set_k(v: int, ch=ch) -> None:
            # map_stream consults target_chain_len() per item, so a live
            # chain_k write takes effect at the next group boundary.
            # Growth is clamped to the ChainPolicy's overhead-aware
            # recommendation: chaining past the K that already holds the
            # dispatch-gap share under target buys nothing and only
            # delays host visibility — on a backend with a negligible
            # gap (local CPU) the recommendation is 1 and the tuner's
            # grow is a no-op the read-back check discards.
            ch.chain_k = max(1, min(int(v), ch.policy.chain_len()))

        knobs = [Knob(
            name=f"{prefix}.chain_k", get=get_k, set=set_k,
            lo=1, hi=ch.policy.max_chain, inverted=True,
            pinned=ch.pinned, pin_source=ch.pin_source,
        )]
        # the pack-thread knob deliberately keeps its process-global
        # name: it closes over module-global state shared by every
        # stream, so all registrations ARE the same knob
        knobs.extend(bridge.pack_knobs())
        return knobs

    def _device_feed(
        self, host_batches: Iterator[dict[str, np.ndarray]]
    ) -> Iterator[dict[str, Any]]:
        """Stage host batch dicts onto the device with transfer/compute
        overlap; picks the native ring when it applies. (The streaming
        entry is :meth:`run`'s pipeline — this is the same ``to_device``
        stage exposed for direct feeds and introspection.)"""
        from sparkdl_tpu.ingest.pipeline import _ToDeviceStage

        stage = _ToDeviceStage(
            self._transfer, self._feed_depth(), self.ragged_rows,
            max(self._buckets), None, "device",
            pinned=self._prefetch_pinned,
        )
        return iter(stage.build(iter(host_batches), None))

    def run_batch(self, arrays: dict[str, np.ndarray]):
        """One-shot dispatch for the online serving path: pad the stacked
        batch to its bucket, stage it (dp-sharded on multi-chip hosts —
        the same ``_transfer`` the streaming path uses), run the SAME
        jitted program the batch path compiled, and unpad.

        Returns the output array [n, ...] (or a tuple of arrays for
        multi-output apply_fns). An empty input (a serving flush tick)
        still runs the smallest-bucket program — pad_to_bucket zero-fills
        it — so the outputs keep their real dtypes and feature shapes,
        just with 0 rows.
        """
        return self.run_batch_async(arrays).result()

    def run_batch_async(self, arrays: dict[str, np.ndarray]) -> "BatchResult":
        """The future-returning :meth:`run_batch`: dispatch now, start
        the async D2H copy, and hand back a :class:`BatchResult` whose
        ``result()`` blocks only for whatever copy time is left. The
        micro-batcher pipelines on this — it assembles and dispatches
        the NEXT micro-batch while the previous one's readback lands.
        Dispatch/occupancy semantics are identical to :meth:`run_batch`
        (one request group = one dispatch, never chained)."""
        fault_point("dispatch")
        padded = pad_to_bucket(arrays, self._buckets)
        t0 = time.perf_counter()
        with span("serving.device_step", rows=padded.n_valid,
                  bucket=padded.bucket):
            # one request group = one dispatch, NEVER chained: chaining
            # would couple unrelated requests' failure domains, and the
            # micro-batcher already amortizes dispatch across riders
            out = self._jitted(self._transfer(padded.arrays))
            ticket = start_fetch(out, path="serving")
        return BatchResult(ticket, padded.n_valid, t0)

    def _transfer(self, arrays: dict[str, np.ndarray]):
        # the partitioner owns placement: dp meshes commit one shard per
        # local chip (jit compiles the apply SPMD from the sharding),
        # pinned replicas commit to their device, single-device stays
        # the plain uncommitted put. check=False: every batch through
        # here is already padded to a bucket rounded to the data axes
        return self._partitioner.shard_batch(arrays, check=False)


class BatchResult:
    """In-flight :meth:`BatchedRunner.run_batch_async` result.

    ``result()`` collects the host output (unpadded to the live rows),
    records the dispatch into the spine exactly once, and re-raises
    this batch's device error if its program failed. Thread-safe and
    idempotent, so the micro-batcher may resolve from any thread; a
    fallback-pool timeout is not terminal (the result stays
    collectable).

    Metric semantics: the recorded ``sparkdl_dispatch_seconds`` wall
    spans dispatch to COLLECTION — when resolution is pipelined (the
    micro-batcher keeps ``max_inflight`` batches open) it includes the
    bounded residency behind the predecessors, so the serving wall
    histogram reads as pipeline latency, not pure device time (the
    count stays exact; overhead_share only gets more conservative).
    The synchronous :meth:`BatchedRunner.run_batch` resolves
    immediately and keeps the old pure-dispatch wall."""

    __slots__ = ("_ticket", "_n_valid", "_t0", "_done", "_value", "_exc",
                 "_lock")

    def __init__(self, ticket: FetchTicket, n_valid: int, t0: float):
        self._ticket = ticket
        self._n_valid = n_valid
        self._t0 = t0
        self._done = False
        self._value: Any = None
        self._exc: "BaseException | None" = None
        self._lock = threading.Lock()

    def result(self, timeout: "float | None" = None):
        with self._lock:
            if not self._done:
                try:
                    out = self._ticket.result(timeout)
                except FuturesTimeoutError:
                    raise  # not terminal: collect again later
                except BaseException as e:
                    self._exc = e
                else:
                    if isinstance(out, (tuple, list)):
                        self._value = tuple(
                            np.asarray(o)[: self._n_valid] for o in out
                        )
                    else:
                        self._value = np.asarray(out)[: self._n_valid]
                self._done = True
                record_dispatch(
                    "serving", 1, time.perf_counter() - self._t0
                )
            if self._exc is not None:
                raise self._exc
            return self._value


#: graph object -> {cache key: BatchedRunner}; weak so graphs can be GC'd.
_GRAPH_RUNNERS: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def cached_graph_runner(graph, key, make_apply_fn: Callable[[], Callable],
                        batch_size: int,
                        ragged_rows: bool = False) -> BatchedRunner:
    """Process-wide BatchedRunner cache keyed by (graph identity, key).

    One jax.jit per (ingested graph, shape/batch config) no matter how many
    partitions, transformer copies, or transformer classes touch it.
    """
    per_graph = _GRAPH_RUNNERS.setdefault(graph, {})
    if key not in per_graph:
        per_graph[key] = BatchedRunner(
            make_apply_fn(), batch_size=batch_size, ragged_rows=ragged_rows
        )
    return per_graph[key]


def try_extract(extract: Callable[[Any], dict[str, np.ndarray]],
                row: Any) -> "tuple[dict[str, np.ndarray] | None, Exception | None]":
    """Run ``extract`` on one row, capturing the error instead of raising —
    the single bad-row convention shared by the batch partition path and
    the online micro-batcher: a row that cannot be featurized degrades to
    a per-row error and never poisons its batch."""
    try:
        return extract(row), None
    except Exception as e:
        return None, e


def run_partition_with_passthrough(
    rows: "list[dict]",
    extract: Callable[[dict], dict[str, np.ndarray]],
    runner: BatchedRunner,
    output_col: str,
    postprocess: Callable[[np.ndarray], Any] | None = None,
    input_cols: "tuple[str, ...] | None" = None,
) -> Iterator[dict]:
    """Run inference for a partition, appending ``output_col`` to each row.

    ``extract`` turns a row into the numeric feature dict the model eats;
    rows it raises on are yielded unchanged with output None (mirrors the
    reference's tolerance of undecodable rows). Misconfiguration stays loud
    rather than masked as bad data: missing ``input_cols`` raise
    immediately, and an all-rows-failed partition logs a warning with the
    first error.
    """
    if rows and input_cols:
        missing = [c for c in input_cols if c not in rows[0]]
        if missing:
            raise KeyError(
                f"input column(s) {missing} not in DataFrame columns "
                f"{sorted(rows[0].keys())}"
            )
    feeds: list[dict[str, np.ndarray] | None] = []
    first_error: Exception | None = None
    with span("surface.extract", rows=len(rows)):
        for r in rows:
            feed, err = try_extract(extract, r)
            first_error = first_error or err
            feeds.append(feed)
    valid = [f for f in feeds if f is not None]
    if rows and not valid and first_error is not None:
        logging.getLogger(__name__).warning(
            "all %d rows in partition failed extraction (output=None); "
            "first error: %r", len(rows), first_error,
        )
    # surface.run: from handing the runner its rows to the last output
    # taken from it. Recorded when that is over, and not held open as a
    # live span, because this generator yields to its consumer in between
    # and an ambient span must not leak into the consumer's code.
    parent, t_run = tracing.current_context(), time.monotonic()
    outputs = runner.run(iter(valid)) if valid else iter(())
    try:
        for r, f in zip(rows, feeds):
            out_row = dict(r)
            if f is None:
                out_row[output_col] = None
            else:
                o = next(outputs)
                out_row[output_col] = postprocess(o) if postprocess else o
            yield out_row
    finally:
        tracing.record_span("surface.run", t_run, time.monotonic(),
                            parent=parent, rows=len(valid))
