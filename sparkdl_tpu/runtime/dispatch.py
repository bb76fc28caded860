"""Fused multi-step dispatch: the bench.py scan-K win as a runtime layer.

Every device dispatch pays a fixed launch overhead before its program
runs; chaining K device-resident steps inside one jit (``lax.scan``)
pays it once per K steps. How much that is worth depends on the
measured gap of the backend in use (:func:`calibrate_dispatch_gap`;
PERF.md's bring-up section has the v5e's) against the program time.
This module makes that amortization generic so every production hot path
— :class:`~sparkdl_tpu.transformers._inference.BatchedRunner` batches,
``train/finetune`` optimizer steps, ``serving/continuous`` decode tokens
— pays one dispatch per K steps instead of one per step. Same
pipeline-overhead argument tf.data makes for input pipelines (Murray et
al., arXiv:2101.12127) and deferred graphs make for TensorFlow (Abadi et
al., arXiv:1605.08695), applied at the dispatch boundary.

Three pieces:

* :func:`calibrate_dispatch_gap` — measured per-dispatch overhead of
  THIS process's backend (a trivial jitted program timed wall-to-wall:
  anything it "takes" is launch cost, not compute);
* :class:`ChainPolicy` — picks K from the measured program time vs the
  calibrated gap so the overhead share stays under ``target_overhead``,
  degrading to K=1 for long programs (>~50 ms, where chaining buys
  nothing and only delays host visibility);
* :class:`ScanChainer` — stacks K same-shape device-resident inputs,
  runs one jit-compiled ``lax.scan`` over them, and unstacks the
  results. An iteration counter is threaded through the carry so the
  loop body stays iteration-dependent and CSE/loop-invariant motion can
  never collapse the K steps into one. :func:`chain_carry` is the
  carried-state (training) variant with buffer donation.

Everything dispatched through here lands in the observability spine:
``sparkdl_dispatches_total{path=...}``, the
``sparkdl_dispatch_chain_len`` histogram, the per-dispatch wall
histogram ``sparkdl_dispatch_seconds``, and a ``dispatch.chain`` span —
so the dispatch-gap share is a first-class metric in every bench JSON
artifact (:func:`overhead_share`).
"""

from __future__ import annotations

import dataclasses
import math
import os
import time
from typing import Any, Callable, Iterable, Iterator

from sparkdl_tpu.observability.registry import registry
from sparkdl_tpu.observability.tracing import span
from sparkdl_tpu.reliability.faults import fault_point

__all__ = [
    "ChainPolicy",
    "ScanChainer",
    "SpecPolicy",
    "calibrate_dispatch_gap",
    "chain_carry",
    "default_chain_k",
    "dispatch_metrics",
    "overhead_share",
    "record_dispatch",
    "shape_key",
]

#: Chain-length histogram bounds: powers of two up to the largest K the
#: bench ever measured a win at (PERF.md: saturation by K=32..64).
CHAIN_LEN_BUCKETS: tuple[float, ...] = (1, 2, 4, 8, 16, 32, 64, 128)

_METRICS = None


def dispatch_metrics():
    """Lazy handles for the dispatch spine (one tuple per process):
    (dispatches counter by path, chain-length histogram, wall histogram).
    """
    global _METRICS
    if _METRICS is None:
        _METRICS = (
            registry().counter(
                "sparkdl_dispatches_total",
                "device dispatches issued (one jitted call = one)",
                labels=("path",)),
            registry().histogram(
                "sparkdl_dispatch_chain_len",
                "steps fused into each device dispatch",
                labels=("path",), buckets=CHAIN_LEN_BUCKETS),
            registry().histogram(
                "sparkdl_dispatch_seconds",
                "wall time of each device dispatch (all chained steps)",
                labels=("path",)),
        )
    return _METRICS


def record_dispatch(path: str, k: int, wall_s: "float | None" = None) -> None:
    """Record one device dispatch that fused ``k`` steps on ``path``."""
    dispatches, chain_len, wall = dispatch_metrics()
    dispatches.inc(path=path)
    chain_len.observe(k, path=path)
    if wall_s is not None:
        wall.observe(wall_s, path=path)


def dispatch_count(path: "str | None" = None) -> float:
    """Current value of the dispatch counter (summed over paths when
    ``path`` is None) — the benches' ``dispatch_count`` source."""
    fam = registry().get("sparkdl_dispatches_total")
    if fam is None:
        return 0.0
    values = fam.snapshot_values()
    if path is not None:
        return float(values.get(f'path="{path}"', 0.0))
    return float(sum(values.values()))


# -- dispatch-gap calibration -------------------------------------------------

_GAP_CACHE: "dict[str, float]" = {}


def calibrate_dispatch_gap(samples: int = 30, *,
                           refresh: bool = False) -> float:
    """Median wall seconds of a trivial jitted dispatch on the current
    backend.

    A one-element elementwise program has effectively zero compute, so
    its wall time IS the per-dispatch overhead. Cached per backend;
    ``SPARKDL_TPU_DISPATCH_GAP_MS`` overrides (no measurement run), for
    environments where a calibration burst is unwelcome.
    """
    env = os.environ.get("SPARKDL_TPU_DISPATCH_GAP_MS")
    if env:
        return float(env) / 1e3
    import jax

    backend = jax.default_backend()
    if not refresh and backend in _GAP_CACHE:
        return _GAP_CACHE[backend]
    import jax.numpy as jnp

    probe = jax.jit(lambda x: x + 1.0)
    x = jax.device_put(jnp.zeros((), jnp.float32))
    probe(x).block_until_ready()  # compile outside the timed region
    times = []
    for _ in range(max(3, samples)):
        t0 = time.perf_counter()
        probe(x).block_until_ready()
        times.append(time.perf_counter() - t0)
    times.sort()
    gap = times[len(times) // 2]
    _GAP_CACHE[backend] = gap
    registry().gauge(
        "sparkdl_dispatch_gap_seconds",
        "calibrated per-dispatch overhead of this backend",
    ).set(gap)
    return gap


def overhead_share(n_dispatches: float, wall_s: float,
                   gap_s: "float | None" = None) -> "float | None":
    """Dispatch-overhead share of a measured wall interval:
    ``n * gap / wall`` — what fraction of the wall clock was launch
    cost rather than device program. The number the benches emit so the
    trajectory captures amortization, not just img/s."""
    if wall_s <= 0 or n_dispatches <= 0:
        return None
    if gap_s is None:
        gap_s = calibrate_dispatch_gap()
    return min(1.0, n_dispatches * gap_s / wall_s)


# -- chain-length policy ------------------------------------------------------


@dataclasses.dataclass
class ChainPolicy:
    """Pick K so the dispatch-gap share of wall time stays under target.

    Overhead share of a K-chain is ``gap / (gap + K * program)``; solving
    for share <= ``target_overhead`` gives
    ``K >= gap * (1 - t) / (t * program)``. K is rounded UP to a power of
    two (bounded jit-cache churn: at most log2(max_chain) compiles per
    program) and clamped to ``[1, max_chain]``. Programs longer than
    ``max_program_s`` (~50 ms) get K=1 — the gap is already <5% there,
    and chaining only delays host visibility (metrics, checkpoints,
    retirements).

    ``record(wall_s, k)`` feeds the measured per-step program time back
    (EMA); until the first record, :meth:`chain_len` returns 1 so the
    first dispatch doubles as the measurement probe.
    """

    target_overhead: float = 0.02
    max_chain: int = 32
    max_program_s: float = 0.050
    gap_s: "float | None" = None  # None: calibrate lazily on first use
    ema: float = 0.5
    program_s: "float | None" = dataclasses.field(default=None)

    def gap(self) -> float:
        if self.gap_s is None:
            self.gap_s = calibrate_dispatch_gap()
        return self.gap_s

    def record(self, wall_s: float, k: int) -> None:
        """Fold one measured dispatch (k fused steps, wall seconds).

        Deliberately does NOT trigger gap calibration: record() sits on
        every hot path even when the chain length is pinned (where the
        policy is only a program-time estimator, e.g. the decode
        deadline bound), and the 30-probe calibration burst must never
        ride a production dispatch. Until the gap is known the estimate
        includes it — a slight overestimate, which only makes
        chain_len()/deadline bounds more conservative.
        """
        gap = self.gap_s if self.gap_s is not None else 0.0
        prog = max((wall_s - gap) / max(k, 1), 1e-9)
        if self.program_s is None:
            self.program_s = prog
        else:
            self.program_s += self.ema * (prog - self.program_s)

    def chain_len(self) -> int:
        if self.program_s is None:
            return 1  # first dispatch measures
        if self.program_s >= self.max_program_s:
            return 1  # long program: overhead share already < target-ish
        t = self.target_overhead
        k = self.gap() * (1.0 - t) / (t * self.program_s)
        if k <= 1.0:
            return 1
        # the 1e-9 guard keeps float fuzz from bumping an exact power of
        # two (ideal K = 4.0000000001) to the next one
        return min(self.max_chain, 1 << math.ceil(math.log2(k) - 1e-9))


@dataclasses.dataclass
class SpecPolicy:
    """Pick the speculative verify width from measured acceptance.

    :class:`ChainPolicy` chains k IDENTICAL steps, so its only question
    is dispatch-gap amortization. A speculative verify chains k
    *conditional* steps: position j only produces a real token if every
    draft before it was accepted, so the useful width depends on the
    measured per-position acceptance rate ``p``. Expected real tokens
    from a width-k verify are ``E(k) = (1-p^k)/(1-p)`` (a geometric
    series — each extra position converts with one more factor of p).

    ``spec_len`` returns the largest power-of-two ``k <= max_k`` whose
    expected utilization stays above ``util`` (``E(k) >= util * k``):
    below that, the marginal verify positions are mostly wasted FLOPs.
    Acceptance below ``min_rate`` returns 1 — drafting is not paying
    for itself and the engine serves plain (chained) decode instead.

    The estimator is a pair of geometrically-decayed counts
    (proposed/accepted per dispatch), seeded with an OPTIMISTIC prior:
    cold engines open at full width (the first verifies double as
    measurement probes — repetitive/shared-prefix workloads, the ones
    speculation exists for, get their speedup immediately), and one
    unlucky one-draft dispatch cannot poison the estimate the way a
    plain EMA of per-dispatch ratios would. Stood-down is NOT
    terminal: every ``probe_every``-th consultation while below
    ``min_rate`` returns a width-2 probation probe — the same
    reintegration discipline as quarantined replicas — so a workload
    that turns acceptance-friendly again is re-detected without any
    operator action.
    """

    max_k: int = 8
    util: float = 0.5
    min_rate: float = 0.2
    decay: float = 0.2
    prior: float = 8.0
    probe_every: int = 16

    def __post_init__(self) -> None:
        self._proposed = self.prior
        self._accepted = self.prior
        self._stood_down = 0

    @property
    def rate(self) -> float:
        """Decayed-count acceptance estimate (optimistic at cold)."""
        return self._accepted / self._proposed

    def record(self, proposed: int, accepted: int) -> None:
        if proposed < 1:
            return
        self._proposed = (1 - self.decay) * self._proposed + proposed
        self._accepted = (1 - self.decay) * self._accepted + accepted

    def expected_tokens(self, k: int) -> float:
        """E(k) under the current acceptance estimate."""
        p = min(max(self.rate, 0.0), 0.999999)
        return (1.0 - p ** k) / (1.0 - p)

    def spec_len(self) -> int:
        if self.max_k < 2:
            return 1
        if self.rate < self.min_rate:
            self._stood_down += 1
            if self._stood_down % self.probe_every == 0:
                return 2  # probation probe: re-measure acceptance
            return 1
        self._stood_down = 0
        k = 2
        while (2 * k <= self.max_k
               and self.expected_tokens(2 * k) >= self.util * 2 * k):
            k *= 2
        return k


def default_chain_k() -> "int | None":
    """Process-wide chain_k override: ``SPARKDL_TPU_CHAIN_K`` (int), or
    None meaning auto (ChainPolicy decides from measurements). A value
    below 1 is a misconfiguration and raises — same contract as the
    constructor argument (``1`` is how chaining is disabled)."""
    env = os.environ.get("SPARKDL_TPU_CHAIN_K")
    if not env:
        return None
    k = int(env)
    if k < 1:
        raise ValueError(
            f"SPARKDL_TPU_CHAIN_K must be >= 1, got {env!r} "
            "(set 1 to disable chaining)"
        )
    return k


# -- the chainer --------------------------------------------------------------


def shape_key(tree: Any) -> Any:
    """Hashable (structure, shapes, dtypes) key for a batch pytree: only
    inputs with equal keys may join one chain (the scan stacks them).
    The single grouping predicate — ``ScanChainer.map_stream`` and the
    finetune chain loop both use it, so the semantics cannot drift."""
    import jax

    leaves, treedef = jax.tree.flatten(tree)
    return treedef, tuple(
        (tuple(getattr(l, "shape", ())), str(getattr(l, "dtype", type(l))))
        for l in leaves
    )


class ScanChainer:
    """Fuse K same-shape ``step_fn`` applications into one device dispatch.

    ``step_fn(x) -> y`` is any jittable map (no carried state; use
    :func:`chain_carry` for optimizer-style carries). A chained dispatch
    jit-compiles::

        def chained(*xs):
            stacked = tree.map(stack, *xs)      # free inside jit: fused
            def body(i, x):                     # i threads iteration
                return i + 1, step_fn(x)        # dependence (anti-CSE)
            _, ys = lax.scan(body, 0, stacked)
            return ys

    and unstacks ``ys`` back into per-step outputs — bitwise identical to
    K separate ``jit(step_fn)`` calls (the scan body is the same HLO;
    parity is pinned by tests/runtime/test_dispatch.py). jit's shape
    cache keys on (K, input shapes): one compile per (chain length,
    bucket).

    ``chain_k``: None = auto (``SPARKDL_TPU_CHAIN_K`` env if set, else
    the :class:`ChainPolicy` picks from measured program time vs the
    calibrated dispatch gap); 1 disables chaining; N pins the chain
    length. Ragged tails (fewer than K same-shape items buffered when
    the stream ends or the shape changes) run unchained — K=1 reuses the
    single-step executable instead of compiling a one-off tail length.
    """

    def __init__(self, step_fn: Callable[[Any], Any], *, path: str,
                 chain_k: "int | None" = None,
                 policy: "ChainPolicy | None" = None):
        import jax

        if chain_k is not None and chain_k < 1:
            raise ValueError(f"chain_k must be >= 1, got {chain_k}")
        self.step_fn = step_fn
        self.path = path
        env_k = default_chain_k()
        if chain_k is not None and env_k is not None and env_k != chain_k:
            # two explicit pins that disagree is a misconfiguration the
            # autotuner must never paper over (ISSUE 8): fail loud
            raise ValueError(
                f"conflicting chain-K pins: explicit chain_k={chain_k} "
                f"vs SPARKDL_TPU_CHAIN_K={env_k} — pin it one way, not "
                "both"
            )
        self.chain_k = chain_k if chain_k is not None else env_k
        #: True when the chain length was explicitly configured (arg or
        #: env): the autotuner registers a pinned knob and never moves it
        self.pinned = self.chain_k is not None
        self.pin_source = (
            "chain_k" if chain_k is not None
            else "SPARKDL_TPU_CHAIN_K" if env_k is not None else None
        )
        self.policy = policy if policy is not None else ChainPolicy()
        if self.chain_k is None:
            # auto mode consults policy.chain_len() per dispatch: pay the
            # 30-probe gap calibration ONCE here at construction, never
            # mid-stream on a production dispatch (or inside an engine
            # lock)
            self.policy.gap()
        self.jit_single = jax.jit(step_fn)
        self._jit_chained = jax.jit(self._chained)

    def _chained(self, *xs):
        import jax
        import jax.numpy as jnp
        from jax import lax

        stacked = jax.tree.map(lambda *leaves: jnp.stack(leaves), *xs)

        def body(i, x):
            # the carried counter keeps the body iteration-dependent so
            # XLA can never hoist/collapse identical steps (PERF.md
            # measurement discipline) — it costs one scalar add
            return i + 1, self.step_fn(x)

        _, ys = lax.scan(body, jnp.zeros((), jnp.int32), stacked)
        return ys

    # -- dispatching ---------------------------------------------------------
    def target_chain_len(self) -> int:
        """The chain length the next group aims for."""
        if self.chain_k is not None:
            return self.chain_k
        return self.policy.chain_len()

    def dispatch_single(self, x: Any) -> Any:
        """One unchained dispatch (counts toward the spine like any
        other): the probe/tail/K=1 path of :meth:`map_stream`. (The
        serving ``run_batch`` path shares :attr:`jit_single` but keeps
        its own timing/span — it must wrap the transfer inside the
        ``serving.device_step`` span and record path="serving".)"""
        import jax

        fault_point("dispatch")
        t0 = time.perf_counter()
        with span("dispatch.chain", path=self.path, k=1):
            y = self.jit_single(x)
            jax.block_until_ready(y)
        wall = time.perf_counter() - t0
        record_dispatch(self.path, 1, wall)
        self.policy.record(wall, 1)
        return y

    def dispatch_chain(self, xs: "list[Any]") -> "list[Any]":
        """Fuse ``len(xs)`` same-shape steps into one dispatch; returns
        per-step outputs in order."""
        import jax

        k = len(xs)
        if k == 1:
            return [self.dispatch_single(xs[0])]
        fault_point("dispatch")
        t0 = time.perf_counter()
        with span("dispatch.chain", path=self.path, k=k):
            ys = self._jit_chained(*xs)
            jax.block_until_ready(ys)
        wall = time.perf_counter() - t0
        record_dispatch(self.path, k, wall)
        self.policy.record(wall, k)
        return [jax.tree.map(lambda a: a[i], ys) for i in range(k)]

    def map_stream(self, it: Iterable[Any]) -> Iterator[Any]:
        """Map ``step_fn`` over a stream of device-resident inputs,
        fusing runs of same-shape items into chained dispatches; yields
        one output per input, in order.

        Buffering never reorders: a shape change (ragged tail bucket)
        flushes the pending group first. Pending items held for a chain
        are bounded by the target K, so host memory stays O(K batches).
        """
        pending: "list[Any]" = []
        pending_key = None
        for x in it:
            key = shape_key(x)
            if pending and key != pending_key:
                yield from self._flush(pending)
                pending = []
            pending.append(x)
            pending_key = key
            k = self.target_chain_len()
            if len(pending) >= k:
                if k > 1:
                    yield from self.dispatch_chain(pending)
                else:
                    yield from self._flush(pending)
                pending = []
        if pending:
            yield from self._flush(pending)

    def _flush(self, pending: "list[Any]") -> Iterator[Any]:
        """Tail/ragged flush: run unchained (no one-off-K compile)."""
        for x in pending:
            yield self.dispatch_single(x)


def chain_carry(step_fn: Callable[[Any, Any], "tuple[Any, Any]"], *,
                donate: bool = True) -> Callable:
    """Jit a carried-state K-chain: ``chained(state, stacked_batches) ->
    (state, stacked_outs)`` running ``step_fn(state, batch)`` K times in
    one dispatch (K = the stacked leading dim; jit recompiles per K).

    The carry IS the iteration dependence — steps cannot collapse — and
    ``donate=True`` donates the incoming state buffers so K optimizer
    steps update in place instead of holding two copies of the params
    (the bench_train.py discipline, productionized for
    ``train/finetune``)."""
    import jax
    from jax import lax

    def chained(state, xs):
        return lax.scan(step_fn, state, xs)

    return jax.jit(chained, donate_argnums=(0,) if donate else ())
