"""Step-time / throughput / MFU / infeed meters.

The headline numbers this framework is scored on are images/sec/chip and
MFU (BASELINE.md targets); this module is where they are measured, the same
way in tests, benches and production runs.

MFU definition used throughout: ``achieved FLOP/s / peak FLOP/s``, with
achieved = (model FLOPs per step, from XLA's compiled cost analysis or a
caller-supplied analytic count) / measured step wall time, and peak = the
per-chip matrix-unit peak for the platform x dtype, times chips. This is
*model* FLOPs utilization (the "How to Scale Your Model" convention), not
hardware-counter utilization — rematerialized FLOPs don't inflate it when
the caller supplies the analytic count.
"""

from __future__ import annotations

import collections
import dataclasses
import statistics
import time
from typing import Any, Callable

import jax


@dataclasses.dataclass(frozen=True)
class ChipPeak:
    """Published per-chip peaks: dense bf16 matmul FLOP/s, HBM bytes/s."""

    bf16_flops: float
    hbm_bytes_per_s: float


#: Peaks keyed by ``jax.Device.device_kind`` EXACTLY as the chip prints
#: it — one row per chip this repository has run on. A kind that is not
#: here is an error, never a default: a guessed peak makes every MFU and
#: roofline share derived from it wrong without saying so.
DEVICE_PEAKS: dict[str, ChipPeak] = {
    # TPU v5e (Google Cloud documentation, "TPU v5e"): 197 TFLOP/s bf16,
    # 819 GB/s HBM. device_kind as printed by chip_smoke.py on the v5e.
    "TPU v5 lite": ChipPeak(bf16_flops=197e12, hbm_bytes_per_s=819e9),
}


class UnknownDeviceError(LookupError):
    """A TPU whose ``device_kind`` has no row in :data:`DEVICE_PEAKS`."""


def percentile(values: "list[float] | tuple[float, ...]",
               p: float) -> float | None:
    """Linear-interpolated percentile (numpy's default method), stdlib-only
    so meters never pay an array round-trip for a scalar.

    ``p`` in [0, 100]; returns None on an empty sample.
    """
    return _percentile_sorted(sorted(values), p)


def _percentile_sorted(s: "list[float]", p: float) -> float | None:
    """percentile() on an already-sorted sample (one sort, many ps)."""
    if not 0 <= p <= 100:
        raise ValueError(f"percentile must be in [0, 100], got {p}")
    if not s:
        return None
    if len(s) == 1:
        return float(s[0])
    rank = (p / 100.0) * (len(s) - 1)
    lo = int(rank)
    hi = min(lo + 1, len(s) - 1)
    frac = rank - lo
    return float(s[lo] * (1.0 - frac) + s[hi] * frac)


def device_peak(device: "jax.Device | None" = None) -> "ChipPeak | None":
    """The :data:`DEVICE_PEAKS` row of ``device`` (default: the first
    device). None off-TPU — a CPU run has no device peak and its MFU is
    "not measured"; a TPU kind without a row raises
    :class:`UnknownDeviceError`."""
    if device is None:
        device = jax.devices()[0]
    if device.platform != "tpu":
        return None
    try:
        return DEVICE_PEAKS[device.device_kind]
    except KeyError:
        raise UnknownDeviceError(
            f"no peak FLOP/s / HBM bandwidth on record for device_kind "
            f"{device.device_kind!r} (known: {sorted(DEVICE_PEAKS)}); add "
            "its published peaks to observability.metrics.DEVICE_PEAKS "
            "with their source"
        ) from None


def device_peak_flops(device: "jax.Device | None" = None,
                      dtype: str = "bf16") -> float | None:
    """Peak FLOP/s of one chip from :func:`device_peak` (None off-TPU).
    fp32 is the bf16 number /2: the MXU computes in bf16 with fp32
    accumulate, and pure-fp32 runs at half rate."""
    peak = device_peak(device)
    if peak is None:
        return None
    if dtype in ("f32", "fp32", "float32"):
        return peak.bf16_flops / 2
    return peak.bf16_flops


def compiled_flops(fn: Callable, *args: Any, **kwargs: Any) -> float | None:
    """FLOPs of one call of jitted ``fn`` per XLA's cost analysis.

    Returns None when the backend doesn't report cost analysis. ``fn`` may
    already be jitted or plain; args may be concrete arrays or
    ShapeDtypeStructs (lowering is abstract either way).
    """
    try:
        jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
        compiled = jitted.lower(*args, **kwargs).compile()
        flops = compiled.cost_analysis().get("flops")
        return float(flops) if flops and flops > 0 else None
    except Exception:
        return None


class StepMeter:
    """Accumulates per-step timings into throughput / MFU / infeed metrics.

    Usage inside a training or inference loop::

        meter = StepMeter(flops_per_example=..., n_chips=jax.device_count())
        for batch in data:
            with meter.step(examples=len(batch)):
                out = step_fn(state, batch)
                jax.block_until_ready(out)
            # optionally: meter.note_infeed_wait(seconds)

    ``summary()`` returns the structured per-host metrics dict SURVEY.md §5
    calls for (step time, examples/sec/chip, infeed-starvation %, MFU).
    """

    def __init__(self, *, flops_per_example: float | None = None,
                 flops_per_step: float | None = None,
                 n_chips: int | None = None,
                 peak_flops_per_chip: float | None = None,
                 window: int = 50, warmup_steps: int = 1):
        self.flops_per_example = flops_per_example
        self.flops_per_step = flops_per_step
        self.n_chips = n_chips if n_chips is not None else jax.device_count()
        self.peak_flops_per_chip = (
            peak_flops_per_chip
            if peak_flops_per_chip is not None
            else device_peak_flops()
        )
        self.warmup_steps = warmup_steps
        self._times = collections.deque(maxlen=window)
        self._examples = collections.deque(maxlen=window)
        self._infeed = collections.deque(maxlen=window)
        self._seen = 0
        self._total_examples = 0

    # -- recording -----------------------------------------------------------
    class _StepCtx:
        def __init__(self, meter: "StepMeter", examples: int):
            self._m, self._ex = meter, examples

        def __enter__(self):
            self._t0 = time.perf_counter()
            return self

        def __exit__(self, exc_type, *exc):
            if exc_type is None:
                self._m.record(time.perf_counter() - self._t0, self._ex)

    def step(self, examples: int = 0) -> "_StepCtx":
        return StepMeter._StepCtx(self, examples)

    def record(self, step_time_s: float, examples: int = 0,
               infeed_wait_s: float = 0.0) -> None:
        self._seen += 1
        if self._seen <= self.warmup_steps:  # compile step poisons the mean
            return
        self._times.append(step_time_s)
        self._examples.append(examples)
        self._infeed.append(infeed_wait_s)
        self._total_examples += examples

    def note_infeed_wait(self, seconds: float) -> None:
        """Attribute host-input stall time to the most recent step."""
        if self._infeed:
            self._infeed[-1] += seconds

    # -- derived metrics -----------------------------------------------------
    @property
    def steps_recorded(self) -> int:
        return len(self._times)

    def mean_step_time(self) -> float | None:
        return statistics.fmean(self._times) if self._times else None

    def step_time_percentile(self, p: float) -> float | None:
        """Percentile of recorded step times over the window (p in
        [0, 100]); None until a step is recorded."""
        return percentile(list(self._times), p)

    def step_time_percentiles(
        self, ps: "tuple[float, ...]" = (50, 95, 99)
    ) -> dict[str, float | None]:
        """The serving-latency trio (p50/p95/p99 by default) off a single
        sort of the window — what ``serving.metrics`` reports per
        request."""
        s = sorted(self._times)
        return {f"p{p:g}": _percentile_sorted(s, p) for p in ps}

    def examples_per_sec(self) -> float | None:
        t = sum(self._times)
        return sum(self._examples) / t if t > 0 else None

    def examples_per_sec_per_chip(self) -> float | None:
        eps = self.examples_per_sec()
        return eps / self.n_chips if eps is not None else None

    def infeed_starvation_pct(self) -> float | None:
        t = sum(self._times)
        return 100.0 * sum(self._infeed) / t if t > 0 else None

    def achieved_flops_per_sec(self) -> float | None:
        t = sum(self._times)
        if t <= 0:
            return None
        if self.flops_per_step is not None:
            return self.flops_per_step * len(self._times) / t
        if self.flops_per_example is not None:
            return self.flops_per_example * sum(self._examples) / t
        return None

    def mfu(self) -> float | None:
        achieved = self.achieved_flops_per_sec()
        peak = self.peak_flops_per_chip
        if achieved is None or not peak:
            return None
        return achieved / (peak * self.n_chips)

    def summary(self) -> dict[str, float | int | None]:
        return {
            "steps": self.steps_recorded,
            "total_examples": self._total_examples,
            "step_time_mean_s": self.mean_step_time(),
            "examples_per_sec": self.examples_per_sec(),
            "examples_per_sec_per_chip": self.examples_per_sec_per_chip(),
            "infeed_starvation_pct": self.infeed_starvation_pct(),
            "mfu": self.mfu(),
            "n_chips": self.n_chips,
        }


def aggregate_across_hosts(metrics: dict[str, float | None]) -> dict:
    """All-hosts mean/min/max of each numeric metric, identical on every
    host (SURVEY.md §5: per-host metrics aggregated to the driver).

    Single-process (the common test path) returns mean=min=max=value.
    """
    import numpy as np

    # Key set must be identical on every host or the allgather misaligns
    # (a straggler host with None metrics would otherwise ship fewer
    # columns) — so keep ALL keys and encode missing values as NaN, then
    # reduce with the nan-aware ops.
    keys = sorted(metrics.keys())
    local = np.asarray(
        [
            float(metrics[k])
            if isinstance(metrics[k], (int, float)) else np.nan
            for k in keys
        ],
        np.float64,
    )
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        stacked = multihost_utils.process_allgather(local)
    else:
        stacked = local[None]
    out: dict[str, dict[str, float]] = {}
    for i, k in enumerate(keys):
        col = stacked[:, i]
        col = col[~np.isnan(col)]
        if col.size == 0:
            continue
        out[k] = {
            "mean": float(col.mean()),
            "min": float(col.min()),
            "max": float(col.max()),
        }
    return out
