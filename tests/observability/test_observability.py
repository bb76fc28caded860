"""Observability tests: meters, cost analysis, profiler, health probe."""

import glob
import math

import jax
import jax.numpy as jnp
import numpy as np

import pytest

from sparkdl_tpu.observability import (
    StepMeter,
    aggregate_across_hosts,
    check_health,
    compiled_flops,
    device_peak_flops,
    percentile,
    trace,
)


class TestStepMeter:
    def test_throughput_and_step_time(self):
        meter = StepMeter(n_chips=4, warmup_steps=1, peak_flops_per_chip=1e12)
        meter.record(10.0, examples=100)   # warmup, dropped
        for _ in range(5):
            meter.record(0.5, examples=100)
        assert meter.steps_recorded == 5
        assert math.isclose(meter.mean_step_time(), 0.5)
        assert math.isclose(meter.examples_per_sec(), 200.0)
        assert math.isclose(meter.examples_per_sec_per_chip(), 50.0)

    def test_mfu_from_flops_per_example(self):
        meter = StepMeter(
            flops_per_example=1e9, n_chips=2,
            peak_flops_per_chip=1e12, warmup_steps=0,
        )
        # 100 examples in 0.1 s -> 1e12 FLOP/s achieved; peak 2e12 -> 0.5
        meter.record(0.1, examples=100)
        assert math.isclose(meter.mfu(), 0.5, rel_tol=1e-9)

    def test_mfu_from_flops_per_step(self):
        meter = StepMeter(
            flops_per_step=5e11, n_chips=1,
            peak_flops_per_chip=1e12, warmup_steps=0,
        )
        meter.record(1.0, examples=1)
        assert math.isclose(meter.mfu(), 0.5, rel_tol=1e-9)

    def test_infeed_starvation(self):
        meter = StepMeter(warmup_steps=0, n_chips=1)
        meter.record(1.0, examples=1, infeed_wait_s=0.25)
        meter.record(1.0, examples=1)
        meter.note_infeed_wait(0.25)
        assert math.isclose(meter.infeed_starvation_pct(), 25.0)

    def test_step_context_manager(self):
        meter = StepMeter(warmup_steps=0, n_chips=1)
        with meter.step(examples=8):
            pass
        assert meter.steps_recorded == 1
        assert meter.summary()["total_examples"] == 8

    def test_summary_handles_empty(self):
        s = StepMeter(n_chips=1).summary()
        assert s["steps"] == 0 and s["mfu"] is None

    def test_step_time_percentiles(self):
        meter = StepMeter(n_chips=1, warmup_steps=0, window=200)
        for t in range(1, 101):  # 0.01 .. 1.00 s
            meter.record(t / 100.0, examples=1)
        pcts = meter.step_time_percentiles()
        assert set(pcts) == {"p50", "p95", "p99"}
        assert math.isclose(pcts["p50"], 0.505)  # interpolated median
        assert math.isclose(pcts["p95"], 0.9505)
        assert math.isclose(pcts["p99"], 0.9901)
        assert math.isclose(meter.step_time_percentile(0), 0.01)
        assert math.isclose(meter.step_time_percentile(100), 1.0)

    def test_percentiles_empty_and_single(self):
        assert StepMeter(n_chips=1).step_time_percentile(95) is None
        meter = StepMeter(n_chips=1, warmup_steps=0)
        meter.record(0.25, examples=1)
        assert meter.step_time_percentiles() == {
            "p50": 0.25, "p95": 0.25, "p99": 0.25,
        }


class TestPercentile:
    def test_matches_numpy_linear_interpolation(self):
        rng = np.random.default_rng(7)
        vals = rng.standard_normal(37).tolist()
        for p in (0, 10, 50, 90, 95, 99, 100):
            assert math.isclose(
                percentile(vals, p), float(np.percentile(vals, p)),
                rel_tol=1e-12, abs_tol=1e-12,
            )

    def test_empty_returns_none(self):
        assert percentile([], 95) is None

    def test_out_of_range_rejected(self):
        with pytest.raises(ValueError, match="percentile"):
            percentile([1.0], 101)


class TestCompiledFlops:
    def test_matmul_flops_close_to_analytic(self):
        m = n = k = 64

        def f(a, b):
            return a @ b

        flops = compiled_flops(
            f,
            jax.ShapeDtypeStruct((m, k), jnp.float32),
            jax.ShapeDtypeStruct((k, n), jnp.float32),
        )
        if flops is None:  # backend without cost analysis: tolerated
            return
        assert flops >= 2 * m * n * k * 0.5  # within 2x of 2mnk
        assert flops <= 2 * m * n * k * 2

    def test_peak_flops_unknown_on_cpu(self):
        assert device_peak_flops() is None  # tests run on fake CPU devices

    def test_peak_table_is_keyed_by_the_printed_device_kind(self):
        import types

        from sparkdl_tpu.observability.metrics import (
            UnknownDeviceError,
            device_peak,
        )

        v5e = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
        assert device_peak(v5e).bf16_flops == 197e12
        assert device_peak(v5e).hbm_bytes_per_s == 819e9
        assert device_peak_flops(v5e, "float32") == 197e12 / 2
        # an unknown TPU is an error, never a default (and never the
        # substring accident that read "TPU v5 lite" as the "v5" row)
        for kind in ("TPU v5", "TPU v9", "tpu v5 lite"):
            with pytest.raises(UnknownDeviceError, match="DEVICE_PEAKS"):
                device_peak(types.SimpleNamespace(platform="tpu",
                                                  device_kind=kind))


class TestAggregation:
    def test_single_process_identity(self):
        agg = aggregate_across_hosts({"a": 2.0, "b": 4, "skip": None})
        assert agg["a"] == {"mean": 2.0, "min": 2.0, "max": 2.0}
        assert agg["b"]["mean"] == 4.0
        assert "skip" not in agg


class TestProfiling:
    def test_trace_writes_xplane(self, tmp_path):
        with trace(tmp_path):
            x = jnp.ones((32, 32)) @ jnp.ones((32, 32))
            jax.block_until_ready(x)
        files = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
        assert files, "profiler produced no xplane trace"


class TestHealth:
    def test_healthy_on_fake_mesh(self):
        report = check_health()
        assert report.ok, report.error
        assert report.collective_ok
        assert report.n_local_devices == 8
        assert "OK" in report.summary()

    def test_device_count_mismatch_flagged(self):
        report = check_health(expect_local_devices=5)
        assert not report.ok
        assert "expected 5" in (report.error or "")
        assert "UNHEALTHY" in report.summary()
