"""TPURunner local-mode tests (SURVEY.md §4: HorovodRunner's np<0 local mode
is the multi-node-without-a-cluster story; here it really launches processes
and initializes the global JAX runtime across them)."""

import numpy as np
import pytest

from sparkdl_tpu import HorovodRunner, TPURunner


def _train_fn(scale=1.0):
    import jax
    import jax.numpy as jnp
    from jax.experimental import multihost_utils

    assert jax.process_count() == 2
    x = jnp.ones(3) * (jax.process_index() + 1) * scale
    gathered = multihost_utils.process_allgather(x)
    return {
        "rank": jax.process_index(),
        "nprocs": jax.process_count(),
        "global_devices": jax.device_count(),
        "sum": float(gathered.sum()),
    }


@pytest.mark.slow
def test_local_mode_two_processes():
    hr = TPURunner(np=-2, devices_per_process=2)
    out = hr.run(_train_fn, scale=2.0)
    assert out["rank"] == 0  # rank 0's result comes back
    assert out["nprocs"] == 2
    assert out["global_devices"] == 4  # 2 procs x 2 fake devices
    # allgather saw both ranks: (1+2) * 3 elements * scale 2
    assert out["sum"] == pytest.approx(18.0)


@pytest.mark.slow
def test_failure_aborts_job():
    def boom():
        import jax  # noqa: F401  (join the job before dying)

        raise RuntimeError("worker exploded")

    with pytest.raises(RuntimeError, match="rank"):
        TPURunner(np=-2, timeout_s=120).run(boom)


def test_horovod_runner_alias():
    assert HorovodRunner is TPURunner


def test_np_zero_rejected():
    with pytest.raises(ValueError):
        TPURunner(np=0)


def test_positive_np_without_cluster():
    with pytest.raises(RuntimeError, match="cluster"):
        TPURunner(np=4).run(lambda: None)


def test_bad_verbosity_rejected():
    with pytest.raises(ValueError):
        TPURunner(np=-1, driver_log_verbosity="loud")


class _InlineBackend:
    """Runs the (possibly wrapped) fn in-process — isolates the
    metrics_summary wrapper from real process launching."""

    def run(self, nprocs, fn, kwargs, verbosity="all"):
        return fn(**kwargs)


def test_metrics_summary_logs_cross_host_rollup(caplog):
    """metrics_summary=True: after main returns, every rank joins the
    aggregate_across_hosts rollup of the metrics registry and rank 0
    logs it (single-process here: mean == min == max == local value)."""
    import json
    import logging

    from sparkdl_tpu.observability.registry import registry

    registry().reset()

    def main(n):
        registry().counter("sparkdl_rollup_probe_total").inc(n)
        return n * 2

    runner = TPURunner(np=-1, backend=_InlineBackend(),
                       metrics_summary=True)
    with caplog.at_level(logging.INFO, logger="sparkdl_tpu.metrics"):
        assert runner.run(main, n=3) == 6
    recs = [r for r in caplog.records if "all-host metrics" in r.message]
    assert recs, caplog.records
    agg = json.loads(recs[0].message.split("all-host metrics ", 1)[1])
    assert agg["sparkdl_rollup_probe_total"] == {
        "mean": 3.0, "min": 3.0, "max": 3.0,
    }
    # default stays off: no wrapper, no rollup logline
    caplog.clear()
    registry().reset()
    with caplog.at_level(logging.INFO, logger="sparkdl_tpu.metrics"):
        TPURunner(np=-1, backend=_InlineBackend()).run(main, n=1)
    assert not [r for r in caplog.records if "all-host metrics" in r.message]


def test_tpu_launch_refuses_a_parent_that_holds_the_chip(monkeypatch):
    """A chip belongs to one process: a parent whose jax backend is a
    live TPU must not start ranks that need it (they would hang until
    timeout_s) — the launch raises at once. A CPU parent launches."""
    import jax

    from sparkdl_tpu.runner.backends import require_parent_off_chip

    jax.devices()  # this process has an initialised (CPU) backend
    require_parent_off_chip("cpu parent")  # silent
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="holds its chip"):
        TPURunner(np=-4, local_platform="tpu").run(lambda: None)


def test_tpu_rank_pins_form_one_job_layout():
    from sparkdl_tpu.runner.backends import tpu_rank_overrides

    env = tpu_rank_overrides(2, 4, [8476, 8477, 8478, 8479])
    assert env == {
        "TPU_VISIBLE_DEVICES": "2",
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "2,2,1",
        "TPU_PROCESS_ADDRESSES": "localhost:8476,localhost:8477,"
                                 "localhost:8478,localhost:8479",
        "TPU_PROCESS_PORT": "8478",
        "CLOUD_TPU_TASK_ID": "2",
    }
    # a rank count with no layout that has run on hardware: loud
    with pytest.raises(ValueError, match="no single-host TPU process"):
        tpu_rank_overrides(0, 3, [1, 2, 3])
