"""HPO engine: search-space sampling, fmin contract, failure tolerance."""

import numpy as np
import pytest

from sparkdl_tpu.hpo import Trials, fmin, hp, sample_space


def test_sample_space_kinds():
    rng = np.random.default_rng(0)
    space = {
        "lr": hp.loguniform("lr", np.log(1e-5), np.log(1e-2)),
        "dropout": hp.uniform("dropout", 0.0, 0.5),
        "batch": hp.choice("batch", [16, 32, 64]),
        "layers": hp.quniform("layers", 1, 4, 1),
        "fixed": "adam",
    }
    s = sample_space(space, rng)
    assert 1e-5 <= s["lr"] <= 1e-2
    assert 0.0 <= s["dropout"] <= 0.5
    assert s["batch"] in (16, 32, 64)
    assert s["layers"] in (1.0, 2.0, 3.0, 4.0)
    assert s["fixed"] == "adam"


def test_fmin_finds_minimum():
    space = {"x": hp.uniform("x", -5, 5)}
    best = fmin(lambda p: (p["x"] - 2.0) ** 2, space,
                max_evals=60, seed=1, use_hyperopt=False)
    assert abs(best["x"] - 2.0) < 0.5


def test_fmin_parallel_and_failures():
    space = {"x": hp.uniform("x", 0, 1)}
    calls = []

    def objective(p):
        calls.append(p)
        if p["x"] > 0.8:
            raise RuntimeError("boom")
        return {"loss": p["x"], "status": "ok", "aux": 42}

    trials = Trials()
    best = fmin(objective, space, max_evals=20, seed=2, parallelism=4,
                trials=trials, use_hyperopt=False)
    assert len(trials.trials) == 20
    assert any(t["status"] == "fail" for t in trials.trials) or all(
        c["x"] <= 0.8 for c in calls
    )
    assert best["x"] == trials.best_trial["params"]["x"]
    assert trials.best_trial.get("aux") == 42


def test_trials_no_success_raises():
    t = Trials(trials=[{"status": "fail", "loss": None}])
    with pytest.raises(RuntimeError, match="no successful"):
        _ = t.best_trial


@pytest.mark.slow
def test_process_trials_isolated_interpreters():
    """trial_runner='processes': each trial evaluates in its own fresh
    interpreter (SparkTrials' executor-side isolation, single-host form),
    with failures tolerated and parallelism bounded."""
    import os as _os

    space = {"x": hp.uniform("x", -5, 5)}
    trials = Trials()

    def objective(p):
        import os
        if p["x"] < -4.0:
            raise RuntimeError("synthetic trial failure")
        return {"loss": (p["x"] - 2.0) ** 2, "pid": os.getpid()}

    best = fmin(objective, space, max_evals=8, seed=3,
                use_hyperopt=False, parallelism=3,
                trial_runner="processes", trials=trials)
    assert abs(best["x"] - 2.0) < 2.5
    ok = [t for t in trials.trials if t["status"] == "ok"]
    assert ok, trials.trials
    pids = {t["pid"] for t in ok}
    assert _os.getpid() not in pids  # not in the driver process
    assert len(pids) == len(ok)  # one fresh interpreter per trial
    assert [t["tid"] for t in trials.trials] == list(range(8))


def test_process_trials_pin_disjoint_devices():
    """On a chip-ful host the processes runner pins each concurrent trial
    to its own chip (env must precede the child's jax import) and queues
    excess trials for a free chip instead of oversubscribing."""
    from sparkdl_tpu.hpo import _run_trials_processes

    def objective(p):
        import os
        import time
        time.sleep(0.3)  # hold the chip so concurrent trials overlap
        return {
            "loss": p["x"],
            "chip": os.environ.get("TPU_VISIBLE_DEVICES"),
            "bounds": os.environ.get("TPU_PROCESS_BOUNDS"),
        }

    # 2 chips, 2 concurrent trials: each sees its own chip
    res = _run_trials_processes(
        objective, [{"x": 0.0}, {"x": 1.0}], parallelism=2,
        pin_devices=[3, 5],
    )
    assert sorted(r["chip"] for r in res) == ["3", "5"]
    assert all(r["bounds"] == "1,1,1" for r in res)

    # 3 trials on 2 chips with parallelism=3: never oversubscribed —
    # every trial still lands on one of the two pinned chips
    res = _run_trials_processes(
        objective, [{"x": float(i)} for i in range(3)], parallelism=3,
        pin_devices=[0, 1],
    )
    assert len(res) == 3 and all(r["status"] == "ok" for r in res)
    assert {r["chip"] for r in res} <= {"0", "1"}

    # chipless pool: unpinned, env untouched (explicit [] keeps this
    # hermetic on hosts where autodetection would find chips)
    res = _run_trials_processes(
        objective, [{"x": 0.0}], parallelism=1, pin_devices=[],
    )
    assert res[0]["chip"] is None


def test_process_trials_refuse_a_parent_that_holds_the_chip(monkeypatch):
    """The parent-holds-the-chip check (runner.backends): a driver that
    already ran something on a TPU backend would launch pinned trials
    that hang on the chip it holds — fmin raises at launch instead."""
    import jax

    jax.devices()  # initialised backend (CPU here) ...
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # ... a TPU
    with pytest.raises(RuntimeError, match="holds its chip"):
        fmin(lambda p: p["x"], {"x": hp.uniform("x", 0, 1)}, max_evals=2,
             use_hyperopt=False, trial_runner="processes")


def test_local_pinnable_chips_detection(monkeypatch):
    """Chip detection never initializes jax (the driver would acquire
    every chip): it honors an existing TPU_VISIBLE_DEVICES restriction,
    else counts /dev/accel* entries (chip-granular, unlike jax device
    counts which are cores)."""
    from sparkdl_tpu.runner import backends

    monkeypatch.setenv("TPU_VISIBLE_DEVICES", "2,3")
    assert backends.local_pinnable_chips() == [2, 3]
    monkeypatch.setenv("TPU_VISIBLE_DEVICES", "")
    assert backends.local_pinnable_chips() == []
    monkeypatch.delenv("TPU_VISIBLE_DEVICES")
    monkeypatch.setattr(
        "glob.glob", lambda pat: ["/dev/accel0", "/dev/accel1"]
        if pat == "/dev/accel*" else [],
    )
    assert backends.local_pinnable_chips() == [0, 1]


def test_vfio_fallback_demands_second_tpu_signal(monkeypatch):
    """/dev/vfio entries alone must not pin (GPUs/NICs passthrough the
    same way — ADVICE r5): pinning needs libtpu or a Google PCI vendor id,
    else the pool is unpinned rather than pointing children at
    nonexistent chip indices."""
    from sparkdl_tpu.runner import backends

    monkeypatch.delenv("TPU_VISIBLE_DEVICES", raising=False)
    monkeypatch.setattr(
        "glob.glob",
        lambda pat: (["/dev/vfio/0", "/dev/vfio/1", "/dev/vfio/vfio"]
                     if pat == "/dev/vfio/*" else []),
    )
    # vfio entries + confirmed TPU signal -> logical chip indices
    monkeypatch.setattr(backends, "_vfio_is_tpu", lambda: True)
    assert backends.local_pinnable_chips() == [0, 1]
    # same entries, no TPU signal -> unpinned fallback
    monkeypatch.setattr(backends, "_vfio_is_tpu", lambda: False)
    assert backends.local_pinnable_chips() == []


def test_vfio_is_tpu_checks_pci_vendor(monkeypatch, tmp_path):
    """The second signal itself: Google's PCI vendor id qualifies, other
    vendors don't (libtpu lookup forced to miss so ONLY the PCI path is
    under test — the dev image actually ships libtpu)."""
    from sparkdl_tpu.runner import backends

    monkeypatch.setattr("importlib.util.find_spec", lambda name: None)
    vendor = tmp_path / "vendor"
    vendor.write_text("0x1ae0\n")
    monkeypatch.setattr(
        "glob.glob",
        lambda pat: ([str(vendor)]
                     if pat == "/sys/bus/pci/devices/*/vendor" else []),
    )
    assert backends._vfio_is_tpu() is True
    vendor.write_text("0x10de\n")
    assert backends._vfio_is_tpu() is False


def test_fmin_warns_when_tpe_gate_bypasses_installed_hyperopt(
        monkeypatch, caplog):
    """ADVICE r5: the distributed-intent gate silently downgraded TPE to
    seeded random search; callers must hear about it and the forcing
    knob."""
    import logging

    from sparkdl_tpu import hpo

    monkeypatch.setattr(hpo, "_hyperopt", object())  # "installed"
    space = {"x": hp.uniform("x", 0, 1)}
    with caplog.at_level(logging.WARNING, logger="sparkdl_tpu.hpo"):
        fmin(lambda p: p["x"], space, max_evals=2, parallelism=2, seed=0)
    assert any("use_hyperopt=True" in r.message and "TPE" in r.message
               for r in caplog.records), caplog.records
    # an explicit use_hyperopt=False is a decision, not a surprise: quiet
    caplog.clear()
    with caplog.at_level(logging.WARNING, logger="sparkdl_tpu.hpo"):
        fmin(lambda p: p["x"], space, max_evals=2, use_hyperopt=False,
             seed=0)
    assert not caplog.records


class _FakeRDD:
    def __init__(self, data):
        self.data = data
        self.mapped = None

    def map(self, f):
        out = _FakeRDD(self.data)
        out.mapped = f
        return out

    def collect(self):
        return [self.mapped(x) for x in self.data]


class _FakeSparkContext:
    def __init__(self):
        self.calls = []

    def parallelize(self, data, numSlices):
        self.calls.append(numSlices)
        return _FakeRDD(list(data))


class _FakeSparkSession:
    def __init__(self):
        self.sparkContext = _FakeSparkContext()


def test_spark_trials_fan_out_semantics():
    """trial_runner='spark' drives sc.parallelize(...).map(...).collect()
    — the SparkTrials task-per-trial shape — exercised against a
    semantics-matched fake (the repo's fake-Spark testing discipline)."""
    spark = _FakeSparkSession()
    space = {"x": hp.uniform("x", -5, 5)}
    trials = Trials()
    best = fmin(lambda p: (p["x"] - 2.0) ** 2, space, max_evals=12,
                seed=5, use_hyperopt=False, parallelism=4,
                trial_runner="spark", spark=spark, trials=trials)
    assert abs(best["x"] - 2.0) < 1.5
    assert spark.sparkContext.calls == [4]  # parallelism -> numSlices
    assert len(trials.trials) == 12
    assert all(t["status"] == "ok" for t in trials.trials)


def test_spark_trials_without_session_raises():
    space = {"x": hp.uniform("x", 0, 1)}
    with pytest.raises(RuntimeError, match="SparkSession"):
        fmin(lambda p: p["x"], space, max_evals=2, use_hyperopt=False,
             trial_runner="spark")


def test_unknown_trial_runner_rejected():
    space = {"x": hp.uniform("x", 0, 1)}
    with pytest.raises(ValueError, match="trial_runner"):
        fmin(lambda p: p["x"], space, max_evals=2, use_hyperopt=False,
             trial_runner="bogus")
