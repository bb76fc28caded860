"""ONE test, so that xdist (``--dist loadfile`` hands out files by their
number of tests) starts it last: the traced rehearsal of the serving cell,
a process that compiles, names every per-layer metric that cell reports
and says what each phase after its window took."""

import json
import os
import shutil
import subprocess
import sys

from benchmark import manifest as mf

ROOT = mf.repo_root()
CELL = "gpt2xl-backlog"


def test_the_traced_rehearsal_names_every_metric_of_its_cell():
    cmd = [sys.executable, "-m", "benchmark.run", "--workload", CELL,
           "--seed", str(2**31 + 24), "--seconds", "2", "--trace", "1",
           "--rehearse"]
    taskset = shutil.which("taskset")
    if taskset is not None:  # one core, as tests/benchmark/test_benchmark.py
        cmd = [taskset, "-c", str(max(os.sched_getaffinity(0))), *cmd]
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "BENCH_RUN")}
    env.update(JAX_PLATFORMS="cpu", TF_CPP_MIN_LOG_LEVEL="2",
               PYTHONPATH=ROOT)
    proc = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    # the metrics THIS cell reports; what only another cell reports is no
    # business of its rehearsal
    metrics = mf.resolve_cell(CELL, ROOT).per_layer
    names = [m["name"] for m in metrics]
    assert names
    for name in names:
        assert name in proc.stdout, name
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True
    # the keys the line had, and last the numbers compared beside their
    # limits, which are the last lines of standard error too
    assert list(line) == ["correct", "attempted", "failed", "device",
                          "compiles_in_window", "metrics", "rehearsal",
                          "compared"]
    assert all(c["ok"] and c["value"] <= c["limit"]
               for c in line["compared"].values())
    assert proc.stderr.strip().splitlines()[-1].startswith(
        "compared token_gap_mean_over_logit_std = ")
    # each phase after the window says what it took, as it ends, on a
    # [benchmark] line; a rehearsal takes no device trace and says so
    said = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("[benchmark] after the window: ")]
    phases = ["drain ", "correctness check ", "stop_trace, load_xplane and "
              "reduce_trace not run", "per-layer readers ", ""]
    assert len(said) == len(phases), said
    for ln, phase in zip(said, phases):
        assert ln.startswith("[benchmark] after the window: " + phase), ln
    assert "spans " in said[3] and said[4].endswith("to the result line")
    # the engine's own spans and the compile listener were there to read:
    # only the metrics that need a device trace are left out of the line
    assert sorted(set(names) - set(line["metrics"])) == sorted(
        m["name"] for m in metrics if m["source"] == "device_trace")
