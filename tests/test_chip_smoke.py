"""chip_smoke.py's contract, as far as a CPU can check it: no TPU is a
non-zero exit before anything compiles; the rehearsal passes and says it
is one; a fault armed in a phase turns the exit code non-zero."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _smoke(*args, **env):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), *args],
        cwd=REPO, env={**os.environ, **env}, capture_output=True, text=True,
        timeout=600)


def test_without_a_tpu_it_fails_before_compiling():
    proc = _smoke(JAX_PLATFORMS="cpu")
    assert proc.returncode == 2
    assert "no TPU backend" in proc.stderr
    assert proc.stdout == ""  # no phase ran, no result line


def test_rehearsal_of_the_cheap_phases_passes_and_is_labelled():
    proc = _smoke("--rehearse", "--phases", "device,kernel")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    lines = proc.stdout.strip().splitlines()
    assert lines[0].startswith("=== REHEARSAL")
    assert "[kernel] ok" in proc.stdout and "[serve] skipped" in proc.stdout
    assert json.loads(lines[-1]) == {
        "ok": True, "rehearsal": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 1}}


def test_a_fault_in_a_phase_turns_the_exit_code_nonzero():
    # every phase opens on a fault point, armed through the environment
    # as a chip run would arm it; the phases before it still pass
    proc = _smoke("--rehearse", "--phases", "device,kernel",
                  SPARKDL_TPU_FAULT_PLAN="smoke.kernel@1")
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "[device] ok" in proc.stdout
    assert "[kernel] FAILED" in proc.stdout
    assert json.loads(proc.stdout.strip().splitlines()[-1])["failed"] == [
        "kernel"]


@pytest.mark.slow
def test_a_production_fault_site_fails_its_phase():
    # the dispatch layer under BatchedRunner, inside the featurize phase
    proc = _smoke("--rehearse", "--phases", "featurize",
                  SPARKDL_TPU_FAULT_PLAN="dispatch@1*")
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "[featurize] FAILED" in proc.stdout


@pytest.mark.slow
def test_full_rehearsal_passes():
    proc = _smoke("--rehearse")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    for phase in ("device", "featurize", "serve", "train", "kernel"):
        assert f"[{phase}] ok" in proc.stdout
    assert "[four_chip] skipped: 1 device" in proc.stdout
