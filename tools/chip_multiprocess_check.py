"""One process per chip, checked from a parent that never touches jax.

Run on a host with N >= 1 TPU chips (``chip_smoke.py`` holds the chips in
ONE process and cannot launch children that need them; this is the other
half of that rule):

1. ``fmin(trial_runner="processes")``: N concurrent trials, each in its own
   interpreter pinned to one chip. Every child must see exactly one TPU
   device, each under a different pin, and all N must hold their chip at
   the same time (two processes cannot share one chip, so N overlapping
   trials are on N chips; a pinned child reports its one chip at local
   coordinates (0,0,0), so the coordinates cannot tell them apart).
2. ``TPURunner(np=-N, local_platform="tpu")``: N ranks, rank r on chip r,
   joined into ONE N-device ``jax.distributed`` job (an all-gather across
   the ranks proves the chips talk).

Prints one JSON line per check and exits non-zero if either fails. The
parent imports jax (the package does) but never initialises a backend,
which is what ``require_parent_off_chip`` enforces.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _trial(params: dict) -> dict:
    """Runs in a pinned child: report what this process can see, then
    hold the chip long enough that all N trials overlap."""
    import time

    import jax
    import jax.numpy as jnp

    devs = jax.devices()
    total = float(jnp.arange(8.0).sum())  # the chip answers
    held_from = time.time()
    time.sleep(float(os.environ.get("CHECK_HOLD_S", "10")))
    return {
        "loss": abs(total - 28.0) + params["x"] * 0.0,
        "pid": os.getpid(),
        "pin": os.environ.get("TPU_VISIBLE_DEVICES"),
        "platform": devs[0].platform,
        "n_devices": len(devs),
        "held": [held_from, time.time()],
    }


def _rank_fn() -> dict:
    """Runs on every rank of the TPURunner job."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import multihost_utils

    ranks = multihost_utils.process_allgather(
        jnp.asarray([jax.process_index()], jnp.int32))
    coords = multihost_utils.process_allgather(
        jnp.asarray(jax.local_devices()[0].coords, jnp.int32))
    return {
        "platform": jax.default_backend(),
        "process_count": jax.process_count(),
        "local_devices": jax.local_device_count(),
        "global_devices": jax.device_count(),
        "ranks_seen": sorted(int(r) for r in ranks.ravel()),
        "chip_coords_by_process": [[int(c) for c in row] for row in coords],
    }


def main() -> int:
    from sparkdl_tpu.hpo import Trials, fmin, hp
    from sparkdl_tpu.runner.backends import local_pinnable_chips
    from sparkdl_tpu.runner.tpu_runner import TPURunner

    chips = local_pinnable_chips()
    print(json.dumps({"check": "detect", "pinnable_chips": chips}),
          flush=True)
    n = len(chips)
    if n < 1:
        print("no TPU chips detected on this host", file=sys.stderr)
        return 2
    ok = True

    trials = Trials()
    fmin(_trial, {"x": hp.uniform("x", 0, 1)}, max_evals=n, parallelism=n,
         trial_runner="processes", trials=trials, use_hyperopt=False)
    seen = [{k: t.get(k) for k in ("status", "pid", "pin", "platform",
                                   "n_devices", "held", "error")}
            for t in trials.trials]
    hpo_ok = (all(t["status"] == "ok" and t["platform"] == "tpu"
                  and t["n_devices"] == 1 for t in seen)
              and len({t["pin"] for t in seen}) == n
              and len({t["pid"] for t in seen}) == n
              # every trial held its chip while every other one did
              and max(t["held"][0] for t in seen)
              < min(t["held"][1] for t in seen))
    print(json.dumps({"check": "hpo_pinned_trials", "ok": hpo_ok,
                      "trials": seen}), flush=True)
    ok &= hpo_ok

    try:
        job = TPURunner(np=-n, local_platform="tpu", timeout_s=150,
                        driver_log_verbosity="all").run(_rank_fn)
        job_ok = (job["platform"] == "tpu" and job["process_count"] == n
                  and job["local_devices"] == 1
                  and job["global_devices"] == n
                  and job["ranks_seen"] == list(range(n)))
        print(json.dumps({"check": "tpurunner_one_job", "ok": job_ok,
                          "rank0": job}), flush=True)
    except Exception as e:  # report the reason: it decides the design
        job_ok = False
        print(json.dumps({"check": "tpurunner_one_job", "ok": False,
                          "error": repr(e)}), flush=True)
    ok &= job_ok
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
