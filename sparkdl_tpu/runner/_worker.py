"""Worker-process entry point for TPURunner's local-process backend.

Launched as ``python -m sparkdl_tpu.runner._worker <payload> <rank> <np>
<coordinator> <result_path>``. The payload (cloudpickle) carries the user fn
and kwargs; env overrides (JAX_PLATFORMS, XLA_FLAGS, the TPU chip pin) are
set by the parent in this process's environment before exec, so they are
in place before jax is imported.
"""

from __future__ import annotations

import pickle
import sys
import traceback


def main(argv: list[str]) -> int:
    payload_path, rank_s, np_s, coordinator, result_path = argv
    rank, nprocs = int(rank_s), int(np_s)

    import cloudpickle

    with open(payload_path, "rb") as f:
        payload = cloudpickle.load(f)

    import jax

    from sparkdl_tpu.runtime.chip import configure_compile_cache

    configure_compile_cache()
    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=nprocs,
        process_id=rank,
    )

    # Pre-flight slice health probe + optional profiler server (SURVEY.md §5
    # failure detection): fail fast if a chip or the collective path is bad,
    # before the user's train_fn compiles anything. Local mode shares the
    # parent's host, so the env knobs (SPARKDL_TPU_SKIP_HEALTH_CHECK /
    # SPARKDL_TPU_PROFILER_PORT) are read right here.
    from sparkdl_tpu.observability.health import preflight, preflight_env_opts

    try:
        preflight(rank=rank, **preflight_env_opts())
    except RuntimeError:
        return 2

    fn = payload["fn"]
    kwargs = payload["kwargs"]
    try:
        # Deterministic rank-crash site (reliability/faults.py): the plan
        # rides the inherited environment (SPARKDL_TPU_FAULT_PLAN), so a
        # parent can arm "worker.rank" (any rank — each child counts its
        # own hits) or "worker.rank.<r>" (that rank only) and the child
        # kills itself — the preemption drill for the backend's
        # peer-teardown watchdog.
        from sparkdl_tpu.reliability.faults import fault_point

        fault_point("worker.rank")
        fault_point(f"worker.rank.{rank}")
        result = fn(**kwargs)
    except Exception:
        traceback.print_exc()
        return 1

    if rank == 0:
        with open(result_path, "wb") as f:
            try:
                pickle.dump(("ok", result), f)
            except Exception as e:  # unpicklable user return value
                f.seek(0)
                pickle.dump(("unpicklable", repr(e)), f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
