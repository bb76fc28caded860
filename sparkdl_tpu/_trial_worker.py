"""Subprocess entry for process-isolated HPO trials (hpo.fmin
trial_runner='processes'): unpickle (objective, params), evaluate, write
the result dict back. A fresh interpreter per trial gives each one its
own jax runtime/devices — the single-host analogue of SparkTrials'
executor-side evaluation."""

from __future__ import annotations

import sys


def main(payload_path: str, result_path: str) -> int:
    import cloudpickle

    with open(payload_path, "rb") as f:
        payload = cloudpickle.load(f)
    objective, params = payload["objective"], payload["params"]
    from sparkdl_tpu.runtime.chip import configure_compile_cache

    configure_compile_cache()
    try:
        out = objective(params)
        loss = out["loss"] if isinstance(out, dict) else float(out)
        extra = out if isinstance(out, dict) else {}
        result = {"loss": float(loss), "status": "ok",
                  **{k: v for k, v in extra.items()
                     if k not in ("loss", "status")}}
    except Exception as e:  # the parent records the failure, sweep survives
        result = {"loss": None, "status": "fail", "error": repr(e)}
    with open(result_path, "wb") as f:
        cloudpickle.dump(result, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1], sys.argv[2]))
