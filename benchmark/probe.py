"""Readings that the limits and the fixed rates were set from: ``python -m benchmark.probe``.

Not part of a benchmark run. One process reads, over some seeds, the numbers
the correctness check compares: the program's as configured, and the
control's, which is the program with the configuration's own lower-precision
path switched on. It prints one JSON line per reading; PERF.md section 2
quotes them.

    python -m benchmark.probe --workloads gpt2xl-backlog \\
        --seeds 11,12,13,14,15,16,17 --control-seeds 21,22,23 --seconds 25
"""

from __future__ import annotations

import argparse
import sys

from benchmark import harness
from benchmark import manifest as mf


def main(argv: "list[str] | None" = None) -> int:
    ap = argparse.ArgumentParser(prog="python -m benchmark.probe")
    ap.add_argument("--workloads", required=True,
                    help="comma-separated cells of ONE runner kind")
    ap.add_argument("--seeds", required=True,
                    help="seeds read on the engine as configured")
    ap.add_argument("--control-seeds", default="",
                    help="seeds read on the control engine")
    ap.add_argument("--seconds", type=float, default=25.0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    cells = [mf.resolve_cell(w) for w in args.workloads.split(",")]
    kinds = {c.mix["runner"] for c in cells}
    if len(kinds) != 1:
        ap.error(f"cells of different runner kinds: {sorted(kinds)}")
    runner = harness.load_runner(cells[0])

    from sparkdl_tpu.runtime import chip

    if args.rehearse:
        if not chip.explicit_cpu():
            ap.error("--rehearse needs an exported JAX_PLATFORMS=cpu")
    else:
        chip.require_tpu()
    chip.configure_compile_cache()
    import jax

    jax.config.update("jax_compilation_cache_max_size", -1)
    runner.probe(cells, [int(s) for s in args.seeds.split(",") if s],
                 [int(s) for s in args.control_seeds.split(",") if s],
                 args.seconds, args.rehearse)
    return 0


if __name__ == "__main__":
    sys.exit(main())
