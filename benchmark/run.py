"""One run of one cell: ``python -m benchmark.run --workload W --seed N --seconds S --trace 0|1``.

A new process that needs a TPU. It builds the cell from its data files,
warms the shapes that cell's traffic uses (all of that is ``setup_s``),
measures for ``--seconds``, checks what the timed path produced against the
float32 reference once the window has closed, and prints ONE JSON object as
the last line of its standard output. ``--trace 0`` reports the cell's
end-to-end metrics; ``--trace 1`` turns the program's spans on, wraps a few
seconds of the steady window in ``jax.profiler`` and reports the per-layer
metrics and a breakdown instead.

``--rehearse`` (tests, debugging) runs the mix's ``rehearse`` sizes on the
CPU and needs an exported ``JAX_PLATFORMS=cpu``; its line says
``platform: cpu`` and carries no device metric.
"""

import time

_T_PROCESS = time.monotonic()  # as near the process's start as Python lets us

import sys  # noqa: E402

from benchmark.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_process=_T_PROCESS))
