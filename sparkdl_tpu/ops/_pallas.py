"""Shared helpers for the Pallas kernel family (flash attention/decode,
fused GEMM+BN): scratch-space constructors and the interpret-mode default.
One definition so a convention change (e.g. an env override for interpret
mode) lands everywhere at once."""

from __future__ import annotations


def vmem(shape, dtype):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.VMEM(shape, dtype)


def smem(shape, dtype):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.SMEM(shape, dtype)


def smem_space():
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.SMEM


def auto_interpret() -> bool:
    """Compiled Mosaic on a TPU backend; the Pallas interpreter only
    under the explicit-CPU harness (``JAX_PLATFORMS=cpu``: the tests and
    the virtual mesh). Any other backend raises — in particular jax's
    own silent drop to CPU when no TPU answers must not turn a kernel
    into an interpreted one."""
    from sparkdl_tpu.runtime.chip import require_tpu

    return not require_tpu(explicit_cpu_ok=True)
