"""Per-layer metric ``prefill_device_share.backlog``: share of the traced stretch in which the device ran a prefill chunk program (``jit__chunk_*``)."""

from benchmark.engine_readers import prefill_device_share as compute  # noqa: F401
