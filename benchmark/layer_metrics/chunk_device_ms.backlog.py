"""Per-layer metric ``chunk_device_ms.backlog``: device time of one prefill chunk program (``jit__chunk_*``), a mean over the whole executions of the traced stretch."""

from benchmark.prefill_readers import chunk_device_ms as compute  # noqa: F401
