"""Per-layer metric ``decode_device_ms.backlog``: device time of the decode program per tick, from the trace."""

from benchmark.readers import decode_device_ms as compute  # noqa: F401
