"""Per-layer metric ``prefill_device_us_per_token.backlog``: device time of the chunk programs in the traced stretch per REAL prompt token dispatched in it (``tokens`` of its ``serving.prefill_chunk`` spans)."""

from benchmark.prefill_readers import prefill_device_us_per_token as compute  # noqa: F401
