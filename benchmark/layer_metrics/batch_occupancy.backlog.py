"""Per-layer metric ``batch_occupancy.backlog``: live rows over slots, mean over the window's decode ticks."""

from benchmark.readers import batch_occupancy as compute  # noqa: F401
