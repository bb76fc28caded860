"""Per-layer metric ``prefill_share.backlog``: share of the window inside the engine's prefill spans."""

from benchmark.readers import prefill_share as compute  # noqa: F401
