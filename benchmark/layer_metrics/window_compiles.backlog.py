"""Per-layer metric ``window_compiles.backlog``: ``xla.compile`` spans that ended inside the window (0 when the warm-up is right)."""

from benchmark.engine_readers import window_compiles as compute  # noqa: F401
