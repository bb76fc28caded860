"""Per-layer metric ``setup_programs.backlog``: ``xla.compile`` spans (programs compiled, or loaded from the cache) that ended before the window."""

from benchmark.engine_readers import setup_programs as compute  # noqa: F401
