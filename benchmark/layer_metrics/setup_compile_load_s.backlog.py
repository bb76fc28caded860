"""Per-layer metric ``setup_compile_load_s.backlog``: seconds inside ``xla.compile`` spans that ended before the window (jax times the cache's part inside them)."""

from benchmark.engine_readers import setup_compile_load_s as compute  # noqa: F401
