"""Per-layer metric ``setup_cache_load_s.backlog``: seconds inside ``xla.cache_load`` spans that ended before the window: reading cached programs and loading them."""

from benchmark.engine_readers import setup_cache_load_s as compute  # noqa: F401
