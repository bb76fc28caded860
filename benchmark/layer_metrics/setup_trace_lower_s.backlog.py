"""Per-layer metric ``setup_trace_lower_s.backlog``: seconds inside ``xla.trace`` and ``xla.lower`` spans that ended before the window."""

from benchmark.engine_readers import setup_trace_lower_s as compute  # noqa: F401
