"""Per-layer metric ``engine_tokens_per_s.backlog``: tokens the engine appended to live requests inside the window, by its own count (``tokens`` of ``serving.retire``, one per ``serving.first_token``), per second."""

from benchmark.engine_readers import engine_tokens_per_s as compute  # noqa: F401
