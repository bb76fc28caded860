"""Per-layer metric ``token_gap_p95_ms.backlog``: 95th percentile of the gaps between successive tokens of one request (``serving.first_token``, then the ``serving.decode_step`` spans that link it)."""

from benchmark.engine_readers import token_gap_p95_ms as compute  # noqa: F401
