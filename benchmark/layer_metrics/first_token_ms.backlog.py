"""Per-layer metric ``first_token_ms.backlog``: median time from a request's enqueueing (start of its ``serving.queue_wait``) to its first token on the host (end of its ``serving.first_token``)."""

from benchmark.engine_readers import first_token_ms as compute  # noqa: F401
