"""Per-layer metric ``queue_wait_ms.backlog``: median ``serving.queue_wait`` span ending inside the window: the tick a freed slot waits for its client."""

from benchmark.engine_readers import queue_wait_ms as compute  # noqa: F401
