"""Per-layer metric ``prefill_turn_wait_ms.backlog``: mean part of a request's ``serving.prefill`` spent in ticks that gave it no chunk: its wait for a turn at the chunk budget."""

from benchmark.prefill_readers import prefill_turn_wait_ms as compute  # noqa: F401
