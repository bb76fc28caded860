"""Per-layer metric ``tick_host_self_ms.backlog``: mean ``serving.tick`` less the ``serving.decode_wait`` and ``serving.first_token`` spans inside it: the part of a tick in which the host was not waiting for a program."""

from benchmark.engine_readers import tick_host_self_ms as compute  # noqa: F401
