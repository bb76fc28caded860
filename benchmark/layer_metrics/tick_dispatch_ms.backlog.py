"""Per-layer metric ``tick_dispatch_ms.backlog``: mean ``serving.decode_dispatch`` span: the host's own work to launch the decode program."""

from benchmark.engine_readers import tick_dispatch_ms as compute  # noqa: F401
