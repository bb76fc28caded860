"""Per-layer metric ``tick_host_ms.backlog``: mean decode tick on the host's clock less the decode program's mean device time, over the traced stretch."""

from benchmark.readers import tick_host_ms as compute  # noqa: F401
