"""Per-layer metric ``delta_step_device_ms.hybrid``: device ms a decode tick inside the one-token gated-delta-rule state updates of its linear layers, from the run's own trace."""

from benchmark.readers_olmo_hybrid import delta_step_device_ms as compute  # noqa: F401
