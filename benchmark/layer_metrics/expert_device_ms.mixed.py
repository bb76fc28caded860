"""Per-layer metric ``expert_device_ms.mixed``: device ms a decode tick inside the grouped products of its expert layers, from the run's own trace."""

from benchmark.readers_afmoe import expert_device_ms as compute  # noqa: F401
