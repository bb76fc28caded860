"""Per-layer metric ``expert_device_ms.swa``: device ms a decode tick inside the grouped products of the held experts, from the run's own trace."""

from benchmark.readers_mimo_v2_flash import expert_device_ms as compute  # noqa: F401
