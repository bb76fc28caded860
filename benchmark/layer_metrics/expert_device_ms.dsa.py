"""Per-layer metric ``expert_device_ms.dsa``: device ms a decode tick inside the grouped products of the held experts, from the run's own trace."""

from benchmark.readers_glm_moe_dsa import expert_device_ms as compute  # noqa: F401
