"""Per-layer metric ``expert_device_ms.conv``: device ms a decode tick inside the grouped products of its 64 held experts (the ``gmm`` kernel, found by name), from the run's own trace."""

from benchmark.readers_lfm2_moe import expert_device_ms as compute  # noqa: F401
