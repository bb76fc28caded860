"""Per-layer metric ``indexer_device_ms.dsa``: device ms a decode tick inside the indexers' scoring and selection (both full layers), found by the shapes of what the operations make."""

from benchmark.readers_glm_moe_dsa import indexer_device_ms as compute  # noqa: F401
