"""Per-layer metric ``sparse_attn_device_ms.dsa``: device ms a decode tick inside the selected read and the absorbed products, all five layers, found by the shapes of what the operations make."""

from benchmark.readers_glm_moe_dsa import sparse_attn_device_ms as compute  # noqa: F401
