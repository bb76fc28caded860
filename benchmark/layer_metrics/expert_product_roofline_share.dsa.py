"""Per-layer metric ``expert_product_roofline_share.dsa``: the hit held experts' kernels once and each pair's row in and out, over the peaks, against the device time inside the grouped products."""

from benchmark.readers_glm_moe_dsa import expert_product_roofline_share as compute  # noqa: F401
