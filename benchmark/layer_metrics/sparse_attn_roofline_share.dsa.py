"""Per-layer metric ``sparse_attn_roofline_share.dsa``: each attended column's 576 values once a layer (``sel_cols`` x 1,152 B x 5) and the absorbed products over them, over the peaks, against the device time inside them."""

from benchmark.readers_glm_moe_dsa import sparse_attn_roofline_share as compute  # noqa: F401
