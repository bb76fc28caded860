"""Per-layer metric ``selected_cols_share.dsa``: columns the riding rows' attention attended over the columns of their contexts (``sel_cols / kv_cols_live``): how sparse the traffic made a step."""

from benchmark.readers_glm_moe_dsa import selected_cols_share as compute  # noqa: F401
