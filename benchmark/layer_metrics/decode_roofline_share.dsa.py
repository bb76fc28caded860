"""Per-layer metric ``decode_roofline_share.dsa``: what a glm_moe_dsa decode tick needs (fixed weights once, the hit held experts' kernels, every layer's SELECTED columns of 576 values, a key a scored column of each full layer) over the peaks, against the decode program's device time."""

from benchmark.readers_glm_moe_dsa import decode_roofline_share as compute  # noqa: F401
