"""Per-layer metric ``decode_roofline_share.swa``: what a mimo_v2_flash decode tick needs (fixed weights, the hit held experts' kernels, the full layers' live K/V, the rings' live columns in and one out) over the peaks, against the decode program's device time."""

from benchmark.readers_mimo_v2_flash import decode_roofline_share as compute  # noqa: F401
