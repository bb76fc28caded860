"""Per-layer metric ``decode_roofline_share.conv``: what an lfm2_moe decode tick needs (fixed weights, the hit experts' kernels and each pair's row in and out, the attention layers' live K/V, the riding rows' tails in and out) over the peaks, against the decode program's device time: the share of the whole step."""

from benchmark.readers_lfm2_moe import decode_roofline_share as compute  # noqa: F401
