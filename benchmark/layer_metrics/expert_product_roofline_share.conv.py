"""Per-layer metric ``expert_product_roofline_share.conv``: what the grouped products of a tick need (the hit experts' kernels, each pair's row in and out) over the peaks, against the device time inside the ``gmm`` kernel, at an expert width of 1,536 that its tiles of 1,024 do not divide."""

from benchmark.readers_lfm2_moe import expert_product_roofline_share as compute  # noqa: F401
