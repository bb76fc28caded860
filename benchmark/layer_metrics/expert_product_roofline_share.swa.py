"""Per-layer metric ``expert_product_roofline_share.swa``: what the grouped products of a tick need (the hit held experts' kernels, each pair's row in and out) over the peaks, against the device time inside the ``gmm`` kernel."""

from benchmark.readers_mimo_v2_flash import expert_product_roofline_share as compute  # noqa: F401
