"""Per-layer metric ``expert_product_roofline_share.mixed``: what the grouped products of a decode tick need over the peaks, against the device time inside them."""

from benchmark.readers_afmoe import expert_product_roofline_share as compute  # noqa: F401
