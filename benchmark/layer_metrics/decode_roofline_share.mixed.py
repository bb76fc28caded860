"""Per-layer metric ``decode_roofline_share.mixed``: what an expert family's decode tick needs (fixed weights, the hit experts' kernels, K/V inside each layer's reach) over the peaks, against the decode program's device time."""

from benchmark.readers_afmoe import decode_roofline_share as compute  # noqa: F401
