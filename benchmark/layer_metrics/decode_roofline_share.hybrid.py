"""Per-layer metric ``decode_roofline_share.hybrid``: what a hybrid family's decode tick needs (fixed weights, K/V of the full layers, each row's recurrent state in and out) over the peaks, against the decode program's device time."""

from benchmark.readers_olmo_hybrid import decode_roofline_share as compute  # noqa: F401
