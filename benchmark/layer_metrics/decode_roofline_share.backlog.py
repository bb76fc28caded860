"""Per-layer metric ``decode_roofline_share.backlog``: bytes a decode tick needs over the peak bandwidth, against the decode program's device time."""

from benchmark.readers import decode_roofline_share as compute  # noqa: F401
