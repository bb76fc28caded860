"""Per-layer metric ``delta_scan_roofline_share.hybrid``: what the gated delta rule over the traced chunks' ``scan_tokens`` needs over the chip's peaks, against the device time inside the chunkwise recurrences."""

from benchmark.readers_olmo_hybrid import delta_scan_roofline_share as compute  # noqa: F401
