"""Per-layer metric ``indexer_roofline_share.dsa``: a key of 128 values for every column a tick's indexers must score (``index_cols``) and their projections, both full layers, over the peaks, against the device time inside them."""

from benchmark.readers_glm_moe_dsa import indexer_roofline_share as compute  # noqa: F401
