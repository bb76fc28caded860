"""Per-layer metric ``experts_hit_share.conv``: experts given at least one row over the 64 held, mean over expert layers and decode ticks."""

from benchmark.readers_lfm2_moe import experts_hit_share as compute  # noqa: F401
