"""Per-layer metric ``experts_hit_share.mixed``: experts given at least one row over experts held, mean over expert layers and decode ticks."""

from benchmark.readers_afmoe import experts_hit_share as compute  # noqa: F401
