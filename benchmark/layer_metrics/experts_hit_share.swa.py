"""Per-layer metric ``experts_hit_share.swa``: held experts given at least one row over the experts HELD (16 of the router's 256), mean over expert layers and decode ticks."""

from benchmark.readers_mimo_v2_flash import experts_hit_share as compute  # noqa: F401
