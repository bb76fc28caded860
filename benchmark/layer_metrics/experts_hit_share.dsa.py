"""Per-layer metric ``experts_hit_share.dsa``: held experts given at least one row a tick, as a share of the 16 held."""

from benchmark.readers_glm_moe_dsa import experts_hit_share as compute  # noqa: F401
