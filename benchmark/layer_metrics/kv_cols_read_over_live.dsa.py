"""Per-layer metric ``kv_cols_read_over_live.dsa``: ``latent`` columns the decode ticks fetched (the selection's size a slot) over the live columns of the rows' contexts: under 1 where the selection bites."""

from benchmark.readers_glm_moe_dsa import kv_cols_read_over_live as compute  # noqa: F401
