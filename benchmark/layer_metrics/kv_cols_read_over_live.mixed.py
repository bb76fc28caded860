"""Per-layer metric ``kv_cols_read_over_live.mixed``: K/V columns gathered through the table over the live columns inside each layer's reach."""

from benchmark.readers_afmoe import kv_cols_read_over_live as compute  # noqa: F401
