"""Per-layer metric ``kv_cols_read_over_live.swa``: K/V columns the full layers gathered through the table over the live columns of the rows' contexts."""

from benchmark.readers_mimo_v2_flash import kv_cols_read_over_live as compute  # noqa: F401
