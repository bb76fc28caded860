"""Per-layer metric ``kv_cols_read_over_live.hybrid``: K/V columns gathered through the table over the live columns, in the layers that keep K/V."""

from benchmark.readers_olmo_hybrid import kv_cols_read_over_live as compute  # noqa: F401
