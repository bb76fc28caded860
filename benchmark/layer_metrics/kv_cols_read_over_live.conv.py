"""Per-layer metric ``kv_cols_read_over_live.conv``: K/V columns the two attention layers gathered through the table over the live columns of the rows' contexts: what a paged one-query kernel for value heads of 64 would be judged by."""

from benchmark.readers_lfm2_moe import kv_cols_read_over_live as compute  # noqa: F401
