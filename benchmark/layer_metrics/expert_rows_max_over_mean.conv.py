"""Per-layer metric ``expert_rows_max_over_mean.conv``: the fullest expert's rows over the mean rows of a hit expert, mean over decode ticks."""

from benchmark.readers_lfm2_moe import expert_rows_max_over_mean as compute  # noqa: F401
