"""Per-layer metric ``expert_rows_max_over_mean.mixed``: the fullest expert's rows over the mean rows of a hit expert, mean over decode ticks."""

from benchmark.readers_afmoe import expert_rows_max_over_mean as compute  # noqa: F401
