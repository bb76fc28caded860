"""Per-layer metric ``host_busy_share.backlog``: share of the window in which the engine thread worked and did not wait for a program (the sum ``tick_host_self_ms`` averages, over the window)."""

from benchmark.prefill_readers import host_busy_share as compute  # noqa: F401
