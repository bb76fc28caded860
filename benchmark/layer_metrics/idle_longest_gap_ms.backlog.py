"""Per-layer metric ``idle_longest_gap_ms.backlog``: the longest single idle gap of the device inside the traced stretch."""

from benchmark.prefill_readers import idle_longest_gap_ms as compute  # noqa: F401
