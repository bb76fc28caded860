"""Per-layer metric ``delta_scan_device_ms.hybrid``: device ms a prefill chunk inside the chunkwise gated-delta-rule recurrences of its linear layers, from the run's own trace."""

from benchmark.readers_olmo_hybrid import delta_scan_device_ms as compute  # noqa: F401
