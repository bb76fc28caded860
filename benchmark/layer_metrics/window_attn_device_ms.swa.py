"""Per-layer metric ``window_attn_device_ms.swa``: device ms a decode tick inside the operations that read or write a window layer's ring of its last 128 columns, found by shape in the run's own trace."""

from benchmark.readers_mimo_v2_flash import window_attn_device_ms as compute  # noqa: F401
