"""Per-layer metric ``short_conv_device_ms.conv``: device ms a decode tick inside the operations that read or write a gated short convolution's tail or make its split, found by shape in the run's own trace."""

from benchmark.readers_lfm2_moe import short_conv_device_ms as compute  # noqa: F401
