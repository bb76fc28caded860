"""Per-layer metric ``device_idle_share.backlog``: share of the traced stretch in which no operation ran on the device."""

from benchmark.readers import device_idle_share as compute  # noqa: F401
