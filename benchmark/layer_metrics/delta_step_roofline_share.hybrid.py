"""Per-layer metric ``delta_step_roofline_share.hybrid``: the bytes the state updates of a tick must move (``state_bytes`` of the traced ticks) over the chip's bandwidth, against the device time inside them."""

from benchmark.readers_olmo_hybrid import delta_step_roofline_share as compute  # noqa: F401
