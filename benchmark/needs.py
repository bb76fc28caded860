"""What a call NEEDS, from its shapes: floating-point operations and bytes the
algorithm cannot avoid. Never what the compiler emitted, so a share of a
roofline computed from these cannot pass 100% by construction of the count."""

from __future__ import annotations

from benchmark.reference import gpt2_sizes


def gpt2_param_bytes(hf: dict, dense_bytes: int = 2) -> int:
    """Bytes of the parameters one decode tick must read: every block's dense
    kernels and biases at ``dense_bytes``, layer norms in float32, the whole
    tied embedding in float32 (the head multiplies by all of it). The
    position table is left out: a tick reads one row per sequence."""
    s = gpt2_sizes(hf)
    h, f, n = s["hidden"], s["inner"], s["layers"]
    block = dense_bytes * (4 * h * h + 4 * h + 2 * h * f + f + h) + 4 * 4 * h
    return n * block + 4 * s["vocab"] * h + 4 * 2 * h


def gpt2_kv_bytes_per_token(hf: dict, kv_bytes: int = 2) -> int:
    """K and V of one token over all layers."""
    s = gpt2_sizes(hf)
    return 2 * s["layers"] * s["hidden"] * kv_bytes


def gpt2_decode_bytes(hf: dict, rows: int, context_tokens: int,
                      dense_bytes: int = 2, kv_bytes: int = 2) -> int:
    """Bytes one decode tick must move: the parameters once, the K/V of every
    token in the live rows' contexts once, the new K/V of each row written."""
    per_tok = gpt2_kv_bytes_per_token(hf, kv_bytes)
    return (gpt2_param_bytes(hf, dense_bytes)
            + per_tok * context_tokens + per_tok * rows)


def gpt2_decode_flops(hf: dict, rows: int, context_tokens: int) -> int:
    """FLOPs of one decode tick: 2 per dense weight per row, the tied head,
    and attention's 4 * hidden per context token per layer."""
    s = gpt2_sizes(hf)
    h, f, n = s["hidden"], s["inner"], s["layers"]
    dense = 2 * rows * (n * (4 * h * h + 2 * h * f) + s["vocab"] * h)
    return dense + 4 * h * n * context_tokens


def roofline_seconds(flops: float, bytes_: float, peak) -> "tuple[float, str]":
    """The least time the chip could take, and which bound sets it."""
    t_flops = flops / peak.bf16_flops_per_s
    t_bytes = bytes_ / peak.hbm_bytes_per_s
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "memory")
