"""Pallas TPU flash attention: fused blockwise softmax attention.

The reference runs attention inside opaque TF graphs on GPU (SURVEY.md
§2.18 — libtensorflow kernel dispatch); here the hot op is a hand-written
Pallas kernel tiled for the MXU: Q/K/V blocks stream HBM→VMEM, scores and
probabilities live only in VMEM scratch (never materialised at [L, L] in
HBM), and the online-softmax running (max, denominator) accumulators ride
along in VMEM across the K-block grid dimension. Forward saves only the
per-row logsumexp; the backward pass recomputes probabilities blockwise in
two further kernels (dq; dk/dv), the standard flash-attention trade of
FLOPs for HBM bandwidth — the right trade on TPU where HBM is the
bottleneck and the MXU is rarely saturated by attention.

TPU layout notes: row-statistics (logsumexp, the dO·O correction term)
travel in an all-lanes-equal [*, L, 128] layout so kernel reads/writes
never need a cross-lane transpose; the key-padding mask travels as
[BH, 1, L] (a legal block shape because its sublane dim equals the array
dim). The dk/dv kernel contracts over the sublane dim via dot_general
instead of materialising transposed score blocks.

Public layout: [B, L, H, D] (matching ``parallel.ring_attention``), folded
to [B*H, L, D] for the kernels. Supports causal masking and a [B, Lk] bool
key-padding mask; attention-probs dropout is unsupported (the usual
flash-attention trade-off, same caveat as the ring path).

On CPU (tests; the reference-parity virtual-mesh harness) the kernels run
in Pallas interpreter mode automatically.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from sparkdl_tpu.ops._pallas import auto_interpret

_NEG_INF = -1e30  # large-negative, not -inf: keeps exp()/where() NaN-free
_LANES = 128  # TPU lane width: last-dim tile size


@dataclasses.dataclass(frozen=True)
class _Config:
    """Static kernel configuration (hashable: custom_vjp nondiff arg)."""

    scale: float
    causal: bool
    block_q: int
    block_k: int
    interpret: bool
    #: global position of query row 0 (cached prefill: queries sit at
    #: [q_offset, q_offset+Lq) against keys at [0, Lk))
    q_offset: int = 0


def _ceil_to(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _vmem(shape, dtype):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.VMEM(shape, dtype)


def _causal_mask(s, qi, ki, bq, bk, q_offset=0):
    q_pos = (q_offset + qi * bq
             + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0))
    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return jnp.where(q_pos >= k_pos, s, _NEG_INF)


# ---------------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref,
                acc_scr, m_scr, l_scr, *, cfg: _Config):
    """Grid (bh, q_blocks, k_blocks); k innermost so VMEM scratch carries
    the online-softmax state across K blocks for one Q block."""
    qi, ki = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)
    bq, bk = cfg.block_q, cfg.block_k

    @pl.when(ki == 0)
    def _init():
        acc_scr[:] = jnp.zeros_like(acc_scr)
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)

    # Causal: skip K blocks strictly above the diagonal band.
    run = True
    if cfg.causal:
        run = ki * bk <= cfg.q_offset + qi * bq + bq - 1

    @pl.when(run)
    def _attend():
        # Operands stay in their storage dtype (bf16): the MXU computes
        # bf16 x bf16 with f32 accumulate natively; upcasting first would
        # force 6-pass f32 matmuls (measured ~6x slower on v5e).
        q = q_ref[0]  # [bq, d]
        k = k_ref[0]  # [bk, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * cfg.scale  # [bq, bk] f32
        s = jnp.where(mask_ref[0] != 0, s, _NEG_INF)  # [1, bk] broadcast
        if cfg.causal:
            s = _causal_mask(s, qi, ki, bq, bk, cfg.q_offset)

        m_prev = m_scr[:]  # [bq, LANES] (all lanes equal)
        l_prev = l_scr[:]
        m_cur = jnp.max(s, axis=-1, keepdims=True)  # [bq, 1]
        m_next = jnp.maximum(m_prev, m_cur)  # broadcast → [bq, LANES]
        correction = jnp.exp(m_prev[:, :1] - m_next[:, :1])  # [bq, 1]
        p = jnp.exp(s - m_next[:, :1])  # [bq, bk]
        l_scr[:] = l_prev * correction + jnp.sum(p, axis=-1, keepdims=True)
        m_scr[:] = m_next
        v = v_ref[0]  # [bk, d] storage dtype
        # Probabilities drop to the V dtype for the PV matmul (the
        # standard flash trade); accumulation stays f32 in scratch.
        acc_scr[:] = acc_scr[:] * correction + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(ki == nk - 1)
    def _finalize():
        l = jnp.maximum(l_scr[:], 1e-30)  # [bq, LANES]
        o_ref[0] = (acc_scr[:] / l[:, :1]).astype(o_ref.dtype)
        lse_ref[0] = m_scr[:] + jnp.log(l)  # all lanes equal


def _fwd(cfg: _Config, q, k, v, mask):
    """q,k,v: [BH, L, D] (padded); mask: [BH, 1, Lk] int32.

    Returns (o [BH, Lq, D], lse [BH, Lq, LANES] all-lanes-equal).
    """
    bh, lq, d = q.shape
    lk = k.shape[1]
    bq, bk = cfg.block_q, cfg.block_k
    return pl.pallas_call(
        functools.partial(_fwd_kernel, cfg=cfg),
        grid=(bh, lq // bq, lk // bk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, 1, bk), lambda b, i, j: (b, 0, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, _LANES), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, lq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, lq, _LANES), jnp.float32),
        ],
        scratch_shapes=[
            _vmem((bq, d), jnp.float32),
            _vmem((bq, _LANES), jnp.float32),
            _vmem((bq, _LANES), jnp.float32),
        ],
        interpret=cfg.interpret,
    )(q, k, v, mask)


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------


def _recompute_p(q_ref, k_ref, mask_ref, lse_ref, qi, ki, cfg):
    """Rebuild the probability block p = exp(s - lse): [bq, bk] f32."""
    s = jax.lax.dot_general(
        q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * cfg.scale
    s = jnp.where(mask_ref[0] != 0, s, _NEG_INF)
    if cfg.causal:
        s = _causal_mask(s, qi, ki, cfg.block_q, cfg.block_k,
                         cfg.q_offset)
    return jnp.exp(s - lse_ref[0][:, :1])


def _bwd_dq_kernel(q_ref, k_ref, v_ref, mask_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, dq_scr, *, cfg: _Config):
    """Grid (bh, q_blocks, k_blocks): accumulate dq for one Q block."""
    qi, ki = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    run = True
    if cfg.causal:
        run = (ki * cfg.block_k
               <= cfg.q_offset + qi * cfg.block_q + cfg.block_q - 1)

    @pl.when(run)
    def _accum():
        p = _recompute_p(q_ref, k_ref, mask_ref, lse_ref, qi, ki, cfg)
        dp = jax.lax.dot_general(
            do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [bq, bk] f32
        ds = p * (dp - delta_ref[0][:, :1]) * cfg.scale
        k = k_ref[0]
        dq_scr[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, mask_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr, *, cfg: _Config):
    """Grid (bh, k_blocks, q_blocks): accumulate dk/dv for one K block.

    All contractions with p/ds run over the sublane (query) dim via
    dot_general, so no transposed score block is ever materialised.
    """
    ki, qi = pl.program_id(1), pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    run = True
    if cfg.causal:
        run = (cfg.q_offset + qi * cfg.block_q + cfg.block_q - 1
               >= ki * cfg.block_k)

    @pl.when(run)
    def _accum():
        p = _recompute_p(q_ref, k_ref, mask_ref, lse_ref, qi, ki, cfg)
        do = do_ref[0]  # [bq, d] storage dtype
        v = v_ref[0]  # [bk, d]
        # dv += p^T @ dO — contract the query dim (sublanes of p).
        dv_scr[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [bq, bk] f32
        ds = (p * (dp - delta_ref[0][:, :1]) * cfg.scale)
        q = q_ref[0]
        # dk += ds^T @ Q — again contracting the query dim.
        dk_scr[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd(cfg: _Config, q, k, v, mask, do, lse, delta):
    """lse/delta: [BH, Lq, LANES] all-lanes-equal."""
    bh, lq, d = q.shape
    lk = k.shape[1]
    bq, bk = cfg.block_q, cfg.block_k
    q_spec = pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0))
    row_spec = pl.BlockSpec((1, bq, _LANES), lambda b, i, j: (b, i, 0))
    k_spec = pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0))
    mask_spec = pl.BlockSpec((1, 1, bk), lambda b, i, j: (b, 0, j))
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, cfg=cfg),
        grid=(bh, lq // bq, lk // bk),
        in_specs=[q_spec, k_spec, k_spec, mask_spec, q_spec, row_spec,
                  row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((bh, lq, d), q.dtype),
        scratch_shapes=[_vmem((bq, d), jnp.float32)],
        interpret=cfg.interpret,
    )(q, k, v, mask, do, lse, delta)

    # dk/dv: K-block-major grid; Q-indexed operands stream over axis 2.
    kq_spec = pl.BlockSpec((1, bq, d), lambda b, j, i: (b, i, 0))
    krow_spec = pl.BlockSpec((1, bq, _LANES), lambda b, j, i: (b, i, 0))
    kk_spec = pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0))
    kmask_spec = pl.BlockSpec((1, 1, bk), lambda b, j, i: (b, 0, j))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, cfg=cfg),
        grid=(bh, lk // bk, lq // bq),
        in_specs=[kq_spec, kk_spec, kk_spec, kmask_spec, kq_spec, krow_spec,
                  krow_spec],
        out_specs=[kk_spec, kk_spec],
        out_shape=[
            jax.ShapeDtypeStruct((bh, lk, d), k.dtype),
            jax.ShapeDtypeStruct((bh, lk, d), v.dtype),
        ],
        scratch_shapes=[
            _vmem((bk, d), jnp.float32),
            _vmem((bk, d), jnp.float32),
        ],
        interpret=cfg.interpret,
    )(q, k, v, mask, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom_vjp wrapper over padded [BH, L, D] arrays
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _flash(cfg: _Config, q, k, v, mask):
    o, _ = _fwd(cfg, q, k, v, mask)
    return o


def _flash_fwd(cfg: _Config, q, k, v, mask):
    o, lse = _fwd(cfg, q, k, v, mask)
    # Residual keeps one lane; bwd re-broadcasts (XLA fuses the broadcast
    # into the pallas input copy).
    return o, (q, k, v, mask, o, lse[:, :, 0])


def _flash_bwd(cfg: _Config, res, do):
    q, k, v, mask, o, lse = res
    # delta_i = rowsum(dO_i * O_i): the softmax-jacobian correction term.
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    lse_b = jnp.broadcast_to(lse[..., None], (*lse.shape, _LANES))
    delta_b = jnp.broadcast_to(delta[..., None], (*delta.shape, _LANES))
    dq, dk, dv = _bwd(cfg, q, k, v, mask, do, lse_b, delta_b)
    return dq, dk, dv, np.zeros(mask.shape, jax.dtypes.float0)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    kv_mask: jax.Array | None = None,
    *,
    causal: bool = False,
    scale: float | None = None,
    block_q: int = 512,
    block_k: int = 512,
    q_offset: int = 0,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused flash attention over [B, L, H, D] tensors.

    kv_mask: optional [B, Lk] bool — False key positions (padding) are
    excluded. interpret=None compiles on a TPU and selects the Pallas
    interpreter only under the explicit-CPU harness (``auto_interpret``).
    Differentiable in q/k/v (blockwise-recomputed backward kernels).
    q_offset (static): global position of query row 0 for the causal
    mask — cached prefill places L queries at [q_offset, q_offset+L)
    against Lk >= L keys at [0, Lk).

    Block sizes default to 512: on real hardware a (bq, bk) program is
    ~bq*bk*d*4 FLOPs against ~microsecond-scale per-program overhead, so
    128-sized blocks leave the MXU idle (measured 7x slower at L=4096 on
    v5e than 512 blocks); short sequences still shrink blocks to the
    padded length.
    """
    b, lq, h, d = q.shape
    lk = k.shape[1]
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    if interpret is None:
        interpret = auto_interpret()

    # Pad: L to block multiples (block shrinks to the padded length for
    # short sequences), D to the 128-lane tile. Padded keys are masked;
    # padded Q rows attend real keys (finite lse, so backward stays
    # NaN-free) and are sliced away. Mosaic requires the K block (the lane
    # dim of the score tile) be 128-aligned unless it spans the whole
    # array, so compiled mode rounds block_k up.
    bq = min(block_q, _ceil_to(lq, 8))
    if interpret:
        bk = min(block_k, _ceil_to(lk, 8))
    else:
        bk = min(_ceil_to(block_k, _LANES), _ceil_to(lk, _LANES))
    lq_p, lk_p, d_p = _ceil_to(lq, bq), _ceil_to(lk, bk), _ceil_to(d, _LANES)

    def fold(t, l_p):  # [B, L, H, D] -> [B*H, L_pad, D_pad]
        t = jnp.pad(t, ((0, 0), (0, l_p - t.shape[1]), (0, 0),
                        (0, d_p - d)))
        return t.transpose(0, 2, 1, 3).reshape(b * h, l_p, t.shape[-1])

    qf, kf, vf = fold(q, lq_p), fold(k, lk_p), fold(v, lk_p)
    if kv_mask is None:
        mask = jnp.ones((b, lk), jnp.int32)
    else:
        mask = kv_mask.astype(jnp.int32)
    mask = jnp.pad(mask, ((0, 0), (0, lk_p - lk)))
    mask = jnp.broadcast_to(mask[:, None, :], (b, h, lk_p)).reshape(
        b * h, 1, lk_p)

    cfg = _Config(scale=float(scale), causal=bool(causal),
                  block_q=bq, block_k=bk, interpret=bool(interpret),
                  q_offset=int(q_offset))
    o = _flash(cfg, qf, kf, vf, mask)
    o = o.reshape(b, h, lq_p, d_p).transpose(0, 2, 1, 3)
    return o[:, :lq, :, :d]
