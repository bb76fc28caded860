"""Pallas TPU paged decode: one query a row over K and V read where the
block pool stores them, through the block table, each row to its own depth.

The per-slot cached step gathers every row's ``nb`` blocks out of the pool
(``kv_pool.layer_rows``), writes them out as rows and reads them again in
the products (``gpt.merged_axis_attention``): every row pays for the
deepest row's power-of-two depth, twice. This kernel leaves the pool in
device memory and copies, for row ``s``, the ``ceil(depth[s] / block)``
blocks its table names and no more, a GROUP of blocks a step of its loop
(each block its own copy, the blocks being scattered; the next group's
copies run under this group's products, the next row's first group under
this row's last), and keeps a running maximum, sum and accumulator over
the groups. Nothing is materialised but a row's ``[heads, v_head_dim]``
result.

Which pools it takes is :func:`reads_in_place`'s to say, a rule on shapes
alone, asked with K's and V's trailing shapes: each array keeps a token's
heads side by side on ONE unpadded axis whose width is a whole count of lane
tiles, so that a block ``pool[layer, b]`` is one contiguous piece of bytes.
K and V need not be alike: a key head may be wider than a value head
(MiMo-V2-Flash: keys of 192 on an axis of 768 over values of 128 on one of
512), NEITHER head need be whole tiles (LFM2's 8 x 64 on an axis of 512,
K and V alike), and a GROUP of query heads may share each K/V head (64
over 4). A group's scores and weighted sum are TWO products over the merged
axes, against the query laid block-diagonally OUTSIDE the kernel (row ``h``
holds head ``h``'s query in the columns of its own K/V head ``g(h) = h //
(H // G)`` and zeros elsewhere, so other heads add exact zeros; a boundary
at 192 is a mask in ``jnp`` and never a slice of a tile in here), of which
head ``h`` keeps K/V head ``g(h)``'s columns at the row's end: a column
slice a K/V head where a value head is whole tiles, and where it is not
(a slice of 64 would cut a tile) a select by lane over whole rows of V's
axis. With as many
K/V heads as query heads and one head size (Olmo-Hybrid's 30 x 128) that
is a diagonal of single rows: the special case, not a second path. The
matrix unit's time is the loading of K's and V's tiles, the same whether
one row streams against a tile or sixty-four; one small product a head
reads the same milliseconds on the chip (both forms run at the speed of
the copies alone, PERF.md section 6, PR 35) and is sixty products and sixty
single-row stores a group to trace and lower, at every start of every
program that holds the kernel.

It also takes a LATENT pool: ONE array that is keys and values both (GLM's
``[c^kv | k^r | 0]`` of 640 a token under 64 absorbed queries: one K/V head
as wide as the axis). No V array is passed: a block is copied once and both
products read that copy. With it come a per-row BIAS over the table's
columns, added to every head's scores beside the depth's mask (a learned
selection as a mask: 0 where a column is attended, ``-1e30`` where not),
and the caller's SCALE (the stored width is not the width the model scales
by). All three are resolved while the kernel is traced
(:func:`paged_decode_partial`; ``ops/sparse_attention.attend_in_place`` is
what calls it so): a call with K and V, no bias and no scale lowers to what
it did before the kernel knew them.

Same precisions as ``merged_axis_attention``: operands as stored, float32
scores, maximum, sum and accumulator, the weights cast to the operands'
dtype before the product with V. This call's own column is NOT the
kernel's: it hands back ``(acc, max, sum)`` of the old columns and
:func:`paged_decode_attention` joins the new one to them.

Inference-only: no custom VJP (decode never backprops).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from sparkdl_tpu.ops._pallas import auto_interpret
from sparkdl_tpu.ops._pallas import smem as _smem
from sparkdl_tpu.ops._pallas import vmem as _vmem

_NEG_INF = -1e30
#: lanes of a TPU vector tile (``kv_pool.LANE_TILE``; this module imports
#: nothing of the models)
_LANE_TILE = 128
#: what a step of the kernel's loop moves of the WIDER of K and V (and of
#: the other, as many tokens): a block's copy is a few tenths of a
#: microsecond of bandwidth and a step's overhead as much, so a step moves
#: a group of blocks of about this many bytes. Two buffers of each array
#: are then 8 MiB at most, which with a group's scores fits the 16 MiB of
#: faster memory a kernel gets when it asks for none: the kernel asks for
#: none, because a call that NAMES its limit keeps the chip's compiler from
#: holding anything else there across it (MiMo-V2-Flash's step: each
#: layer's 96 MiB query kernel in the other order, 0.31 ms a layer out of
#: and into device memory where it is 0.15 held there; PERF.md section 6,
#: PR 37)
_GROUP_BYTES = 2 << 20


def reads_in_place(k_tail: "tuple[int, ...]", v_tail: "tuple[int, ...]",
                   kv_heads: int, head_dim: int,
                   v_head_dim: "int | None" = None) -> bool:
    """Whether the paged decode attention reads a pool whose K and V have
    these trailing shapes in place (THE rule, asked by the module that calls
    the kernel and by the engine's count of what a step reads): each array's
    ``kv_heads`` heads side by side on one axis with no pad, and that axis
    a whole count of lane tiles. Olmo-Hybrid's 30 x 128 is, MiMo-V2-Flash's
    4 x 192 over 4 x 128, and LFM2's 8 x 64 on 512 (a head of either array
    may be under a tile if its axis is whole tiles); GPT-2 XL's 25 x 64
    padded to 1664, a per-head pool ``(4, 128)`` and an axis of 32 are not,
    and keep ``layer_rows`` and their own attention. How many query heads
    share a K/V head is not the rule's."""
    v_head_dim = head_dim if v_head_dim is None else v_head_dim
    return (tuple(k_tail) == (kv_heads * head_dim,)
            and tuple(v_tail) == (kv_heads * v_head_dim,)
            and k_tail[0] % _LANE_TILE == 0
            and v_tail[0] % _LANE_TILE == 0)


def _group_blocks(nb: int, block_bytes: int) -> int:
    """Blocks a step of the loop copies: the power of two whose bytes (of
    the wider of K and V) come nearest under :data:`_GROUP_BYTES`, and no
    more than the table holds."""
    g = max(1, _GROUP_BYTES // block_bytes)
    return min(1 << (g.bit_length() - 1), nb)


def _kernel(table_ref, depth_ref, qbd_ref, *refs,
            layer: int, group: int, bs: int, heads: int, kv_heads: int,
            head_dim: int, v_head_dim: int, arrays: int, biased: bool,
            scale: "float | None"):
    """``refs``: the row's bias if ``biased``, the pool's ``arrays`` (K and
    V, or ONE array that is both), the three results, a pair of buffers an
    array and the scratch. All three parameters are resolved while tracing:
    a call with K and V, no bias and no scale traces what it always did."""
    from jax.experimental.pallas import tpu as pltpu

    refs = list(refs)
    bias_ref = refs.pop(0) if biased else None
    pool, (o_ref, m_ref, l_ref) = refs[:arrays], refs[arrays:arrays + 3]
    bufs = refs[arrays + 3:2 * arrays + 3]
    sems, first, acc_scr, m_scr, l_scr = refs[2 * arrays + 3:]
    # one array: a block's copy is the keys of the scores AND the values
    # of the weighted sum
    kbuf, vbuf = bufs[0], bufs[-1]
    s = pl.program_id(0)
    rows = pl.num_programs(0)
    span = group * bs                     # tokens a group holds

    def blocks_of(row):
        return pl.cdiv(depth_ref[row], bs)

    def copies(row, grp, slot, act):
        """``start`` or ``wait`` for the copies of group ``grp`` of ``row``
        into buffer ``slot``: the blocks of the group that lie inside the
        row's depth, each from where the table says the pool keeps it."""
        n = jnp.minimum(blocks_of(row) - grp * group, group)

        def one(j, _):
            blk = table_ref[row, grp * group + j]
            at = pl.ds(pl.multiple_of(j * bs, bs), bs)
            for sem, (hbm, buf) in enumerate(zip(pool, bufs)):
                getattr(pltpu.make_async_copy(
                    hbm.at[layer, blk], buf.at[slot, at],
                    sems.at[sem, slot]), act)()
            return _

        jax.lax.fori_loop(0, n, one, 0)

    @pl.when(s == 0)
    def _first_row():
        # what a partial group leaves of a buffer is multiplied by weights
        # of exactly zero: it must be finite, so V's starts as zeros (later
        # it holds blocks of the pool, which are)
        vbuf[...] = jnp.zeros_like(vbuf)
        first[0] = 0      # the buffer this row's first group goes into
        first[1] = 0      # whether the row before started its copies

    groups = pl.cdiv(blocks_of(s), group)
    nxt = jnp.minimum(s + 1, rows - 1)
    hand_on = (s + 1 < rows) & (depth_ref[nxt] > 0)
    slot0, primed = first[0], first[1]

    @pl.when((groups > 0) & (primed == 0))
    def _cold():
        copies(s, 0, slot0, "start")

    m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)
    depth = depth_ref[s]

    def step(g, _):
        slot = (slot0 + g) % 2

        last = g + 1 == groups

        @pl.when(jnp.logical_not(last) | hand_on)
        def _ahead():
            # under this group's products: the row's next group, or after
            # its last the first group of the row that follows
            copies(jnp.where(last, nxt, s), jnp.where(last, 0, g + 1),
                   1 - slot, "start")

        copies(s, g, slot, "wait")
        x = jax.lax.dot_general(
            qbd_ref[0], kbuf[slot], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        x = x / math.sqrt(head_dim) if scale is None else x * scale
        if biased:
            # the row's own bias over this group's columns, every head's
            x = x + bias_ref[0, :, pl.ds(pl.multiple_of(g * span, span), span)]
        pos = g * span + jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
        x = jnp.where(pos < depth, x, _NEG_INF)
        m_prev = m_scr[...]
        m_next = jnp.maximum(m_prev, x.max(axis=-1, keepdims=True))
        corr = jnp.exp(m_prev - m_next)
        p = jnp.exp(x - m_next)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=-1, keepdims=True)
        m_scr[...] = m_next
        acc_scr[...] = acc_scr[...] * corr + jnp.dot(
            p.astype(vbuf.dtype), vbuf[slot],
            preferred_element_type=jnp.float32)
        return _

    jax.lax.fori_loop(0, groups, step, 0)

    first[0] = (slot0 + groups) % 2
    first[1] = ((groups > 0) & hand_on).astype(jnp.int32)
    # the query heads of K/V head g keep g's columns of their rows of the
    # merged accumulator: the diagonal blocks, side by side
    share = heads // kv_heads
    if v_head_dim % _LANE_TILE == 0:
        for g in range(kv_heads):
            cols = slice(g * v_head_dim, (g + 1) * v_head_dim)
            o_ref[0, :, cols] = acc_scr[g * share:(g + 1) * share, cols]
    else:
        # a value head under a lane tile (8 x 64 on an axis of 512): a
        # column slice would cut a tile, so the diagonal is taken by whole
        # rows of the axis: from K/V head g's first lane on, g's rows take
        # the place of the heads' before it
        lane = jax.lax.broadcasted_iota(jnp.int32, o_ref.shape[1:], 1)
        out = acc_scr[:share]
        for g in range(1, kv_heads):
            out = jnp.where(lane >= g * v_head_dim,
                            acc_scr[g * share:(g + 1) * share], out)
        o_ref[0] = out
    m_ref[0] = m_scr[:heads]
    l_ref[0] = l_scr[:heads]


def _block_diagonal(q, kv_heads: int, rows: int):
    """The query laid block-diagonally on K's merged axis: ``out[s, h, g(h)
    * Dk + d] = q[s, h, d]`` for head ``h``'s own K/V head ``g(h) = h // (H
    // kv_heads)`` and zero elsewhere, on ``rows >= H`` rows (the rest are
    zeros). ``q`` ``[S, H, Dk]`` -> ``[S, rows, kv_heads * Dk]``."""
    _, heads, head_dim = q.shape
    q = jnp.pad(q, ((0, 0), (0, rows - heads), (0, 0)))
    if kv_heads == 1:
        return q        # one K/V head: every column is every row's own
    own = (jnp.arange(kv_heads * head_dim)[None, :] // head_dim
           == (jnp.arange(rows) // (heads // kv_heads))[:, None])
    return jnp.where(own, jnp.tile(q, (1, 1, kv_heads)), 0)


def paged_decode_partial(q, k_pool, v_pool, layer: int, table, depth, *,
                         bias=None, scale: "float | None" = None):
    """Attention of one query a row over the OLD columns of a paged pool.

    ``q`` ``[S, H, Dk]``; ``k_pool`` ``[layers, blocks, block, G*Dk]`` and
    ``v_pool`` ``[layers, blocks, block, G*Dv]``, the pool's arrays WHOLE
    (:func:`reads_in_place`; ``G`` divides ``H``, query heads ``g * H/G ..``
    share K/V head ``g``), of which the kernel reads layer ``layer``
    (static); ``table`` ``[S, nb]`` int32, each row's blocks in order, every
    entry inside the pool; ``depth`` ``[S]`` int32, the columns row ``s``
    sees: it fetches ``ceil(depth[s] / block)`` blocks and a row of depth 0
    none. Returns float32 ``(acc [S, G, H/G, Dv], m [S, H, 1], l [S, H,
    1])``: the unnormalised weighted sum of V, the running maximum of the
    scores and the sum of the weights under it (``acc = 0``, ``m = -1e30``,
    ``l = 0`` for a row of depth 0).

    ``v_pool`` None: ``k_pool`` is the values too (a LATENT pool: one
    column a token that the scores and the weighted sum both read, GLM's
    ``[c^kv | k^r | 0]`` of 640 under 64 absorbed queries). Each block is
    copied ONCE and both products run over that copy. ``bias`` ``[S, nb *
    block]`` float32, added to every head's scores of row ``s`` beside the
    depth's mask (0 where a column is attended, ``-1e30`` where it is not:
    a selection as a mask; a group none of whose columns is attended leaves
    a sum that the next attended column's maximum multiplies by exactly
    zero). ``scale`` multiplies the scores where it is not one over the
    root of ``Dk`` (a stored column's width is not the width the model
    scales by).
    """
    from jax.experimental.pallas import tpu as pltpu

    rows, heads, head_dim = q.shape
    bs, k_width = k_pool.shape[2:]
    pools = (k_pool,) if v_pool is None else (k_pool, v_pool)
    v_width = pools[-1].shape[-1]
    kv_heads = k_width // head_dim
    v_head_dim = v_width // max(kv_heads, 1)
    if (kv_heads < 1 or heads % kv_heads or not reads_in_place(
            k_pool.shape[3:], pools[-1].shape[3:], kv_heads, head_dim,
            v_head_dim)):
        raise ValueError(
            f"the paged decode kernel reads K and V of whole lane tiles, "
            f"heads of {head_dim} side by side under {heads} query heads, "
            f"not {' and '.join(str(a.shape) for a in pools)}")
    if any(a.dtype != q.dtype for a in pools):
        raise ValueError(
            f"the paged decode kernel takes K and V as the query's dtype "
            f"{q.dtype} with no scales, not "
            f"{' and '.join(str(a.dtype) for a in pools)}")
    group = _group_blocks(
        table.shape[1],
        bs * max(k_width, v_width) * k_pool.dtype.itemsize)
    span = group * bs
    share = heads // kv_heads
    hp = -(-heads // 16) * 16       # whole sublane tiles of rows
    qbd = _block_diagonal(q, kv_heads, hp)
    operands, in_specs = [qbd], [
        pl.BlockSpec((1, hp, k_width), lambda s, *_: (s, 0, 0))]
    if bias is not None:
        # whole groups of columns: the loop reads a group's span of it
        bias = jnp.pad(bias.astype(jnp.float32),
                       ((0, 0), (0, -bias.shape[1] % span)),
                       constant_values=_NEG_INF)[:, None, :]
        operands.append(bias)
        in_specs.append(pl.BlockSpec((1, 1, bias.shape[2]),
                                     lambda s, *_: (s, 0, 0)))
    in_specs += [pl.BlockSpec(memory_space=pl.ANY)] * len(pools)
    acc, m, l = pl.pallas_call(
        functools.partial(_kernel, layer=layer, group=group, bs=bs,
                          heads=heads, kv_heads=kv_heads, head_dim=head_dim,
                          v_head_dim=v_head_dim, arrays=len(pools),
                          biased=bias is not None, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(rows,),
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((1, share, v_width), lambda s, *_: (s, 0, 0)),
                pl.BlockSpec((1, heads, 1), lambda s, *_: (s, 0, 0)),
                pl.BlockSpec((1, heads, 1), lambda s, *_: (s, 0, 0)),
            ],
            scratch_shapes=[
                *(_vmem((2, span, a.shape[-1]), a.dtype) for a in pools),
                pltpu.SemaphoreType.DMA((len(pools), 2)),
                _smem((2,), jnp.int32),
                _vmem((hp, v_width), jnp.float32),
                _vmem((hp, 1), jnp.float32),
                _vmem((hp, 1), jnp.float32),
            ]),
        out_shape=[
            # head g * H/G + i's columns are [s, i, g*Dv : (g+1)*Dv]
            jax.ShapeDtypeStruct((rows, share, v_width), jnp.float32),
            jax.ShapeDtypeStruct((rows, heads, 1), jnp.float32),
            jax.ShapeDtypeStruct((rows, heads, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=auto_interpret(),
        name="paged_decode",
    )(table.astype(jnp.int32), depth.astype(jnp.int32), *operands, *pools)
    acc = acc.reshape(rows, share, kv_heads, v_head_dim).swapaxes(1, 2)
    return acc, m, l


def paged_decode_attention(q, k_pool, v_pool, layer: int, table, idx,
                           k_new, v_new):
    """The per-slot cached step's attention over a pool that
    :func:`reads_in_place`: what ``merged_axis_attention`` (or, for grouped
    heads of unequal size, ``mimo_v2_flash.merged_sink_attention`` with no
    sink) over ``layer_rows`` computes, with the old columns read in the
    pool.

    ``q`` ``[S, 1, H, Dk]``, one query a row; the pool, ``layer``, and
    ``table`` ``[S, nb]`` as a family's paged cache holds them (a sentinel
    entry marks a row that holds no block, which reads nothing); ``idx``
    ``[S]``, the old columns each row sees; ``k_new`` ``[S, 1, G*Dk]`` and
    ``v_new`` ``[S, 1, G*Dv]``, this call's own column, which joins the
    softmax beside the old ones and is written nowhere. Returns ``[S, 1,
    H*Dv]``, the heads side by side as the output projection takes them.
    """
    rows, width, heads, head_dim = q.shape
    if width != 1:
        raise ValueError(
            f"the paged decode kernel takes one query a row, not {width}")
    blocks = k_pool.shape[1]
    depth = jnp.where(table[:, 0] < blocks, idx, 0)
    acc, m, l = paged_decode_partial(
        q[:, 0], k_pool, v_pool, layer, jnp.minimum(table, blocks - 1),
        depth)
    kv_heads = acc.shape[1]
    # by K/V head and the query heads that share it: [S, G, H/G, ..]
    by_group = (rows, kv_heads, heads // kv_heads, -1)
    m, l = m.reshape(by_group), l.reshape(by_group)
    x_new = jnp.einsum(
        "sgrd,sgd->sgr", q.reshape(by_group),
        k_new.reshape(rows, kv_heads, head_dim),
        preferred_element_type=jnp.float32)[..., None] / math.sqrt(head_dim)
    top = jnp.maximum(m, x_new)
    e_old, e_new = jnp.exp(m - top), jnp.exp(x_new - top)
    total = l * e_old + e_new
    p_new = (e_new / total).astype(q.dtype).astype(jnp.float32)
    v_new = v_new.reshape(rows, kv_heads, 1, -1).astype(jnp.float32)
    out = acc * (e_old / total) + p_new * v_new
    return out.astype(q.dtype).reshape(rows, 1, -1)
