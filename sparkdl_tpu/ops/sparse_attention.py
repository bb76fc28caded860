"""A learned sparse attention's two stages, as compiled ``jax.numpy``: an
INDEXER that scores every column of a row and keeps the largest, and an
attention in the ABSORBED (latent) form over the kept columns alone.

What calls them is ``models/glm_moe_dsa.py``. A one-token step reads the
kept columns in one of two ways, and :func:`attends_in_place` says which,
from the width of the step's tables alone. While a table is no wider than a
few selections the step makes its selection as a MASK
(:func:`select_mask`) and attends every live block of its rows where the
pool keeps it, under that mask (:func:`attend_in_place`: the paged
one-query kernel of ``ops/paged_decode.py`` over the ``latent`` array
alone): more bytes than the selection's, streamed block by block at several
times the rate at which single columns can be fetched. Past that width it
makes the selection as positions (:func:`pick_columns`), reads those columns
of the pool ONE BY ONE and no others (:func:`selected_columns`:
token-granular, 2,048 columns may lie in 2,048 blocks) and attends them as
they are stored (:func:`absorbed_attention`): what a context of tens of
selections needs, whose blocks are mostly not attended. A step whose tables
are no wider than the selection attends every column of them. A call over
many tokens makes its selection as a mask over its row's columns too, since
gathering each query's own columns would make per-head K and V once a
query. Scores, the selection and the softmax are float32 in every form; a
tie goes to the LOWER position, as a stable ``top_k`` gives it; a column
outside the selection has a weight of exactly zero.

Each stage runs under a ``jax.named_scope`` (``dsa_indexer``,
``dsa_select``, ``dsa_selected_read``, ``dsa_absorbed_attention``,
``dsa_attend_in_place``), which names its operations in the lowered program
and in a trace that keeps metadata.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from sparkdl_tpu.models.kv_pool import layer_rows
from sparkdl_tpu.ops.paged_decode import paged_decode_partial

_NEG_INF = -1e30
#: selections a step's table may be wide for the step to attend in place
#: (:func:`attends_in_place`). Measured on the chip, one layer alone, 32
#: rows of 64 heads over ``latent`` columns of 640, a selection of 2,048
#: (``tools/sparse_attention_probe.py``; PERF.md section 6, PR 45). The
#: kernel costs 3.7 ns a LIVE column (0.34 TB/s: the matrix unit sets its
#: pace, 64 rows against a group's 1,024 x 640 twice) and the bisection
#: 0.19-0.25 ms; the gather costs 1.70 ms a layer WHATEVER the depth and
#: its sort 0.37-0.44 ms (0.77 at 32,768). Five layers of which two
#: select, milliseconds, in place | gathered: rows as deep as
#: ``glm52-sparse-agent-backlog`` draws them (2.7 k a row, the deepest 7.4
#: k) 2.4-2.5 | 9.3-10.1 at every width; EVERY row at the table's whole
#: width 5.4 | 9.3 at 8,192 columns, 10.3 | 9.3 at 16,384, 19.9 | 10.0 at
#: 32,768. The two cross at 470 k live columns, a MEAN depth of 14.7 k
#: over 32 rows: a table of 8 selections (its deepest row 8-16 k) passes
#: that only with nearly every row within a tenth of full, and then loses
#: a tenth; one of 16 is for rows that deep and loses up to half
IN_PLACE_SELECTIONS = 8
#: indexer heads a pass of the scoring: bounds the scores' temporaries of a
#: chunk at 16 k columns (``[256, heads, columns]`` in float32)
INDEX_HEAD_GROUP = 8


def index_scores(q_i: jax.Array, k_i: jax.Array, w: jax.Array) -> jax.Array:
    """``I[b, l, s] = sum_h w[b, l, h] ReLU(q_i[b, l, h] . k_i[b, s])`` in
    float32, :data:`INDEX_HEAD_GROUP` heads a pass. q_i ``[B, L, H, D]``; k_i
    ``[B, W, D]``; w ``[B, L, H]`` float32. Returns ``[B, L, W]``."""
    with jax.named_scope("dsa_indexer"):
        b, l, h, _ = q_i.shape
        g = math.gcd(h, INDEX_HEAD_GROUP)

        def part(q, wt):
            s = jnp.einsum("blhd,bwd->blhw", q, k_i,
                           preferred_element_type=jnp.float32)
            return (jax.nn.relu(s) * wt[..., None]).sum(2)

        if g == h:
            return part(q_i, w)
        q_g = jnp.moveaxis(q_i.reshape(b, l, h // g, g, -1), 2, 0)
        w_g = jnp.moveaxis(w.reshape(b, l, h // g, g), 2, 0)
        total, _ = jax.lax.scan(
            lambda acc, qw: (acc + part(*qw), None),
            jnp.zeros((b, l, k_i.shape[1]), jnp.float32), (q_g, w_g))
        return total


def _ordered_bits(x: jax.Array) -> jax.Array:
    """float32 -> uint32 whose UNSIGNED order is the floats' own (``-inf``
    lowest; ``-0.0`` taken as ``+0.0``, as a comparison takes it)."""
    bits = jax.lax.bitcast_convert_type(
        jnp.where(x == 0, 0.0, x).astype(jnp.float32), jnp.uint32)
    return jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))


def _kth_largest(bits: jax.Array, k: int) -> jax.Array:
    """The ``k``-th largest of each row of ``bits`` ``[..., W]`` (uint32, ``W
    >= k``), ``[..., 1]``: the largest ``t`` that ``k`` values reach, found a
    bit at a time, 32 passes of a compare and a count. A chunk's 256 rows of
    16 k columns are 16 MB a pass where sorting them (what ``top_k`` of 2,048
    compiles to on the chip) is a hundred passes of compare-exchanges."""
    def bit(i, least):
        tried = least | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(
            jnp.uint32)))
        reached = (bits >= tried).sum(-1, keepdims=True) >= k
        return jnp.where(reached, tried, least)

    return jax.lax.fori_loop(
        0, 32, bit, jnp.zeros(bits.shape[:-1] + (1,), jnp.uint32))


def select_mask(scores: jax.Array, k: int) -> jax.Array:
    """For each row of ``scores`` ``[..., W]`` (float32; ``-inf`` where a
    column may not be taken) the mask of its ``k`` largest, ties to the
    LOWER position; every allowed column where there are no more than
    ``k``. The set a stable ``top_k`` takes, as a mask over the columns,
    with no sort: the ``k``-th largest value is found by bisection over the
    scores' bits (:func:`_kth_largest`), the values above it are taken, and
    of those equal to it the first that fit."""
    with jax.named_scope("dsa_select"):
        allowed = scores > -jnp.inf
        if scores.shape[-1] <= k:
            return allowed
        bits = _ordered_bits(scores)
        least = _kth_largest(bits, k)
        above = bits > least
        level = (bits == least) & allowed
        room = k - above.sum(-1, keepdims=True)
        return above | (level & (jnp.cumsum(level, axis=-1) <= room))


def pick_columns(scores: jax.Array, k: int
                 ) -> "tuple[jax.Array, jax.Array]":
    """A step's selection as positions: for each row of ``scores`` ``[S,
    W]`` (float32; ``-inf`` where a column may not be taken) the positions
    of its ``k`` largest, ties to the lower position, and which of them were
    allowed at all (a row with fewer than ``k`` allowed columns takes them
    all). ``(positions [S, k] int32, taken [S, k] bool)``."""
    with jax.named_scope("dsa_select"):
        best, pos = jax.lax.top_k(scores, k)
        return pos, best > -jnp.inf


def absorbed_attention(q_full, old, seen, new, new_seen, scale):
    """One query a row over stored columns as they lie: the absorbed form.
    q_full ``[S, H, Cp]`` (``[q~ | q^rope | 0]``, a stored column's width);
    old ``[S, K, Cp]`` of which row ``s`` attends ``seen[s]`` ``[S, K]``;
    new ``[S, Cp]``, this call's own column, attended where ``new_seen``
    ``[S]``. Float32 scores and softmax. Returns ``sum_s p_s column_s``
    ``[S, H, Cp]`` float32: its first ``kv_lora_rank`` values are ``sum_s p_s
    c^kv_s``."""
    with jax.named_scope("dsa_absorbed_attention"):
        s_old = jnp.einsum("shc,skc->shk", q_full, old,
                           preferred_element_type=jnp.float32) * scale
        s_old = jnp.where(seen[:, None, :], s_old, _NEG_INF)
        s_new = jnp.einsum("shc,sc->sh", q_full, new,
                           preferred_element_type=jnp.float32) * scale
        s_new = jnp.where(new_seen[:, None], s_new, _NEG_INF)
        top = jnp.maximum(s_old.max(-1), s_new)
        e_old, e_new = jnp.exp(s_old - top[..., None]), jnp.exp(s_new - top)
        total = e_old.sum(-1) + e_new
        p_old = (e_old / total[..., None]).astype(old.dtype)
        return (jnp.einsum("shk,skc->shc", p_old, old,
                           preferred_element_type=jnp.float32)
                + (e_new / total)[..., None]
                * new[:, None, :].astype(jnp.float32))


def selected_columns(cache: dict, at: int, picked, idx):
    """The stored ``latent`` columns a step's rows attend, ``(old [S, K,
    Cp], seen [S, K], new_seen [S])``: each row's PICKED positions read one
    by one through the table (``picked`` = ``(positions [S, K], taken [S,
    K])``; the position ``idx[s]`` is this call's own column, which is not in
    the pool yet), or, where nothing was picked (no row's table reaches past
    the selection's size), every column of the rows' tables."""
    table, pool = cache["table"], cache["latent"]
    bs = pool.shape[2]
    if picked is None:
        old, = layer_rows(cache, at, table, pool.dtype, names=("latent",))
        seen = jnp.arange(old.shape[1])[None, :] < idx[:, None]
        return old, seen, jnp.ones(idx.shape, bool)
    with jax.named_scope("dsa_selected_read"):
        pos, taken = picked
        own = pos == idx[:, None]
        blk = jnp.take_along_axis(table, pos // bs, axis=1)
        old = pool[at, jnp.minimum(blk, pool.shape[1] - 1), pos % bs]
        return old, taken & ~own, (taken & own).any(-1)


def attends_in_place(width: int, selection: int) -> bool:
    """Whether a one-token step over tables of ``width`` columns (blocks a
    row x a block's tokens) that attends ``selection`` columns a row makes
    its selection as a MASK and attends every live block where the pool
    keeps it (:func:`attend_in_place`), where it would pick positions and
    read them one by one (THE rule, on shapes alone, asked by the module
    that makes the step and by the engine's count of what a step reads): a
    table wider than the selection and no wider than
    :data:`IN_PLACE_SELECTIONS` of them."""
    return selection < width <= IN_PLACE_SELECTIONS * selection


def attend_in_place(cache: dict, at: int, q_full, mask, idx, new, scale):
    """:func:`absorbed_attention` over the columns ``mask`` holds, with the
    old ones read where the pool keeps them: each row's live blocks of
    ``latent`` through the table, whole, to its own depth
    (``ops/paged_decode.py`` over the one array), every column outside the
    selection under a weight of exactly zero. q_full ``[S, H, Cp]``; mask
    ``[S, W]`` over the table's columns, of which position ``idx[s]`` is
    this call's own column ``new`` ``[S, Cp]`` (not in the pool yet) and
    those before it are stored. Returns ``[S, H, Cp]`` float32."""
    with jax.named_scope("dsa_attend_in_place"):
        table, pool = cache["table"], cache["latent"]
        blocks = pool.shape[1]
        # (a sentinel first marks a row that holds no block: it reads none)
        depth = jnp.where(table[:, 0] < blocks, idx, 0)
        acc, top_old, sum_old = paged_decode_partial(
            q_full, pool, None, at, jnp.minimum(table, blocks - 1), depth,
            bias=jnp.where(mask, 0.0, _NEG_INF), scale=scale)
        own = jnp.arange(mask.shape[1])[None, :] == idx[:, None]
        s_new = jnp.einsum("shc,sc->sh", q_full, new,
                           preferred_element_type=jnp.float32) * scale
        s_new = jnp.where((mask & own).any(-1)[:, None], s_new,
                          _NEG_INF)[..., None]
        top = jnp.maximum(top_old, s_new)
        e_old, e_new = jnp.exp(top_old - top), jnp.exp(s_new - top)
        total = sum_old * e_old + e_new
        return (acc[:, 0] * (e_old / total)
                + (e_new / total) * new[:, None, :].astype(jnp.float32))
