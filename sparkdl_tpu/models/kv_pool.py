"""How a token's K and V lie in the device pool: the ONE module that knows.

A block pool is a ``dict`` of arrays by name, ``k`` and ``v``
``[layers, blocks, block_size, *tail]`` in a storage dtype and, where that
dtype is int8, ``k_scale`` / ``v_scale`` ``[layers, blocks, block_size]``
(one fp32 scale per written column). K and V may DIFFER in their tail (a
family whose value heads are narrower than its key heads,
:func:`kv_tails`): every read and write below takes each array's tail off
that array, never V's off ``pool["k"]``. This module owns every decision
about that format: the trailing axes (:func:`kv_tail`), the storage dtypes and
their scales (:data:`KV_DTYPES`, :func:`quantize_kv`), the sentinel block id
(``blocks``, one past the last) that clips on a read and drops on a write,
and the forms of a read through a table, a block write and a column write
that the chip's compiler answers IN PLACE, not with a copy of the pool.

A family may keep, beside its blocks, arrays indexed by SLOT
(``[state_layers, n_slots, ...]``, as ``ServingFamily.state_arrays`` names
and shapes them), which hold either a recurrent state
(``models/olmo_hybrid.py``: linear-attention layers) or a window layer's
last ``window`` columns of K and V as a ring (``models/mimo_v2_flash.py``):
what a layer keeps whatever the context's length. They ride the same
donated dict through every program, and the rule is that what reads or
writes BLOCKS leaves them alone. :func:`block_arrays` is the pool without
them; the reads below gather from it, the write loops carry it alone and
hand the slot arrays back as they came. ``layers`` of ``k`` and ``v`` are
the layers that keep K/V, which for such a family are not all of them.

A family may also NAME the two arrays it keeps by block
(``ServingFamily.block_arrays``: ``models/glm_moe_dsa.py`` keeps ``latent``
in every layer and ``index_k`` in some, where another keeps ``k`` and ``v``
over the same layers): each array then has its own count of layers and its
own trailing axes, and everything below takes an array's layers, like its
tail, off that array. The names are the family's alone: the functions here
that must tell the arrays by block from those by slot take them as
``names`` (:data:`KV` where a caller gives none), in the order the programs
carry the pair (``PagedSizes.arrays``, which the engine fills from
``ServingFamily.pool_arrays``).

Every model family (``models/gpt.py``, ``models/afmoe.py``,
``models/olmo_hybrid.py``, ``models/mimo_v2_flash.py``) reads a layer's
rows through :func:`layer_rows`; the serving engine's programs
(``serving/paged_programs.py``) read and write whole blocks and columns
through the functions below it. Pure functions of a pool and indices: what
the engine's closures once took from their enclosing scope is read off the
pool itself (an array's layers and tail are its own ``shape[0]`` and
``shape[3:]``, the blocks are :func:`n_blocks`, an int8 array is one that
has a ``<name>_scale`` beside it).
Nothing here imports the rest of the package; the host's bookkeeping (free
list, refcounts, tables) is :mod:`~sparkdl_tpu.serving.kv_blocks`'s.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

#: lanes of a TPU vector tile: a minor axis that is no multiple of it is
#: padded to one by the chip, or loses the minor place to an axis that is
LANE_TILE = 128
#: sublanes of one: the axis before the minor one is stored in rows of it
SUBLANE_TILE = 8

#: Supported pool storage layouts: "fp32" stores at the model's compute
#: dtype (exact, the default), "bf16"/"int8" compress the resident pool
#: (compute still runs at the model dtype; see :func:`quantize_kv`).
KV_DTYPES = ("fp32", "bf16", "int8")


# -- the trailing axes --------------------------------------------------------

def kv_tail(kv_heads: int, head_dim: int) -> "tuple[int, ...]":
    """The trailing axes of a token's K (or V) in a block pool, chosen from
    the heads alone. Heads keep their own axis, ``(kv_heads, head_dim)``,
    where the chip then keeps the pool row-major: a head that fills whole
    lane tiles, and a count of heads that fills whole sublane tiles (a
    multiple of 8) or is one of the small tiles the chip has (2, 4). Any
    other (GPT-2's head of 64; 30 heads of 128, which the chip would store
    with the block's 16 tokens in the sublanes and the heads outside them,
    and copy the whole pool to the other order and back around every
    program that writes a column) would leave the chip no minor axes that
    tile as stored: its heads are stored side by side on ONE axis,
    ``(kv_heads * head_dim,)`` with zero columns up to the next whole tile
    (1600 -> 1664: unpadded, the chip takes the block axis for its lanes).
    A family's module takes the pool in either shape.
    ``tests/serving/test_paged_step_chip_compile.py`` reads both rules off
    a described v5e."""
    if head_dim % LANE_TILE == 0 and (kv_heads % SUBLANE_TILE == 0
                                      or kv_heads in (2, 4)):
        return (kv_heads, head_dim)
    return _merged_tail(kv_heads, head_dim)


def _merged_tail(kv_heads: int, head_dim: int) -> "tuple[int]":
    """Heads side by side on ONE axis, padded to whole lane tiles."""
    return (-(-kv_heads * head_dim // LANE_TILE) * LANE_TILE,)


def kv_tails(kv_heads: int, head_dim: int, v_head_dim: "int | None" = None
             ) -> "tuple[tuple[int, ...], tuple[int, ...]]":
    """``(K's tail, V's tail)`` of a family whose value heads are
    ``v_head_dim`` wide (None: as wide as its key heads, and both tails are
    :func:`kv_tail`'s). Each is :func:`kv_tail` of its own head size,
    unless the two would then differ in their NUMBER of axes (4 key heads
    of 192 go on one axis of 768, 4 value heads of 128 could keep two): V
    then goes on one merged axis too (512: whole lane tiles, no pad), so
    that one form of every write and of the products over the rows serves
    both arrays of a pool. The described v5e keeps both row-major and
    writes both in place (``tests/serving/test_paged_step_chip_compile.py``)."""
    k_tail = kv_tail(kv_heads, head_dim)
    if v_head_dim is None or v_head_dim == head_dim:
        return k_tail, k_tail
    v_tail = kv_tail(kv_heads, v_head_dim)
    if len(v_tail) != len(k_tail):
        return (_merged_tail(kv_heads, head_dim),
                _merged_tail(kv_heads, v_head_dim))
    return k_tail, v_tail


def kv_stored(x, tail: "tuple[int, ...]"):
    """K or V ``[..., kv_heads, head_dim]`` in a pool's trailing shape
    ``[..., *tail]`` (storage only: the pad of a merged axis is zeros)."""
    if len(tail) == 2:
        return x
    x = x.reshape(*x.shape[:-2], -1)
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, tail[0] - x.shape[-1])])


def kv_per_head(x, kv_heads: int, head_dim: int):
    """The inverse of :func:`kv_stored`: ``[..., kv_heads, head_dim]`` of
    what a pool stores, whichever trailing shape it keeps."""
    if x.shape[-2:] == (kv_heads, head_dim):
        return x
    return x[..., :kv_heads * head_dim].reshape(
        *x.shape[:-1], kv_heads, head_dim)


# -- the pool, and its storage dtypes -----------------------------------------

def init_block_pool(config, n_blocks: int,
                    block_size: int, dtype: str = "fp32",
                    n_slots: "int | None" = None) -> dict:
    """Zeroed block-paged KV pool for continuous serving
    (``serving.kv_blocks``): k/v stacked over the layers that keep K/V,
    ``[pool_layers, n_blocks, block_size, *tail]``, the trailing axes
    :func:`kv_tails` of the family's heads (``config.serving_family()``; V's
    are its own where a value head is not a key head's size), so
    that the chip keeps layers and blocks major and a block's bytes
    together (GPT-2 XL: ``{3,2,1,0:T(8,128)(2,1)}``). A family that names
    its own two arrays (``ServingFamily.block_arrays``) gets those, each
    over its own layers.

    Unlike ``models.gpt.init_cache`` (one dense row per batch slot), the
    pool's capacity is ``n_blocks x block_size`` TOKENS shared by every
    slot through a block table; which blocks are free, shared or cached is
    :class:`~sparkdl_tpu.serving.kv_blocks.KVBlockPool`'s to say.

    ``dtype`` picks the STORAGE layout (:data:`KV_DTYPES`):

    - ``"fp32"`` — store at the model's compute dtype (``config.dtype``),
      the exact layout; gather/scatter are plain copies.
    - ``"bf16"`` — store bfloat16, dequantize to the compute dtype on
      gather: half the pool bytes per token.
    - ``"int8"`` — store int8 with one fp32 scale per written COLUMN
      (``k_scale``/``v_scale``, ``[num_layers, n_blocks, block_size]``,
      riding the block structure): ~4x fewer pool bytes per token, by the
      rule of :func:`quantize_kv` / :func:`dequantize_kv`. Compute always
      runs at ``config.dtype``; only the resident pool is compressed.

    A family with state layers also gets its arrays by slot, ``[state_layers,
    n_slots, *shape]`` each in its own dtype, zeroed (``n_slots`` is then
    required): never compressed, never indexed by block.
    """
    fam = config.serving_family()
    store = {"fp32": fam.dtype, "bf16": jnp.bfloat16,
             "int8": jnp.int8}.get(dtype)
    if store is None:
        raise ValueError(
            f"unknown KV pool dtype {dtype!r} ({' | '.join(KV_DTYPES)})")
    pool = {}
    for name, layers, tail in fam.pool_arrays:
        pool[name] = jnp.zeros((layers, n_blocks, block_size) + tail, store)
        if dtype == "int8":
            pool[name + "_scale"] = jnp.zeros(
                (layers, n_blocks, block_size), jnp.float32)
    if fam.state_layers:
        if n_slots is None:
            raise ValueError(
                "a family with state layers keeps its state by slot: "
                "init_block_pool needs n_slots")
        for name, tail, store in fam.state_arrays:
            pool[name] = jnp.zeros((fam.state_layers, n_slots) + tail, store)
    return pool


#: the pair of arrays a token keeps BY BLOCK where the family names none
#: (``ServingFamily.block_arrays``)
KV = ("k", "v")


def n_blocks(pool: dict, names: "tuple[str, ...]" = KV) -> int:
    """Blocks of the pool, which is also its sentinel block id."""
    return pool[names[0]].shape[1]


def block_arrays(pool: dict, names: "tuple[str, ...]" = KV) -> dict:
    """The pool's arrays that are indexed by BLOCK (``names`` and their
    scales): all of it but a recurrent family's arrays by slot, which have
    another second axis and which no block-wise read or write touches."""
    by_block = {n for name in names for n in (name, name + "_scale")}
    return {name: a for name, a in pool.items() if name in by_block}


def slot_arrays(pool: dict, names: "tuple[str, ...]" = KV) -> dict:
    """The pool's arrays indexed by SLOT: a recurrent state, or a window
    layer's ring of columns (whatever is neither of ``names`` nor a scale
    of theirs)."""
    by_block = block_arrays(pool, names)
    return {name: a for name, a in pool.items() if name not in by_block}


def install_slot(pool: dict, slot: jax.Array, rows: dict) -> dict:
    """One sequence's state ``rows`` (by array name, ``[state_layers, 1,
    ...]``: a prefill's running state at its last real token) into row
    ``slot`` of the DONATED pool's arrays by slot, in place."""
    return {**pool, **{
        name: lax.dynamic_update_slice_in_dim(
            pool[name], vals.astype(pool[name].dtype), slot, axis=1)
        for name, vals in rows.items()}}


def quantize_kv(x: jax.Array,
                tail: int = 1) -> "tuple[jax.Array, jax.Array]":
    """Symmetric per-column int8 quantization of K/V columns.

    ``x`` is ``[..., C]``, a token's K or V on the pool's one merged axis
    (any leading index shape; ``tail`` trailing axes make a column where a
    pool keeps more than one, ``[..., H, D]``); returns ``(int8 values,
    fp32 scales[...])`` with one scale per column — the absmax maps to
    ±127, so requantize(dequantize(q, s)) == (q, s) exactly (the property
    that makes copy-on-write prefix sharing lossless under int8: a
    gathered-then-reinstalled block is bit-identical to its donor). Zero
    columns get a tiny floor scale and quantize to zero; the merged axis's
    zero pad stays zero.
    """
    axes = tuple(range(-tail, 0))
    amax = jnp.max(jnp.abs(x), axis=axes)
    scale = (jnp.maximum(amax, 1e-30) / 127.0).astype(jnp.float32)
    q = jnp.round(x.astype(jnp.float32) / jnp.expand_dims(scale, axes))
    return jnp.clip(q, -127, 127).astype(jnp.int8), scale


def dequantize_kv(q: jax.Array, scale: jax.Array,
                  dtype: Any = jnp.float32) -> jax.Array:
    """Inverse of :func:`quantize_kv`: int8 ``[..., C]`` columns (or
    ``[..., H, D]``: the axes the scales lack) and their per-column scales
    back to ``dtype``."""
    axes = tuple(range(scale.ndim - q.ndim, 0))
    return (q.astype(jnp.float32)
            * jnp.expand_dims(scale, axes)).astype(dtype)


def stored_as(pool: dict, name: str, vals: jax.Array) -> dict:
    """THE quantize-on-write rule, the one every pool write applies (so
    column writes and installs can never desynchronize): what K/V values
    (compute dtype, the pool's trailing axes) become in the pool, by array
    name. int8 stores values + their per-column scales; bf16/fp32 a cast."""
    if name + "_scale" in pool:
        q, s = quantize_kv(vals, pool[name].ndim - 3)
        return {name: q, name + "_scale": s}
    return {name: vals.astype(pool[name].dtype)}


# -- the one read through a table ---------------------------------------------

def layer_rows(cache: dict, layer: int, table: jax.Array, dtype: Any,
               names: "tuple[str, ...]" = ("k", "v")
               ) -> "tuple[jax.Array, ...]":
    """One layer's K and V of a pool (or the arrays ``names``, ``layer``
    being the row among each one's own layers) as per-slot rows ``[S,
    entries * block_size, *tail]``, in the shape they are stored in, through
    the table entries ``table`` ``[S, entries]`` (the live head of the block
    table, or a window layer's sub-table).

    ``cache`` holds the pool's arrays by name (a family's paged cache is
    the pool plus ``table`` and ``idx``). ONE gather over (layer, block),
    ``pool[layer, table]`` -> ``[S, entries, block_size, *tail]``, read
    where the pool lies: no layer's slab is sliced out first (a copy of
    that share of the pool a layer, and the pool would lose the layout it
    is stored in), then rows by merging major axes, which moves nothing;
    that slice alone is dequantized to ``dtype`` (the rule of
    :func:`dequantize_kv`; a bf16 pool is cast). Entries past the pool (the
    table's sentinel) clip to the layer's last block, whose columns the
    caller's masks hide.
    """
    at = (jnp.full_like(table, layer),
          jnp.minimum(table, cache[names[0]].shape[1] - 1))

    def rows(name):
        x = cache[name][at]
        scale = cache.get(name + "_scale")
        x = (x.astype(dtype) if scale is None
             else dequantize_kv(x, scale[at], dtype))
        return x.reshape(table.shape[0], table.shape[1] * x.shape[2],
                         *x.shape[3:])

    return tuple(rows(name) for name in names)


# -- whole blocks, read and written -------------------------------------------

def gather_blocks(pool: dict, ids: jax.Array,
                  names: "tuple[str, ...]" = KV) -> dict:
    """Every array's blocks ``ids`` in storage dtype, ``[layers, len(ids),
    block, ...]``: ONE gather over (layer, block), read where the pool lies
    (sliced by layer first, the compiler copies the pool to another layout:
    1.4 GB of temporaries in a one-chunk prefill at 2.7 GB). A sentinel id
    clips to the last block."""
    # (one index a count of layers: a pool whose arrays all span the same
    # layers lowers to the text it lowered to)
    arrays = block_arrays(pool, names)
    layers = {n: jnp.arange(n)[:, None] for n in dict.fromkeys(
        a.shape[0] for a in arrays.values())}
    blk = jnp.minimum(ids, n_blocks(pool, names) - 1)[None, :]
    return {name: a[layers[a.shape[0]], blk] for name, a in arrays.items()}


def gather_blocks_as(pool: dict, ids: jax.Array, dtype: Any,
                     names: "tuple[str, ...]" = KV
                     ) -> "tuple[jax.Array, jax.Array]":
    """Blocks ``ids`` of K and V (of ``names``, in their order) -> the
    compute dtype ``dtype``."""
    raw = gather_blocks(pool, ids, names)
    return tuple(
        dequantize_kv(raw[name], raw[name + "_scale"], dtype)
        if name + "_scale" in pool else raw[name].astype(dtype)
        for name in names)


def write_blocks(pool: dict, ids: jax.Array, vals: dict) -> dict:
    """Whole blocks ``vals`` (by array name, storage dtype, ``[layers,
    len(ids), block, ...]``) into the DONATED pool, one at a time and in
    place; ``vals`` names every array the pool keeps by block (the loop
    carries those and nothing by slot). As ONE scatter the compiler re-lays
    the pool out and back (two copies of each of K and V an install: 16 ms
    and 1.4 GB of temporaries at 2.7 GB). A sentinel id rewrites what is
    there — no block corrupted."""
    blocks = n_blocks(pool, tuple(vals))
    live = ids < blocks
    blk = jnp.minimum(ids, blocks - 1)

    def body(i, pool):
        out = dict(pool)
        for name, x in vals.items():
            new = lax.dynamic_slice_in_dim(x, i, 1, axis=1)
            at = (0, blk[i]) + (0,) * (new.ndim - 2)
            old = lax.dynamic_slice(pool[name], at, new.shape)
            out[name] = lax.dynamic_update_slice(
                pool[name], jnp.where(live[i], new, old), at)
        return out

    return {**pool, **lax.fori_loop(
        0, ids.shape[0], body, {name: pool[name] for name in vals})}


def _stored(pool: dict, names: "tuple[str, ...]",
            new: "tuple[jax.Array, ...]") -> dict:
    """Values of the arrays ``names``, in their order, as the pool stores
    them, by array name (:func:`stored_as`)."""
    out = {}
    for name, vals in zip(names, new, strict=True):
        out.update(stored_as(pool, name, vals))
    return out


def write_kv_blocks(pool: dict, ids: jax.Array, newk: jax.Array,
                    newv: jax.Array, names: "tuple[str, ...]" = KV) -> dict:
    """Whole blocks of K and V (of ``names``, in their order) at the compute
    dtype into the pool (the prefill install, the sp and disagg handoffs)."""
    return write_blocks(pool, ids, _stored(pool, names, (newk, newv)))


# -- columns, written ---------------------------------------------------------

def scatter_columns(pool: dict, blk: jax.Array, off: jax.Array,
                    newk: jax.Array, newv: jax.Array,
                    names: "tuple[str, ...]" = KV) -> dict:
    """Freshly written columns (``[layers, *blk.shape, *tail]`` of the
    arrays ``names``, in their order, each over its own layers; blk/off
    share any index shape: ``[S]`` decode, ``[S, k]`` verify) into the
    DONATED pool, in place. Sentinel blocks write nothing — no block
    corrupted."""
    blocks = n_blocks(pool, names)
    cols = _stored(pool, names, (newk, newv))
    if all(pool[name].ndim == 4 for name in names):
        # the merged axis: ONE scatter a pool array, indexed by (layer, block,
        # offset) with a column of ``tail`` the window. (With the layer axis
        # left a slice, ``.at[:, blk, off]``, the window spans the layers and
        # the chip's compiler re-lays the whole pool out with the layers in the
        # sublanes and back: seen in the compiled text, PERF.md section 6.)
        at = {}
        for vals in cols.values():
            at.setdefault(vals.shape[0], (jnp.arange(vals.shape[0]).reshape(
                (-1,) + (1,) * blk.ndim), blk[None], off[None]))
        return {**pool, **{
            name: pool[name].at[at[vals.shape[0]]].set(vals, mode="drop")
            for name, vals in cols.items()}}
    # a pool that keeps heads and head size apart (a head fills a lane tile) is
    # written as PR 29 measured it: its columns go in one at a time, a loop of
    # dynamic-update-slices that carries the pool (the sliced scatter copied
    # that whole pool; the indexed one above compiles in place for it too but
    # has not been measured on its cell, ROADMAP A12)
    cols = {name: vals.reshape(
                (vals.shape[0], -1) + vals.shape[1 + blk.ndim:])
            for name, vals in cols.items()}
    blk, off = blk.reshape(-1), off.reshape(-1)
    live = blk < blocks
    blk = jnp.minimum(blk, blocks - 1)

    def body(c, pool):
        out = dict(pool)
        for name, vals in cols.items():
            col = lax.dynamic_slice_in_dim(
                vals, c, 1, axis=1)[:, :, None]
            at = (0, blk[c], off[c]) + (0,) * (col.ndim - 3)
            old = lax.dynamic_slice(pool[name], at, col.shape)
            out[name] = lax.dynamic_update_slice(
                pool[name], jnp.where(live[c], col, old), at)
        return out

    return {**pool, **lax.fori_loop(
        0, blk.shape[0], body, {name: pool[name] for name in cols})}
