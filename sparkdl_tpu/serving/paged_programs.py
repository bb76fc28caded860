"""The device programs of :class:`~sparkdl_tpu.serving.continuous.
ContinuousGPTEngine`, as named functions.

Each is a function of (:class:`PagedSizes`, the family's module, its
arrays): the engine binds the first two (:func:`bound`) and hands the rest
to ``jax.jit`` under the function's own name, so a program can be lowered,
compiled for a described chip or timed from ``(sizes, module, arrays)``
with no engine, queue or thread around it. How K and V lie in the pool is
not decided here: every read and write of it goes through
:mod:`sparkdl_tpu.models.kv_pool`.

NAMES ARE PART OF THE CONTRACT. The benchmark finds programs in a device
trace by name: ``paged_step`` (``decode_device_ms``, both roofline shares,
``expert_device_ms``) and ``_chunk_`` (``prefill_device_share``). A
function renamed here reads as null there with every CPU test green;
``tests/serving/test_paged_programs.py`` holds the names.
"""

from __future__ import annotations

import dataclasses
import functools
import inspect
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from sparkdl_tpu.models import kv_pool
from sparkdl_tpu.ops import delta_solve, paged_decode, sparse_attention
from sparkdl_tpu.parallel import moe_dropless


@dataclasses.dataclass(frozen=True)
class PagedSizes:
    """What the engine derives from ``n_slots``, ``max_len``,
    ``kv_block_size``, ``prefill_chunk`` and the family, and its programs
    close over. No option: nobody sets it."""

    n_slots: int
    block_size: int
    mb: int        #: table width, blocks per sequence
    w: int         #: gathered virtual-cache width, ``mb * block_size``
    wp: int        #: a private prefill cache's width: ``w`` + one chunk
    max_pos: int   #: the last position a chunk's pad tail may claim
    dtype: Any     #: compute dtype (the pool may store another)
    #: the two arrays a token keeps by block, as the family names them
    #: (``ServingFamily.pool_arrays``): what ``ck`` and ``cv`` below are a
    #: prompt's private rows of, what a module's cached call takes and
    #: hands back its columns under, and what ``kv_pool`` is told apart
    #: from the pool's arrays by slot by
    arrays: "tuple[str, str]" = kv_pool.KV


def _rules():
    """What a module asks WHILE IT IS TRACED that no ``head`` holds: the
    rules that pick a kernel or a gather, interpreted or not. Constants of a
    process, unless a test replaces one to compile a step's other form."""
    return (paged_decode.reads_in_place, sparse_attention.IN_PLACE_SELECTIONS,
            delta_solve.solves_in_kernel, paged_decode.auto_interpret,
            delta_solve.auto_interpret, moe_dropless.auto_interpret)


def bound(fn, *head):
    """``fn`` with its leading arguments fixed, STILL under ``fn``'s name:
    ``jax.jit`` names a program after the function it is given, and a bare
    ``functools.partial`` has none (``jit__unknown`` on the device). The
    signature is what is left to pass (no ``__wrapped__``: jit would read
    the unbound one off it), so the compiled parameters keep their names.

    ONE function a ``(fn, *head)`` a process: jax keys what it has traced,
    lowered and compiled on the function a ``jax.jit`` was handed and that
    jit's options, so engines of equal configuration (a pool's replicas, an
    autoscaler's next one, a test's second) share one set of programs where
    each used to trace and compile its own. That leans on ``head`` comparing
    by value (:class:`PagedSizes` is frozen, a flax module hashes by its
    fields) and on ``fn`` closing over nothing but its ``head`` and
    :func:`_rules`: whatever else it read would stay the first engine's."""
    return _bound(fn, head, _rules())


@functools.cache
def _bound(fn, head, rules):
    def program(*args):
        return fn(*head, *args)

    program.__name__ = fn.__name__
    program.__qualname__ = fn.__qualname__
    left = list(inspect.signature(fn).parameters.values())[len(head):]
    program.__signature__ = inspect.Signature(left)
    return program


# -- the tick -----------------------------------------------------------------

def _paged_step(sizes, model, variables, pool, table, idx, tok, prev, k, nb):
    # k tokens for every slot THROUGH the block table: the model takes the pool
    # itself as a paged cache (a ``table`` entry, models/gpt.py), so each layer
    # gathers only its own live blocks into an [S, nb*bs] slice — the math
    # ``generate`` does over its dense cache, so greedy tokens stay
    # bitwise-identical — and hands back the one new column per row, which is
    # the ONLY write to the donated pool (in place, at (block, offset)): no
    # all-layer dense view, no copy of the pool. ``nb`` (static, bucketed) is
    # the block count covering the DEEPEST live row through this chain — the
    # gather and attention touch only the live head of the table, often FEWER
    # columns than ``max_len`` (masked-width invariance keeps tokens
    # bitwise). Rows are right-aligned (no left pad: column i
    # holds real token i), so the causal mask alone masks garbage columns and
    # positions need no start offset. Sentinel table entries clip on gather
    # (masked garbage) and write nothing (kv_pool.scatter_columns: no block
    # corrupted).
    #
    # A family with state layers (the pool then holds arrays by slot) has no
    # sentinel to drop a write: the rows that are LIVE in this step are the
    # ones whose table row holds a block, and the module moves the state of
    # no other row. A free slot, and a slot whose prompt's last chunk is
    # queued ahead of this step but whose row joins only the next one (the
    # table this step was handed is the one of its launch), keep their state
    # bit for bit. The state rides the donated pool through the chain.
    #
    # A row's input token is the HOST's where it has one (``tok >= 0``: the row
    # joined since the last step, from a prefill, a handoff or a resume, or the
    # last step's ids have been read) and otherwise the one the step before
    # this made, ``prev``, which is that step's second output and has never
    # left the device: the engine launches a step before it has read the last
    # one's ids. Every step hands its last tokens on the same way.
    sub = table[:, :nb]
    tok = jnp.where(tok >= 0, tok, prev)
    recurrent = kv_pool.slot_arrays(pool, sizes.arrays)
    live = ({"live": table[:, 0] < kv_pool.n_blocks(pool, sizes.arrays)}
            if recurrent else {})

    def body(carry, _):
        pool, idx, tok = carry
        logits, new = model.apply(
            variables, tok[:, None],
            cache=dict(pool, table=sub, idx=idx, **live),
        )
        ntok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        rows = jnp.arange(sizes.n_slots)
        blk = table[rows, idx // sizes.block_size]
        off = idx % sizes.block_size
        pool = kv_pool.scatter_columns(
            pool, blk, off, *(new[name][:, :, 0] for name in sizes.arrays),
            names=sizes.arrays)
        pool.update({name: new[name] for name in recurrent})
        out = ntok
        if "expert_counts" in new:
            # rows each expert got, behind the step's tokens: ONE array, so one
            # device-to-host read a tick
            out = jnp.concatenate(
                [ntok, new["expert_counts"].reshape(-1)])
        return (pool, idx + 1, ntok), out

    (pool, _, last), toks = lax.scan(
        body, (pool, idx, tok), None, length=k
    )
    return toks, last, pool


def _paged_verify(sizes, model, variables, pool, table, idx, toks, k, nb):
    # Speculative verify: score a k-token span for every slot in ONE dispatch.
    # Column 0 of ``toks`` is each slot's current last token, columns 1.. its
    # proposed drafts; the L=k per-slot step (models/gpt.py) writes all k
    # columns at [idx[s], idx[s]+k) and the per-row causal mask conditions
    # position j on the real context plus drafts [:j] — exactly the logits
    # greedy acceptance needs, through the same paged cache as _paged_step so
    # greedy tokens stay bitwise. Columns of REJECTED drafts scatter back as
    # garbage PAST the accepted frontier (the host advances pidx only over
    # accepted inputs): they sit causally masked until the next dispatch's own
    # writes overwrite them — the same garbage-but-finite contract as
    # retired-slot columns.
    logits, new = model.apply(
        variables, toks,
        cache=dict(pool, table=table[:, :nb], idx=idx),
    )
    out = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    rows = jnp.arange(sizes.n_slots)[:, None]
    pos = idx[:, None] + jnp.arange(k)[None, :]
    blk = table[rows, pos // sizes.block_size]
    off = pos % sizes.block_size
    return out, kv_pool.scatter_columns(
        pool, blk, off, *(new[name] for name in sizes.arrays),
        names=sizes.arrays)


# -- chunked prefill ----------------------------------------------------------

def _gathered(sizes, pool, ids):
    # cached-prefix blocks -> the head of a private prefill cache (the copy
    # that makes partial-block sharing copy-on-write: the sharer re-installs
    # into blocks it owns, the donor block is never written). Sentinel ids clip
    # to garbage the chunked prefill masks/overwrites. Quantized pools
    # dequantize here: the private cache is compute-dtype, and the final
    # install requantizes — an exact round trip (quantize_kv absmax maps to
    # ±127), so a COW-shared block re-installs bit-identical to its donor.
    def private(x):
        tail = x.shape[3:]
        pad = (((0, 0), (0, 0), (0, sizes.wp - sizes.w))
               + ((0, 0),) * len(tail))
        return jnp.pad(x.reshape((x.shape[0], 1, sizes.w) + tail), pad)

    return tuple(private(x) for x in kv_pool.gather_blocks_as(
        pool, ids, sizes.dtype, sizes.arrays))


def _chunk_apply(sizes, model, variables, ck, cv, idx, ids, cols, n=None,
                 rec=None):
    # one bounded prefill chunk, right-aligned: writes K/V at columns [idx,
    # idx+width) of the private cache, where width = ids.shape[1] is the
    # POWER-OF-2 BUCKET of this chunk's real token count (compile reuse: a
    # 24-token suffix pays a 32-wide program, not a chunk-cap-wide one).
    # ``cols`` (static, bucketed >=
    # idx+width) bounds the attention to the LIVE head of the buffer — every
    # column past it is causally masked garbage anyway, so slicing changes
    # nothing but the wasted FLOPs. The tail of the chunk is zero-padded on the
    # right; pad queries produce garbage columns PAST every real position, so
    # the causal mask hides them until real writes overwrite them — no
    # attention_mask needed.
    #
    # A recurrence has no causal mask to hide a pad: a family with state
    # layers is handed ``n``, the chunk's count of REAL tokens, and ``rec``,
    # the running state of this prompt by array name (``[state_layers, 1,
    # ...]``); its module lets no token past ``n`` move the state and hands
    # the state at token ``n`` back, which is this function's fourth result.
    positions = jnp.minimum(
        idx + jnp.arange(ids.shape[1])[None, :], sizes.max_pos)
    first, second = sizes.arrays
    cache = {first: ck[:, :, :cols], second: cv[:, :, :cols],
             "idx": idx, **({} if n is None else dict(rec, n=n))}
    logits, cache = model.apply(
        variables, ids, cache=cache, positions=positions,
    )
    ck = ck.at[:, :, :cols].set(cache[first])
    cv = cv.at[:, :, :cols].set(cache[second])
    if n is None:
        return logits, ck, cv
    return logits, ck, cv, {name: cache[name] for name in rec}


def _fresh(sizes, pool):
    # a prompt's private cache and running state at token 0 for a family with
    # state layers: no cached prefix is ever gathered for it (its blocks do
    # not hold the state at the boundary), so the cache starts as zeros and
    # not as a gather of sentinels, and the state as the sequence's start
    first, second = (pool[name] for name in sizes.arrays)

    def zeros(a):
        return jnp.zeros((a.shape[0], 1, sizes.wp) + a.shape[3:], sizes.dtype)

    ck = zeros(first)
    # (ONE array for both where K and V are shaped alike: Olmo's programs
    # then lower to the text they lowered to)
    cv = (ck if (second.shape[0],) + second.shape[3:]
          == (first.shape[0],) + first.shape[3:] else zeros(second))
    rec = {name: jnp.zeros((a.shape[0], 1) + a.shape[2:], a.dtype)
           for name, a in kv_pool.slot_arrays(pool, sizes.arrays).items()}
    return ck, cv, rec


def _installed(sizes, pool, ck, cv, ids, slot=None, rec=None):
    # private prefill cache -> the slot's OWNED pool blocks
    # (quantize-on-install rides the shared kv_pool.stored_as rule). ids
    # carries the sentinel at shared-prefix positions (their content already
    # lives in the shared blocks) and past the covered span: those writes drop.
    # A family with state layers: the prompt's state at its last token ``rec``
    # (a recurrent state; a window layer's ring of its last columns) -> row
    # ``slot`` of the pool's arrays by slot, whole (whatever the row held, of
    # the sequence before it, is gone).
    def blocks(rows, name):
        a = pool[name]
        return rows[:, 0, :sizes.w].reshape(
            (a.shape[0], sizes.mb, sizes.block_size) + a.shape[3:])

    first, second = sizes.arrays
    pool = kv_pool.write_kv_blocks(
        pool, ids, blocks(ck, first), blocks(cv, second), sizes.arrays)
    return pool if rec is None else kv_pool.install_slot(pool, slot, rec)


# Four fused chunk programs so a prefill pays the minimum dispatch count
# (dispatch gap dominates small programs — the ISSUE 3 lesson applied to
# admission): the FIRST chunk fuses the prefix gather, the FINAL chunk fuses
# the block install, so a suffix that fits one chunk is ONE device dispatch end
# to end. A chunk unrolls every layer: on the chip the layers share their
# code (the engine compiles the four with
# runtime.chip.alike_layers_options), or a program that installs is twenty
# times the size and loads as slowly.
#
# A family with state layers passes each program two things more, after
# ``cols``: ``n``, the chunk's real token count, and (mid, final) ``rec``, the
# prompt's running state, or (one, final) ``slot``, the row of the pool's
# arrays by slot that the last chunk installs the state into. The programs of
# the other families are called without them and are what they were.

def _chunk_one(sizes, model, variables, pool, gids, idx, ids, inst, cols,
               n=None, slot=None):
    logits, ck, cv, *last = _chunk_first(
        sizes, model, variables, pool, gids, idx, ids, cols, n)
    return logits, _installed(sizes, pool, ck, cv, inst, slot, *last)


def _chunk_first(sizes, model, variables, pool, gids, idx, ids, cols,
                 n=None):
    if n is not None:
        ck, cv, rec = _fresh(sizes, pool)
        return _chunk_apply(sizes, model, variables, ck, cv, idx, ids, cols,
                            n, rec)
    ck, cv = _gathered(sizes, pool, gids)
    return _chunk_apply(sizes, model, variables, ck, cv, idx, ids, cols)


def _chunk_mid(sizes, model, variables, ck, cv, idx, ids, cols, n=None,
               rec=None):
    return _chunk_apply(sizes, model, variables, ck, cv, idx, ids, cols, n,
                        rec)


# (ck/cv are deliberately NOT donated here or in _chunk_one: no output shares
# their shape, so donation could not alias — jax would warn "donated buffers
# were not usable" on every compile and free nothing earlier; they die on the
# host right after the call regardless)
def _chunk_final(sizes, model, variables, pool, ck, cv, idx, ids, inst,
                 cols, n=None, rec=None, slot=None):
    logits, ck, cv, *last = _chunk_apply(
        sizes, model, variables, ck, cv, idx, ids, cols, n, rec)
    return logits, _installed(sizes, pool, ck, cv, inst, slot, *last)


# -- whole blocks across a boundary: tiers, handoffs --------------------------

def _park_fetch(sizes, pool, ids):
    # the D2H half of a park: the given blocks' RAW storage-dtype bytes (int8
    # codes + their scales, no dequantize) — raw is both the 4x cheaper
    # transfer the quantized layout bought and what makes a resumed session
    # bitwise-identical: unpark writes back the exact bytes decode would have
    # read. A prefill tier's export (disagg/workers.py) is this same gather.
    return kv_pool.gather_blocks(pool, ids, sizes.arrays)


def _unpark_install(pool, ids, payload):
    # the H2D half of a resume: whole-block raw writes into freshly allocated
    # blocks, in place (sentinel ids write nothing — same contract as every
    # other pool write)
    return kv_pool.write_blocks(pool, ids, {
        name: vals.astype(pool[name].dtype)
        for name, vals in payload.items()})


def _install_blocks(pool, kdata, vdata, inst):
    # K and V that crossed a boundary at the compute dtype (the
    # sequence-parallel handoff, a decode tier's adopted prefill) into the
    # decode pool's owned blocks: the same kv_pool.write_kv_blocks path as the
    # fused single-device install (sentinels at shared-prefix positions drop;
    # quantized pools quantize HERE, once: the exact requantize round trip,
    # quantize_kv, that keeps a transferred block bitwise-identical to a local
    # prefill's)
    return kv_pool.write_kv_blocks(pool, inst, kdata, vdata)


# -- sequence-parallel prefill (the engine jits these with shardings) ---------

def _sp_chunk(sizes, model, variables, sppool, head, idx, ids, sblk, soff,
              nbh):
    # One SPATIAL prefill chunk: gather the staged head (sentinels clip to
    # causally-masked garbage), write this chunk's K/V into it through the
    # model's cached path — queries sharded over sp, K all-gathered by GSPMD
    # for the dense masked softmax, so logits are bitwise-identical to the
    # single-device chunk — then scatter the freshly written columns back to
    # their staged blocks (sentinel targets drop: pad columns never land).
    wc = ids.shape[1]
    layers, tail = sppool["k"].shape[0], sppool["k"].shape[3:]
    kbuf = sppool["k"][:, head].reshape(
        (layers, 1, nbh * sizes.block_size) + tail)
    vbuf = sppool["v"][:, head].reshape(
        (layers, 1, nbh * sizes.block_size) + tail)
    positions = jnp.minimum(
        idx + jnp.arange(wc)[None, :], sizes.max_pos)
    cache = {"k": kbuf, "v": vbuf, "idx": idx}
    logits, cache = model.apply(
        variables, ids, cache=cache, positions=positions)
    newk = jax.lax.dynamic_slice_in_dim(
        cache["k"][:, 0], idx, wc, axis=1)
    newv = jax.lax.dynamic_slice_in_dim(
        cache["v"][:, 0], idx, wc, axis=1)
    ix = (slice(None), sblk, soff)
    out = dict(sppool)
    out["k"] = sppool["k"].at[ix].set(newk, mode="drop")
    out["v"] = sppool["v"].at[ix].set(newv, mode="drop")
    return logits, out


def _sp_seed(sppool, kdata, vdata, ids):
    # cached-prefix K/V -> the staged blocks backing the hit span (the prefix
    # gather, sharded along the same axis): whole-block writes, sentinel
    # targets drop
    out = dict(sppool)
    out["k"] = sppool["k"].at[:, ids].set(kdata, mode="drop")
    out["v"] = sppool["v"].at[:, ids].set(vdata, mode="drop")
    return out


def _sp_gather(sppool, ids):
    # prefill->decode handoff: the request's staged blocks, gathered ONCE
    # across the sp shards (replicated out; the host hop to the single-device
    # decode pool is the documented boundary between the two device worlds)
    return sppool["k"][:, ids], sppool["v"][:, ids]


def _sp_prefix_fetch(sizes, pool, gids):
    # cached prefix blocks out of the DECODE pool, dequantized to the compute
    # dtype (the same values the single-device first chunk gathers into its
    # private cache)
    return kv_pool.gather_blocks_as(pool, gids, sizes.dtype)

