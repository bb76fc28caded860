"""What a serving engine asks of a model family.

``ContinuousGPTEngine`` holds a block-paged K/V pool and a set of jitted
programs around ONE module call; which module, and how a token's K/V is
shaped, is the family's to say. A configuration answers
``config.serving_family()`` with a :class:`ServingFamily`; the engine reads
nothing else off the configuration. ``GPTConfig`` answers with its own
fields (so GPT-2's programs are what they were); a new family answers from
its own module (``models/afmoe.py``, ``models/olmo_hybrid.py``,
``models/mimo_v2_flash.py``, ``models/lfm2_moe.py``,
``models/glm_moe_dsa.py``).

The module's contract is :class:`~sparkdl_tpu.models.gpt.GPTLMHeadModel`'s:
``module.apply(variables, ids, cache=None | dense | paged, positions=...)``
returns ``(logits, cache)``; a paged cache hands back this call's new
columns ``[layers, S, L, *kv_tail]``, in the shape the pool stores a token's
K or V in (:attr:`ServingFamily.kv_tail` for K, :attr:`ServingFamily.v_tail`
for V: a value head may be narrower than a key head, and then the two differ
in their trailing shape), and the caller writes them into its pool.
``layers`` there are the layers that KEEP K/V a token
(:attr:`ServingFamily.pool_layers`): a family may keep, in some layers,
arrays by SLOT instead (:attr:`ServingFamily.state_layers`), whose size does
not grow with the context: a recurrent state (``models/olmo_hybrid.py``),
the last ``window`` columns of a window layer's K and V kept as a ring
(``models/mimo_v2_flash.py``, :attr:`ServingFamily.ring_columns`), or the
last few inputs of a short convolution and nothing else
(``models/lfm2_moe.py``, :attr:`ServingFamily.tail_columns`). The pool
holds them by slot beside its blocks and the module hands them back whole.

What a token keeps BY BLOCK need not be a K and a V: a family names its own
two arrays (:attr:`ServingFamily.block_arrays`; ``models/glm_moe_dsa.py``
keeps ``latent``, one compressed column a token in every layer, and
``index_k``, an indexer's key in some of them: each with its own count of
layers and its own trailing axes). The engine's programs carry the pair in
that order wherever they carried ``k`` and ``v``, and the module's cached
calls take and hand back the arrays under those names.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import numpy as np

from sparkdl_tpu.models import kv_pool


@dataclasses.dataclass(frozen=True)
class ServingFamily:
    module: Any
    layers: int
    kv_heads: int          #: heads of K and V (under the query heads)
    head_dim: int          #: a key head's size, and a value head's unless
    #: ``v_head_dim`` says another
    dtype: Any             #: compute dtype, and the native K/V dtype
    #: a learned position table's length; None where positions extrapolate
    max_positions: "int | None" = None
    #: layers whose attention sees only the last ``window`` positions (they
    #: gather only the table entries the window covers); the rest are full
    window_layers: int = 0
    window: "int | None" = None
    #: expert layers, and (token, expert) pairs a row makes in each: the
    #: module's cached calls then hand back ``expert_counts``
    #: ``[expert_layers, experts]`` int32 beside their K/V
    expert_layers: int = 0
    experts: int = 0
    experts_per_token: int = 0
    #: the family is served at its native K/V dtype by the one-chip
    #: prefill and the plain step alone: ``sp``, ``spec_k`` and
    #: ``kv_dtype`` refuse it at construction
    paged_only: bool = False
    #: layers that keep K/V a token: the leading axis of the pool's ``k``
    #: and ``v`` (None: every layer does)
    kv_layers: "int | None" = None
    #: the module's one-token step reads the old K/V where the pool keeps
    #: it, each row's own blocks and no more (``ops/paged_decode.py``, by
    #: its rule ``reads_in_place`` on :attr:`kv_tail` and :attr:`v_tail`:
    #: K's and V's heads each side by side on one unpadded axis of whole
    #: lane tiles; the two may differ in width, a head of either may be
    #: under a tile and a group of query heads may share each), where it
    #: would gather
    #: every row's ``nb`` blocks: what the engine's count of a step's reads
    #: follows
    decode_reads_in_place: bool = False
    #: the chunkwise recurrence of the state layers solves its sub-chunks'
    #: triangular systems in the kernel (``ops/delta_solve.py``, by its rule
    #: ``solves_in_kernel`` on the family's widths) where it would hand them
    #: to ``solve_triangular``: what a chunk's span says of its program
    scan_solved_in_kernel: bool = False
    #: layers that keep a recurrent state a SLOT, whatever the context's
    #: length, and the arrays each keeps, ``(name, shape a slot, dtype)``:
    #: the pool holds ``name`` as ``[state_layers, n_slots, *shape]``. No
    #: block holds any of it, so what moves blocks alone (the prefix cache,
    #: a park, a handoff) does not carry a sequence of such a family
    state_layers: int = 0
    state_arrays: "tuple[tuple[str, tuple[int, ...], Any], ...]" = ()
    #: a value head's size where it is not the key's (None: ``head_dim``)
    v_head_dim: "int | None" = None
    #: the arrays by slot are RINGS of this many columns: each state layer
    #: is a window layer that keeps its last ``ring_columns`` positions' K
    #: and V a slot, written at ``position % ring_columns``, and no block
    #: (0: the arrays by slot are no ring). Not ``window_layers``: those
    #: keep every block and gather the entries their window covers
    ring_columns: int = 0
    #: the arrays by slot are TAILS of this many columns: each state layer
    #: is a short convolution that keeps its last ``tail_columns`` inputs a
    #: slot, in the compute dtype, and nothing else (0: they are no tail)
    tail_columns: int = 0
    #: the two arrays a token keeps BY BLOCK where they are not ``k`` and
    #: ``v`` over :attr:`pool_layers`: ``(name, layers, trailing axes)``
    #: each, the pool's ``name`` being ``[layers, blocks, block_size,
    #: *axes]`` in the compute dtype (no compressed storage). Empty: ``k``
    #: and ``v``, shaped by the heads above
    block_arrays: "tuple[tuple[str, int, tuple[int, ...]], ...]" = ()
    #: the module's one-token step attends over at most this many columns a
    #: row a layer, which it selects itself (a learned sparse attention),
    #: whatever the row's depth (0: over every column). What it FETCHES to
    #: do so follows the width of the step's tables
    #: (``ops/sparse_attention.attends_in_place``, which the engine's count
    #: of a step's reads asks too): the riding rows' live blocks, whole,
    #: under the selection as a mask while a table is no wider than a few
    #: selections; the selected columns one by one past that
    selected_columns: int = 0

    @property
    def pool_arrays(self) -> "tuple[tuple[str, int, tuple[int, ...]], ...]":
        """``(name, layers, trailing axes)`` of the two arrays the pool
        keeps by block, in the order the programs carry them."""
        return self.block_arrays or (
            ("k", self.pool_layers, self.kv_tail),
            ("v", self.pool_layers, self.v_tail))

    @property
    def pool_layers(self) -> int:
        """The leading axis of the pool's ``k`` and ``v``."""
        return self.layers if self.kv_layers is None else self.kv_layers

    @property
    def state_bytes_per_slot(self) -> int:
        """Bytes of recurrent state one slot holds, all state layers."""
        return self.state_layers * sum(
            math.prod(shape) * np.dtype(dtype).itemsize
            for _, shape, dtype in self.state_arrays)

    @property
    def kv_tail(self) -> "tuple[int, ...]":
        """The trailing axes of a token's K (and, where a value head is a
        key head's size, V) in the block pool ``[layers, blocks,
        block_size, *kv_tail]``: :func:`~sparkdl_tpu.models.kv_pool.kv_tails`
        of this family's heads."""
        return kv_pool.kv_tails(self.kv_heads, self.head_dim,
                                self.v_head_dim)[0]

    @property
    def v_tail(self) -> "tuple[int, ...]":
        """The trailing axes of a token's V in the block pool."""
        return kv_pool.kv_tails(self.kv_heads, self.head_dim,
                                self.v_head_dim)[1]

    def window_blocks(self, nb: int, block_size: int) -> int:
        """Table entries a window layer of this family gathers in a decode
        step where a full layer gathers ``nb``."""
        if not self.window_layers:
            return nb
        return window_blocks(self.window, nb, block_size)


def window_blocks(window: int, nb: int, block_size: int,
                  width: int = 1) -> int:
    """Table entries that can cover ``window`` positions behind each of
    ``width`` new columns, wherever in a block the newest falls: what a
    window layer gathers where a full layer gathers ``nb``."""
    return min(nb, (window + width - 2) // block_size + 2)

