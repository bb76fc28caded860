"""Pallas TPU solve of the chunkwise gated delta rule's triangular systems:
``(I + A) x = rhs`` for ``A`` strictly lower triangular of order 64, every
sub-chunk of every head, with the system in the chip's faster memory from
its first row to its last.

``jax.scipy.linalg.solve_triangular`` is to the chip's compiler ONE diagonal
block of order 64: it inverts it row by row in 64 sequential steps, each a
few operations out of and into device memory, and multiplies the inverse
into the right-hand side in a second operation (0.49 ms a layer of
Olmo-Hybrid's chunk of 256, a tenth of the cell's device time; ledger, PR
38). Here the right-hand side is copied into the result's block once and
forward substitution runs over it in place, by blocks of one sublane tile
(8 rows): column ``j`` of ``A`` times the finished row ``j``, taken off
every row from ``j``'s tile down (``A`` is zero on and above its diagonal,
so the rows of the tile that are already finished lose exact zeros). All of
it is on the vector unit in float32: the products are single multiplies,
which is what ``Precision.HIGHEST`` asks of a matrix unit and more than it
gives, and there are 0.07 G of them a layer. No inverse is formed and no
power of ``A``: where keys repeat and ``beta`` is 2 the powers cancel
catastrophically and substitution does not (``tests/ops/
test_delta_solve.py``).

Which systems it takes is :func:`solves_in_kernel`'s to say, a rule on
shapes alone. What the rule refuses keeps ``solve_triangular``, which stays
the definition. The result is shaped as the right-hand side is: the pad of
its last axis to whole lane tiles is the kernel's own and never an array.

Inference-only: no custom VJP.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from sparkdl_tpu.ops._pallas import auto_interpret

#: lanes and sublanes of a float32 vector tile
_LANE_TILE, _SUBLANE_TILE = 128, 8
#: the order of a system: a sub-chunk of the chunkwise recurrence
ORDER = 64
#: what a step of the grid holds of ``a``, ``rhs`` and the result, each
#: twice (the next step's copies run under this step's substitution), as
#: the chip pads them: well inside the 16 MiB of faster memory a kernel gets
#: when it asks for none. The kernel asks for none (``ops/paged_decode.py``
#: has why)
_STEP_BYTES = 4 << 20


def _padded(n: int, tile: int) -> int:
    return -(-n // tile) * tile


def _system_bytes(order: int, width: int) -> int:
    """One system in the faster memory: ``a``, ``rhs`` and the result with
    their last axes padded to whole lane tiles, two buffers of each."""
    return 2 * 4 * order * (_padded(order, _LANE_TILE)
                            + 2 * _padded(width, _LANE_TILE))


def solves_in_kernel(order: int, width: int, dtype) -> bool:
    """Whether systems of this order against right-hand sides of ``width``
    columns are solved by the kernel (THE rule, asked by the module that
    solves and by the family's count of what its engine runs): float32, the
    order a sub-chunk's 64, a right-hand side of at least one lane tile
    (under that a vector register is mostly pad, and the tiny configurations
    of the CPU tests keep the definition) and narrow enough that one system
    fits a step of the grid. Olmo-Hybrid's 96 + 192 is; 8 + 16 is not. How
    many systems there are is not the rule's: a step takes as many of a
    head's as fit."""
    return (jnp.dtype(dtype) == jnp.float32 and order == ORDER
            and width >= _LANE_TILE
            and _system_bytes(order, width) <= _STEP_BYTES)


def _group(systems: int, order: int, width: int) -> int:
    """Systems of a head a step of the grid holds: the largest divisor of
    their count that fits :data:`_STEP_BYTES`."""
    most = max(1, _STEP_BYTES // _system_bytes(order, width))
    return max(g for g in range(1, min(systems, most) + 1)
               if systems % g == 0)


def _kernel(a_ref, rhs_ref, o_ref, *, order: int):
    # the refs are a head's ``[systems, order, columns]``: the block's two
    # leading axes are squeezed, so no index here is a Python integer (each
    # of those is made an array on the default device when the kernel is
    # traced: 0.45 s and more a shape on a TPU's host, 0.06-0.09 s without)
    def one(s, _):
        o_ref[s] = rhs_ref[s]
        for top in range(0, order, _SUBLANE_TILE):
            # rows from this tile down, held while the tile's eight columns
            # are taken off them
            x = o_ref[s, top:, :]
            for j in range(top, top + _SUBLANE_TILE):
                x = x - a_ref[s, top:, j:j + 1] * x[j - top:j - top + 1]
            o_ref[s, top:, :] = x
        return _

    jax.lax.fori_loop(0, a_ref.shape[0], one, 0)


def delta_solve(a, rhs):
    """``x`` of ``(I + tril(a, -1)) x = rhs``, by forward substitution.

    ``a`` ``[B, H, n, 64, 64]`` float32, of which the strict lower triangle
    is read and the rest must be ZERO (the chunkwise recurrence makes it
    so); ``rhs`` ``[B, H, n, 64, W]`` float32 with ``W`` as
    :func:`solves_in_kernel` takes it. Returns ``[B, H, n, 64, W]``
    float32. A system whose ``a`` is all zero hands its right-hand side
    back bit for bit.

    On the chip the kernel is lowered ONCE a process for each shape
    (:func:`_lowered_for_the_chip`) and every call hands that lowered text
    on: a program of six linear layers holds six calls and lowers no kernel.
    """
    if a.ndim != 5 or rhs.ndim != 5 or a.shape[:4] != rhs.shape[:4] or (
            a.shape[-1] != a.shape[-2]):
        raise ValueError(
            f"the delta solve takes a [B, H, n, 64, 64] and rhs [B, H, n, "
            f"64, W], not {a.shape} and {rhs.shape}")
    order, width = rhs.shape[-2:]
    if a.dtype != rhs.dtype or not solves_in_kernel(order, width, a.dtype):
        raise ValueError(
            f"the delta solve takes float32 systems of order {ORDER} against "
            f"at least {_LANE_TILE} columns, not {a.dtype}{list(a.shape)} "
            f"and {rhs.dtype}{list(rhs.shape)}")
    if auto_interpret():
        return _delta_solve(a, rhs, interpret=True)
    return _lowered_for_the_chip(a.shape, rhs.shape).call(a, rhs)


@functools.lru_cache(maxsize=None)
def _lowered_for_the_chip(a_shape, rhs_shape):
    """The kernel for these shapes, traced and lowered for the TPU once and
    kept as text (``jax.export``). A Pallas kernel is lowered, in Python,
    each time a program that holds it is (jax caches a traced function, not
    its lowering into another module), and this body's 64 unrolled columns
    cost 0.35-0.8 s of that in each of the cell's chunk programs, at every
    start: 12.5 s of a 62 s set-up (PERF.md section 6, PR 39). The text is
    merged into a program in milliseconds."""
    shapes = [jax.ShapeDtypeStruct(s, jnp.float32)
              for s in (a_shape, rhs_shape)]
    return jax.export.export(_delta_solve, platforms=("tpu",))(
        *shapes, interpret=False)


@functools.partial(jax.jit, static_argnames="interpret")
def _delta_solve(a, rhs, *, interpret: bool):
    b, h, n = a.shape[:3]
    order, width = rhs.shape[-2:]
    g = _group(n, order, width)

    def block(last):
        return pl.BlockSpec((None, None, g, order, last),
                            lambda i, j, k: (i, j, k, 0, 0))

    return pl.pallas_call(
        functools.partial(_kernel, order=order),
        grid=(b, h, n // g),
        in_specs=[block(order), block(width)],
        out_specs=block(width),
        out_shape=jax.ShapeDtypeStruct(rhs.shape, rhs.dtype),
        interpret=interpret,
        name="delta_solve",
    )(a, rhs)
