"""A dropless expert layer: every (token, expert) pair is computed.

Beside ``expert_parallel.py`` (the capacity-factor GShard block, which
DROPS what overflows an expert's buffer and is a training layer), this is
the serving form: static shapes and no capacity. The ``rows x k`` (token,
expert) pairs are sorted by expert, ``group_sizes`` says how many rows each
expert got, ONE grouped product a projection (:func:`grouped_dot`)
multiplies each run of rows by its own expert's kernel, and the weighted
results are un-sorted and summed per token in selection order. A token's
result is a function of its own row alone, whoever shares the batch.

The grouped product on a TPU is the Pallas grouped matmul (megablox
``gmm``) at row tiles of 128 and the largest kernel block of at most 1,024
x 1,024 elements whose sides DIVIDE the kernels' two dimensions
(:func:`gmm_tiling`: 1,024 x 1,024 at widths of 1,024, 2,048, 4,096 and
6,144; 2,048 x 512 and 512 x 2,048 at LFM2's 1,536, where a tile of 1,024
was half pad along the columns and left the contraction a masked
remainder). At 128 x 1024 x 1024, on the v5e, an expert layer of 128
experts of 2048 x 1024 in bfloat16 took 1.87 ms at a decode tick's 256
rows and 2.55 ms at a chunk's 2,048 where ``jax.lax.ragged_dot`` took 2.83
and 5.34 (PERF.md section 6, PR 29; the kernels' 1.3-1.6 GB need 1.6-2.0
ms). Under the explicit-CPU harness it is ``ragged_dot``, the plain XLA
statement of the same product.

The layer is told which experts it HOLDS (``first_expert``,
``experts_held``): it routes over all of them and computes its own
experts' part. Pairs of experts not held sort last, get weight 0 and no
product; nothing stands in for the chips that hold them.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from sparkdl_tpu.ops._pallas import auto_interpret

#: the rows of a tile of the grouped matmul, and the sides of the LARGEST
#: block of a kernel one takes (:func:`gmm_tiling`)
GMM_TILING = (128, 1024, 1024)


def gmm_tiling(k: int, n: int) -> "tuple[int, int, int]":
    """The grouped matmul's tile for kernels ``[.., k, n]`` (multiples of
    128 both): the row tile and, of the kernel blocks ``[tk, tn]`` whose
    sides DIVIDE ``k`` and ``n`` and that hold no more than 1,024 x 1,024
    elements, the largest; of equals the squarest. No column tile is then
    part pad (the kernel multiplies a tile whole) and no contraction ends in
    a remainder (which the kernel masks, both operands converted to float32
    and back, on every expert visit). Every ``k`` and ``n`` that 1,024
    divides keeps 1,024 x 1,024; LFM2's 2,048 x 1,536 gets 2,048 x 512 and
    its 1,536 x 2,048 gets 512 x 2,048 (on the v5e each within 0.3% of
    the fastest of eight dividing blocks, 10 and 19% under 1,024 x 1,024,
    in the 2 MB a block that 1,024 x 1,024 takes: PERF.md section 6, PR
    46). Where the largest such block is under half of what 1,024 x
    1,024 cut to the kernel holds (both sides 128 x a prime), the tile is
    that one with its pad and its remainder, as every shape's was: no shape
    is worse off than it was."""
    tm, tk_most, tn_most = GMM_TILING
    tk, tn = max(((tk, tn) for tk in range(128, k + 1, 128) if k % tk == 0
                  for tn in range(128, n + 1, 128) if n % tn == 0
                  if tk * tn <= tk_most * tn_most),
                 key=lambda b: (b[0] * b[1], -abs(b[0] - b[1])))
    cut = min(tk_most, k), min(tn_most, n)
    if 2 * tk * tn < cut[0] * cut[1]:
        return tm, *cut
    return tm, tk, tn


def grouped_dot(xs: jax.Array, kernels: jax.Array,
                group_sizes: jax.Array) -> jax.Array:
    """``xs`` [M, K] in runs of rows, run ``g`` of ``group_sizes[g]`` rows
    times ``kernels[g]`` [K, N] -> [M, N] in ``xs``'s dtype. ``M`` is a
    multiple of the row tile; rows past the runs' end hold no result."""
    k, n = kernels.shape[1:]
    if auto_interpret() or k % 128 or n % 128 or xs.shape[0] % GMM_TILING[0]:
        return jax.lax.ragged_dot(xs, kernels, group_sizes)
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    return gmm(xs, kernels, group_sizes, xs.dtype, gmm_tiling(k, n))


def route_sigmoid_topk(h: jax.Array, router_kernel: jax.Array,
                       expert_bias: jax.Array, k: int, *,
                       route_norm: bool = True,
                       route_scale: float = 1.0,
                       norm_eps: float = 1e-20
                       ) -> "tuple[jax.Array, jax.Array]":
    """Sigmoid scores in float32, the top ``k`` by score PLUS bias, weights
    from the unbiased scores (normalised if ``route_norm``: divided by their
    sum plus ``norm_eps``, which is the family's own; then scaled). ``h``
    [T, H] -> ``(sel [T, k] int32, w [T, k] float32)``."""
    # float32 at full precision: a TPU's default would round both operands
    # to bfloat16, and the 8th and 9th of 128 scores are a hair apart
    s = jax.nn.sigmoid(jnp.dot(h.astype(jnp.float32),
                               router_kernel.astype(jnp.float32),
                               precision=jax.lax.Precision.HIGHEST))
    _, sel = jax.lax.top_k(s + expert_bias.astype(jnp.float32), k)
    w = jnp.take_along_axis(s, sel, axis=-1)
    if route_norm:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + norm_eps)
    return sel.astype(jnp.int32), w * route_scale


def dropless_experts(h: jax.Array, sel: jax.Array, w: jax.Array,
                     w_gate: jax.Array, w_up: jax.Array, w_down: jax.Array,
                     *, first_expert: int = 0
                     ) -> "tuple[jax.Array, jax.Array]":
    """The held experts' part of the layer: ``sum_e w_e * swiglu_e(h)`` over
    each token's selected experts that lie in ``[first_expert, first_expert
    + experts_held)``, ``experts_held`` being the kernels' leading size.

    ``h`` [T, H]; ``sel``/``w`` [T, k]; ``w_gate``/``w_up`` [held, H, F];
    ``w_down`` [held, F, H]. Returns ``(out [T, H], counts [held] int32)``,
    ``counts`` the rows each held expert was given.
    """
    t, k = sel.shape
    held_n = w_gate.shape[0]
    local = sel.reshape(-1) - first_expert
    held = (local >= 0) & (local < held_n)
    key = jnp.where(held, local, held_n)            # not held: sorted last
    order = jnp.argsort(key, stable=True)
    counts = jnp.zeros((held_n + 1,), jnp.int32).at[key].add(1)[:held_n]
    # [T*k, H] by expert, padded to whole row tiles of the grouped product
    pad = (-t * k) % GMM_TILING[0]
    xs = jnp.pad(h[order // k], ((0, pad), (0, 0)))
    gate = grouped_dot(xs, w_gate, counts)
    up = grouped_dot(xs, w_up, counts)
    ys = grouped_dot(jax.nn.silu(gate) * up, w_down, counts)[:t * k]
    # rows past the held experts' runs belong to no group: they are given
    # weight 0 AND taken out, whatever the product left in them
    ws = jnp.where(held, w.reshape(-1), 0.0)[order]
    ys = jnp.where(held[order][:, None],
                   ys.astype(jnp.float32) * ws[:, None], 0.0)
    inv = jnp.zeros_like(order).at[order].set(jnp.arange(order.shape[0]))
    out = ys[inv].reshape(t, k, -1).sum(axis=1)
    return out.astype(h.dtype), counts
