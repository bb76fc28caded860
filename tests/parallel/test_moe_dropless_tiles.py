"""The grouped product's tiles (``parallel/moe_dropless.gmm_tiling``): by the
kernels' shape, the largest block of at most 1,024 x 1,024 elements whose
sides divide the kernels' dimensions, and the megablox kernel at those
tiles against ``jax.lax.ragged_dot`` at LFM2-24B-A2B's expert shapes (ISSUE
46). Pure Python and two small runs under the Pallas interpreter: no model
is built."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparkdl_tpu.parallel.moe_dropless import GMM_TILING, gmm_tiling

TOL = 2e-5   # tests/models/test_afmoe.py's, for the layer in float32

#: (k, n) -> the contracted and column tiles; None for the stated fallback,
#: the tile every shape had: 1,024 x 1,024 with its pad and its remainder
CASES = {
    # every shape a cell runs: Trinity, MiMo-V2-Flash, GLM-5.2 as they were
    (2048, 1024): (1024, 1024), (1024, 2048): (1024, 1024),
    (4096, 2048): (1024, 1024), (2048, 4096): (1024, 1024),
    (6144, 2048): (1024, 1024), (2048, 6144): (1024, 1024),
    # LFM2-24B-A2B: gate and up, down
    (2048, 1536): (2048, 512), (1536, 2048): (512, 2048),
    # within 1,024 x 1,024: the whole kernel, as ``min`` gave
    (128, 384): (128, 384), (640, 128): (640, 128), (384, 640): (384, 640),
    # LFM2's dense width, 128 x 4 x 23
    (2048, 11776): (2048, 512), (11776, 2048): (512, 2048),
    # one side 128 x 13 or 128 x 11: that side whole
    (1664, 2048): (1664, 512), (2048, 1408): (512, 1408),
    # both: the largest dividing block is 128 x 1,408
    (1664, 1408): None,
}


@pytest.mark.parametrize("k, n", sorted(CASES))
def test_a_tile_divides_its_dimension_or_is_the_stated_fallback(k, n):
    tm, tk, tn = gmm_tiling(k, n)
    most = GMM_TILING[1] * GMM_TILING[2]
    assert tm == GMM_TILING[0] == 128
    assert tk % 128 == 0 and tn % 128 == 0 and tk * tn <= most
    if CASES[k, n] is None:
        assert (tk, tn) == GMM_TILING[1:] and k % tk and n % tn
        # no dividing block is half of it
        assert not any(
            k % a == 0 and n % b == 0 and most // 2 <= a * b <= most
            for a in range(128, k + 1, 128) for b in range(128, n + 1, 128))
    else:
        assert (tk, tn) == CASES[k, n]
        assert k % tk == 0 and n % tn == 0


@pytest.mark.parametrize("k, n", [(2048, 1536), (1536, 2048)])
def test_the_kernel_at_the_rules_tiles_is_the_grouped_product(k, n):
    """128 rows in 3 uneven groups, bfloat16 operands: float32 sums over the
    whole contraction, wherever it is cut, and ONE rounding at the store."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    tiling = gmm_tiling(k, n)
    assert k % tiling[1] == 0 and n % tiling[2] == 0
    keys = jax.random.split(jax.random.PRNGKey(46), 2)
    xs = jax.random.normal(keys[0], (128, k), jnp.float32).astype(jnp.bfloat16)
    kernels = (0.02 * jax.random.normal(keys[1], (3, k, n), jnp.float32)
               ).astype(jnp.bfloat16)
    sizes = jnp.asarray([5, 90, 33], jnp.int32)
    want = jax.lax.ragged_dot(xs, kernels, sizes,
                              preferred_element_type=jnp.float32)
    summed = gmm(xs, kernels, sizes, jnp.float32, tiling, interpret=True)
    np.testing.assert_allclose(np.asarray(summed), np.asarray(want), atol=TOL)
    stored = gmm(xs, kernels, sizes, jnp.bfloat16, tiling, interpret=True)
    assert stored.dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.asarray(stored.astype(jnp.float32)),
        np.asarray(summed.astype(jnp.bfloat16).astype(jnp.float32)))
