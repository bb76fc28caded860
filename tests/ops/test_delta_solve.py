"""The triangular solve of the chunkwise gated delta rule as a kernel
(``ops/delta_solve.py``, ISSUE 39) under the Pallas interpreter, beside
``jax.scipy.linalg.solve_triangular``, which it takes the place of where its
rule takes the shapes, and beside a float64 solve of the same systems."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.scipy.linalg import solve_triangular

from sparkdl_tpu.ops import delta_solve

ORDER = delta_solve.ORDER


def _systems(b, h, n, width, seed=0, scale=0.3):
    rng = np.random.default_rng(seed)
    a = np.tril(scale * rng.standard_normal((b, h, n, ORDER, ORDER)), -1)
    rhs = rng.standard_normal((b, h, n, ORDER, width))
    return jnp.asarray(a, jnp.float32), jnp.asarray(rhs, jnp.float32)


def _definition(a, rhs):
    return solve_triangular(a + jnp.eye(ORDER, dtype=a.dtype), rhs,
                            lower=True, unit_diagonal=True)


def _float64(a, rhs):
    return np.linalg.solve(np.asarray(a, np.float64) + np.eye(ORDER),
                           np.asarray(rhs, np.float64))


def _error(x, want):
    return float(np.abs(np.asarray(x) - want).max() / np.abs(want).max())


@pytest.mark.parametrize("shape", [
    pytest.param((1, 30, 1, 288), id="published: a chunk of 64 or less"),
    pytest.param((1, 30, 2, 288), id="published: a chunk of 128"),
    pytest.param((1, 30, 4, 288), id="published: a chunk of 256"),
    pytest.param((1, 1, 1, 128), id="the smallest the rule takes"),
    pytest.param((2, 3, 5, 136),
                 id="two rows, a prime count of sub-chunks, a width of no "
                    "whole tile"),
])
def test_the_kernel_solves_what_solve_triangular_solves(shape):
    b, h, n, width = shape
    a, rhs = _systems(b, h, n, width, seed=n)
    got = delta_solve.delta_solve(a, rhs)
    assert got.shape == rhs.shape and got.dtype == jnp.float32
    want = _float64(a, rhs)
    # substitution in float32 is no further from the float64 solve than the
    # definition is
    assert _error(got, want) < 2e-6
    assert _error(got, want) < 2 * _error(_definition(a, rhs), want)


def test_repeated_keys_and_beta_two_do_not_blow_the_kernel_up():
    """``test_olmo_hybrid``'s hardest system through the kernel: every key
    of a sub-chunk the same and beta 2, so ``A`` is 2 x the all-ones strict
    lower triangle. Its powers grow as 2^k (a product of them reads a
    relative error of 6e20 in float32); its inverse applied to a bounded
    right-hand side stays bounded, and substitution finds it."""
    a = jnp.broadcast_to(
        2.0 * jnp.tril(jnp.ones((ORDER, ORDER), jnp.float32), -1),
        (1, 2, 2, ORDER, ORDER))
    _, rhs = _systems(1, 2, 2, 128, seed=3)
    got = delta_solve.delta_solve(a, rhs)
    want = _float64(a, rhs)
    assert np.isfinite(np.asarray(got)).all()
    assert _error(got, want) < 1e-4
    assert _error(got, want) < 4 * _error(_definition(a, rhs), want)


def test_a_system_of_zeros_hands_its_right_hand_side_back_bit_for_bit():
    """The pad tokens' case: rows of ``beta = 0`` have no entry in ``A`` and
    must come back as they went in. One system of zeros beside one that is
    not, in one call."""
    a, rhs = _systems(1, 2, 2, 288, seed=5)
    a = a.at[0, 1, 0].set(0.0)
    # rows 40.. of another system are pad: their rows of A and of rhs are 0
    a = a.at[0, 0, 1, 40:].set(0.0)
    rhs = rhs.at[0, 0, 1, 40:].set(0.0)
    got = np.asarray(delta_solve.delta_solve(a, rhs))
    assert np.array_equal(got[0, 1, 0], np.asarray(rhs[0, 1, 0]))
    assert not np.array_equal(got[0, 1, 1], np.asarray(rhs[0, 1, 1]))
    assert np.array_equal(got[0, 0, 1, 40:], np.zeros((24, 288), np.float32))
    # and the rows before the pad are what they are without it
    alone = np.linalg.solve(
        np.asarray(a[0, 0, 1, :40, :40], np.float64) + np.eye(40),
        np.asarray(rhs[0, 0, 1, :40], np.float64))
    np.testing.assert_allclose(got[0, 0, 1, :40], alone, atol=2e-5)


@pytest.mark.parametrize("order, width, dtype, taken", [
    (64, 288, jnp.float32, True),     # Olmo-Hybrid: 96 + 192
    (64, 128, jnp.float32, True),     # one lane tile: the smallest
    (64, 2048, jnp.float32, True),
    (64, 127, jnp.float32, False),    # under a lane tile
    (64, 24, jnp.float32, False),     # the CPU tests' tiny widths: 8 + 16
    (64, 288, jnp.bfloat16, False),
    (64, 288, jnp.float64, False),
    (32, 288, jnp.float32, False),    # another sub-chunk
    (128, 288, jnp.float32, False),
    (64, 1 << 14, jnp.float32, False),  # one system does not fit a step
])
def test_the_rule_on_shapes(order, width, dtype, taken):
    assert delta_solve.solves_in_kernel(order, width, dtype) is taken


def test_a_step_takes_as_many_of_a_heads_systems_as_fit():
    assert delta_solve._group(4, 64, 288) == 4
    assert delta_solve._group(8, 64, 288) == 8
    # 448 KiB a system, two buffers counted: nine fit 4 MiB; of 64 systems
    # a head, the largest divisor under that
    assert delta_solve._group(64, 64, 288) == 8
    assert delta_solve._group(7, 64, 2048) == 1


@pytest.mark.parametrize("a_shape, rhs_shape, dtype", [
    ((1, 2, 1, 64, 64), (1, 2, 1, 64, 288), jnp.bfloat16),
    ((1, 2, 1, 64, 64), (1, 2, 1, 64, 24), jnp.float32),
    ((2, 1, 64, 64), (2, 1, 64, 288), jnp.float32),
    ((1, 2, 1, 64, 64), (1, 2, 2, 64, 288), jnp.float32),
    ((1, 2, 1, 32, 32), (1, 2, 1, 32, 288), jnp.float32),
    ((1, 2, 1, 64, 32), (1, 2, 1, 64, 288), jnp.float32),
])
def test_what_the_rule_refuses_raises(a_shape, rhs_shape, dtype):
    with pytest.raises(ValueError, match="the delta solve takes"):
        delta_solve.delta_solve(jnp.zeros(a_shape, dtype),
                                jnp.zeros(rhs_shape, dtype))


def test_two_calls_of_one_shape_share_one_traced_function():
    """Under the interpreter the call is under its own ``jax.jit``: two
    layers that solve the same shapes lower ONE function, and a second
    program of those shapes traces nothing. (On the chip the kernel is
    lowered once a process for each shape and kept as text:
    ``tests/serving/test_paged_step_chip_compile.py``.)"""
    a, rhs = _systems(1, 2, 1, 128)

    def two_layers(a, rhs):
        return delta_solve.delta_solve(a, delta_solve.delta_solve(a, rhs))

    text = jax.jit(two_layers).lower(a, rhs).as_text()
    assert text.count("func.func private @_delta_solve") == 1
    assert text.count("call @_delta_solve") == 2
