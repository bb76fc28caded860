"""Async-fetch parity suite (ISSUE 4): the pipelined readback must be
bitwise-identical to the blocking one on every configuration the batch
path serves — dp-sharded (the conftest 8-device mesh), chained, ragged
tails, tuple outputs — and the future-returning serving variant must
match its blocking twin.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from sparkdl_tpu.transformers._inference import BatchedRunner

DIM = 8
_W = jnp.asarray(
    np.random.default_rng(7).standard_normal((DIM, DIM)), jnp.float32
)


def _apply(b):
    return jnp.tanh(b["x"] @ _W)


def _rows(n, seed=0):
    rng = np.random.default_rng(seed)
    return [{"x": rng.standard_normal(DIM).astype(np.float32)}
            for _ in range(n)]


@pytest.mark.parametrize("chain_k,n_rows", [(1, 13), (4, 32), (4, 37),
                                            (8, 64)])
def test_async_readback_bitwise_vs_blocking(chain_k, n_rows):
    # n_rows=37 exercises the ragged tail (not a multiple of bucket*K)
    rows = _rows(n_rows)
    blocking = list(BatchedRunner(
        _apply, batch_size=4, data_parallel=False, chain_k=chain_k,
        async_fetch=False,
    ).run(iter(rows)))
    pipelined = list(BatchedRunner(
        _apply, batch_size=4, data_parallel=False, chain_k=chain_k,
    ).run(iter(rows)))
    assert len(pipelined) == len(blocking) == n_rows
    for a, b in zip(pipelined, blocking):
        np.testing.assert_array_equal(a, b)


def test_async_readback_bitwise_on_dp_mesh():
    # conftest forces 8 virtual devices: data_parallel auto-shards.
    # chain_k is pinned on both sides: left to auto, each runner's
    # ChainPolicy picks K from its own wall-clock measurements, and a
    # K=1 program against a K=4 scan differs in the last float32 bit on
    # the dp mesh — the readback mode is the one thing under test
    rows = _rows(50)
    blocking = list(BatchedRunner(
        _apply, batch_size=16, chain_k=2, async_fetch=False,
    ).run(iter(rows)))
    pipelined = list(BatchedRunner(
        _apply, batch_size=16, chain_k=2).run(iter(rows)))
    assert len(pipelined) == 50
    for a, b in zip(pipelined, blocking):
        np.testing.assert_array_equal(a, b)


def test_async_readback_tuple_outputs():
    def multi(b):
        return (b["x"] * 2.0, b["x"].sum(axis=-1))

    rows = _rows(11)
    blocking = list(BatchedRunner(
        multi, batch_size=4, data_parallel=False, chain_k=2,
        async_fetch=False,
    ).run(iter(rows)))
    pipelined = list(BatchedRunner(
        multi, batch_size=4, data_parallel=False, chain_k=2,
    ).run(iter(rows)))
    for (a0, a1), (b0, b1) in zip(pipelined, blocking):
        np.testing.assert_array_equal(a0, b0)
        np.testing.assert_array_equal(a1, b1)


def test_run_batch_async_matches_run_batch():
    runner = BatchedRunner(_apply, batch_size=8, data_parallel=False)
    arrays = {"x": np.stack([r["x"] for r in _rows(5, seed=3)])}
    sync = runner.run_batch(arrays)
    fut = runner.run_batch_async(arrays)
    async_out = fut.result()
    np.testing.assert_array_equal(async_out, sync)
    # idempotent: resolving twice returns the same object
    assert fut.result() is async_out


def test_fetch_window_sizing_and_validation():
    r = BatchedRunner(_apply, batch_size=4, data_parallel=False, chain_k=4,
                      prefetch=3)
    assert r._fetch_window() == 12  # prefetch depth x chain_k
    r2 = BatchedRunner(_apply, batch_size=4, data_parallel=False,
                       fetch_window=5)
    assert r2._fetch_window() == 5
    with pytest.raises(ValueError, match="fetch_window"):
        BatchedRunner(_apply, batch_size=4, fetch_window=0)


def test_device_pin_rejects_data_parallel_true():
    import jax

    with pytest.raises(ValueError, match="ReplicaPool"):
        BatchedRunner(_apply, batch_size=4, data_parallel=True,
                      device=jax.local_devices()[0])
