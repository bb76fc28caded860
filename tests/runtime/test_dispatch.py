"""Fused multi-step dispatch (runtime/dispatch.py): chained-vs-unchained
parity must be BITWISE — chaining is a dispatch decision, never a numeric
one — and the dispatch counter must drop ~K* when chains form.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparkdl_tpu.observability.registry import registry
from sparkdl_tpu.runtime.dispatch import (
    ChainPolicy,
    ScanChainer,
    calibrate_dispatch_gap,
    chain_carry,
    dispatch_count,
    overhead_share,
)

W = jnp.asarray(
    np.random.default_rng(7).standard_normal((16, 16)), jnp.float32
) / 4.0


def _step(batch):
    return jnp.tanh(batch["x"] @ W)


def _items(n, rows=8, seed=0):
    rng = np.random.default_rng(seed)
    return [
        {"x": jax.device_put(
            rng.standard_normal((rows, 16)).astype(np.float32))}
        for _ in range(n)
    ]


@pytest.mark.parametrize("k", [1, 4, 8])
def test_map_stream_bitwise_parity(k):
    items = _items(16)
    single = jax.jit(_step)
    want = [np.asarray(single(x)) for x in items]
    got = [
        np.asarray(y)
        for y in ScanChainer(_step, path="t_parity", chain_k=k)
        .map_stream(iter(items))
    ]
    assert len(got) == len(want)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)  # bitwise, not allclose


def test_chain_dispatch_count_drops_k_fold():
    items = _items(16)
    before = dispatch_count("t_count")
    list(ScanChainer(_step, path="t_count", chain_k=8)
         .map_stream(iter(items)))
    assert dispatch_count("t_count") - before == 2  # 16 steps, K=8
    before = dispatch_count("t_count")
    list(ScanChainer(_step, path="t_count", chain_k=1)
         .map_stream(iter(items)))
    assert dispatch_count("t_count") - before == 16


def test_ragged_tail_runs_unchained():
    # 10 items at K=4: two chains + two single flushes = 4 dispatches
    items = _items(10)
    before = dispatch_count("t_tail")
    out = list(ScanChainer(_step, path="t_tail", chain_k=4)
               .map_stream(iter(items)))
    assert len(out) == 10
    assert dispatch_count("t_tail") - before == 4
    single = jax.jit(_step)
    for got, item in zip(out, items):
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(single(item)))


def test_shape_change_flushes_pending():
    # a smaller tail bucket mid-stream may not join the chain; order and
    # values must survive the flush
    items = _items(3) + _items(2, rows=4, seed=1) + _items(3, seed=2)
    chainer = ScanChainer(_step, path="t_shapes", chain_k=3)
    out = list(chainer.map_stream(iter(items)))
    assert [o.shape[0] for o in out] == [8, 8, 8, 4, 4, 8, 8, 8]
    single = jax.jit(_step)
    for got, item in zip(out, items):
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(single(item)))


def test_empty_stream_and_tuple_outputs():
    chainer = ScanChainer(_step, path="t_empty", chain_k=4)
    assert list(chainer.map_stream(iter(()))) == []

    def multi(batch):
        return batch["x"] + 1.0, batch["x"].sum(axis=-1)

    items = _items(4)
    out = list(ScanChainer(multi, path="t_multi", chain_k=4)
               .map_stream(iter(items)))
    single = jax.jit(multi)
    for got, item in zip(out, items):
        want = single(item)
        assert len(got) == 2
        np.testing.assert_array_equal(np.asarray(got[0]),
                                      np.asarray(want[0]))
        np.testing.assert_array_equal(np.asarray(got[1]),
                                      np.asarray(want[1]))


def test_auto_policy_measures_then_chains():
    # a huge injected gap makes any program "cheap": the first dispatch
    # measures (K=1), every later group chains at max_chain
    policy = ChainPolicy(gap_s=10.0, max_chain=8)
    chainer = ScanChainer(_step, path="t_auto", chain_k=None,
                          policy=policy)
    chainer.chain_k = None  # guard against SPARKDL_TPU_CHAIN_K in env
    before = dispatch_count("t_auto")
    out = list(chainer.map_stream(iter(_items(9))))
    assert len(out) == 9
    assert policy.chain_len() == 8
    assert dispatch_count("t_auto") - before == 2  # 1 probe + one 8-chain


def test_chain_policy_bounds():
    p = ChainPolicy(gap_s=1e-3, target_overhead=0.02, max_chain=32)
    assert p.chain_len() == 1  # unmeasured: first dispatch probes
    p.record(1e-3 + 1e-4, 1)  # program ~100us against a 1ms gap
    k = p.chain_len()
    assert k == 32  # ideal K ~490, clamped
    assert k & (k - 1) == 0
    # long programs do not chain: overhead already amortized
    p2 = ChainPolicy(gap_s=2.4e-3)
    p2.record(0.2, 1)
    assert p2.chain_len() == 1
    # program comfortably over the gap/target ratio: modest power of two
    p3 = ChainPolicy(gap_s=1e-3, target_overhead=0.2, max_chain=32)
    p3.record(1e-3 + 1e-3, 1)  # program == gap
    assert p3.chain_len() == 4  # ideal 4.0 -> 4


def test_chain_carry_matches_sequential_steps():
    def step(state, batch):
        new = jax.tree.map(
            lambda s: s + jnp.sum(batch["x"]) * 1e-3, state
        )
        return new, {"norm": new["w"].sum()}

    state0 = {"w": jnp.ones((4, 4), jnp.float32)}
    xs_list = _items(6, rows=2, seed=3)
    single = jax.jit(step)
    s_ref = state0
    norms_ref = []
    for x in xs_list:
        s_ref, m = single(s_ref, x)
        norms_ref.append(float(m["norm"]))
    chained = chain_carry(step, donate=False)
    stacked = jax.tree.map(lambda *a: jnp.stack(a), *xs_list)
    s_got, ms = chained(state0, stacked)
    # the carried state is the contract: bitwise across scan/sequential
    np.testing.assert_array_equal(np.asarray(s_got["w"]),
                                  np.asarray(s_ref["w"]))
    # the reported metric is a 16-element float32 reduction that XLA:CPU
    # may order differently inside a scan body than in the standalone
    # program: equal to float32 rounding, not bitwise
    np.testing.assert_allclose(
        np.asarray(ms["norm"]), np.asarray(norms_ref, np.float32),
        rtol=2 * np.finfo(np.float32).eps,
    )


def test_env_chain_k_rejects_values_below_one(monkeypatch):
    from sparkdl_tpu.runtime.dispatch import default_chain_k

    monkeypatch.setenv("SPARKDL_TPU_CHAIN_K", "0")
    with pytest.raises(ValueError, match="SPARKDL_TPU_CHAIN_K"):
        default_chain_k()
    with pytest.raises(ValueError, match="SPARKDL_TPU_CHAIN_K"):
        ScanChainer(_step, path="t_env", chain_k=None)
    monkeypatch.setenv("SPARKDL_TPU_CHAIN_K", "4")
    assert ScanChainer(_step, path="t_env", chain_k=None).chain_k == 4
    monkeypatch.delenv("SPARKDL_TPU_CHAIN_K")
    assert default_chain_k() is None


def test_calibrate_gap_env_override_and_cache(monkeypatch):
    monkeypatch.setenv("SPARKDL_TPU_DISPATCH_GAP_MS", "2.5")
    assert calibrate_dispatch_gap() == pytest.approx(2.5e-3)
    monkeypatch.delenv("SPARKDL_TPU_DISPATCH_GAP_MS")
    # refresh: other tests may have calibrated (and a registry reset may
    # have wiped the gauge since) — this test owns its own measurement
    g1 = calibrate_dispatch_gap(refresh=True)
    assert 0 < g1 < 0.1  # CPU dispatch is tens of microseconds
    assert calibrate_dispatch_gap() == g1  # cached per backend
    gauge = registry().get("sparkdl_dispatch_gap_seconds")
    assert gauge is not None and gauge.snapshot_values()[""] == g1


def test_overhead_share():
    assert overhead_share(10, 1.0, gap_s=0.01) == pytest.approx(0.1)
    assert overhead_share(0, 1.0, gap_s=0.01) is None
    assert overhead_share(1000, 1.0, gap_s=0.01) == 1.0  # clamped
