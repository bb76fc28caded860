"""Runtime elasticity on the REAL actuators (ISSUE 15): ReplicaPool
add/remove under live traffic (the drain contract — zero accepted
batches lost), and Router add_host/remove_host riding the shared
drain-transfer path with sticky-session/digest purge."""

import threading
import time

import numpy as np
import pytest

import jax.numpy as jnp

from sparkdl_tpu.observability import flight
from sparkdl_tpu.observability.registry import registry
from sparkdl_tpu.reliability import faults
from sparkdl_tpu.reliability.faults import inject
from sparkdl_tpu.serving import ReplicaPool, ServingEngine

DIM = 6
_W = jnp.asarray(
    np.random.default_rng(3).standard_normal((DIM, DIM)), jnp.float32
)


def _apply(b):
    return jnp.tanh(b["x"] @ _W)


def setup_function(_fn):
    faults.disarm()


class _SlowRunner:
    """Wraps a runner with a holdable gate so work piles up in replica
    queues deterministically."""

    def __init__(self, inner):
        self._inner = inner
        self.gate = threading.Event()
        self.gate.set()
        self.chunk_size = inner.chunk_size
        self.served = 0

    def run_batch(self, arrays):
        self.gate.wait(30)
        self.served += 1
        return self._inner.run_batch(arrays)


def _make_pool(n=2, **kw):
    from sparkdl_tpu.transformers._inference import BatchedRunner

    runners = []

    def make_runner(device):
        r = _SlowRunner(BatchedRunner(
            _apply, batch_size=8, data_parallel=False, device=device))
        runners.append(r)
        return r

    pool = ReplicaPool(make_runner=make_runner, n_replicas=n, **kw)
    return pool, runners


def test_add_replica_joins_routing_and_serves():
    pool, runners = _make_pool(n=1)
    try:
        pool.warmup({"x": np.zeros((4, DIM), np.float32)})
        idx = pool.add_replica(
            warmup_arrays={"x": np.zeros((4, DIM), np.float32)})
        assert idx == 1
        assert len(pool.replicas) == 2
        assert pool.max_inflight_batches == 3
        # both replicas take traffic (least-outstanding + rr ties)
        futs = [pool.run_batch_async(
            {"x": np.zeros((4, DIM), np.float32)}) for _ in range(8)]
        for f in futs:
            f.result(30)
        assert all(r.served > 0 for r in runners)
        # indices are never reused across scale cycles
        pool.remove_replica(index=1)
        assert pool.add_replica() == 2
    finally:
        pool.close()


def test_remove_replica_transfers_queued_work_zero_lost():
    pool, runners = _make_pool(n=2)
    try:
        pool.warmup({"x": np.zeros((2, DIM), np.float32)})
        # hold replica 1's executor so its queue builds
        runners[1].gate.clear()
        futs = []
        vals = []
        for i in range(12):
            v = float(i % 7)
            vals.append(v)
            futs.append(pool.run_batch_async(
                {"x": np.full((2, DIM), v, np.float32)}))
        # scale down the WEDGED replica: its queued work must transfer
        # to the survivor; the in-flight batch finishes once the gate
        # opens (remove_replica joins the worker)
        t = threading.Timer(0.3, runners[1].gate.set)
        t.start()
        removed = pool.remove_replica(index=1, timeout_s=30.0)
        t.cancel()
        runners[1].gate.set()
        assert removed == 1
        assert len(pool.replicas) == 1
        # ZERO accepted batches lost: every future resolves correctly
        for v, f in zip(vals, futs):
            out = np.asarray(f.result(30))
            expect = np.tanh(np.full((2, DIM), v) @ np.asarray(_W))
            np.testing.assert_allclose(out, expect, rtol=1e-5)
    finally:
        pool.close()


def test_remove_replica_prefers_quarantined_victim():
    pool, runners = _make_pool(n=2, max_failures=1, probation_s=600.0)
    try:
        pool.warmup({"x": np.zeros((2, DIM), np.float32)})
        r0 = pool.replicas[0]
        with pool._lock:
            r0.breaker.record_failure()
        assert r0.quarantined
        assert pool.remove_replica() == 0  # the broken one goes first
        assert [r.index for r in pool.replicas] == [1]
    finally:
        pool.close()


def test_remove_last_replica_refuses():
    pool, _ = _make_pool(n=1)
    try:
        with pytest.raises(ValueError, match="below one replica"):
            pool.remove_replica()
    finally:
        pool.close()


def test_scale_down_fault_aborts_before_any_state_moves():
    """The replica.scale_down site fires BEFORE the victim leaves
    routing: an injected fault defers the whole scale-down — no work
    moves, no replica vanishes, traffic unaffected."""
    pool, _ = _make_pool(n=2)
    try:
        pool.warmup({"x": np.zeros((2, DIM), np.float32)})
        with inject("replica.scale_down:OSError@1"):
            with pytest.raises(OSError):
                pool.remove_replica()
        assert len(pool.replicas) == 2
        futs = [pool.run_batch_async(
            {"x": np.zeros((2, DIM), np.float32)}) for _ in range(4)]
        for f in futs:
            f.result(30)
        # clean retry succeeds
        assert pool.remove_replica() in (0, 1)
        assert len(pool.replicas) == 1
    finally:
        pool.close()


def test_retiring_replica_stays_under_watchdog_scan(wait_until):
    """A victim whose in-flight dispatch wedges DURING retirement must
    stay on the watchdog's scan list: its riders get the same deadline
    re-route every live dispatch gets, instead of hanging forever on a
    removed replica."""
    pool, runners = _make_pool(n=2, dispatch_timeout_s=0.2,
                               probation_s=600.0)
    try:
        pool.warmup({"x": np.zeros((2, DIM), np.float32)})
        runners[1].gate.clear()  # wedge replica 1's executor
        # two concurrent works: least-outstanding spreads one per replica
        futs = [pool.run_batch_async(
            {"x": np.full((2, DIM), 1.0, np.float32)}) for _ in range(2)]
        wait_until(lambda: any(r.current_work is not None
                               for r in pool.replicas
                               if r.index == 1),
                   desc="work in flight on replica 1")
        # retire the wedged replica; the join times out (0.1 < gate)
        assert pool.remove_replica(index=1, timeout_s=0.1) == 1
        # the watchdog must deadline-fail the wedged dispatch and
        # re-route it to the survivor — riders resolve, nothing hangs
        expect = np.tanh(np.full((2, DIM), 1.0) @ np.asarray(_W))
        for f in futs:
            np.testing.assert_allclose(
                np.asarray(f.result(10)), expect, rtol=1e-5)
        fam = registry().get("sparkdl_replica_hung_total")
        assert fam is not None and \
            fam.snapshot_values().get("", 0.0) >= 1
    finally:
        runners[1].gate.set()
        pool.close()


def test_scale_events_land_in_flight_ring():
    pool, _ = _make_pool(n=1)
    try:
        pool.add_replica()
        pool.remove_replica()
        kinds = {e.get("kind") for e in flight.flight_recorder().events()
                 if str(e.get("kind", "")).startswith("pool.scale_")}
        assert {"pool.scale_up", "pool.scale_down"} <= kinds
    finally:
        pool.close()


def test_engine_over_elastic_pool_keeps_serving():
    """ServingEngine riding a pool that scales mid-traffic: every
    submitted request resolves with the right answer."""
    registry().reset()
    pool, _ = _make_pool(n=1)
    engine = ServingEngine(pool, max_queue_depth=4096, max_wait_s=0.001)
    try:
        pool.warmup({"x": np.zeros((1, DIM), np.float32)})
        futs = []
        for i in range(60):
            futs.append(engine.submit(
                {"x": np.full((DIM,), float(i % 5), np.float32)}))
            if i == 20:
                pool.add_replica()
            if i == 40:
                pool.remove_replica()
        for i, f in enumerate(futs):
            out = np.asarray(f.result(60))
            expect = np.tanh(np.full((DIM,), float(i % 5))
                             @ np.asarray(_W))
            np.testing.assert_allclose(out, expect, rtol=1e-5)
        snap = engine.snapshot()
        assert snap["completed"] == 60
        assert snap["failed"] == 0
    finally:
        engine.close()
        pool.close()


# -- fabric tier --------------------------------------------------------------

def _gpt_fleet(n=2):
    """A tiny in-process GPT fleet (the fabric test idiom)."""
    import jax

    from sparkdl_tpu.fabric.host import InProcessHost
    from sparkdl_tpu.fabric.router import Router
    from sparkdl_tpu.models.gpt import GPTConfig, GPTLMHeadModel
    from sparkdl_tpu.serving import ContinuousGPTEngine

    cfg = GPTConfig.tiny()
    model = GPTLMHeadModel(cfg)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8), jnp.int32))
    engines = [
        ContinuousGPTEngine(cfg, variables, n_slots=2, max_len=32,
                            kv_block_size=4,
                            idle_wait_s=0.001, host_id=f"h{i}")
        for i in range(n)
    ]
    hosts = [InProcessHost(e, host_id=e.host_id) for e in engines]
    router = Router(hosts[:n], auto_refresh=False)
    return cfg, engines, hosts, router


@pytest.mark.slow
def test_router_remove_host_drains_and_purges_then_add_host_rejoins():
    import numpy as np

    cfg, engines, hosts, router = _gpt_fleet(2)
    try:
        rng = np.random.default_rng(5)
        prompt = rng.integers(1, cfg.vocab_size, 8).tolist()
        payload = {"prompt": prompt, "max_new_tokens": 3}
        # pin a sticky session onto h0
        router.submit(payload, session="s1").result(30)
        router.refresh()
        assert router._sessions.get("s1") == "h0"
        # fleet scale-down: drain + forget h0, handle returned
        handle = router.remove_host("h0")
        assert handle is hosts[0]
        assert router.hosts() == ["h1"]
        # sticky session purged: the next turn re-places on a survivor
        assert "s1" not in router._sessions
        fut = router.submit(payload, session="s1")
        fut.result(30)
        assert router._sessions.get("s1") == "h1"
        # removing the last host refuses
        with pytest.raises(ValueError, match="last fabric host"):
            router.remove_host("h1")
        # a FRESH host joins at runtime and takes traffic
        from sparkdl_tpu.fabric.host import InProcessHost
        from sparkdl_tpu.serving import ContinuousGPTEngine
        import jax

        model_vars = engines[0]  # reuse variables via engine 0's config
        del model_vars
        from sparkdl_tpu.models.gpt import GPTLMHeadModel
        variables = GPTLMHeadModel(cfg).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
        e2 = ContinuousGPTEngine(cfg, variables, n_slots=2, max_len=32,
                                 kv_block_size=4,
                                 idle_wait_s=0.001, host_id="h2")
        engines.append(e2)
        assert router.add_host(InProcessHost(e2, host_id="h2")) == "h2"
        assert set(router.hosts()) == {"h1", "h2"}
        with pytest.raises(ValueError, match="duplicate host id"):
            router.add_host(InProcessHost(e2, host_id="h2"))
        router.submit(payload).result(30)
    finally:
        router.close()
        for e in engines:
            e.close(drain=False)


def test_drain_purges_prefix_digest_immediately():
    cfg, engines, hosts, router = _gpt_fleet(2)
    try:
        import numpy as np

        rng = np.random.default_rng(6)
        prompt = rng.integers(1, cfg.vocab_size, 12).tolist()
        router.submit({"prompt": prompt, "max_new_tokens": 2}
                      ).result(30)
        router.refresh()  # digests seeded from the radix caches
        assert any(s.digest is not None and s.digest.hashes
                   for s in router._hosts.values())
        drained = [s for s in router._hosts.values()
                   if s.digest is not None][0]
        router.drain_host(drained.host_id)
        # the departing host's digest is gone THE MOMENT drain begins:
        # affinity can no longer steer placements at a dying cache
        assert router._hosts[drained.host_id].digest is None
    finally:
        router.close()
        for e in engines:
            e.close(drain=False)
