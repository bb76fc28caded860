"""Tiered KV cache: host-DRAM session parking + disk spill (ROADMAP
item 1).

Parking is a memory-placement decision, never a quality decision: the
headline contract is that a parked-then-resumed session's greedy
tokens are BITWISE identical to a session that never parked — across
storage dtypes and decode modes — because park/unpark move raw
storage-dtype bytes, not recomputed values. Around that: the single
eviction policy (device→host→disk, LRU, leaves first), refcounted
shares and COW donors pinning their blocks on device, the chaos
contract on ``kv.park``/``kv.unpark`` (torn park → plain eviction,
corrupt unpark → re-prefill; the request always completes), and the
coordination satellites (autoscaler shrink floor, fabric headroom,
healthz occupancy).
"""

import threading
from concurrent.futures import Future

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import oracle
from sparkdl_tpu.models.gpt import GPTConfig, GPTLMHeadModel
from sparkdl_tpu.observability.flight import (
    flight_recorder,
    healthz_report,
)
from sparkdl_tpu.observability.registry import registry
from sparkdl_tpu.reliability.faults import inject
from sparkdl_tpu.serving import ContinuousGPTEngine
from sparkdl_tpu.serving.kv_blocks import KVBlockPool
from sparkdl_tpu.serving.kv_tiers import TieredKVStore
from sparkdl_tpu.serving.prefix_cache import PrefixCache

MAX_LEN = 32


@pytest.fixture(scope="module")
def bundle():
    cfg = GPTConfig.tiny()
    model = GPTLMHeadModel(cfg)
    variables = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )
    return cfg, model, variables


def _engine(cfg, variables, **kw):
    kw.setdefault("n_slots", 2)
    kw.setdefault("max_len", MAX_LEN)
    kw.setdefault("auto_start", False)
    kw.setdefault("kv_block_size", 4)
    return ContinuousGPTEngine(cfg, variables, **kw)


def _drain(eng, futs):
    while not all(f.done() for f in futs):
        eng.tick()


def _counter(name):
    fam = registry().snapshot().get(name)
    if fam is None:
        return 0.0
    return sum(fam["values"].values())


def _two_turns(eng, prompts, *, park):
    """Run turn 1, optionally park everything cold, run turn 2 (each
    prompt extended by its own turn-1 reply + one fresh token).
    Returns the list of turn-2 outputs."""
    futs = [eng.submit(p, 4) for p in prompts]
    _drain(eng, futs)
    replies = [f.result(timeout=0).tolist() for f in futs]
    if park:
        eng.park_cold()
    futs2 = [eng.submit(p + r + [5], 4)
             for p, r in zip(prompts, replies)]
    _drain(eng, futs2)
    return [f.result(timeout=0).tolist() for f in futs2]


# -- resume parity (the headline contract) -----------------------------------

@pytest.mark.parametrize(
    "kv_dtype,mode",
    [
        # the endpoints run tier-1; the interior combos ride the full
        # (slow-included) gate — same engines, just 4 more pairings
        ("fp32", "plain"),
        pytest.param("fp32", "chained", marks=pytest.mark.slow),
        pytest.param("fp32", "spec", marks=pytest.mark.slow),
        pytest.param("int8", "plain", marks=pytest.mark.slow),
        pytest.param("int8", "chained", marks=pytest.mark.slow),
        ("int8", "spec"),
    ],
)
def test_park_resume_bitwise_identical_to_never_parked(
        bundle, kv_dtype, mode):
    """A session that parked between turns and resumed must produce
    turn-2 greedy tokens bitwise identical to the same engine
    configuration that never parked — park/unpark move the raw
    storage bytes, so fp32 and int8, plain, chained, and speculative
    decode all round-trip exactly."""
    cfg, model, variables = bundle
    kw = dict(kv_dtype=kv_dtype, kv_blocks=24, host_kv_blocks=64,
              disk_kv_blocks=16)
    if mode == "chained":
        kw["chain_tokens"] = 4
    elif mode == "spec":
        kw["spec_k"] = 3
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, cfg.vocab_size, size=9).tolist()
               for _ in range(3)]

    with _engine(cfg, variables, **kw) as parked_eng:
        got = _two_turns(parked_eng, prompts, park=True)
        snap = parked_eng._kv_snapshot()["tiers"]
    with _engine(cfg, variables, **kw) as plain_eng:
        want = _two_turns(plain_eng, prompts, park=False)

    assert got == want
    # the resume path actually engaged: blocks parked AND paged back
    assert snap["parks"] > 0
    assert snap["unparks"] > 0
    assert snap["park_fallbacks"] == 0
    if kv_dtype == "fp32" and mode == "plain":
        # fp32 plain additionally pins against the unbatched oracle
        for p, r2 in zip(prompts, got):
            turn1 = oracle(model, variables, p, 4).tolist()
            full = p + turn1 + [5]
            assert r2 == oracle(model, variables, full, 4).tolist()


# -- refcounted shares / COW donors never park --------------------------------

def test_live_blocks_and_cow_donor_pinned_while_decoding(bundle):
    """park_cold() mid-decode must park NOTHING: every block of the
    decoding donor (including the partial tail block a COW sharer
    matched) is refcounted by its slot's table. Both the donor and the
    sharer finish bitwise-correct, and once both retire their cold
    blocks do park."""
    cfg, model, variables = bundle
    with _engine(cfg, variables, host_kv_blocks=32,
                 kv_blocks=16) as eng:
        donor_prompt = [5, 3, 9, 2, 7, 11]  # tail partial at bs=4
        fa = eng.submit(donor_prompt, 8)
        for _ in range(3):  # admit + prefill + a few decode steps
            eng.tick()
        assert not fa.done()
        # the sharer COW-matches the donor's partial tail block
        fb = eng.submit(donor_prompt + [1, 6], 4)
        eng.tick()
        assert not fa.done()  # both still mid-flight at park time
        freed = eng.park_cold()
        snap = eng._kv_snapshot()["tiers"]
        assert snap["host_blocks"] == 0 and snap["disk_blocks"] == 0
        assert freed == 0
        _drain(eng, [fa, fb])
        assert (fa.result(timeout=0).tolist()
                == oracle(model, variables, donor_prompt, 8).tolist())
        assert (fb.result(timeout=0).tolist()
                == oracle(model, variables, donor_prompt + [1, 6],
                           4).tolist())
        # retired: the same sessions are now cold and DO park
        assert eng.park_cold() > 0
        assert eng._kv_snapshot()["tiers"]["host_blocks"] > 0


# -- LRU demotion ordering (device -> host -> disk) ---------------------------

def _register_session(prefix, pool, tokens):
    bids = pool.allocate(len(tokens) // pool.block_size)
    prefix.register(tuple(tokens), bids)
    prefix.release(bids)  # refcount 0: cold, cached
    return bids


def test_lru_demotion_cascades_device_host_disk_then_drops(bundle):
    """One eviction policy across the hierarchy: demote parks the LRU
    device leaf first; host overflow demotes ITS LRU entry to disk;
    disk overflow drops the LRU disk leaf entirely (that session
    re-prefills — exactly what a flat cache would have forced for
    every one of them)."""
    del bundle
    pool = KVBlockPool(16, 2)
    tiers = TieredKVStore(2, 2, is_droppable=lambda n: not n.children)
    prefix = PrefixCache(pool, tiers=tiers)
    payload = lambda bid: {"k": np.full((1, 2), bid, np.float32)}

    sessions = {name: [10 * i + 1, 10 * i + 2]
                for i, name in enumerate("abcde")}
    for name in "abc":
        _register_session(prefix, pool, sessions[name])
    assert prefix.demote(3, payload) == 3
    # a parked first (LRU) -> demoted host->disk when c overflowed host
    node = lambda name: prefix._root.children[tuple(sessions[name])]
    assert tiers.tier_of(node("a")) == "disk"
    assert tiers.tier_of(node("b")) == "host"
    assert tiers.tier_of(node("c")) == "host"
    assert node("a").tier == "disk"
    # two more sessions park: host overflow pushes b then c to disk,
    # and the disk tier's own overflow drops a — the LRU disk leaf —
    # whose trie entry is pruned, so a fresh match misses (re-prefill)
    for name in "de":
        _register_session(prefix, pool, sessions[name])
    assert prefix.demote(2, payload) == 2
    assert tiers.tier_of(node("b")) == "disk"
    assert tiers.tier_of(node("c")) == "disk"
    assert tuple(sessions["a"]) not in prefix._root.children
    assert tiers.host_used == 2 and tiers.disk_used == 2
    # parked entries are invisible to match (their bytes are a tier
    # away) but restore via fetch round-trips the exact payload
    assert prefix.match(tuple(sessions["c"])).full_blocks == []
    got = tiers.fetch(node("c"))
    assert got is not None and float(got["k"][0, 0]) >= 0


def test_refcounted_share_never_parks(bundle):
    """A cached block some live table still references must stay on
    device no matter how cold its stamp is."""
    del bundle
    pool = KVBlockPool(8, 2)
    tiers = TieredKVStore(8)
    prefix = PrefixCache(pool, tiers=tiers)
    payload = lambda bid: {"k": np.zeros((1, 2), np.float32)}
    bids = pool.allocate(2)
    prefix.register((1, 2, 3, 4), bids)  # still refcount 1: "live"
    assert prefix.demote(2, payload) == 0
    assert tiers.host_used == 0
    prefix.release(bids)  # the session retires -> cold
    assert prefix.demote(2, payload) == 2
    assert tiers.host_used == 2


# -- chaos: kv.park / kv.unpark -----------------------------------------------

def test_torn_park_falls_back_to_eviction_zero_lost(bundle):
    """An injected ``kv.park`` fault mid-demotion must degrade to
    plain eviction: every accepted request completes bitwise-correct,
    the fallback lands in the counter and the flight ring."""
    cfg, model, variables = bundle
    base = flight_recorder().events_total
    # a pool sized so the second wave's admissions must demote the
    # first wave's cold blocks
    with _engine(cfg, variables, host_kv_blocks=32, kv_blocks=10,
                 n_slots=1) as eng:
        rng = np.random.default_rng(3)
        prompts = [rng.integers(1, cfg.vocab_size, size=9).tolist()
                   for _ in range(4)]
        with inject("kv.park:RuntimeError@1"):
            futs = [eng.submit(p, 4) for p in prompts]
            _drain(eng, futs)
        for p, f in zip(prompts, futs):
            assert (f.result(timeout=0).tolist()
                    == oracle(model, variables, p, 4).tolist())
        assert eng._kv_snapshot()["tiers"]["park_fallbacks"] >= 1
    assert _counter("sparkdl_kv_park_fallbacks_total") >= 1
    evs = [e for e in flight_recorder().events()
           if e["kind"] == "kv.park_failed"
           and e["seq"] > base]
    assert evs and evs[0]["error"] == "RuntimeError"


def test_corrupt_unpark_falls_back_to_reprefill_zero_lost(bundle):
    """An injected ``kv.unpark`` fault on resume must prune the parked
    prefix and re-prefill — the turn-2 request still completes with
    bitwise-correct greedy tokens."""
    cfg, model, variables = bundle
    base = flight_recorder().events_total
    with _engine(cfg, variables, host_kv_blocks=64,
                 kv_blocks=24) as eng:
        rng = np.random.default_rng(4)
        prompts = [rng.integers(1, cfg.vocab_size, size=9).tolist()
                   for _ in range(2)]
        futs = [eng.submit(p, 4) for p in prompts]
        _drain(eng, futs)
        replies = [f.result(timeout=0).tolist() for f in futs]
        eng.park_cold()
        with inject("kv.unpark:RuntimeError@1"):
            futs2 = [eng.submit(p + r + [5], 4)
                     for p, r in zip(prompts, replies)]
            _drain(eng, futs2)
        for p, r, f in zip(prompts, replies, futs2):
            want = oracle(model, variables, p + r + [5], 4).tolist()
            assert f.result(timeout=0).tolist() == want
        assert eng._kv_snapshot()["tiers"]["park_fallbacks"] >= 1
    evs = [e for e in flight_recorder().events()
           if e["kind"] == "kv.unpark_failed" and e["seq"] > base]
    assert evs


# -- autoscaler coordination (shrink floor) -----------------------------------

def test_kv_shrink_defers_while_unpark_reservations_hold():
    """Scale-down against a pool whose free blocks are spoken for by
    parked sessions must defer (streak -> healthz degraded), then
    self-clear once the reservations drop."""
    import threading as _t

    from sparkdl_tpu.autoscale import AutoscalePolicy, AutoScaler

    registry().reset()

    kvp = KVBlockPool(32, 4)
    kvp.unpark_reserved = 32  # parked sessions cover the whole pool
    sc = AutoScaler(kv_pool=kvp, kv_lock=_t.Lock(),
                    signals=lambda: (0.0, 0.0),
                    policy=AutoscalePolicy(hysteresis=1,
                                           cooldown_ticks=0,
                                           kv_step_blocks=4))
    try:
        sc.tick()
        kv = sc.snapshot()["autoscaler"]["kv"]
        assert kvp.spare_count == 0  # the shrink moved nothing
        assert kv["shrink_blocked_streak"] == 1
        assert kv["unpark_reserved"] == 32
        assert healthz_report()["status"] == "degraded"
        # sessions resumed (reservations released): self-clearing
        kvp.unpark_reserved = 0
        sc.tick()
        assert kvp.spare_count == 4  # the deferred shrink landed
        snap = sc.snapshot()["autoscaler"]["kv"]
        assert snap["shrink_blocked_streak"] == 0
        assert healthz_report()["status"] == "ok"
    finally:
        sc.close()


# -- observability + fabric awareness -----------------------------------------

def test_capacity_and_healthz_expose_tier_occupancy(bundle):
    cfg, _, variables = bundle
    with _engine(cfg, variables, host_kv_blocks=64,
                 kv_blocks=24) as eng:
        rng = np.random.default_rng(5)
        prompts = [rng.integers(1, cfg.vocab_size, size=9).tolist()
                   for _ in range(3)]
        futs = [eng.submit(p, 4) for p in prompts]
        _drain(eng, futs)
        cap = eng.capacity()
        assert cap["kv_blocks_cold"] > 0  # retired, parkable
        assert cap["kv_parked_blocks"] == 0
        eng.park_cold()
        cap = eng.capacity()
        assert cap["kv_parked_blocks"] > 0
        assert cap["kv_parked_sessions"] >= 3
        assert _counter("sparkdl_kv_tier_blocks") > 0
        hz = healthz_report()
        pools = [p for p in hz["kv_pools"]
                 if p.get("host_tier_blocks") is not None]
        assert pools and pools[0]["host_tier_blocks"] > 0
        assert pools[0]["parked_sessions"] >= 3


def test_headroom_policy_counts_parkable_cold_blocks():
    """Two hosts, equally 'full' by kv_free — but one's pressure is
    cold parkable sessions. The headroom policy must prefer it over
    the genuinely full one."""
    from sparkdl_tpu.fabric import HostHandle, Router

    class FakeHost(HostHandle):
        def __init__(self, host_id, kv_free, kv_cold):
            self.host_id = host_id
            self._kv_free = kv_free
            self._kv_cold = kv_cold
            self.submits = []

        def submit(self, payload, *, timeout_s=None):
            self.submits.append(payload)
            fut = Future()
            fut.set_result(self.host_id)
            return fut

        def capacity(self):
            return {"host_id": self.host_id, "replica_count": 1,
                    "n_slots": 4, "free_slots": 4,
                    "kv_blocks_free": self._kv_free,
                    "kv_blocks_total": 16,
                    "kv_blocks_cold": self._kv_cold,
                    "kv_parked_sessions": 0, "queue_depth": 0,
                    "max_queue_depth": 16, "draining": False}

        def health(self):
            return {"status": "ok", "host_id": self.host_id}

        def snapshot(self):
            return {"host_id": self.host_id,
                    "capacity": self.capacity()}

        def prefix_digest(self, max_entries=1024):
            return None

        def drain(self):
            return []

        def close(self, *, timeout_s=30.0):
            pass

    full = FakeHost("full", kv_free=1, kv_cold=0)
    parkable = FakeHost("parkable", kv_free=1, kv_cold=15)
    r = Router([full, parkable], policy="headroom",
               auto_refresh=False)
    try:
        r.refresh()
        for _ in range(2):
            r.submit({"prompt": [1, 2],
                      "max_new_tokens": 1}).result(5)
        assert len(parkable.submits) == 2 and not full.submits
        hosts = {h["host"]: h
                 for h in r.snapshot()["hosts"]}
        assert hosts["parkable"]["kv_cold"] == 15
    finally:
        r.close()
