"""Cross-tier failure surfaces (ISSUE 16): the zero-loss contract must
survive the tier crossing.

Covered here: the ``handoff.export`` site (prefill-side teardown —
blocks released, victim re-queued at the HEAD, ahead of later
arrivals), the ``handoff.install`` site (typed
:class:`HandoffInstallError` the PhaseRouter answers with a
prefill-tier requeue), identity preservation across the crossing
(request id, deadline, enqueue stamp), deadline expiry mid-handoff
(no block leaks on either tier), and a chaos soak that kills a
prefill host mid-stream under probabilistic install faults — zero
accepted requests lost, counters reconciled."""

import random
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparkdl_tpu.disagg import (
    DecodeWorker,
    HandoffInstallError,
    PhaseRouter,
    PrefillWorker,
)
from sparkdl_tpu.fabric.host import InProcessHost
from sparkdl_tpu.models.gpt import GPTConfig, GPTLMHeadModel
from sparkdl_tpu.reliability import faults
from sparkdl_tpu.reliability.faults import FaultPlan, inject
from sparkdl_tpu.serving import ContinuousGPTEngine
from sparkdl_tpu.serving.queue import DeadlineExceededError

MAX_LEN = 40


@pytest.fixture(scope="module")
def bundle():
    cfg = GPTConfig.tiny()
    model = GPTLMHeadModel(cfg)
    variables = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )
    return cfg, variables


def setup_function(_fn):
    faults.disarm()


def _kw(**over):
    kw = dict(n_slots=2, max_len=MAX_LEN, auto_start=False,
              kv_block_size=4, prefill_chunk=8)
    kw.update(over)
    return kw


def _drain(engine, futs):
    while not all(f.done() for f in futs):
        engine.tick()
    return [f.result(timeout=0) for f in futs]


def _tick_until(engines, futs, timeout_s=30.0):
    t0 = time.monotonic()
    while not all(f.done() for f in futs):
        for e in engines:
            e.tick()
        assert time.monotonic() - t0 < timeout_s, "stalled"
    return futs


# -- export-side faults -------------------------------------------------------

def test_export_fault_releases_blocks_and_requeues_at_head(bundle):
    """An injected ``handoff.export`` fault tears down like _sp_abort:
    every pool block released, the victim back at the QUEUE HEAD, and
    the re-run succeeds — zero loss, no leak."""
    cfg, variables = bundle
    pre = PrefillWorker(cfg, variables, **_kw(n_slots=1))
    try:
        free0 = pre._pool.free_count
        with inject("handoff.export@1"):
            fut = pre.submit(list(range(1, 10)), 4)
            # first attempt aborts; the SAME engine retries from the
            # queue head and succeeds on the second pass
            (h,) = _drain(pre, [fut])
        assert h.first_token >= 0
        assert pre._export_aborts == 1
        assert pre._handoffs == 1
        # abort released everything; the success holds only the cached
        # prompt blocks — evicting them returns the pool to baseline
        pre._prefix.evict(pre._pool.n_blocks)
        assert pre._pool.free_count == free0
    finally:
        pre.close()


def test_export_abort_requeues_ahead_of_later_arrivals(bundle):
    """The faulted victim is OWED its place: with one slot, the abort
    puts it back ahead of requests that arrived after it."""
    cfg, variables = bundle
    pre = PrefillWorker(cfg, variables, **_kw(n_slots=1))
    try:
        with inject("handoff.export@1"):
            fa = pre.submit(list(range(1, 9)), 4)    # victim
            fb = pre.submit(list(range(11, 19)), 4)  # later arrival
            pre.tick()  # admits A; prefill + export fault -> requeue
            ids = [r.request_id for r in pre.queue._dq]
            assert ids == sorted(ids) and len(ids) == 2
            assert ids[0] == fa.request_id  # victim ahead of B
            _drain(pre, [fa, fb])
        assert fa.result(timeout=0).request_id == fa.request_id
        assert fb.result(timeout=0).request_id == fb.request_id
    finally:
        pre.close()


# -- install-side faults ------------------------------------------------------

def test_install_fault_raises_typed_error_and_leaks_nothing(bundle):
    cfg, variables = bundle
    pre = PrefillWorker(cfg, variables, **_kw())
    dec = DecodeWorker(cfg, variables, **_kw())
    try:
        (h,) = _drain(pre, [pre.submit(list(range(1, 10)), 4)])
        free0 = dec._pool.free_count
        with inject("handoff.install@1"):
            fut = dec.submit_handoff(h)
            while not fut.done():
                dec.tick()
        with pytest.raises(HandoffInstallError):
            fut.result(timeout=0)
        assert dec._install_faults == 1
        assert dec._pool.free_count == free0  # fault fired pre-alloc
        # the same handoff installs cleanly afterwards
        (r,) = _drain(dec, [dec.submit_handoff(h)])
        assert len(np.asarray(r)) == 4
    finally:
        pre.close()
        dec.close()


def test_phase_router_requeues_install_victim_ahead_of_later_arrivals(
        bundle):
    """The cross-tier half of the requeue-ordering contract: a handoff
    lost at the DECODE tier re-enters the PREFILL tier's queue head —
    ahead of requests that arrived while it was crossing."""
    cfg, variables = bundle
    pre = PrefillWorker(cfg, variables, **_kw(n_slots=1))
    dec = DecodeWorker(cfg, variables, **_kw())
    pr = PhaseRouter([InProcessHost(pre, host_id="p0")],
                     [InProcessHost(dec, host_id="d0")],
                     auto_refresh=False)
    try:
        with inject("handoff.install@1"):
            fa = pr.submit(list(range(1, 10)), 4)  # the victim
            while dec.queue.depth == 0:  # A crosses to the decode tier
                pre.tick()
            fb = pr.submit(list(range(11, 20)), 4)  # later arrivals
            fc = pr.submit(list(range(21, 30)), 4)
            depth0 = pre.queue.depth
            assert depth0 == 2  # B, C waiting
            dec.tick()  # install fault -> victim back at prefill HEAD
            ids = [r.request_id for r in pre.queue._dq]
            assert len(ids) == 3
            assert ids[0] == min(ids)  # A (earliest id) leads the queue
            _tick_until([pre, dec], [fa, fb, fc])
        snap = pr.snapshot()["disagg"]
        assert snap["requeues"] == 1
        assert snap["failed"] == 0
        assert snap["completed"] == 3
        for f in (fa, fb, fc):
            assert len(np.asarray(f.result(timeout=0))) == 4
    finally:
        pr.close()
        pre.close()
        dec.close()


def test_identity_survives_the_tier_crossing(bundle):
    """One request, one identity: the decode-side Future carries the
    PREFILL-side request id, and the handoff's deadline still binds."""
    cfg, variables = bundle
    pre = PrefillWorker(cfg, variables, **_kw())
    dec = DecodeWorker(cfg, variables, **_kw())
    try:
        fut = pre.submit(list(range(1, 8)), 5, timeout_s=60.0)
        (h,) = _drain(pre, [fut])
        assert h.request_id == fut.request_id
        assert h.deadline is not None
        dfut = dec.submit_handoff(h)
        assert dfut.request_id == h.request_id
        (r,) = _drain(dec, [dfut])
        assert len(np.asarray(r)) == 5
    finally:
        pre.close()
        dec.close()


def test_deadline_expiry_mid_handoff_leaks_no_blocks(bundle):
    """A handoff whose deadline lapses while queued at the decode tier
    fails typed and allocates NOTHING: the staging copy lives on the
    wire object, not in either pool, so expiry cannot leak."""
    cfg, variables = bundle
    pre = PrefillWorker(cfg, variables, **_kw())
    dec = DecodeWorker(cfg, variables, **_kw())
    try:
        (h,) = _drain(pre, [pre.submit(list(range(1, 10)), 4)])
        h.deadline = time.monotonic() - 0.01  # lapsed in transit
        free0 = dec._pool.free_count
        fut = dec.submit_handoff(h)
        while not fut.done():
            dec.tick()
        with pytest.raises(DeadlineExceededError):
            fut.result(timeout=0)
        assert dec._pool.free_count == free0
        assert dec._installs == 0
        # prefill side released its holds at export: evicting the
        # cached prompt blocks returns that pool to full too
        pre._prefix.evict(pre._pool.n_blocks)
        assert pre._pool.free_count == pre._pool.n_blocks
    finally:
        pre.close()
        dec.close()


# -- chaos soak ---------------------------------------------------------------

class _RequestStreams:
    """What a ``%p`` rule draws from (``FaultPlan._rng``), one seeded
    stream a REQUEST: a prompt's n-th install draws the same number
    whichever decode host tries it, on whichever thread, before or after
    whichever other. One stream over all requests hands its hits out by
    thread timing, and the request that happened to take five in a row
    was out of retries in 11 and 21 runs of 390 (PR 27)."""

    def __init__(self, seed):
        self._seed = seed
        self._streams = {}
        self._installing = threading.local()

    def watch(self, worker):
        admit = worker._admit_handoff

        def admit_handoff(slot, req):
            self._installing.prompt = tuple(
                int(t) for t in req.payload.prompt)
            return admit(slot, req)

        worker._admit_handoff = admit_handoff

    def random(self):  # under the plan's lock
        prompt = self._installing.prompt
        if prompt not in self._streams:
            self._streams[prompt] = random.Random(f"{self._seed}:{prompt}")
        return self._streams[prompt].random()


def test_soak_prefill_host_kill_and_install_faults_lose_nothing(bundle):
    """The acceptance bar: a stream of requests through a 2-prefill /
    2-decode fabric, one prefill host killed mid-soak, probabilistic
    install faults throughout — every accepted request completes with
    correct-length output and the PhaseRouter's counters reconcile."""
    cfg, variables = bundle
    pres = [PrefillWorker(cfg, variables, host_id=f"p{i}",
                          **_kw(auto_start=True)) for i in range(2)]
    decs = [DecodeWorker(cfg, variables, host_id=f"d{i}",
                         **_kw(auto_start=True)) for i in range(2)]
    pr = PhaseRouter([InProcessHost(e, host_id=e.host_id) for e in pres],
                     [InProcessHost(e, host_id=e.host_id) for e in decs],
                     auto_refresh=False, max_handoff_retries=4)
    rng = np.random.RandomState(7)
    plan = FaultPlan.parse("handoff.install%0.2")
    plan._rng = _RequestStreams(seed=7)
    for dec in decs:
        plan._rng.watch(dec)
    try:
        with inject(plan):
            futs = []
            for i in range(24):
                p = rng.randint(0, 50, size=rng.randint(4, 14)).tolist()
                futs.append((pr.submit(p, 4), 4))
                if i == 11:
                    # kill one prefill host mid-soak: drain re-queues
                    # its unstarted work on the survivor
                    pr.prefill.remove_host("p0", drain=True)
            for f, m in futs:
                out = np.asarray(f.result(timeout=300))
                assert len(out) == m
        snap = pr.snapshot()["disagg"]
        assert snap["submitted"] == 24
        assert snap["completed"] == 24
        assert snap["failed"] == 0
        assert snap["requeues"] >= 1  # the faults really fired
    finally:
        pr.close()
        for e in pres + decs:
            e.close()
