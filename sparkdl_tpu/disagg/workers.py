"""Tier workers: the prefill-only and decode-only engine modes.

Both are thin subclasses of
:class:`~sparkdl_tpu.serving.continuous.ContinuousGPTEngine` — the
split reuses the colocated engine's admission, chunked prefill,
deferral, expiry, and decode machinery wholesale and overrides exactly
the two seams where a phase boundary exists:

* :class:`PrefillWorker` ends a request where decode would begin:
  ``_finish_prefill`` exports the prompt's pool blocks (raw storage —
  int8 pools ship quantized bytes + scales) instead of occupying a
  decode slot, and its Futures resolve to
  :class:`~sparkdl_tpu.disagg.handoff.KVHandoff`. Admission reserves
  PROMPT blocks only (``_admission_budget_tokens`` → 0): the tier's
  pool capacity is spent entirely on prefill concurrency, which is why
  a prefill tier absorbs long prompts without inflating anyone's
  decode latency.
* :class:`DecodeWorker` begins a request where prefill ended:
  ``submit_handoff`` adopts a transferred handoff into the queue
  (already-accepted — depth limits do not re-reject it) and
  ``_admit_handoff`` installs the wire blocks through the engine's own
  quantizing write path, then hands the slot to the untouched decode
  loop. No prompt token is ever re-run on the decode tier.

Failure surfaces are the two fault sites: ``handoff.export`` tears
down like ``_sp_abort`` (blocks released, victim re-queued at the
head, zero loss) and ``handoff.install`` raises the typed
:class:`~sparkdl_tpu.disagg.handoff.HandoffInstallError` the
:class:`~sparkdl_tpu.disagg.PhaseRouter` converts into a prefill-tier
requeue.
"""

from __future__ import annotations

import time
from concurrent.futures import Future
from typing import Any

import numpy as np

from sparkdl_tpu.observability import flight as flight_mod
from sparkdl_tpu.observability import tracing
from sparkdl_tpu.observability.tracing import span
from sparkdl_tpu.reliability.faults import fault_point
from sparkdl_tpu.serving.continuous import ContinuousGPTEngine, _InFlight
from sparkdl_tpu.serving.queue import Request

from sparkdl_tpu.disagg.handoff import (
    _M_HANDOFF_BYTES,
    _M_HANDOFF_SECONDS,
    _M_HANDOFFS,
    HandoffInstallError,
    KVHandoff,
    observe_phase,
)

__all__ = ["DecodeWorker", "PrefillWorker"]


def _refuse_state(engine: ContinuousGPTEngine, what: str) -> None:
    """A handoff is K/V blocks. A family with state layers keeps, beside
    them, arrays by slot that no block holds (a recurrent state; a window
    layer's ring of its last columns), and a sequence cannot go on without
    them: refused, by name, until a payload carries them."""
    fam = engine._family
    if fam.state_layers:
        raise NotImplementedError(
            f"{what} is not implemented for {type(engine.config).__name__}"
            f": {fam.state_layers} of its layers keep arrays by slot (a "
            "recurrent state, or a window's last columns), which a handoff "
            "of K/V blocks does not carry")
    if fam.block_arrays:
        raise NotImplementedError(
            f"{what} is not implemented for {type(engine.config).__name__}"
            ": what it keeps a token is "
            f"{' and '.join(name for name, _, _ in fam.block_arrays)}, each "
            "over its own layers, and a handoff's payload is K and V")


class PrefillWorker(ContinuousGPTEngine):
    """A :class:`ContinuousGPTEngine` that ONLY prefills (see module
    docstring). ``submit()`` keeps the colocated signature; the Future
    resolves to a :class:`KVHandoff` instead of generated ids. Chunked
    (and, with ``sp > 1``, sequence-parallel) prefill, prefix caching,
    deferral, and deadline expiry all behave exactly as on the
    colocated engine."""

    def __init__(self, config, variables, **kwargs):
        auto_start = kwargs.pop("auto_start", True)
        super().__init__(config, variables, auto_start=False, **kwargs)
        self._handoffs = 0
        self._export_aborts = 0
        if auto_start:
            self.start()

    def submit(self, prompt_ids, max_new_tokens: int, **kwargs) -> Future:
        _refuse_state(self, "exporting a prefill to a decode tier")
        return super().submit(prompt_ids, max_new_tokens, **kwargs)

    def _admission_budget_tokens(self, max_new_tokens: int) -> int:
        # prompt blocks only: the decode tier owns the generation span
        return 0

    def _finish_prefill(self, slot, st, first) -> None:
        """Export instead of decode: package the prompt's pool blocks
        (+ the first decode token the final chunk computed) as a
        :class:`KVHandoff` and resolve the Future with it. The prompt
        stays registered in THIS tier's prefix cache, so a later prompt
        sharing the prefix prefills only its suffix before exporting."""
        import jax.numpy as jnp

        from sparkdl_tpu.runtime.batching import pow2_bucket

        try:
            # the injectable stand-in for a failed export gather or a
            # dead wire: fires BEFORE the prefix registration, so the
            # abort path releases a state identical to _sp_abort's
            fault_point("handoff.export")
        except Exception as e:
            self._export_abort(slot, st, e)
            return
        blocks = st.shared + st.owned
        plen = len(st.prompt)
        nbp = -(-plen // self._kv_bs)
        row = [int(b) for b in blocks[:nbp]]
        # register BEFORE releasing the request's holds: the cache's
        # own hold keeps the prompt blocks alive for prefix reuse
        self._prefix.register(tuple(int(t) for t in st.prompt), row)
        t0 = time.perf_counter()
        with span("disagg.handoff_export", parent=st.req.trace_ctx,
                  request_id=st.req.request_id, slot=slot, blocks=nbp):
            wb = pow2_bucket(nbp, 1, self._mb)
            ids = np.full((wb,), self._pool.sentinel, np.int32)
            ids[:nbp] = row
            # the export is the raw-storage gather a park makes: NO
            # dequantize — the wire ships the pool's own bytes (int8 +
            # scales, or fp32/bf16 values), so the decode-side install's
            # requantize round-trips exactly
            out = self._park_fetch_fn(self._pool_kv, jnp.asarray(ids))
            # np.asarray forces the gather to COMPLETE before the block
            # references drop below (releasing first would let an
            # eviction + realloc overwrite a block mid-copy)
            out = {name: np.asarray(x)[:, :nbp] for name, x in out.items()}
        _M_HANDOFF_SECONDS.observe(time.perf_counter() - t0)
        del self._prefilling[slot]
        self._prefix.release(blocks)
        # phase boundaries (ISSUE 17): the export stamp ends this
        # tier's work; queue/prefill ship as measured DURATIONS so the
        # decode tier can publish all five phases without sharing a
        # clock with us
        exported_at = time.monotonic()
        taken = st.req.taken_at if st.req.taken_at is not None \
            else st.req.enqueued
        h = KVHandoff(
            prompt=st.prompt, max_new_tokens=st.max_new,
            first_token=int(first), kv_dtype=self.kv_dtype,
            block_size=self._kv_bs,
            k=out["k"], v=out["v"],
            k_scale=out.get("k_scale"), v_scale=out.get("v_scale"),
            request_id=st.req.request_id, deadline=st.req.deadline,
            enqueued=st.req.enqueued, trace_ctx=st.req.trace_ctx,
            src_host=self.host_id,
            exported_at=exported_at,
            queue_wait_s=max(0.0, taken - st.req.enqueued),
            prefill_s=max(0.0, exported_at - taken),
            incident_id=flight_mod.current_incident_id())
        self._handoffs += 1
        _M_HANDOFFS.inc(stage="export")
        _M_HANDOFF_BYTES.inc(h.wire_bytes)
        flight_mod.record_event(
            "disagg.handoff_export", request_id=st.req.request_id,
            host=self.host_id, blocks=nbp, bytes=h.wire_bytes)
        now = time.monotonic()
        self._record_request_span(st.req, now, ok=True, tokens=1)
        self.metrics.record_tokens(1, phase="prefill")
        st.req.future.set_result(h)
        self.metrics.record_request(now - st.req.enqueued, ok=True)

    def _export_abort(self, slot, st, exc: Exception) -> None:
        """An injected ``handoff.export`` fault: tear down exactly like
        ``_sp_abort`` — every block released (staging included), victim
        re-queued at the HEAD (it is owed its place ahead of later
        arrivals), nothing lost. The re-run re-prefills from scratch;
        correctness over the partial work."""
        del self._prefilling[slot]
        self._release_sp_staging(st)
        self._prefix.release(st.all_blocks())
        self._export_aborts += 1
        flight_mod.record_event(
            "disagg.handoff_export_failed",
            request_id=st.req.request_id, host=self.host_id,
            error=type(exc).__name__, prompt_tokens=len(st.prompt))
        self.queue.requeue([st.req])

    def snapshot(self) -> "dict[str, Any]":
        out = super().snapshot()
        out["disagg"] = {"tier": "prefill", "handoffs": self._handoffs,
                         "export_aborts": self._export_aborts}
        return out


class DecodeWorker(ContinuousGPTEngine):
    """A :class:`ContinuousGPTEngine` whose slots start at decode (see
    module docstring). Regular ``submit()`` still works (a decode tier
    can colocate small prompts); ``submit_handoff`` is the cross-tier
    admission surface :class:`~sparkdl_tpu.fabric.host.InProcessHost`
    and the HTTP transport route ``{"handoff": ...}`` payloads to."""

    def __init__(self, config, variables, **kwargs):
        auto_start = kwargs.pop("auto_start", True)
        super().__init__(config, variables, auto_start=False, **kwargs)
        self._installs = 0
        self._install_faults = 0
        if auto_start:
            self.start()

    # -- cross-tier admission -------------------------------------------------
    def submit_handoff(self, handoff: KVHandoff, *,
                       timeout_s: "float | None" = None) -> Future:
        """Adopt one finished prefill. The Future resolves to generated
        ids exactly like ``submit()``'s would have (first token
        included), so callers cannot tell the phases were split.

        Identity carries over: the handoff's request id IS this
        request's id (one trace end to end), its original enqueue stamp
        feeds latency accounting, and its absolute deadline still
        binds (tightened by ``timeout_s`` if given). The request
        enters via ``queue.adopt`` — already accepted upstream, so the
        depth limit never re-rejects it."""
        h = handoff
        _refuse_state(self, "adopting another tier's prefill")
        if int(h.block_size) != self._kv_bs:
            raise ValueError(
                f"handoff block_size {h.block_size} != decode tier "
                f"block_size {self._kv_bs}: tiers must agree on the "
                "block geometry")
        plen = len(h.prompt)
        if plen + h.max_new_tokens > self.max_len:
            raise ValueError(
                f"prompt ({plen}) + max_new_tokens ({h.max_new_tokens})"
                f" exceeds decode-tier max_len {self.max_len}")
        need = -(-(plen + h.max_new_tokens) // self._kv_bs)
        if need > self._pool.n_blocks:
            raise ValueError(
                f"request needs {need} KV blocks; decode-tier pool has "
                f"{self._pool.n_blocks} total — it can never fit")
        deadline = h.deadline
        if timeout_s is not None:
            cap = time.monotonic() + timeout_s
            deadline = cap if deadline is None else min(deadline, cap)
        if h.arrived_at is None:
            # in-process crossing (no from_wire): arrival is now
            h.arrived_at = time.monotonic()
        # postmortem correlation (ISSUE 17): if the prefill tier was
        # mid-incident at export, this tier's next dump joins it
        flight_mod.adopt_incident(h.incident_id)
        rid = int(h.request_id) or tracing.next_request_id()
        fut: Future = Future()
        fut.request_id = rid
        # straight to RUNNING: adopted requests skip take()'s handshake
        # (started=True), and a PENDING Future could be cancelled out
        # from under the install
        fut.set_running_or_notify_cancel()
        req = Request(
            h, fut, deadline,
            h.enqueued if h.enqueued else time.monotonic(),
            trace_ctx=(h.trace_ctx if h.trace_ctx is not None
                       else tracing.request_context(rid)),
            request_id=rid,
            started=True)
        self.queue.adopt(req)
        return fut

    def _admit(self, slot: int, req: Request) -> bool:
        if isinstance(req.payload, KVHandoff):
            return self._admit_handoff(slot, req)
        return super()._admit(slot, req)

    def _admit_handoff(self, slot: int, req: Request) -> bool:
        """Install a transferred handoff into this tier's pool and
        start decode with NO re-prefill. Mirrors ``_admit`` +
        ``_finish_prefill``: longest-prefix match first (full blocks
        only — the wire carries every block whole, so a partial-tail
        COW copy buys nothing), worst-case allocation under the same
        deferral protocol, install through the shared quantizing write
        path, then prefix registration so the transferred prompt is
        shareable on THIS tier too. Returns False on pool exhaustion
        (caller defers — the handoff duck-types GenRequest). Raises
        :class:`HandoffInstallError` when the ``handoff.install`` site
        fires — a request-level error the PhaseRouter answers with a
        prefill-tier requeue."""
        import jax.numpy as jnp

        try:
            fault_point("handoff.install")
        except Exception as e:
            self._install_faults += 1
            flight_mod.record_event(
                "disagg.handoff_install_failed",
                request_id=req.request_id, host=self.host_id,
                error=type(e).__name__)
            raise HandoffInstallError(
                f"KV handoff install failed on host {self.host_id}: "
                f"{e!r}") from e
        h: KVHandoff = req.payload
        prompt = np.asarray(h.prompt, np.int32)
        plen = len(prompt)
        toks = tuple(int(t) for t in prompt)
        nbp = -(-plen // self._kv_bs)
        nb_total = -(-(plen + h.max_new_tokens) // self._kv_bs)
        m = self._prefix.match(toks[:-1])
        if m.partial_block is not None:
            # full blocks only (see docstring): drop the partial hold
            self._prefix.release([m.partial_block])
        shared = m.full_blocks
        n_shared = len(shared)
        try:
            owned = self._alloc_blocks(nb_total - n_shared)
        except Exception as e:
            # an injected kv.alloc fault is exhaustion here too: defer,
            # never fail the transferred request
            flight_mod.record_event(
                "kv.alloc_error", error=type(e).__name__,
                request_id=req.request_id)
            owned = None
        if owned is None:
            self._prefix.release(shared)
            self._defer_pool = self._pool
            return False
        # commit point: blocks are allocated, the install WILL run.
        # Everything before this stamp is decode-queue time; everything
        # after (install + decode loop) is decode-compute time.
        t_adm = time.monotonic()
        self._prefix.record_lookup(m.hit_tokens, plen - m.hit_tokens)
        if m.hit_tokens:
            flight_mod.record_event(
                "kv.prefix_hit", request_id=req.request_id,
                hit_tokens=m.hit_tokens, prompt_tokens=plen)
        # install targets: owned blocks at the non-shared prompt
        # positions; sentinel at shared positions (their content is the
        # cached blocks') and past the prompt (decode writes those)
        inst = np.full((self._mb,), self._pool.sentinel, np.int32)
        inst[n_shared:nbp] = owned[:nbp - n_shared]
        kdata, vdata = self._wire_to_compute(h)
        t0 = time.perf_counter()
        with span("disagg.handoff_install", parent=req.trace_ctx,
                  request_id=req.request_id, slot=slot, blocks=nbp,
                  shared_blocks=n_shared):
            # the engine's own install program (the sp handoff's too):
            # quantized pools quantize there, once
            self._pool_kv = self._install_blocks_fn(
                self._pool_kv, kdata, vdata, jnp.asarray(inst))
        _M_HANDOFF_SECONDS.observe(time.perf_counter() - t0)
        _M_HANDOFFS.inc(stage="install")
        self._installs += 1
        row = np.full((self._mb,), self._pool.sentinel, np.int32)
        row[:n_shared] = shared
        row[n_shared:nb_total] = owned
        self._table[slot] = row
        self._prefix.register(toks, [int(b) for b in row[:nbp]])
        fl = _InFlight(req, [int(h.first_token)], h.max_new_tokens,
                       blocks=shared + owned, prompt=prompt)
        self._join_decode(slot, fl, plen)
        self._pool.reset_deferral_streak()
        # latency attribution (ISSUE 17): this is the single place all
        # five request phases publish from — the prefill tier shipped
        # its two as measured durations; wire/queue/compute are local
        # stamps on THIS clock. fl carries the admit stamp so
        # _complete() can close the (compute, decode) phase.
        arrived = h.arrived_at if h.arrived_at is not None else t_adm
        observe_phase("queue", "prefill", h.queue_wait_s)
        observe_phase("compute", "prefill", h.prefill_s)
        if h.exported_at is not None:
            wire_s = max(0.0, arrived - h.exported_at)
            observe_phase("wire", "handoff", wire_s)
            # the wire crossing as a span: recorded retroactively on
            # the DECODE host (re-anchored export stamp → install end),
            # parented into the request's one fleet-wide trace
            tracing.record_span(
                "handoff.wire", h.exported_at, time.monotonic(),
                parent=req.trace_ctx, request_id=req.request_id,
                src_host=h.src_host, dst_host=self.host_id,
                bytes=h.wire_bytes, wire_s=wire_s,
                decode_queue_s=max(0.0, t_adm - arrived),
                # the prefill tier's measured durations ride along so
                # fleet stitching reads ALL five phases off this one
                # span (stitch_phase_breakdown)
                queue_wait_s=float(h.queue_wait_s),
                prefill_s=float(h.prefill_s))
        observe_phase("queue", "decode", max(0.0, t_adm - arrived))
        fl._phase_admit_start = t_adm
        flight_mod.record_event(
            "disagg.handoff_installed", request_id=req.request_id,
            host=self.host_id, blocks=nbp, shared_blocks=n_shared,
            src_host=h.src_host)
        if self._is_done(fl):  # max_new_tokens=1, or instant eos
            self._complete(fl)
        return True

    def _complete(self, flight: _InFlight) -> None:
        # close the (compute, decode) phase for adopted handoffs: the
        # admit stamp rides the _InFlight (dies with it — failure-safe)
        t_adm = getattr(flight, "_phase_admit_start", None)
        if t_adm is not None:
            observe_phase("compute", "decode",
                          time.monotonic() - t_adm)
        super()._complete(flight)

    def _wire_to_compute(self, h: KVHandoff):
        """Wire storage → install-ready fp32 block data, padded to the
        table width (the pad lands on sentinel targets and drops).
        int8 wire dequantizes exactly (``q·s``); since the wire values
        ORIGINATED from the storage dtype, every downstream cast or
        requantize round-trips exactly — transferred blocks land
        bitwise-identical to locally prefilled ones."""
        k = np.asarray(h.k)
        v = np.asarray(h.v)
        if h.k_scale is not None:
            # one scale a column, whatever trailing axes a column has
            lead = np.shape(h.k_scale) + (1,) * (k.ndim - 3)
            k = (k.astype(np.float32)
                 * np.asarray(h.k_scale, np.float32).reshape(lead))
            v = (v.astype(np.float32)
                 * np.asarray(h.v_scale, np.float32).reshape(lead))
        else:
            k = k.astype(np.float32)
            v = v.astype(np.float32)
        pad = self._mb - k.shape[1]
        if pad > 0:
            ps = (k.shape[0], pad) + k.shape[2:]
            k = np.concatenate([k, np.zeros(ps, k.dtype)], axis=1)
            v = np.concatenate([v, np.zeros(ps, v.dtype)], axis=1)
        return k, v

    def snapshot(self) -> "dict[str, Any]":
        out = super().snapshot()
        out["disagg"] = {"tier": "decode", "installs": self._installs,
                         "install_faults": self._install_faults}
        return out
