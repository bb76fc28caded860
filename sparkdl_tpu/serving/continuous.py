"""Continuous batching for GPT decode: rows join and leave mid-stream.

The lockstep ``generate`` path (models/gpt.py) starts a batch together
and ends it together, so one long row holds every slot hostage and new
arrivals wait for the whole batch to finish — fatal for online serving.
This engine keeps ONE persistent decode batch of ``n_slots`` rows over a
pool of K/V blocks that each row reaches through its own block table:

- a finished row frees its slot immediately;
- a newly admitted prompt is prefilled ALONE (batch-1, in bounded chunks)
  and its K/V installed into the blocks of the free slot's row — the
  in-flight neighbors never notice;
- every engine tick advances all live rows one token in a single jitted
  step whose per-row causal mask lets each row decode at its own depth.

Token identity: greedy tokens of every request are IDENTICAL to its
unbatched ``generate`` decode (tests/serving/test_continuous_gpt.py) —
batching is a scheduling decision, never a quality decision.

Decode is greedy (temperature 0), the deterministic serving default;
sampled decode stays on the lockstep ``DeepTextGenerator`` path.

Three parts, one to a module, the arrows pointing one way:

- the POOL'S DEVICE FORMAT, :mod:`sparkdl_tpu.models.kv_pool`: how a
  token's K and V lie in the block pool (trailing axes, storage dtype and
  scales, the sentinel id) and the reads and writes of it that the chip
  compiles in place;
- the DEVICE PROGRAMS, :mod:`sparkdl_tpu.serving.paged_programs`: the
  decode step, the verify pass, the four chunk programs, the tier and
  handoff copies, as named functions of (sizes, module, arrays);
- the SCHEDULER, this module: queue, slots, host block tables
  (:mod:`~sparkdl_tpu.serving.kv_blocks`), prefix cache, admission and
  retirement. ``__init__`` binds each program to a ``jax.jit`` handle and
  defines none.

A TICK OF THE PAGED LOOP, in order (``tick``): a deadline that has expired
fails its row; free slots admit from the queue; the prefilling rows'
chunks are dispatched; step n+1 is LAUNCHED for every live row with budget
left, taking each row's token from step n's output where it still lies on
the device; a prompt whose last chunk was dispatched above is waited for
and its first token read (the row joins the decode batch with the host's
word for its token, and rides step n+2); and only then are step n's ids
waited for, read and retired. So the loop runs one step ahead of the host:
from one launch to the next, whatever the host does (the waits, the
reads, retiring, admitting, the chunk's dispatch, the launch itself) has a
decode step queued or running under it. The cursor ``_pidx`` moves at the
launch, by one a token whatever the token is. A row that ends by its
budget is known by count: it rides no step past it, and gives its slot
back when its last step is launched (its blocks when its ids are read). A
row that ends by ``eos_id`` is found one step late and rides one step
more, whose token is dropped and whose one column falls in a block the row
still owned at the launch (admission reserves prompt + budget). Whatever
touches a live row's blocks or ``produced`` outside that order (an
expiring deadline, a preemption, ``close``, ``begin_drain``,
``park_cold``, ``export_parked_sessions``, ``snapshot``) first collects
the step in flight (``_collect``). A speculative engine (``spec_k``)
launches, reads and retires in one tick.

THE KV CACHE: each slot's columns map onto refcounted ``block_size``-token
blocks through a block table; the decode step hands the model the pool and
the table's live head as a PAGED cache, so persistent KV memory is bounded
by allocated tokens, not ``n_slots x max_len``. Admission against an
exhausted pool DEFERS (re-queues in order) instead of erroring. Prompts
are prefilled right-aligned in bounded CHUNKS (``prefill_chunk`` tokens per engine tick,
interleaved with decode ticks — a long prompt does not freeze in-flight
decode latency), and a radix prefix cache
(:mod:`~sparkdl_tpu.serving.prefix_cache`) lets a request reuse the cached
K/V of its longest shared prompt prefix and prefill only the suffix
(partial tail blocks shared copy-on-write). Greedy tokens stay
oracle-identical on every path (tests/serving/test_kv_paged.py).

Speculative multi-token decoding (``spec_k=``, ROADMAP item 3): a
draft source (:mod:`~sparkdl_tpu.serving.spec_decode` — radix-trie
continuations + n-gram self-lookup by default, any ``propose()``
object, e.g. a small draft model, via ``draft_source=``) proposes up
to ``k-1`` tokens per live slot, and ONE verify dispatch scores the
whole span (the L=k per-slot step in models/gpt.py): every accepted
draft token is a decode dispatch never issued. Greedy acceptance is
exact, so accepted tokens are bitwise-identical to one-token-at-a-time
decode at every draft length — the engine's oracle contract extends
unchanged (tests/serving/test_spec_decode.py). The verify width is
re-bounded every tick by the same budget/deadline caps as
``chain_tokens`` plus the measured acceptance rate
(:class:`~sparkdl_tpu.runtime.dispatch.SpecPolicy`). The
``spec.verify`` fault site fires BEFORE the verify is dispatched (the
injectable stand-in for a verify that cannot run): the tick falls back
to plain decode — zero lost requests. An error raised by the dispatch
itself is NOT caught: the pool buffer is donated, so there is no valid
state to fall back to — it propagates like any decode-dispatch error
(the engine loop fails every pending Future loudly rather than serving
from a consumed cache).

Quantized KV blocks (``kv_dtype=``): the pool can store ``"bf16"``
or ``"int8"`` (one fp32 scale per written column) instead of the compute
dtype, 2-4x the capacity
(:func:`~sparkdl_tpu.serving.kv_blocks.kv_capacity_ratio`); the rule is
``kv_pool``'s and compute still runs at the model dtype.

Sequence-parallel prefill (``sp=``, ROADMAP item 2): with ``sp=N``
the chunked prefill becomes SPATIAL — each chunk dispatches across N
chips (queries sharded on the ``sp`` mesh axis, K/V all-gathered for
the causal attention) and the accumulating prompt K/V lives in a
sequence-sharded staging pool
(:class:`~sparkdl_tpu.serving.kv_blocks.SeqShardedBlockPool`), so a
long context never has to fit one chip during prefill. ONE gather at
the prefill→decode handoff (``sp.gather`` fault site) installs the
staged K/V into the decode pool; the per-token loop — plain, chained,
speculative — is the untouched single-device path, which is why
greedy tokens stay bitwise across sp∈{1,2} on every decode mode. An
injected collective fault (``sp.permute``/``sp.gather``) re-queues the
victim request instead of failing it (:class:`SpCollectiveError` in
the flight ring). README "Long-context serving" has the sizing
arithmetic; nothing across chips has been measured on the chip (PERF.md
section 7).
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import threading
import time
from concurrent.futures import Future
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from sparkdl_tpu.models.kv_pool import init_block_pool
from sparkdl_tpu.observability import flight as flight_mod
from sparkdl_tpu.observability import slo as slo_mod
from sparkdl_tpu.observability import tracing
from sparkdl_tpu.observability.registry import GaugeShare, registry
from sparkdl_tpu.observability.tracing import span
from sparkdl_tpu.ops.sparse_attention import attends_in_place
from sparkdl_tpu.reliability.faults import fault_point
from sparkdl_tpu.runtime.batching import pow2_bucket
from sparkdl_tpu.runtime.chip import alike_layers_options, watch_compiles
from sparkdl_tpu.runtime.completion import start_fetch
from sparkdl_tpu.runtime.dispatch import (
    ChainPolicy,
    SpecPolicy,
    record_dispatch,
)
from sparkdl_tpu.serving import kv_tiers as kv_tiers_mod
from sparkdl_tpu.serving import paged_programs as programs
from sparkdl_tpu.serving import tenancy
from sparkdl_tpu.serving.kv_blocks import (
    KVBlockPool,
    SeqShardedBlockPool,
    kv_bytes_per_token,
    kv_capacity_ratio,
)
from sparkdl_tpu.serving.metrics import (
    EngineObservability,
    ServingMetrics,
    default_host_id,
)
from sparkdl_tpu.serving.paged_programs import bound
from sparkdl_tpu.serving.prefix_cache import PrefixCache, PrefixMatch
from sparkdl_tpu.serving.queue import (
    DeadlineExceededError,
    EngineClosedError,
    Request,
    RequestQueue,
    record_request_failure,
)
from sparkdl_tpu.serving.spec_decode import (
    default_draft_source,
    greedy_accept,
)


_M_PREFILL_CHUNKS = registry().counter(
    "sparkdl_prefill_chunks_total",
    "bounded prefill chunks dispatched by continuous GPT engines")

_M_SP_RING_STEPS = registry().counter(
    "sparkdl_sp_ring_steps_total",
    "collective hops dispatched by sequence-parallel prefill chunks "
    "(sp - 1 per sharded chunk dispatch)")
_M_SP_PERMUTE_BYTES = registry().counter(
    "sparkdl_sp_permute_bytes_total",
    "estimated K/V bytes moved between sp chips by prefill collectives "
    "(2 x layers x chunk_width x hidden x itemsize x (sp-1) per "
    "dispatch)")


_M_STATE_BYTES = registry().gauge(
    "sparkdl_linear_state_bytes",
    "device bytes of recurrent state held by slot (the linear-attention "
    "layers' states and convolution tails), all engines")
_M_RING_BYTES = registry().gauge(
    "sparkdl_window_ring_bytes",
    "device bytes of window layers' rings held by slot (the last "
    "``window`` columns of K and V a slot a window layer, whatever the "
    "contexts' lengths), all engines")
_M_TAIL_BYTES = registry().gauge(
    "sparkdl_conv_tail_bytes",
    "device bytes of short convolutions' tails held by slot (the last "
    "inputs of a gated short convolution a slot a layer, whatever the "
    "contexts' lengths), all engines")
_M_LATENT_BYTES = registry().gauge(
    "sparkdl_latent_pool_bytes",
    "device bytes of the block arrays a family names itself (a latent "
    "attention's one column a token a layer, an indexer's keys), all "
    "engines")
_M_SCAN_TOKENS = registry().counter(
    "sparkdl_linear_scan_tokens_total",
    "real prompt tokens taken through the chunkwise recurrence of a "
    "family with state layers")

_M_DECODE_AHEAD = registry().counter(
    "sparkdl_serving_decode_ahead_total",
    "paged decode steps launched, by whether the step before was still "
    "unread on the device (ahead=1: the host's part of a tick ran under "
    "it) or not (ahead=0: a row-set's first step, or the loop was "
    "settled in between)", labels=("ahead",))


class SpCollectiveError(RuntimeError):
    """A sequence-parallel collective (ring permute hop or the
    prefill→decode handoff gather) failed. The engine never surfaces
    this to a caller: the victim request's prefill is torn down, its
    blocks released, and the request RE-QUEUED at the head — an
    already-admitted request is never lost to a collective fault (the
    ``sp.permute`` / ``sp.gather`` chaos contract)."""

_M_SPEC_PROPOSED = registry().counter(
    "sparkdl_spec_proposed_total",
    "draft tokens proposed to speculative verify dispatches")
_M_SPEC_ACCEPTED = registry().counter(
    "sparkdl_spec_accepted_total",
    "proposed draft tokens accepted by greedy verify (each one a "
    "decode dispatch never issued)")
_M_SPEC_RATE = registry().gauge(
    "sparkdl_spec_acceptance_rate",
    "cumulative accepted/proposed draft share across this process's "
    "speculative engines")
_M_SPEC_FALLBACKS = registry().counter(
    "sparkdl_spec_fallbacks_total",
    "speculative verify dispatches abandoned to plain decode "
    "(spec.verify fault site)")

#: Process-wide propose/accept totals behind the acceptance-rate gauge.
#: Several engines contribute from their own loop threads — their
#: engine locks are DIFFERENT locks, so this shared state needs its own.
_SPEC_TOTALS = {"proposed": 0, "accepted": 0}
_SPEC_TOTALS_LOCK = threading.Lock()

#: Consecutive pool-exhaustion deferrals before the flight recorder
#: writes a postmortem (one defer is normal backpressure; a streak is
#: the incident an operator will ask about).
_EXHAUST_DUMP_STREAK = 3

#: Seconds between brownout-controller evaluations fed by the engine
#: tick (ISSUE 20): the ladder's hysteresis counts these evaluations,
#: so the stride — not the tick rate — sets its reaction time.
_OVERLOAD_STRIDE_S = 0.25


def _with_init_span(init):
    """``serving.engine_init`` over the whole constructor, under the
    caller's ambient span: the pool's allocation and whatever jax
    compiles for it (``xla.*`` spans) hang beneath it. The sizes are read
    off the built engine, so ``kv_blocks`` is the resolved pool and not
    the argument's None; a constructor that raises leaves the span with
    ``error`` alone."""
    @functools.wraps(init)
    def traced_init(self, *args, **kwargs):
        watch_compiles()
        with span("serving.engine_init") as sp:
            init(self, *args, **kwargs)
            sp.set_attr(n_slots=self.n_slots, max_len=self.max_len,
                        kv_blocks=self._pool.n_blocks)
    return traced_init


@dataclasses.dataclass
class GenRequest:
    """One generation request: prompt token ids + token budget."""

    prompt: np.ndarray
    max_new_tokens: int


@dataclasses.dataclass
class _InFlight:
    """Host-side state of one occupied slot (``blocks`` are the row's
    refcounted KV blocks, released on retire)."""

    req: Request
    produced: list[int]
    max_new: int
    blocks: "list[int]"
    #: prompt ids: the draft proposer's context is prompt + produced —
    #: ids only, never device state
    prompt: np.ndarray
    #: the slot the row decodes in; None once it has given it back, which
    #: a row that ends by its budget does when its last step is LAUNCHED
    slot: "int | None" = None
    #: tokens a launched step is making for this row that the host has not
    #: read yet: they count against the budget, and while there are any
    #: the row's newest token is on the device alone
    unread: int = 0

    @property
    def left(self) -> int:
        """Budget left once the tokens already launched are counted."""
        return self.max_new - len(self.produced) - self.unread


@dataclasses.dataclass
class _StepOut:
    """One decode step that was launched and whose ids the host has
    not read: what it takes to read them, retire its rows and say, in
    ``serving.decode_step``, what the step was."""

    toks: Any  # on the device: [k, n_slots (+ expert counts)]
    fetch: Any  # the FetchTicket of their copy to the host
    rows: "list[tuple[int, _InFlight]]"
    t0: float  # time.monotonic() at the start of the launch
    #: what ``serving.decode_step`` says of it: ``chain`` (its tokens a
    #: row), ``slots``, ``links``, ``nb``, what it gathers
    attrs: "dict[str, Any]"


@dataclasses.dataclass
class _Prefill:
    """One slot mid-chunked-prefill: the prompt's K/V are
    accumulating in a private batch-1 dense cache (``ck``/``cv``),
    ``prefill_chunk`` tokens per engine tick, until installation into
    the slot's pool blocks. ``pos`` counts prompt tokens already in the
    cache, including the ``hit`` tokens gathered from the prefix cache
    (whose prefill was skipped)."""

    req: Request
    prompt: np.ndarray
    max_new: int
    pos: int
    hit: int
    shared: "list[int]"
    owned: "list[int]"
    gather_ids: np.ndarray  # block ids backing the cached prefix
    install_ids: np.ndarray  # owned-block targets for the final chunk
    #: COW source (a shared partial tail block): holds an extra pool
    #: reference until the first chunk's gather has been dispatched
    cow_block: "int | None" = None
    ck: Any = None  # None until the first (gather-fused) chunk ran
    cv: Any = None
    #: a family with state layers: ``[the running state by array name]``
    #: after the chunks so far, beside ``ck``/``cv`` (empty for any other)
    rec: "list[Any]" = dataclasses.field(default_factory=list)
    chunks: int = 0
    #: engine ticks this prompt spent prefilling, the ones that gave it no
    #: chunk included (``serving.prefill`` says both; ``admitted_at`` is
    #: where that span starts, in ``time.monotonic()`` seconds)
    ticks: int = 0
    admitted_at: float = 0.0
    #: sequence-parallel staging blocks (sp > 1): the prompt's K/V
    #: accumulate in these SeqShardedBlockPool blocks — sharded across
    #: the sp chips — instead of the private dense cache, until the
    #: prefill→decode handoff gathers them once
    sp_blocks: "list[int] | None" = None

    def all_blocks(self) -> "list[int]":
        """Every pool reference this prefill holds (release on abort)."""
        return (self.shared + self.owned
                + ([self.cow_block] if self.cow_block is not None
                   else []))


class ContinuousGPTEngine:
    """Async continuous-batching GPT server.

    ``submit(prompt_ids, max_new_tokens)`` returns a Future of the
    generated ids (prompt not included). Admission control is two-layer:
    queue depth (QueueFullError) and cache capacity. Only what can NEVER
    fit rejects at submit, loudly (raw prompt + budget vs ``max_len``,
    worst-case blocks vs the whole pool); a
    request that merely cannot fit right now is admitted and DEFERRED
    at tick time — re-queued at the head, retried as slots retire and
    free their blocks. ``kv_block_size``/``kv_blocks`` size the pool
    (default: ``n_slots`` rows of ``max_len``, so the default engine never
    defers); ``prefill_chunk`` bounds the prompt tokens prefilled per tick
    (pin via arg or ``SPARKDL_TPU_PREFILL_CHUNK``).

    ``auto_start=False`` exposes :meth:`tick` for deterministic
    single-step tests; the default runs the loop on a daemon thread.

    ``chain_tokens`` fuses up to k decode steps into ONE device dispatch
    (``lax.scan`` over the donated cache — runtime/dispatch.py),
    trading admission/retirement granularity (checks run every k tokens)
    for k-fold dispatch amortization; k is re-bounded every tick by the
    remaining budgets and deadlines in flight (:meth:`_bounded_tokens`).
    Greedy tokens are identical at any k. None = auto-calibrate from the
    dispatch gap; 1 (default) = one token per dispatch.

    The module docstring has the mechanisms of the rest: ``sp`` (pin via
    ``SPARKDL_TPU_SP``; a power of two, at most the visible device count;
    ``sp_kv_blocks`` sizes the staging pool, default = the decode pool
    rounded up to divide ``sp``; None/1 = off), ``spec_k`` (up to
    ``spec_k - 1`` drafts a slot from ``draft_source``, default radix-trie
    + n-gram; None = off) and ``kv_dtype`` ("fp32" | "bf16" | "int8", the
    pool's storage).
    """

    @_with_init_span
    def __init__(self, config, variables, *, n_slots: int = 8,
                 max_len: int = 512, max_queue_depth: int = 256,
                 eos_id: Optional[int] = None,
                 idle_wait_s: float = 0.005,
                 chain_tokens: "int | None" = 1,
                 kv_block_size: int = 16,
                 kv_blocks: "int | None" = None,
                 prefill_chunk: "int | None" = None,
                 sp: "int | None" = None,
                 sp_kv_blocks: "int | None" = None,
                 spec_k: "int | None" = None,
                 draft_source: Any = None,
                 kv_dtype: str = "fp32",
                 host_kv_blocks: "int | None" = None,
                 disk_kv_blocks: "int | None" = None,
                 kv_spill_dir: "str | None" = None,
                 metrics: ServingMetrics | None = None,
                 slo: "slo_mod.SLO | None" = None,
                 tenants: "tenancy.TenantRegistry | None" = None,
                 host_id: "str | None" = None,
                 auto_start: bool = True):
        if n_slots < 1:
            raise ValueError(f"n_slots must be >= 1, got {n_slots}")
        if chain_tokens is not None and chain_tokens < 1:
            raise ValueError(
                f"chain_tokens must be >= 1, got {chain_tokens}"
            )
        if spec_k is not None and spec_k < 2:
            raise ValueError(
                f"spec_k must be >= 2 (one draft + its verify), got "
                f"{spec_k}; None disables speculative decoding"
            )
        if disk_kv_blocks is not None and host_kv_blocks is None:
            raise ValueError(
                "disk_kv_blocks requires host_kv_blocks: the disk tier "
                "sits below the host tier (blocks spill host->disk, "
                "never device->disk directly)"
            )
        if host_kv_blocks is not None and host_kv_blocks < 1:
            raise ValueError(
                f"host_kv_blocks must be >= 1, got {host_kv_blocks}")
        if disk_kv_blocks is not None and disk_kv_blocks < 0:
            raise ValueError(
                f"disk_kv_blocks must be >= 0, got {disk_kv_blocks}")
        if sp is not None and sp < 1:
            raise ValueError(f"sp must be >= 1, got {sp}")
        # Resolve the env pin HERE, before the family's validation, so
        # SPARKDL_TPU_SP=2 is refused exactly as sp=2 the argument would
        # be (pins are loud — a silently non-sp engine is the failure
        # mode resolve_pin exists to prevent).
        from sparkdl_tpu.ingest.pipeline import resolve_pin
        sp_val, _, _ = resolve_pin(sp, "SPARKDL_TPU_SP", 1, what="sp")
        # THE seam to the model (models/family.py): the module, and how
        # it shapes a token's K/V. Nothing below reads the configuration's
        # own fields.
        fam = config.serving_family()
        if fam.max_positions is not None and max_len > fam.max_positions:
            raise ValueError(
                f"max_len {max_len} exceeds the learned position table "
                f"(max_seq_len={fam.max_positions})"
            )
        if fam.paged_only and (sp_val > 1 or spec_k is not None
                               or kv_dtype != "fp32"):
            raise ValueError(
                f"{type(config).__name__} is served by the plain step at "
                "its native K/V dtype alone: sp > 1, spec_k and kv_dtype "
                "are not implemented for this family"
            )
        if fam.state_layers and host_kv_blocks is not None:
            raise ValueError(
                f"{type(config).__name__} keeps arrays by slot (a recurrent "
                f"state, or a window's last columns) in {fam.state_layers} "
                "of its layers, which no K/V block "
                "holds: a parked block could not resume its sequence, so "
                "tiered KV (host_kv_blocks, park_cold, a parked turn's "
                "resume) is not implemented for this family"
            )

        self.config = config
        self._family = fam
        self.variables = variables
        #: stable host identity for the fabric's router tier (ISSUE 14):
        #: snapshot()/capacity are keyed by it, the prefix digest names
        #: it, and SPARKDL_TPU_HOST_ID pins it per process
        self.host_id = host_id if host_id is not None else default_host_id()
        self.n_slots = n_slots
        self.max_len = max_len
        self.eos_id = eos_id
        self.idle_wait_s = idle_wait_s
        self.chain_tokens = chain_tokens
        self.spec_k = spec_k
        self.kv_dtype = kv_dtype
        self.sp = 1  # raised past 1 by _init_sp
        self._sp_handoffs = 0
        self._spec_policy = (SpecPolicy(max_k=spec_k)
                             if spec_k is not None else None)
        self._spec_dispatches = 0
        self._spec_proposed = 0
        self._spec_accepted = 0
        self._spec_tokens = 0
        self._spec_fallbacks = 0
        self._chain_policy = ChainPolicy(
            max_chain=chain_tokens if chain_tokens is not None else 32
        )
        if chain_tokens is None:
            # auto mode reads the gap per tick: calibrate once here,
            # outside the engine lock, never inside the decode loop
            self._chain_policy.gap()
        self.queue = RequestQueue(max_depth=max_queue_depth,
                                  tenants=tenants)
        #: next monotonic stamp the tick feeds the process brownout
        #: controller (bounded evaluation stride, not per-tick)
        self._overload_next = 0.0
        self.metrics = metrics if metrics is not None else ServingMetrics()
        self._model = fam.module
        self._inflight: dict[int, _InFlight] = {}
        self._prefilling: dict[int, _Prefill] = {}
        self._last_tok = np.zeros((n_slots,), np.int32)
        #: the decode steps launched and not yet read, oldest first
        #: (the loop keeps ONE ahead of its own reads; two for the moment
        #: between a launch and the read before it), and when the last
        #: one was read
        self._steps_out: "collections.deque[_StepOut]" = collections.deque()
        self._collected_at = 0.0
        #: (slot, prefill, its first token on the device) of the prompts
        #: whose last chunk this tick dispatched; read later in the tick
        self._firsts: "list[tuple[int, _Prefill, Any]]" = []
        self._prefill_seconds = 0.0
        self._prefill_chunks = 0
        self._deferrals = 0
        #: prompt tokens of prefix matches that a family with state layers
        #: could not honour (_admit)
        self._prefix_passed_up = 0
        #: host/disk tier store for parked cold sessions (ROADMAP
        #: item 1); None = flat single-tier cache (the default)
        self._kv_tiers = None
        self._park_fallbacks = 0
        self._max_tick_prefill_tokens = 0
        self._prefill_rr = 0
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None

        model = self._model
        if kv_block_size < 1:
            raise ValueError(
                f"kv_block_size must be >= 1, got {kv_block_size}")
        # default 256: the chunk is a decode-LATENCY bound (one
        # tick never prefills more than this many tokens), so it
        # should sit well ABOVE typical prompts — throttling every
        # cold admission to tiny chunks serializes admission for no
        # latency benefit. Shrink it when long prompts must not
        # stall live decode ticks.
        chunk, _, _ = resolve_pin(
            prefill_chunk, "SPARKDL_TPU_PREFILL_CHUNK", 256,
            what="prefill_chunk",
        )
        if chunk < 1:
            raise ValueError(
                f"prefill_chunk must be >= 1, got {chunk}")
        self.prefill_chunk = chunk
        bs_kv = kv_block_size
        mb = -(-max_len // bs_kv)  # table width, blocks per sequence
        w = mb * bs_kv  # gathered virtual-cache width (>= max_len)
        # widest chunk PROGRAM ever built: chunks bucket to their
        # real token count, and no chunk carries more than a whole
        # prompt (<= w) even when the per-tick budget is larger
        self._chunk_cap = min(chunk, w)
        # private prefill cache is one max-width chunk wider than
        # the table span: a chunk write must never clamp
        wp = w + self._chunk_cap
        if kv_blocks is None:
            # default pool = every slot at max_len, so the default
            # engine never defers; shrink kv_blocks to make memory the
            # real bound
            kv_blocks = n_slots * mb
        if kv_blocks < 1:
            raise ValueError(
                f"kv_blocks must be >= 1, got {kv_blocks}")
        self._kv_bs = bs_kv
        self._mb = mb
        self._w = w
        self._wp = wp
        if kv_dtype != "fp32":
            # the bring-up of a COMPRESSED pool is a distinct
            # failure surface (scale buffers, storage casts) the
            # chaos harness must reach: an injected kv.quantize
            # fault fails construction loudly BEFORE any
            # process-wide registration leaks (gauges register
            # below, EngineObservability last)
            fault_point("kv.quantize")
        self._pool = KVBlockPool(kv_blocks, bs_kv, dtype=kv_dtype)
        #: which pool the last deferral was short on (_defer reads
        #: it; the sp staging branch points it at _sp_pool)
        self._defer_pool = self._pool
        if host_kv_blocks is not None:
            # disk overflow may only drop trie LEAVES — dropping
            # an interior parked node would orphan its (parked)
            # descendants' payloads
            self._kv_tiers = kv_tiers_mod.TieredKVStore(
                host_kv_blocks, disk_kv_blocks or 0,
                spill_dir=kv_spill_dir,
                is_droppable=lambda node: not node.children)
        self._prefix = PrefixCache(self._pool,
                                   tiers=self._kv_tiers)
        self._draft = (draft_source if draft_source is not None
                       else default_draft_source(self._prefix))
        # the device pool, shaped and stored as models/kv_pool.py says:
        # the only compressed tensor (the programs below compute, and
        # keep their private prefill caches, at the model dtype)
        self._pool_kv = init_block_pool(config, kv_blocks, bs_kv,
                                        dtype=kv_dtype, n_slots=n_slots)
        # block tables: one row per slot, sentinel (= kv_blocks)
        # marks empty entries — gather clips it, scatter drops it
        self._table = np.full((n_slots, mb), self._pool.sentinel,
                              np.int32)
        self._pidx = np.zeros((n_slots,), np.int32)
        # every slot's newest token as the last step left it ON THE
        # DEVICE: the next step's input for the rows whose ids the host
        # has not read (device_put: jnp.zeros would compile a program)
        self._dev_tok = jax.device_put(np.zeros((n_slots,), np.int32))
        # what the programs close over (serving/paged_programs.py):
        # derived here, set by nobody
        sizes = self._sizes = programs.PagedSizes(
            n_slots=n_slots, block_size=bs_kv, mb=mb, w=w, wp=wp,
            max_pos=(fam.max_positions - 1
                     if fam.max_positions is not None else wp + chunk),
            dtype=fam.dtype,
            arrays=tuple(name for name, _, _ in fam.pool_arrays))
        # One binding a program, under the function's own name (what
        # the device trace shows and the benchmark reads). The jit
        # calls stay in THIS file: sparkdl-lint's donation-safety rule
        # learns the donating handles from them and checks that every
        # call site below rebinds self._pool_kv.
        self._paged_step_fn = jax.jit(
            bound(programs._paged_step, sizes, model),
            donate_argnums=(1,), static_argnums=(6, 7))
        self._paged_verify_fn = jax.jit(
            bound(programs._paged_verify, sizes, model),
            donate_argnums=(1,), static_argnums=(5, 6))
        alike = alike_layers_options()
        self._chunk_one_fn = jax.jit(
            bound(programs._chunk_one, sizes, model),
            donate_argnums=(1,), static_argnums=(6,),
            compiler_options=alike)
        self._chunk_first_fn = jax.jit(
            bound(programs._chunk_first, sizes, model),
            static_argnums=(5,), compiler_options=alike)
        self._chunk_mid_fn = jax.jit(
            bound(programs._chunk_mid, sizes, model),
            # (a family with state layers: its running state too)
            donate_argnums=(1, 2) + ((7,) if fam.state_layers else ()),
            static_argnums=(5,), compiler_options=alike)
        self._chunk_final_fn = jax.jit(
            bound(programs._chunk_final, sizes, model),
            donate_argnums=(1,), static_argnums=(7,),
            compiler_options=alike)
        self._park_fetch_fn = jax.jit(
            bound(programs._park_fetch, sizes))
        self._unpark_install_fn = jax.jit(
            programs._unpark_install, donate_argnums=(0,))
        self._install_blocks_fn = jax.jit(
            programs._install_blocks, donate_argnums=(0,))
        # what the family holds by slot: rings of a window's columns,
        # tails of a short convolution's inputs, or a recurrent state
        self._g_state = GaugeShare(
            _M_RING_BYTES if fam.ring_columns
            else _M_TAIL_BYTES if fam.tail_columns else _M_STATE_BYTES)
        self._g_state.set(n_slots * fam.state_bytes_per_slot)
        # the pool's arrays where the family names them itself
        self._g_named = None
        if fam.block_arrays:
            self._g_named = GaugeShare(_M_LATENT_BYTES)
            self._g_named.set(
                kv_blocks * bs_kv * kv_bytes_per_token(config, kv_dtype))
        if sp_val > 1:
            self._init_sp(sp_val, sp_kv_blocks)
        # process-wide registrations go LAST: a constructor failure above
        # (bad config, cache init OOM) must not leak a tracker/provider
        # bound to a half-built engine

        self._obs = EngineObservability(
            "continuous", self._flight_context, slo=slo, n_slots=n_slots)
        self.slo_tracker = self._obs.tracker
        if auto_start:
            self.start()

    # -- sequence-parallel prefill (ISSUE 13 / ROADMAP item 2) ---------------
    def _init_sp(self, sp: int, sp_kv_blocks: "int | None") -> None:
        """Spatial prefill chunks (the module docstring has the design):
        a dp=1 mesh over the first ``sp`` local devices, a sequence-sharded
        STAGING pool (block axis on the ``sp`` mesh axis, placed through
        the partitioner's ``KV_POOL_RULES``), and explicit-sharding chunk
        programs whose QUERIES are sharded over ``sp`` (the all-gather
        schedule of ``models.gpt.sp_prefill``; the ring rotation is the
        large-sp / on-chip variant).

        Staging stores the COMPUTE dtype even under quantized decode
        pools: chunks then attend over exact K/V (bitwise-identical to
        the sp=1 private-cache path) and the handoff install quantizes
        ONCE — exactly where the single-device install does.
        """
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        from sparkdl_tpu.partition.mesh_factory import make_mesh
        from sparkdl_tpu.partition.rules import (
            KV_POOL_RULES,
            match_partition_rules,
            sequence_activation_spec,
        )

        if sp & (sp - 1):
            raise ValueError(
                f"sp must be a power of two (chunk widths bucket to "
                f"powers of two and shard evenly), got {sp}")
        devs = jax.devices()
        if sp > len(devs):
            raise ValueError(
                f"sp={sp} exceeds the {len(devs)} visible devices")
        self.sp = sp
        # every chunk-program width (pow2_bucket clamped to _chunk_cap)
        # must SHARD EVENLY over sp — a non-divisible cap (prefill_chunk
        # not a multiple of sp, or an odd table span) would crash the
        # first full-width dispatch on the ids in_sharding. Floor the
        # cap to a multiple of sp (never below sp) and clamp the
        # per-tick budget under it (a tick must never stage more real
        # tokens than one program can carry).
        self._chunk_cap = max(sp, (self._chunk_cap // sp) * sp)
        self.prefill_chunk = min(self.prefill_chunk, self._chunk_cap)
        bs_kv = self._kv_bs
        fam = self._family
        # a staged chunk's pad tail is clamped under the per-tick budget
        # as it stands AFTER the clamp above
        sizes = dataclasses.replace(self._sizes, max_pos=(
            fam.max_positions - 1 if fam.max_positions is not None
            else self._wp + self.prefill_chunk))
        mesh = make_mesh(dp=1, sp=sp, devices=devs[:sp])
        self._sp_mesh = mesh
        n_sp = (sp_kv_blocks if sp_kv_blocks is not None
                else self._pool.n_blocks)
        n_sp = -(-n_sp // sp) * sp  # shard the block axis evenly
        # staged-head span with CHUNK HEADROOM: a prefix hit offsets
        # the chunk grid, so the final chunk's bucketed width can cross
        # the table-span boundary (c0 + wc up to w - 1 + chunk_cap) —
        # without the headroom the model's cached write would silently
        # clamp, exactly the overflow the non-sp private cache sizes
        # wp = w + chunk_cap against
        self._mb_sp = -(-(self._w + self._chunk_cap) // bs_kv)
        self._sp_pool = SeqShardedBlockPool(n_sp, bs_kv, sp)
        sp_tree = init_block_pool(self.config, n_sp, bs_kv)
        specs = match_partition_rules(KV_POOL_RULES, sp_tree)
        pool_sh = jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), specs)
        # sparkdl-lint: disable=lock-discipline -- constructor path: the engine loop thread does not exist until auto_start, so no concurrent reader
        self._sp_pool_kv = jax.device_put(sp_tree, pool_sh)
        rep = NamedSharding(mesh, P())
        ids_sh = NamedSharding(
            mesh, sequence_activation_spec(ndim=2, seq_dim=1))
        logits_sh = NamedSharding(
            mesh, sequence_activation_spec(ndim=3, seq_dim=1))
        # host-side arithmetic for sparkdl_sp_permute_bytes_total: each
        # chip contributes its K/V chunk shard to sp-1 peers
        self._sp_bytes_per_col = (
            2 * fam.pool_layers * fam.kv_heads * fam.head_dim
            * np.dtype(fam.dtype).itemsize * (sp - 1))
        # the staged world's programs (serving/paged_programs.py), with
        # where each argument lives on the sp mesh; the handoff's install
        # into the decode pool is the engine's _install_blocks_fn
        self._sp_chunk_fn = jax.jit(
            bound(programs._sp_chunk, sizes, self._model),
            donate_argnums=(1,), static_argnums=(7,),
            in_shardings=(rep, pool_sh, rep, rep, ids_sh, rep, rep),
            out_shardings=(logits_sh, pool_sh))
        self._sp_seed_fn = jax.jit(
            programs._sp_seed, donate_argnums=(0,),
            in_shardings=(pool_sh, rep, rep, rep), out_shardings=pool_sh)
        self._sp_gather_fn = jax.jit(
            programs._sp_gather,
            in_shardings=(pool_sh, rep), out_shardings=(rep, rep))
        self._sp_prefix_fetch_fn = jax.jit(
            bound(programs._sp_prefix_fetch, sizes))

    # -- submission ----------------------------------------------------------
    def submit(self, prompt_ids, max_new_tokens: int, *,
               timeout_s: float | None = None,
               tenant: str = "default",
               priority: "int | None" = None) -> Future:
        """Admit one prompt; Future resolves to the generated ids
        (np.int32 array, ``<= max_new_tokens`` long — shorter on eos).

        ``tenant``/``priority`` scope the request for quota, DRR
        weight, and class scheduling (ISSUE 20) — the defaults are the
        bitwise-compatible single-user path. See
        :meth:`RequestQueue.submit` for the typed admission rejects
        (``TenantThrottledError``/``BrownoutShedError``)."""
        prompt = np.asarray(prompt_ids, np.int32)
        if prompt.ndim != 1 or prompt.size == 0:
            raise ValueError(
                f"prompt must be a non-empty 1-D id array, got shape "
                f"{prompt.shape}"
            )
        if max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}"
            )
        # tokens are stored unpadded, so the per-request bound is the RAW
        # length — and the pool: a request whose worst-case block count
        # exceeds the whole pool can never fit and is rejected loudly;
        # one that merely cannot fit NOW is admitted and deferred at tick
        # time.
        if len(prompt) + max_new_tokens > self.max_len:
            raise ValueError(
                f"prompt {len(prompt)} + max_new_tokens "
                f"{max_new_tokens} exceeds cache max_len "
                f"{self.max_len}: raise max_len or shorten the "
                "request"
            )
        need = -(-(len(prompt)
                   + self._admission_budget_tokens(max_new_tokens))
                 // self._kv_bs)
        if need > self._pool.n_blocks:
            raise ValueError(
                f"request needs {need} KV blocks but the pool holds "
                f"{self._pool.n_blocks}: it can never fit — raise "
                "kv_blocks or shorten the request"
            )
        if self.sp > 1:
            nbp = -(-len(prompt) // self._kv_bs)
            if nbp > self._sp_pool.n_blocks:
                raise ValueError(
                    f"prompt needs {nbp} staging blocks but the "
                    f"sp pool holds {self._sp_pool.n_blocks}: it "
                    "can never prefill — raise sp_kv_blocks or "
                    "shorten the prompt"
                )
        return self.queue.submit(
            GenRequest(prompt, max_new_tokens), timeout_s=timeout_s,
            tenant=tenant, priority=priority,
        )

    def _admission_budget_tokens(self, max_new_tokens: int) -> int:
        """Decode-side tokens an admission must reserve blocks for
        beyond the prompt. The colocated engine reserves the FULL token
        budget up front (decode can never hit mid-stream exhaustion);
        a prefill-tier worker (:mod:`sparkdl_tpu.disagg`) overrides this
        to 0 — it only ever holds prompt K/V, the decode tier owns the
        generation span."""
        return max_new_tokens

    # -- engine loop ---------------------------------------------------------
    def start(self) -> "ContinuousGPTEngine":
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._loop, name="sparkdl-continuous-gpt", daemon=True
            )
            self._thread.start()
        return self

    def close(self, *, drain: bool = True,
              timeout_s: float | None = 30.0) -> None:
        """Stop. ``drain=True`` finishes every admitted request (queued
        and in-flight) first; ``drain=False`` fails them now."""
        self.queue.close()
        if not drain:
            self.queue.fail_pending()
            self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout_s)
        elif drain:  # manual-tick mode: drain inline
            while (self.queue.depth > 0 or self._inflight
                   or self._prefilling):
                self.tick()
        self._stop.set()
        # join timeout or a crashed loop may leave requests queued: no
        # Future may ever be left unresolved
        self.queue.fail_pending()
        with self._lock:
            self._fail_inflight(EngineClosedError("engine shut down"))
        self._obs.close(drain=drain)
        self._pool.close()
        self._g_state.set(0)
        if self._g_named is not None:
            self._g_named.set(0)
        if self.sp > 1:
            self._sp_pool.close()
        if self._kv_tiers is not None:
            self._kv_tiers.close()

    def begin_drain(self) -> "list[Request]":
        """Graceful host drain, phase one (ISSUE 14): stop admission and
        hand back every request that was accepted but NOT yet placed in
        a slot — the fabric re-queues them onto surviving hosts
        (``RequestQueue.requeue`` on the target; trace ids, deadlines,
        and Futures ride the returned :class:`Request` objects
        untouched). Requests already prefilling/decoding are NOT
        returned: they finish here — the engine loop exits on its own
        once the last one retires, after which :meth:`close` joins
        instantly. Idempotent-ish: a second call returns []."""
        self.queue.close()
        reqs = self.queue.extract_pending()
        self._settle()
        flight_mod.record_event(
            "engine.drain_begin", engine=getattr(self._obs, "name", None),
            host=self.host_id, extracted=len(reqs),
            inflight=len(self._inflight) + len(self._prefilling))
        return reqs

    def reopen(self) -> "ContinuousGPTEngine":
        """Reverse :meth:`begin_drain` (ISSUE 16): accept submits again
        and, if the loop thread exited on graceful drain, restart it —
        the spare-host rejoin path (an AutoScaler that parked a drained
        handle puts it back in service through ``Router.add_host``).
        Only for engines that were DRAINED, never CLOSED: close() tears
        down pools and observability, which do not come back."""
        self._stop.clear()
        self.queue.reopen()
        t = self._thread
        if t is not None and not t.is_alive():
            self._thread = None
            self.start()
        return self

    def prefix_digest(self, max_entries: int = 1024) -> dict:
        """The compact prefix→host digest this host publishes
        (ISSUE 14): chained hashes of its cached block-aligned prompt
        prefixes, most-recently-used first, bounded. A router matches an
        incoming prompt's own block hashes against these to estimate
        how many prefill blocks this host already holds."""
        with self._lock:
            # version is the prefix cache's membership-mutation counter
            # (ISSUE 19), NOT a per-publish sequence: two wholesale
            # fetches with no traffic between them carry the same
            # version, and a delta whose ``since`` matches it replays
            # exactly the mutations this snapshot missed.
            return {
                "host_id": self.host_id,
                "block_size": self._kv_bs,
                "version": self._prefix.digest_version,
                "hashes": self._prefix.block_hashes(max_entries),
            }

    def prefix_digest_delta(self, since_version: int,
                            max_entries: int = 1024) -> "dict | None":
        """Membership adds/evictions since ``since_version`` — the
        steady-state digest refresh (ISSUE 19): a router tracking this
        host pulls KBs of deltas instead of re-shipping the wholesale
        digest every interval. ``None`` = gap (the caller fell behind
        the bounded journal, or claims a future version): refresh
        wholesale. The ``digest.delta`` fault site models a torn delta
        read — the router answers any error here the same way, with a
        wholesale re-sync."""
        with self._lock:
            fault_point("digest.delta")
            delta = self._prefix.block_hash_delta(
                int(since_version), max_entries)
            if delta is None:
                return None
            delta["host_id"] = self.host_id
            delta["block_size"] = self._kv_bs
            return delta

    def _loop(self) -> None:
        try:
            while not self._stop.is_set():
                did_work = self.tick()
                if self.queue.closed and not did_work:
                    with self._lock:
                        if (self.queue.depth == 0 and not self._inflight
                                and not self._prefilling):
                            return  # graceful drain complete
            # non-graceful: surviving inflight failed by close()
        except BaseException as e:
            # a crashed loop (device OOM, XLA error) must not strand
            # callers blocked on their Futures
            exc = (e if isinstance(e, Exception)
                   else EngineClosedError(f"engine loop died: {e!r}"))
            self.queue.close()
            self.queue.fail_pending(exc)
            with self._lock:
                self._fail_inflight(exc)
            raise

    # -- one scheduling quantum ---------------------------------------------
    def tick(self) -> bool:
        """Admit into free slots, advance chunked prefills by at most
        ``prefill_chunk`` tokens, launch every live row's next token,
        then read and retire the step launched a tick ago (the module
        docstring has the order and why). Returns True if any work
        happened (False = idle tick). Thread-safe; the background loop
        is just ``while True: tick()``."""
        # one span a WORKING tick, in a trace of its own that links its
        # riders; an idle engine ticks 200 times a second and leaves none
        with self._lock, span("serving.tick") as tick_span:
            now = time.monotonic()
            self._overload_tick(now)
            self._expire_inflight(now)
            admitted = 0
            free = [s for s in range(self.n_slots)
                    if s not in self._inflight
                    and s not in self._prefilling]
            if not free and self._prefilling:
                # saturated with a background prefill in flight: a more
                # urgent waiting class may claim its slot (ISSUE 20)
                if self._maybe_preempt(now):
                    free = [s for s in range(self.n_slots)
                            if s not in self._inflight
                            and s not in self._prefilling]
            if free:
                wait = (0.0 if self._inflight or self._prefilling
                        or self._steps_out else self.idle_wait_s)
                reqs = self.queue.take(len(free), wait)
                deferred = False
                for i, req in enumerate(reqs):
                    slot = free.pop(0)
                    try:
                        placed = self._admit_traced(slot, req)
                        if not placed and self._steps_out:
                            # the rows whose last ids are still out hold
                            # blocks that a loop which had read them
                            # would have freed by now: read them, ask again
                            self._collect()
                            placed = self._admit_traced(slot, req)
                    except Exception as e:
                        # take() already moved this Future to RUNNING, so
                        # nobody else can resolve it: a failed admission
                        # (prefill OOM, compile error) is THIS request's
                        # error, never the engine's — the slot stays free
                        # and the loop keeps serving
                        free.insert(0, slot)
                        self._fail_request(req, e, tokens=0)
                        continue
                    if not placed:
                        # pool exhausted: defer this request AND every
                        # later one taken this tick back to the queue
                        # head, in order — deferral never reorders
                        # accepted traffic (a later arrival must not
                        # grab the blocks the deferred one is owed)
                        free.insert(0, slot)
                        self._defer(reqs[i:])
                        deferred = True
                        break
                    admitted += 1
                if not deferred and (
                        self._pool.deferral_streak
                        or (self.sp > 1
                            and self._sp_pool.deferral_streak)):
                    # free slots existed and nothing deferred this tick
                    # (the deferred work admitted, or left the queue —
                    # e.g. expired): the exhaustion episode is over. A
                    # streak must never outlive the pressure, or an
                    # idle, recovered engine would read degraded
                    # forever and the next real incident would miss its
                    # postmortem trigger. (The pool also clears the
                    # streak itself whenever release() frees blocks.)
                    self._pool.reset_deferral_streak()
                    if self.sp > 1:
                        self._sp_pool.reset_deferral_streak()
            else:
                self.queue.sweep_expired()  # deadlines don't wait for slots
            did_work = False
            if self._prefilling:
                self._prefill_tick()
                did_work = True
            if self._inflight or self._steps_out or self._firsts:
                # (no live row but a step still out: the last tokens of
                # a drain, or the step a late eos rode)
                self._decode_step()
                did_work = True
            if not (did_work or admitted):
                tick_span.discard()
            elif tick_span.context is not None:
                tick_span.set_attr(
                    inflight=len(self._inflight),
                    prefilling=len(self._prefilling), admitted=admitted,
                    links=[f.req.request_id
                           for f in self._inflight.values()]
                    + [st.req.request_id
                       for st in self._prefilling.values()])
            return did_work

    def _defer(self, reqs: "list[Request]") -> None:
        """KV pool exhaustion: re-queue in order, count the streak ON
        THE POOL THAT ACTUALLY DEFERRED (``_admit`` marks
        ``_defer_pool`` — decode pool or the sp staging pool; a staging
        stall recorded against the decode pool would read healthy and
        never trip the postmortem), and after ``_EXHAUST_DUMP_STREAK``
        consecutive deferrals hand the flight recorder a postmortem
        trigger (providers capture the pool state). Self-recovering:
        blocks free as slots retire."""
        self.queue.requeue(reqs)
        self._deferrals += 1
        gen: GenRequest = reqs[0].payload
        pool = self._defer_pool
        staging = pool is not self._pool
        # the recovery bar: worst-case blocks of the request being owed
        # (ignores prefix-cache sharing — a conservative overestimate,
        # so a partial free can never clear a streak the request's
        # admission would still defer on). Staging holds prompt blocks
        # only; the decode pool the full prompt + budget span.
        span = (len(gen.prompt) if staging
                else len(gen.prompt)
                + self._admission_budget_tokens(gen.max_new_tokens))
        pool.record_deferral(need=-(-span // self._kv_bs))
        streak = pool.deferral_streak
        flight_mod.record_event(
            "kv.admission_deferred",
            engine=getattr(self._obs, "name", None),
            request_id=reqs[0].request_id,
            deferred=len(reqs),
            streak=streak,
            pool="sp_staging" if staging else "decode",
            blocks_free=pool.free_count,
            blocks_total=pool.n_blocks,
        )
        if streak == _EXHAUST_DUMP_STREAK:
            flight_mod.trigger_dump(
                "kv.pool_exhausted",
                streak=streak,
                pool="sp_staging" if staging else "decode",
                blocks_total=pool.n_blocks,
            )

    def _maybe_preempt(self, now: float) -> bool:
        """Priority preemption between prefill chunks (ISSUE 20): with
        every slot busy and a strictly more urgent class waiting, tear
        down the LEAST urgent background prefill and re-queue its
        request at its own class head — zero lost. Only requests in
        the background class (priority >= PRIORITY_BACKGROUND) are
        preemptible, and only BETWEEN chunks (mid-dispatch state never
        exists at tick boundaries). The victim's pool references go
        back through the prefix cache, so its already-registered
        prefix blocks stay cached (and parkable via the kv_tiers
        path): the re-run prefills only what the cache cannot serve.
        The ``tenant.preempt`` fault site fires before teardown; an
        injected fault still re-queues the victim (chaos contract) —
        it only suppresses the slot handover this tick. Returns True
        when a slot was freed. Called under the engine lock."""
        waiting = self.queue.highest_waiting_priority()
        if waiting is None:
            return False
        slot, st = max(self._prefilling.items(),
                       key=lambda kv: kv[1].req.priority)
        if (st.req.priority < tenancy.PRIORITY_BACKGROUND
                or waiting >= st.req.priority):
            return False
        fault: "Exception | None" = None
        try:
            fault_point("tenant.preempt")
        except Exception as e:
            fault = e
        # the same teardown discipline as _sp_abort: drop the prefill
        # record, release staging + every pool reference, THEN requeue
        # — on the fault path too, so the victim is never lost
        self._collect()
        del self._prefilling[slot]
        self._release_sp_staging(st)
        self._prefix.release(st.all_blocks())
        if fault is None:
            tenancy.note_preemption()
            flight_mod.record_event(
                "tenant.preempted",
                request_id=st.req.request_id, tenant=st.req.tenant,
                victim_priority=st.req.priority,
                waiting_priority=waiting,
                prefilled=st.pos, prompt_tokens=len(st.prompt))
        else:
            flight_mod.record_event(
                "tenant.preempt_failed",
                error=type(fault).__name__,
                request_id=st.req.request_id, tenant=st.req.tenant)
        self.queue.requeue([st.req])
        return fault is None

    def _overload_tick(self, now: float) -> None:
        """Feed the process brownout controller (when installed) this
        engine's overload signals — worst SLO burn rate across
        dimensions plus queue fill fraction — on a bounded stride, so
        the ladder's hysteresis counts wall-clock-ish evaluations, not
        raw tick rate. No controller installed = zero work (the
        bitwise default path)."""
        ctrl = tenancy.process_overload()
        if ctrl is None or now < self._overload_next:
            return
        self._overload_next = now + _OVERLOAD_STRIDE_S
        burn = None
        if self.slo_tracker is not None:
            rep = self.slo_tracker.sample()
            burns = [d["burn_rate"] for d in
                     (rep.get("latency"), rep.get("availability"))
                     if isinstance(d, dict)]
            if burns:
                burn = max(burns)
        ctrl.evaluate(
            burn_rate=burn,
            queue_frac=self.queue.depth / self.queue.max_depth)

    def _admit_traced(self, slot: int, req: Request) -> bool:
        """:meth:`_admit` inside the request's ``serving.admit`` span:
        what admission itself costs a tick (prefix match, block
        allocation) and what it found."""
        with span("serving.admit", parent=req.trace_ctx,
                  request_id=req.request_id, slot=slot,
                  prompt_len=len(req.payload.prompt)) as sp:
            passed_up = self._prefix_passed_up
            placed = self._admit(slot, req)
            if sp.context is not None:
                st = self._prefilling.get(slot) if placed else None
                flight = self._inflight.get(slot) if placed else None
                sp.set_attr(
                    deferred=not placed,
                    cached_tokens=st.hit if st is not None else 0,
                    **({"prefix_passed_up":
                        self._prefix_passed_up - passed_up}
                       if self._family.state_layers else {}),
                    blocks=(len(st.all_blocks()) if st is not None
                            else len(flight.blocks)
                            if flight is not None else 0))
            return placed

    # -- admission + chunked prefill -----------------------------------------
    def _admit(self, slot: int, req: Request) -> bool:
        """Place one taken request into ``slot``: match the longest
        cached prefix, allocate the request's worst-case remaining blocks
        up front (so decode can never hit mid-stream exhaustion), and
        queue the suffix for chunked prefill. False = pool exhausted right
        now (the caller defers)."""
        gen: GenRequest = req.payload
        prompt = np.asarray(gen.prompt, np.int32)
        plen = len(prompt)
        toks = tuple(int(t) for t in prompt)
        nb_total = -(-(plen
                       + self._admission_budget_tokens(gen.max_new_tokens))
                     // self._kv_bs)
        # turn resume: page any parked prefix of this prompt back in
        # BEFORE matching, so the match below sees device blocks and
        # the resume costs one H2D copy per block instead of a
        # re-prefill. Restored blocks hold a temporary reference
        # (restore allocation may demote OTHER cold leaves, never
        # these) released as soon as match has taken its own.
        restored: "list[int]" = []
        if self._kv_tiers is not None:
            restored = self._prefix.restore_path(
                toks[:-1], alloc_block=self._alloc_one_block,
                install=self._install_parked)
            self._update_unpark_reserved()
            if restored:
                flight_mod.record_event(
                    "kv.unparked", request_id=req.request_id,
                    blocks=len(restored))
        # the last prompt token must always prefill — the cache holds
        # K/V, not the logits that seed decode
        m = self._prefix.match(toks[:-1])
        passed_up = 0
        if restored:
            self._prefix.release(restored)
        matched = (m.full_blocks
                   + ([m.partial_block] if m.partial_block is not None
                      else []))
        if self._family.state_layers and m.hit_tokens:
            # the matched blocks hold K/V up to the boundary and nothing of
            # the state AT it, which every state layer would need to go on
            # from there: the match is passed up and the prompt prefilled
            # whole (ROADMAP B4: state snapshots at block boundaries)
            self._prefix.release(matched)
            passed_up = m.hit_tokens
            m, matched = PrefixMatch([], None, 0, 0), []
        try:
            owned = self._alloc_blocks(nb_total - len(m.full_blocks))
        except Exception as e:
            # an injected kv.alloc fault (chaos harness) or allocator
            # error is exhaustion, not a request error: defer, recover
            flight_mod.record_event(
                "kv.alloc_error", error=type(e).__name__,
                request_id=req.request_id)
            owned = None
        if owned is None:
            self._prefix.release(matched)
            self._defer_pool = self._pool
            return False
        # the first chunk will gather the cached prefix into the private
        # prefill cache (also the COW copy of a partial tail block);
        # sentinel entries are masked garbage, so no-hit = fresh cache.
        # The partial block keeps its extra reference until that gather
        # has been DISPATCHED (releasing it now would let an eviction +
        # realloc overwrite it before the copy).
        gids = np.full((self._mb,), self._pool.sentinel, np.int32)
        gids[:len(m.full_blocks)] = m.full_blocks
        if m.partial_block is not None:
            gids[len(m.full_blocks)] = m.partial_block
        n_shared = len(m.full_blocks)
        inst = np.full((self._mb,), self._pool.sentinel, np.int32)
        inst[n_shared:n_shared + len(owned)] = owned
        sp_blocks = None
        cow = m.partial_block
        if self.sp > 1:
            # sequence-parallel staging: the prompt's K/V accumulate in
            # sp-sharded blocks (striped across chips), allocated up
            # front like the decode blocks — exhaustion defers
            try:
                sp_blocks = self._sp_pool.allocate(
                    -(-plen // self._kv_bs))
            except Exception as e:
                # an injected kv.alloc fault on the STAGING allocate is
                # exhaustion too — defer, never fail the request (and
                # never leak the decode blocks already taken above)
                flight_mod.record_event(
                    "kv.alloc_error", error=type(e).__name__,
                    request_id=req.request_id)
                sp_blocks = None
            if sp_blocks is None:
                # staging exhausted: same deferral contract as the
                # decode pool — the caller's _defer records the streak
                # on the STAGING pool (the one actually short)
                self._prefix.release(matched + owned)
                self._defer_pool = self._sp_pool
                return False
            if m.full_blocks or cow is not None:
                try:
                    self._sp_seed_prefix(gids, sp_blocks,
                                         len(m.full_blocks)
                                         + (cow is not None))
                except Exception:
                    self._sp_pool.release(
                        self._sp_pool.deref(sp_blocks))
                    self._prefix.release(matched + owned)
                    raise
                if cow is not None:
                    # the COW copy is dispatched into the staged block:
                    # the sp chunks never read the decode pool again, so
                    # the partial tail's extra hold can drop now
                    self._prefix.release([cow])
                    cow = None
        self._prefix.record_lookup(m.hit_tokens, plen - m.hit_tokens)
        self._prefix_passed_up += passed_up  # (counted once: it is placed)
        if m.hit_tokens:
            flight_mod.record_event(
                "kv.prefix_hit", request_id=req.request_id,
                hit_tokens=m.hit_tokens, prompt_tokens=plen)
        self._prefilling[slot] = _Prefill(
            req=req, prompt=prompt, max_new=gen.max_new_tokens,
            pos=m.hit_tokens, hit=m.hit_tokens,
            shared=m.full_blocks, owned=owned,
            gather_ids=gids, install_ids=inst,
            cow_block=cow, sp_blocks=sp_blocks,
            admitted_at=time.monotonic(),
        )
        self._pool.reset_deferral_streak()
        if self.sp > 1:
            self._sp_pool.reset_deferral_streak()
        return True

    def _sp_seed_prefix(self, gids: np.ndarray, sp_blocks: "list[int]",
                        n_hit_blocks: int) -> None:
        """Copy the matched prefix span (full blocks + COW partial
        tail) from the decode pool into the staged blocks backing it —
        one dequantizing fetch, one sharded seed scatter."""
        seed = np.full((self._mb,), self._sp_pool.sentinel, np.int32)
        seed[:n_hit_blocks] = sp_blocks[:n_hit_blocks]
        kd, vd = self._sp_prefix_fetch_fn(
            self._pool_kv, jnp.asarray(gids))
        self._sp_pool_kv = self._sp_seed_fn(
            self._sp_pool_kv, np.asarray(kd), np.asarray(vd),
            jnp.asarray(seed))

    def _alloc_blocks(self, n: int) -> "list[int] | None":
        got = self._pool.allocate(n)
        if got is None:
            short = n - self._pool.free_count
            if self._kv_tiers is not None:
                # tiered: page cold leaves OUT (device->host->disk)
                # instead of discarding them — the demoted sessions
                # resume with one H2D copy, not a re-prefill
                freed = self._prefix.demote(short, self._park_payload)
                self._update_unpark_reserved()
            else:
                freed = self._prefix.evict(short)
            if freed >= short:
                got = self._pool.allocate(n)
        return got

    def _alloc_one_block(self) -> "int | None":
        got = self._alloc_blocks(1)
        return got[0] if got else None

    # -- tiered park/resume (ROADMAP item 1) ----------------------------------
    def _park_payload(self, bid: int) -> "dict | None":
        """D2H-fetch one cold block's raw bytes for parking. None =
        torn park (injected ``kv.park`` fault or transfer failure):
        the caller falls back to plain eviction — the session simply
        re-prefills next turn, nothing is lost."""
        t0 = time.monotonic()
        try:
            fault_point("kv.park")
            tree = self._park_fetch_fn(
                self._pool_kv, jnp.asarray([bid], jnp.int32))
            ticket = start_fetch(tree, path="kv_park")
            # sparkdl-lint: disable=blocking-in-hot-loop -- a park only runs when allocation already came up short, and the copy is one block (the alternative, plain eviction, costs that session a full re-prefill)
            fetched = ticket.result()
        except Exception as e:
            self._park_fallbacks += 1
            kv_tiers_mod._M_FALLBACKS.inc(op="park")
            flight_mod.record_event(
                "kv.park_failed", error=type(e).__name__, block=bid)
            return None
        payload = {name: np.asarray(v)[:, 0]
                   for name, v in fetched.items()}
        kv_tiers_mod._M_PARK_SEC.observe(time.monotonic() - t0)
        return payload

    def _install_parked(self, bid: int, payload: dict) -> bool:
        """H2D-install one parked block's raw bytes into a fresh pool
        block. False = corrupt unpark (injected ``kv.unpark`` fault):
        the caller prunes the parked subtree and the suffix
        re-prefills — the request still completes."""
        t0 = time.monotonic()
        try:
            fault_point("kv.unpark")
            tree = {name: jnp.asarray(np.asarray(v)[:, None])
                    for name, v in payload.items()}
            # sparkdl-lint: disable=lock-discipline -- only reachable from _admit's restore_path callback, which the admission loop enters holding self._lock
            self._pool_kv = self._unpark_install_fn(
                self._pool_kv, jnp.asarray([bid], jnp.int32), tree)
        except Exception as e:
            # sparkdl-lint: disable=lock-discipline -- same reach as the install above: restore_path's caller (_admit) already holds self._lock
            self._park_fallbacks += 1
            kv_tiers_mod._M_FALLBACKS.inc(op="unpark")
            flight_mod.record_event(
                "kv.unpark_failed", error=type(e).__name__, block=bid)
            return False
        kv_tiers_mod._M_UNPARK_SEC.observe(time.monotonic() - t0)
        return True

    def _update_unpark_reserved(self) -> None:
        """Tell the pool how many free blocks parked state expects to
        claim on resume, so the autoscaler's shrink defers instead of
        stranding unparks behind re-prefills (capped at the pool —
        over-subscription past that is already a full pool)."""
        if self._kv_tiers is None:
            return
        s = self._kv_tiers.stats()
        self._pool.unpark_reserved = min(
            s["host_blocks"] + s["disk_blocks"], self._pool.n_blocks)

    def park_cold(self, max_blocks: "int | None" = None) -> int:
        """Explicitly page every currently cold cached block out to
        the host tier (benches/tests; production parks lazily under
        allocation pressure). Returns device blocks freed. Refcounted
        shares and partial-block COW donors never park."""
        if self._kv_tiers is None:
            raise RuntimeError(
                "park_cold needs a host tier: construct the engine "
                "with host_kv_blocks")
        with self._lock:
            self._collect()
            n = (max_blocks if max_blocks is not None
                 else self._prefix.cached_blocks)
            freed = self._prefix.demote(
                n, self._park_payload, evict_fallback=False)
            self._update_unpark_reserved()
            return freed

    # -- parked-session migration (ISSUE 19) ----------------------------------
    def export_parked_sessions(self,
                               max_sessions: "int | None" = None
                               ) -> "dict | None":
        """Serialize every parked session's block-aligned prefix path
        for re-parking on another host — the drain/scale-down tail of
        ROADMAP item 1: without this, parked state strands on the host
        that parked it and every idle conversation re-prefills cold.
        Each session ships its WHOLE path (device-resident ancestors
        are D2H-fetched like a park; parked blocks are peeked from
        their tier) through the handoff raw-storage codec, so the
        importing host resumes bitwise-identically. Exported parked
        subtrees are pruned here — the state now lives on the target;
        a torn export (``kv.migrate`` fault) skips that session, which
        simply re-prefills on resume (never lost, never duplicated).
        None when this engine has no tier store."""
        if self._kv_tiers is None:
            return None
        from sparkdl_tpu.disagg.handoff import _enc

        t0 = time.monotonic()
        sessions: "list[dict]" = []
        with self._lock:
            self._collect()
            paths = self._prefix.parked_leaf_paths()
            if max_sessions is not None:
                paths = paths[:int(max_sessions)]
            prune: "list[Any]" = []
            for tokens, nodes in paths:
                try:
                    fault_point("kv.migrate")
                    blocks = []
                    for n in nodes:
                        pl = (self._park_payload(n.block_id)
                              if n.tier == "device"
                              else self._kv_tiers.peek(n))
                        if pl is None:
                            raise RuntimeError(
                                "torn export: block payload unavailable")
                        blocks.append(
                            {k: _enc(np.asarray(v))
                             for k, v in pl.items()})
                except Exception as e:
                    kv_tiers_mod._M_MIGRATIONS.inc(outcome="export_failed")
                    flight_mod.record_event(
                        "kv.migrate_export_failed", host=self.host_id,
                        error=type(e).__name__)
                    continue
                sessions.append({"tokens": [int(t) for t in tokens],
                                 "blocks": blocks})
                kv_tiers_mod._M_MIGRATIONS.inc(outcome="exported")
                kv_tiers_mod._M_MIG_BLOCKS.inc(len(blocks))
                top = next(
                    (n for n in nodes if n.tier != "device"), None)
                if top is not None:
                    prune.append(top)
            seen: "set[int]" = set()
            for top in prune:
                # tops are roots of maximal parked subtrees — disjoint,
                # but two leaves under one top share it: prune once
                if id(top) in seen:
                    continue
                seen.add(id(top))
                self._prefix._prune_parked(top)
            self._update_unpark_reserved()
        kv_tiers_mod._M_MIG_SEC.observe(time.monotonic() - t0)
        flight_mod.record_event(
            "kv.migrate_export", host=self.host_id,
            sessions=len(sessions))
        return {"host_id": self.host_id, "block_size": self._kv_bs,
                "kv_dtype": self.kv_dtype, "sessions": sessions}

    def import_parked_sessions(self, bundle: "dict | None") -> int:
        """Adopt migrated parked sessions into this host's tier store
        (the receiving end of :meth:`export_parked_sessions`): each
        session's blocks re-park here and its trie path is grafted in,
        so the next turn's ``restore_path`` pages it in with one H2D
        per block instead of a re-prefill. Sessions on a different
        block grid or storage dtype are skipped whole (their bytes
        cannot install here — re-prefill is the correct fallback), as
        is any session torn by the ``kv.migrate`` fault site. Returns
        sessions adopted."""
        if self._kv_tiers is None or not bundle:
            return 0
        from sparkdl_tpu.disagg.handoff import _dec

        if int(bundle.get("block_size") or 0) != self._kv_bs:
            return 0
        dtype = bundle.get("kv_dtype")
        if dtype is not None and str(dtype) != str(self.kv_dtype):
            return 0
        t0 = time.monotonic()
        adopted = 0
        with self._lock:
            for sess in bundle.get("sessions") or ():
                try:
                    fault_point("kv.migrate")
                    blocks = [{k: _dec(v) for k, v in b.items()}
                              for b in sess["blocks"]]
                    toks = tuple(int(t) for t in sess["tokens"])
                    if len(toks) != len(blocks) * self._kv_bs:
                        raise ValueError("ragged migration payload")
                    self._prefix.adopt_parked(toks, blocks)
                except Exception as e:
                    kv_tiers_mod._M_MIGRATIONS.inc(
                        outcome="import_failed")
                    flight_mod.record_event(
                        "kv.migrate_import_failed", host=self.host_id,
                        error=type(e).__name__)
                    continue
                adopted += 1
                kv_tiers_mod._M_MIGRATIONS.inc(outcome="imported")
            self._update_unpark_reserved()
        kv_tiers_mod._M_MIG_SEC.observe(time.monotonic() - t0)
        flight_mod.record_event(
            "kv.migrate_import", host=self.host_id, sessions=adopted)
        return adopted

    def _prefill_tick(self) -> None:
        """Advance chunked prefills by at most ``prefill_chunk`` REAL
        tokens this tick, round-robin across prefilling slots — the
        bound that keeps a long prompt from freezing in-flight decode
        latency (several short prompts fit one tick's budget; a long
        one takes exactly one chunk per tick)."""
        budget = self.prefill_chunk
        slots = sorted(self._prefilling)
        if len(slots) > 1:
            pivot = self._prefill_rr % len(slots)
            slots = slots[pivot:] + slots[:pivot]
        self._prefill_rr += 1
        for st in self._prefilling.values():
            st.ticks += 1  # whether or not the budget reaches it this tick
        tick_tokens = 0
        for slot in slots:
            st = self._prefilling[slot]
            r = min(self.prefill_chunk, len(st.prompt) - st.pos)
            if r > budget:
                continue  # over this tick's budget: next tick
            budget -= r
            tick_tokens += r
            self._prefill_chunk_step(slot, st, r)
            if budget <= 0:
                break
        self._max_tick_prefill_tokens = max(
            self._max_tick_prefill_tokens, tick_tokens)

    def _prefill_chunk_step(self, slot: int, st: _Prefill,
                            r: int) -> None:
        if st.sp_blocks is not None:
            self._sp_chunk_step(slot, st, r)
            return
        c0 = st.pos
        first = st.ck is None
        final = c0 + r == len(st.prompt)

        # chunk-program width: power-of-2 bucket of the real token
        # count (capped by the budget) — compile reuse without paying
        # the full budget width for a short suffix
        wc = pow2_bucket(r, 8, self._chunk_cap)
        ids = np.zeros((1, wc), np.int32)
        ids[0, :r] = st.prompt[c0:c0 + r]
        # static attention width: bucket of the live buffer head — the
        # program attends over [0, cols) instead of the whole private
        # cache (everything past idx+wc is causally masked garbage)
        cols = pow2_bucket(c0 + wc, 8, self._wp)
        idx = jnp.asarray(c0, jnp.int32)
        ids = jnp.asarray(ids)
        program = ("chunk_one" if first and final else "chunk_first"
                   if first else "chunk_final" if final else "chunk_mid")
        t0 = time.perf_counter()
        # width, cols and program name the compiled shape: an xla.compile
        # under this span says which one was first seen while serving
        fam = self._family
        # a family with state layers: the chunk's real token count reaches
        # the program (a recurrence has no causal mask to hide the pad
        # behind), the running state rides beside ck/cv, and the last chunk
        # installs it into the slot's row
        state = bool(fam.state_layers)
        n = (jnp.asarray(r, jnp.int32),) if state else ()
        row = (jnp.asarray(slot, jnp.int32),) if state else ()
        scan = ({"scan_tokens": r, "pad_tokens": wc - r,
                 "scan_solved_in_kernel": int(fam.scan_solved_in_kernel)}
                if state else {})
        if fam.selected_columns:
            # a family that attends a selection: the columns this chunk's
            # real queries attended, a layer (``sel_cols``: query ``i``
            # keeps ``min(c0 + i + 1, selected_columns)``), and those its
            # indexer scored to select, an indexer layer (``index_cols``:
            # every column up to the query's own, once the program's
            # width passes the selection)
            upto = np.arange(c0 + 1, c0 + r + 1)
            scan = dict(
                scan,
                sel_cols=int(np.minimum(upto, fam.selected_columns).sum()),
                index_cols=(int(upto.sum())
                            if cols > fam.selected_columns else 0))
        with span("serving.prefill_chunk", parent=st.req.trace_ctx,
                  request_id=st.req.request_id, slot=slot,
                  start=c0, tokens=r, first=first, final=final,
                  width=wc, cols=cols, program=program, **scan):
            if first and final:
                logits, self._pool_kv = self._chunk_one_fn(
                    self.variables, self._pool_kv,
                    jnp.asarray(st.gather_ids), idx, ids,
                    jnp.asarray(st.install_ids), cols, *n, *row)
            elif first:
                logits, st.ck, st.cv, *st.rec = self._chunk_first_fn(
                    self.variables, self._pool_kv,
                    jnp.asarray(st.gather_ids), idx, ids, cols, *n)
            elif final:
                logits, self._pool_kv = self._chunk_final_fn(
                    self.variables, self._pool_kv, st.ck, st.cv,
                    idx, ids, jnp.asarray(st.install_ids), cols,
                    *n, *st.rec, *row)
                st.ck = st.cv = None
                st.rec = []
            else:
                logits, st.ck, st.cv, *st.rec = self._chunk_mid_fn(
                    self.variables, st.ck, st.cv, idx, ids, cols,
                    *n, *st.rec)
        if state:
            _M_SCAN_TOKENS.inc(r)
        if first and st.cow_block is not None:
            # the gather is dispatched: the COW copy is sequenced before
            # any later overwrite of the source block — drop the hold
            self._prefix.release([st.cow_block])
            st.cow_block = None
        st.pos += r
        st.chunks += 1
        self._prefill_chunks += 1
        _M_PREFILL_CHUNKS.inc()
        if final:
            # the chunk's last REAL column seeds decode (argmax on
            # device: the same op the oracle's generate uses), queued
            # right behind the chunk. It is READ after this tick's decode
            # step is launched (_read_first_tokens): waiting for it here
            # would leave the device with nothing queued behind the chunk
            self._firsts.append((slot, st, jnp.argmax(logits[0, r - 1])))
        self._prefill_seconds += time.perf_counter() - t0

    def _read_first_tokens(self) -> None:
        """Wait for the last chunks this tick dispatched and read each
        prompt's first token: its row joins the decode batch with it."""
        firsts, self._firsts = self._firsts, []
        for slot, st, first in firsts:
            t0 = time.perf_counter()
            with self._first_token_span(st.req, slot):
                tok = int(first)
            self._finish_prefill(slot, st, tok)
            self._prefill_seconds += time.perf_counter() - t0

    def _first_token_span(self, req: Request, slot: int):
        """Around the blocking read that ends a prefill: the host waits
        there for the prefill program (the chunk's own span closes when
        its DISPATCH returns) and for the copy of one id. The span's end
        is the instant the request's first token reached the host."""
        return span("serving.first_token", parent=req.trace_ctx,
                    request_id=req.request_id, slot=slot,
                    prompt_len=len(req.payload.prompt))

    def _finish_prefill(self, slot: int, st: _Prefill,
                        first: int) -> None:
        if tracing.tracing_enabled():
            # recorded now that the first token is on the host: admission
            # to this instant,
            # ``ticks`` engine ticks of which ``chunks`` gave it a chunk
            # (the others went to other prompts' turns at the budget)
            tracing.record_span(
                "serving.prefill", st.admitted_at, time.monotonic(),
                parent=st.req.trace_ctx, request_id=st.req.request_id,
                slot=slot, prompt_len=len(st.prompt),
                cached_tokens=st.hit, chunks=st.chunks, ticks=st.ticks)
        n_shared = len(st.shared)
        nb_total = n_shared + len(st.owned)
        row = np.full((self._mb,), self._pool.sentinel, np.int32)
        row[:n_shared] = st.shared
        row[n_shared:nb_total] = st.owned
        self._table[slot] = row
        plen = len(st.prompt)
        n_prompt_blocks = -(-plen // self._kv_bs)
        self._prefix.register(
            tuple(int(t) for t in st.prompt),
            [int(b) for b in row[:n_prompt_blocks]],
        )
        self.metrics.record_tokens(1, phase="prefill")
        del self._prefilling[slot]
        flight = _InFlight(st.req, [first], st.max_new,
                           blocks=st.shared + st.owned,
                           prompt=st.prompt)
        self._join_decode(slot, flight, plen)
        if self._is_done(flight):  # max_new_tokens=1, or instant eos
            self._complete(flight)

    def _join_decode(self, slot: int, flight: _InFlight,
                     depth: int) -> None:
        """A row joins the decode batch with ``depth`` columns of K/V in its
        blocks and a first token the HOST knows, ``flight.produced[-1]``:
        a prefill's, a handoff's, a resume's. No launched step made that
        token, so the next step takes the host's word for this row and not
        the device's (``_launch_step``)."""
        self._pidx[slot] = depth
        self._last_tok[slot] = flight.produced[-1]
        flight.slot = slot
        self._inflight[slot] = flight

    # -- sequence-parallel chunk dispatch + handoff ---------------------------
    def _sp_chunk_step(self, slot: int, st: _Prefill, r: int) -> None:
        """One SPATIAL prefill chunk (sp > 1): ``r`` real tokens
        dispatched across the sp chips — queries sharded, K/V
        all-gathered, staged blocks scattered back sharded. The final
        chunk triggers the prefill→decode handoff. Dispatches record
        under ``sparkdl_dispatch_seconds{path="sp_prefill"}`` and NEVER
        feed the ChainPolicy: its calibrated dispatch gap is measured
        on single-device programs, and a collective-bearing dispatch
        would skew the auto-K the decode loop calibrates from."""
        try:
            # the injectable stand-in for a failed collective hop
            # (ring permute / all-gather): fires BEFORE the dispatch so
            # the donated staging pool is never half-consumed — the
            # chaos contract re-queues the victim, losing nothing
            fault_point("sp.permute")
        except Exception as e:
            self._sp_abort(slot, st, "sp.permute", e)
            return
        c0 = st.pos
        final = c0 + r == len(st.prompt)
        bs = self._kv_bs
        wc = pow2_bucket(r, max(8, self.sp), self._chunk_cap)
        ids = np.zeros((1, wc), np.int32)
        ids[0, :r] = st.prompt[c0:c0 + r]
        # staged head covering [0, c0+wc): bucketed block count for
        # compile reuse; sentinel where the prompt span ends. The cap
        # is _mb_sp (table span + chunk headroom), NOT _mb: a
        # hit-offset final chunk can reach past the table span, and a
        # clamped cached write would corrupt real columns
        nbh = pow2_bucket(-(-(c0 + wc) // bs), 1, self._mb_sp)
        head = np.full((nbh,), self._sp_pool.sentinel, np.int32)
        n_have = min(len(st.sp_blocks), nbh)
        head[:n_have] = st.sp_blocks[:n_have]
        # scatter targets for this chunk's columns; pad columns (>= r)
        # go to the sentinel and drop
        cols = c0 + np.arange(wc)
        sblk = np.full((wc,), self._sp_pool.sentinel, np.int32)
        real = np.arange(wc) < r
        sblk[real] = np.asarray(st.sp_blocks, np.int32)[
            cols[real] // bs]
        soff = (cols % bs).astype(np.int32)
        t0 = time.perf_counter()
        with span("serving.sp_prefill_chunk", parent=st.req.trace_ctx,
                  request_id=st.req.request_id, slot=slot, start=c0,
                  tokens=r, sp=self.sp, final=final):
            logits, self._sp_pool_kv = self._sp_chunk_fn(
                self.variables, self._sp_pool_kv, jnp.asarray(head),
                jnp.asarray(c0, jnp.int32), jnp.asarray(ids),
                jnp.asarray(sblk), jnp.asarray(soff), int(nbh))
        record_dispatch("sp_prefill", 1, time.perf_counter() - t0)
        _M_SP_RING_STEPS.inc(self.sp - 1)
        _M_SP_PERMUTE_BYTES.inc(self._sp_bytes_per_col * wc)
        st.pos += r
        st.chunks += 1
        self._prefill_chunks += 1
        _M_PREFILL_CHUNKS.inc()
        if final:
            first = int(jnp.argmax(logits[0, r - 1]))
            if self._sp_handoff(slot, st):
                self._finish_prefill(slot, st, first)
        self._prefill_seconds += time.perf_counter() - t0

    def _sp_handoff(self, slot: int, st: _Prefill) -> bool:
        """Prefill→decode handoff: gather the request's staged K/V once
        across the sp shards and install it into the decode pool's
        owned blocks — after this the per-token loop is EXACTLY the
        single-device paged path. Returns False when the ``sp.gather``
        fault site fired (request re-queued, nothing lost)."""
        try:
            fault_point("sp.gather")
        except Exception as e:
            self._sp_abort(slot, st, "sp.gather", e)
            return False
        gids = np.full((self._mb,), self._sp_pool.sentinel, np.int32)
        gids[:len(st.sp_blocks)] = st.sp_blocks
        with span("serving.sp_handoff", parent=st.req.trace_ctx,
                  request_id=st.req.request_id, sp=self.sp):
            kd, vd = self._sp_gather_fn(
                self._sp_pool_kv, jnp.asarray(gids))
            # host hop: the staged world is mesh-committed, the decode
            # pool single-device — one bounded copy per ADMISSION, not
            # per token
            self._pool_kv = self._install_blocks_fn(
                self._pool_kv, np.asarray(kd), np.asarray(vd),
                jnp.asarray(st.install_ids))
        self._sp_handoffs += 1
        self._release_sp_staging(st)
        return True

    def _sp_abort(self, slot: int, st: _Prefill, site: str,
                  exc: Exception) -> None:
        """A collective fault mid-sp-prefill: tear the prefill down,
        release every block it holds (staging AND decode pool), and
        re-queue the request at the head — zero lost admitted
        requests; the typed error lands in the flight ring."""
        del self._prefilling[slot]
        self._release_sp_staging(st)
        self._prefix.release(st.all_blocks())
        err = SpCollectiveError(f"{site} failed: {exc!r}")
        flight_mod.record_event(
            "sp.collective_failed", site=site,
            error=type(err).__name__, cause=type(exc).__name__,
            request_id=st.req.request_id, sp=self.sp,
            prefilled=st.pos, prompt_tokens=len(st.prompt))
        self.queue.requeue([st.req])

    def _release_sp_staging(self, st: _Prefill) -> None:
        if st.sp_blocks:
            self._sp_pool.release(self._sp_pool.deref(st.sp_blocks))
            st.sp_blocks = None

    def _vacate(self, flight: _InFlight) -> None:
        """Give a decoding row's slot back, if it still has one: the slot
        is free for admission and its table row empty. The row's blocks
        are another matter (:meth:`_free`)."""
        slot, flight.slot = flight.slot, None
        if slot is None:
            return
        del self._inflight[slot]
        self._table[slot] = self._pool.sentinel
        self._pidx[slot] = 0

    def _free(self, flight: _InFlight) -> None:
        """A decoding row ends, whichever way: its slot back, if it still
        has one, and its block references dropped (registered prompt
        blocks stay cached for prefix reuse; the rest free)."""
        self._vacate(flight)
        if flight.blocks:
            self._prefix.release(flight.blocks)

    def _bounded_tokens(self, now: float, cap: int) -> int:
        """Clamp a per-dispatch token count to (a) the smallest
        remaining token budget in flight — the earliest possible
        retirement, so no slot is held past its scheduled exit and no
        decoded token is wasted on budget grounds — and (b) the tightest
        in-flight deadline over the measured per-token time (2x safety),
        so a request never expires inside a dispatch it could have
        survived. Shared by the chained decode AND the speculative
        verify width — budget/deadline semantics cannot drift between
        the two."""
        going = [f for _, f in self._going_on()]
        cap = min(cap, *(f.left for f in going))
        tok_s = self._chain_policy.program_s
        if tok_s:
            for f in going:
                if f.req.deadline is not None:
                    headroom = (f.req.deadline - now) / (2.0 * tok_s)
                    cap = min(cap, int(headroom))
        elif any(f.req.deadline is not None for f in going):
            # no per-token estimate yet and a deadline is in flight: the
            # first dispatch doubles as the measurement probe at k=1 so
            # a request can never expire inside an unmeasured chain
            return 1
        return cap

    def _count_kv_read(self, nb: int, slots: "list[int]",
                       steps: int = 1) -> "dict[str, int]":
        """Count what a paged dispatch reads through the block table,
        per layer, and hand it back as span arguments: every slot's
        ``nb`` blocks at each of the dispatch's ``steps`` model passes
        (``kv_cols_read``), and how much of that is the context of a row
        that rides it (``slots``), which deepens by one a pass
        (``kv_cols_live``). A family whose step reads the pool in place
        (``decode_reads_in_place``) fetches, for each riding row, the
        whole blocks its depth reaches and nothing for any other slot; one
        whose step attends a selection of its own (``selected_columns``)
        fetches that many columns a slot once any row's table passes it,
        or, while the table is narrow enough for the step to attend in
        place under the selection as a mask (``attends_in_place``, the
        rule the module's step asks), the riding rows' whole blocks too."""
        depths = [int(self._pidx[s]) for s in slots]
        bs = self._kv_bs
        fam = self._family
        picks = fam.selected_columns and nb * bs > fam.selected_columns
        if fam.decode_reads_in_place or attends_in_place(
                nb * bs, fam.selected_columns):
            read = sum(-(-(d + j) // bs) * bs
                       for d in depths for j in range(steps))
        elif picks:
            # the step attends a selection: every slot's rows fetch that
            # many columns one by one, whatever the table's width
            read = self.n_slots * fam.selected_columns * steps
        else:
            read = self.n_slots * nb * bs * steps
        live = steps * sum(depths) + len(depths) * steps * (steps - 1) // 2
        self.metrics.record_kv_read(read, live)
        out = {"kv_cols_read": read, "kv_cols_live": live}
        if fam.selected_columns:
            # per layer: the columns the riding rows' attention attended
            # (``sel_cols``: all of a row's while it has no more than the
            # selection's size); per indexer layer: the columns of riding
            # rows that had to be scored to select (``index_cols``: a row
            # no deeper than the selection needs none; what the indexer
            # FETCHES to do it is every slot's ``nb`` blocks where any
            # row's table passes the selection, the span's ``nb`` says)
            out["sel_cols"] = sum(min(d + j, fam.selected_columns)
                                  for d in depths for j in range(steps))
            out["index_cols"] = sum(
                d + j for d in depths for j in range(steps)
                if d + j > fam.selected_columns)
        if fam.state_layers:
            # the rows whose state the dispatch advances, once a pass, and
            # what each reads and writes of it: its state in every state
            # layer, in and out. (K/V is gathered in ``pool_layers`` of the
            # family's layers alone: the readers multiply by that.)
            out["state_rows"] = len(slots) * steps
            out["state_bytes"] = (2 * len(slots) * steps
                                  * fam.state_bytes_per_slot)
        if fam.ring_columns:
            # a state layer that is a window layer: per layer, the ring
            # columns a pass reads (every slot's ring, whole) and how many
            # of them hold a riding row's context inside its window
            out["win_cols_read"] = self.n_slots * fam.ring_columns * steps
            out["win_cols_live"] = sum(
                min(d + j, fam.ring_columns)
                for d in depths for j in range(steps))
        if fam.window_layers:
            # by kind of layer, summed over the layers of the kind: a
            # window layer gathers only the entries its window covers,
            # and of a live row's context only the window is its to read
            wb = fam.window_blocks(nb, self._kv_bs)
            out["kv_cols_read_window"] = (read // nb) * wb * fam.window_layers
            out["kv_cols_read_full"] = read * (fam.pool_layers
                                               - fam.window_layers)
            out["kv_cols_live_window"] = sum(
                min(d + j, fam.window) for d in depths for j in range(steps))
        return out

    def _count_experts(self, counts: np.ndarray) -> "dict[str, Any]":
        """Span arguments from the per-expert row counts of one dispatch
        (``[steps, expert_layers * experts]``): (token, expert) pairs a
        layer computed (``expert_rows``), experts with at least one row
        (``experts_hit``), both a mean over expert layers and summed over
        steps, and the fullest expert's rows (``expert_rows_max``): all
        three over the experts this chip HOLDS. ``expert_pairs`` is what
        the router made, every row's ``experts_per_token`` a layer: where
        the chip holds a share of the experts, ``expert_rows`` over it is
        the share that was routed here."""
        fam = self._family
        counts = counts.reshape(-1, fam.expert_layers, fam.experts)
        rows, hit = int(counts.sum()), int((counts > 0).sum())
        self.metrics.record_experts(rows, hit)
        return {"expert_rows": rows / fam.expert_layers,
                "experts_hit": hit / fam.expert_layers,
                "expert_rows_max": int(counts.max()),
                "expert_pairs": (counts.shape[0] * self.n_slots
                                 * fam.experts_per_token)}

    def _decode_chain_len(self, now: float) -> int:
        """Tokens to fuse into the next plain decode dispatch: the
        configured/auto cap under the shared budget/deadline bound,
        rounded down to a power of two — at most log2(cap) compiled
        chain programs ever exist."""
        if tenancy.overload_level() >= tenancy.LEVEL_DEGRADE:
            return 1  # brownout: shed chained-decode burstiness first
        cap = (self.chain_tokens if self.chain_tokens is not None
               else self._chain_policy.chain_len())
        cap = self._bounded_tokens(now, cap)
        if cap <= 1:
            return 1
        return 1 << (cap.bit_length() - 1)

    def _spec_width(self, now: float) -> int:
        """Verify width (1 + drafts) for the next speculative dispatch:
        the configured ``spec_k`` cap shrunk by the measured acceptance
        rate (SpecPolicy — wasted verify positions are real FLOPs) and
        the same budget/deadline bound as ``chain_tokens``, so a
        deadline-tight stream degrades to plain single-token decode
        mid-flight instead of expiring inside a wide verify. Power of
        two: {2,4,8,...} compiled verify programs, never one per width.
        """
        if tenancy.overload_level() >= tenancy.LEVEL_DEGRADE:
            return 1  # brownout: wasted verify FLOPs are shed first
        cap = min(self.spec_k, self._spec_policy.spec_len())
        cap = self._bounded_tokens(now, cap)
        if cap < 2:
            return 1
        return 1 << (cap.bit_length() - 1)

    def _spec_step(self) -> bool:
        """One propose -> verify -> accept quantum. Returns True when a
        verify dispatch advanced the batch (the tick's decode is done);
        False when speculation stood down this tick — width bounded
        below 2, no proposer had a draft, or the ``spec.verify`` fault
        site fired (the chaos contract: a failed verify falls back to
        plain decode, zero lost requests)."""
        k = self._spec_width(time.monotonic())
        if k < 2:
            return False
        # propose per live slot (ids only, host-side): context is
        # prompt + produced. Slots whose proposer stands down ride the
        # dispatch with filler drafts — the verify batch is all
        # n_slots wide regardless, rejection costs them nothing, and
        # an accidental filler match is by construction the argmax
        # (i.e. a correct token).
        drafts = np.zeros((self.n_slots, k - 1), np.int32)
        real_len: "dict[int, int]" = {}
        proposed = 0
        for slot, f in self._inflight.items():
            ctx = np.concatenate(
                [f.prompt, np.asarray(f.produced, np.int32)])
            got = self._draft.propose(ctx, k - 1)[:k - 1]
            real_len[slot] = len(got)
            proposed += len(got)
            if got:
                drafts[slot, :len(got)] = got
        if not proposed:
            return False
        try:
            # the injectable stand-in for a failed verify dispatch: it
            # fires BEFORE the jitted call so the donated pool is never
            # half-consumed, and the tick serves everyone through the
            # plain decode path instead
            fault_point("spec.verify")
        except Exception as e:
            self._spec_fallbacks += 1
            _M_SPEC_FALLBACKS.inc()
            flight_mod.record_event(
                "spec.verify_failed",
                engine=getattr(self._obs, "name", None),
                error=type(e).__name__, k=k,
                slots=len(self._inflight))
            return False
        toks = np.concatenate(
            [np.asarray(self._last_tok[:, None], np.int32), drafts],
            axis=1)
        need = max(self._pidx[s] for s in self._inflight) + k
        nb = pow2_bucket(-(-need // self._kv_bs), 1, self._mb)
        t0 = time.perf_counter()
        links = ([f.req.request_id for f in self._inflight.values()]
                 if tracing.tracing_enabled() else ())
        # the span runs on over the acceptance loop, so that it can say
        # how many tokens the verify made (its ``tokens``)
        cols = self._count_kv_read(nb, list(self._inflight))  # one pass, k wide
        with span("serving.spec_verify", slots=len(self._inflight),
                  k=k, links=links, **cols) as verify:
            out, self._pool_kv = self._paged_verify_fn(
                self.variables, self._pool_kv,
                jnp.asarray(self._table), jnp.asarray(self._pidx),
                jnp.asarray(toks), k, nb,
            )
            fetch = start_fetch(out, path="decode")
            jax.block_until_ready(out)
            # sparkdl-lint: disable=blocking-in-hot-loop -- block_until_ready above completed the dispatch; only the already-enqueued D2H copy remains
            out = np.asarray(fetch.result())
            wall = time.perf_counter() - t0
            record_dispatch("decode", k, wall)
            # the deadline bound's per-token estimate: a width-k verify
            # is ~ONE model pass (weight-bound regime), so record it as
            # one step — recording k would shrink program_s k-fold and
            # let _bounded_tokens fuse plain chains far past a deadline's
            # real headroom. Slightly overestimating per-token cost (L=k
            # costs ~1.2x L=1) only makes the deadline caps more
            # conservative.
            self._chain_policy.record(wall, 1)
            self.metrics.record_batch(len(self._inflight), self.n_slots)
            self._spec_dispatches += 1
            accepted = tokens = 0
            for slot in list(self._inflight):
                flight = self._inflight[slot]
                m = greedy_accept(drafts[slot], out[slot, :k - 1])
                accepted += min(m, real_len.get(slot, 0))
                # outputs [:m+1] are real greedy tokens (m accepted
                # drafts + the bonus/correction); append with the SAME
                # per-token retire semantics as the chained path — eos
                # or budget mid-span drops the rest and frees the slot
                # now
                for j in range(m + 1):
                    flight.produced.append(int(out[slot, j]))
                    self._last_tok[slot] = out[slot, j]
                    self._pidx[slot] += 1
                    tokens += 1
                    if self._is_done(flight):
                        self._complete(flight)
                        break
            verify.set_attr(tokens=tokens)
        self._spec_tokens += tokens
        self.metrics.record_tokens(tokens, phase="decode")
        self._spec_proposed += proposed
        self._spec_accepted += accepted
        self._spec_policy.record(proposed, accepted)
        _M_SPEC_PROPOSED.inc(proposed)
        if accepted:
            _M_SPEC_ACCEPTED.inc(accepted)
        with _SPEC_TOTALS_LOCK:
            _SPEC_TOTALS["proposed"] += proposed
            _SPEC_TOTALS["accepted"] += accepted
            _M_SPEC_RATE.set(
                _SPEC_TOTALS["accepted"] / _SPEC_TOTALS["proposed"])
        return True

    def _decode_step(self) -> None:
        """Advance the live rows, in one of two shapes. The loop keeps ONE
        step ahead of its own reads: step n+1 is launched from step n's
        tokens while they are still on the device, and only then are step
        n's ids waited for, read and retired, so everything the host does
        between two launches runs under a step. A speculative engine stays
        synchronous: its verify needs the accepted count on the host
        before its width is chosen."""
        if self.spec_k is not None:
            self._read_first_tokens()
            if self._inflight and not self._spec_step():
                self._launch_step(False)
                self._collect()
            return
        ahead = bool(self._steps_out)
        self._launch_step(ahead)
        # with the step launched: the host waits for a prompt's last
        # chunk, which lies behind step n on the device, with step n+1
        # queued behind the chunk. The rows that join here ride step n+2
        self._read_first_tokens()
        if ahead:
            self._collect_step()
        if not self._steps_out:
            # nothing is running under the host (no row went on above):
            # what has just joined goes at once
            self._launch_step(False)

    def _going_on(self) -> "list[tuple[int, _InFlight]]":
        """The live rows that have budget left once the tokens of the step
        in flight are counted: a row that ends by its budget is known by
        count before any read, and rides no further step."""
        return [(s, f) for s, f in self._inflight.items() if f.left > 0]

    def _launch_step(self, ahead: bool) -> None:
        """Launch one paged step for the rows that go on, if any does.
        ``ahead``: the step before is still unread (its rows' newest
        tokens are on the device alone)."""
        rows = self._going_on()
        if not rows:
            return
        k = self._decode_chain_len(time.monotonic())
        # static gather width: blocks covering the deepest live row
        # through this whole chain (idx advances k), bucketed to a power
        # of two for compile reuse, capped at the table width. It decides
        # what the tick costs, so it rides on the spans.
        slots = [s for s, _ in rows]
        need = max(int(self._pidx[s]) for s in slots) + k
        nb = pow2_bucket(-(-need // self._kv_bs), 1, self._mb)
        # decode ticks are batch-level: their spans link every rider's
        # request id so each request's trace pulls in its decode steps
        links = ([f.req.request_id for _, f in rows]
                 if tracing.tracing_enabled() else ())
        attrs = dict(slots=len(rows), chain=k, links=links, nb=nb,
                     **self._count_kv_read(nb, slots, k))
        # the host's word for a row's token, -1 where the step in flight
        # is making it (the program then takes its own, _dev_tok)
        tok = self._last_tok.copy()
        for slot, f in rows:
            if f.unread:
                tok[slot] = -1
        t0 = time.monotonic()
        # table and cursor go over as COPIES: the host writes both again
        # (a row joins, a row retires, the cursor below) before the step
        # that was handed them has run
        with span("serving.decode_dispatch", k=k, nb=nb, ahead=int(ahead)):
            toks, self._dev_tok, self._pool_kv = self._paged_step_fn(
                self.variables, self._pool_kv,
                jnp.asarray(self._table.copy()),
                jnp.asarray(self._pidx.copy()),
                jnp.asarray(tok), self._dev_tok, k, nb,
            )
        # Async token readback (runtime/completion.py): the D2H copy of
        # the ids is enqueued the moment the step is, and rides behind it
        fetch = start_fetch(toks, path="decode")
        _M_DECODE_AHEAD.inc(ahead=str(int(ahead)))
        self._steps_out.append(_StepOut(toks, fetch, rows, t0, attrs))
        for slot, f in rows:
            # one column written per decoded token, whatever the token
            # is: the cursor moves at the launch, so that the next launch
            # needs nothing of this step's result
            f.unread += k
            self._pidx[slot] += k
            if f.left == 0:
                # its last tokens are on their way, which the host knows
                # by count: the slot is free for the next admission as
                # early as in a loop that had read them by now. The
                # blocks stay the row's until it has all its tokens.
                self._vacate(f)

    def _collect_step(self) -> None:
        """Wait for the oldest unread step's ids, read them, retire its
        rows. ``serving.decode_step`` is recorded HERE: from the step's
        launch to the instant its ids reached the host. The step leaves
        ``_steps_out`` once it is read: one the device lost stays there,
        for ``_fail_inflight`` to fail its rows."""
        step = self._steps_out[0]
        # block_until_ready splits compute from collection so
        # sparkdl_fetch_wait_seconds{path="decode"} meters ONLY the
        # residual copy wait, not the decode program itself
        with span("serving.decode_wait"):
            jax.block_until_ready(step.toks)
        # sparkdl-lint: disable=blocking-in-hot-loop -- block_until_ready above completed the step; only the already-enqueued D2H copy remains
        toks = np.asarray(step.fetch.result())
        now = time.monotonic()
        self._steps_out.popleft()
        attrs, k = step.attrs, step.attrs["chain"]
        if self._family.expert_layers:
            # an expert family's step: rows each expert got ride behind
            # the tokens in the same read
            attrs.update(self._count_experts(toks[:, self.n_slots:]))
            toks = toks[:, :self.n_slots]
        tracing.record_span("serving.decode_step", step.t0, now,
                            parent=tracing.current_context(), **attrs)
        for _, flight in step.rows:
            flight.unread -= k
        # what one step costs the loop: the interval since the step
        # before was read, or since this one's launch where nothing was
        # out then. A launch-to-read wall of a step launched AHEAD would
        # hold the rest of the step before it too.
        wall = now - max(step.t0, self._collected_at)
        self._collected_at = now
        record_dispatch("decode", k, wall)
        self._chain_policy.record(wall, k)
        self.metrics.record_batch(len(step.rows), self.n_slots)
        self._retire(toks, k, step.rows, attrs["links"])

    def _collect(self) -> None:
        """Read every step that is out and retire its rows: after it the
        host's view of every row (``produced``, blocks, Futures) is what a
        synchronous loop's would be. Whatever touches that view outside
        the decode loop's own order calls this first, under the engine
        lock. (Device order needs no such care: the pool is threaded
        through every program.)"""
        while self._steps_out:
            self._collect_step()

    def _settle(self) -> None:
        """:meth:`_collect` for a caller that does not hold the engine
        lock."""
        with self._lock:
            self._collect()

    def _retire(self, toks: np.ndarray, k: int,
                rows: "list[tuple[int, _InFlight]]", links) -> None:
        """Hand a read step's ``k`` tokens to the ``rows`` that rode it.
        What the step made: rows times k, less what an eos dropped
        mid-chain, and less the rows that ended while it was out."""
        with span("serving.retire", links=links) as retire:
            tokens = completed = 0
            for j in range(k):
                # a row found at its eos one step late rode this step
                # too: its Future is resolved by now, and what the step
                # made for it is dropped — rows are independent, so it
                # influenced nobody, and its one column fell in a block
                # the row still owned at the launch
                live = [(s, f) for s, f in rows if not f.req.future.done()]
                if not live:
                    break
                for slot, flight in live:
                    flight.produced.append(int(toks[j, slot]))
                    if flight.slot is not None:
                        self._last_tok[slot] = toks[j, slot]
                    tokens += 1
                    if self._is_done(flight):
                        # eos mid-chain: any later tokens the chain
                        # decoded for this row are dropped the same way
                        self._complete(flight)
                        completed += 1
            retire.set_attr(tokens=tokens, completed=completed)
        self.metrics.record_tokens(tokens, phase="decode")

    def _is_done(self, flight: _InFlight) -> bool:
        return (len(flight.produced) >= flight.max_new
                or (self.eos_id is not None
                    and flight.produced[-1] == self.eos_id))

    def _record_request_span(self, req: Request, now: float, *,
                             ok: bool, tokens: int,
                             error: "Exception | None" = None) -> None:
        if tracing.tracing_enabled():
            tracing.record_span(
                "serving.request", req.enqueued, now,
                parent=req.trace_ctx, request_id=req.request_id,
                ok=ok, tokens=tokens,
                **({"error": type(error).__name__} if error else {}),
            )

    def _register_session(self, flight: _InFlight) -> None:
        """Index the finished turn's whole sequence — prompt plus
        produced tokens minus the last (the row's first columns hold
        exactly the KV of ``prompt + produced[:-1]``, in its blocks in
        table order; one more column may follow, written by the step a
        late eos rode) — so the session's NEXT turn, whose prompt embeds
        this turn verbatim, parks and resumes instead of
        re-prefilling. Tiered engines only: without a park tier the
        extra registrations would just bloat the LRU."""
        seq = (tuple(int(t) for t in flight.prompt)
               + tuple(int(t) for t in flight.produced[:-1]))
        if not seq:
            return
        nb = -(-len(seq) // self._kv_bs)
        self._prefix.register(seq, [int(b) for b in flight.blocks[:nb]])

    def _complete(self, flight: _InFlight) -> None:
        """The row has all its tokens: it holds nothing any more, and its
        Future resolves."""
        if self._kv_tiers is not None:
            self._register_session(flight)
        self._free(flight)
        now = time.monotonic()
        self._record_request_span(
            flight.req, now, ok=True, tokens=len(flight.produced))
        flight.req.future.set_result(
            np.asarray(flight.produced, np.int32)
        )
        self.metrics.record_request(now - flight.req.enqueued, ok=True)
        reg = self.queue.tenants
        if reg is not None:
            reg.note_outcome(flight.req.tenant,
                             now - flight.req.enqueued, ok=True)

    def _fail_request(self, req: Request, exc: Exception, *,
                      tokens: int) -> None:
        """The one failure sequence every retire-with-error path shares:
        terminal span, Future exception, shed-load counter, latency
        metric. Skips Futures already resolved elsewhere."""
        if req.future.done():
            return
        now = time.monotonic()
        self._record_request_span(
            req, now, ok=False, tokens=tokens, error=exc)
        req.future.set_exception(exc)
        record_request_failure(exc, request_id=req.request_id)
        self.metrics.record_request(now - req.enqueued, ok=False)
        reg = self.queue.tenants
        if reg is not None:
            reg.note_outcome(req.tenant, now - req.enqueued, ok=False)

    def _expire_inflight(self, now: float) -> None:
        late = [f for f in self._inflight.values() if f.req.expired(now)]
        if late:
            # the tokens they are owed first: a row may have finished
            self._collect()
        for flight in late:
            if flight.req.future.done():
                continue
            self._free(flight)
            self._fail_request(
                flight.req,
                DeadlineExceededError(
                    "deadline exceeded mid-decode "
                    f"({len(flight.produced)}/{flight.max_new} "
                    "tokens)"),
                tokens=len(flight.produced))
        for slot in list(self._prefilling):
            st = self._prefilling[slot]
            if st.req.expired(now):
                self._prefilling.pop(slot)
                self._prefix.release(st.all_blocks())
                self._release_sp_staging(st)
                self._fail_request(
                    st.req,
                    DeadlineExceededError(
                        "deadline exceeded mid-prefill "
                        f"({st.pos}/{len(st.prompt)} prompt tokens)"),
                    tokens=0)

    def _fail_inflight(self, exc: Exception) -> None:
        try:
            self._collect()
        except Exception as e:
            # the step died with the device (a crashed loop lands here):
            # its rows fail below with the rest, none is left waiting
            flight_mod.record_event(
                "engine.step_lost", error=type(e).__name__,
                engine=getattr(self._obs, "name", None))
        # every row still owed tokens: those in a slot, and those that
        # gave theirs back when a step that is now lost was launched
        owed = list(self._inflight.values()) + [
            f for step in self._steps_out for _, f in step.rows
            if f.slot is None and not f.req.future.done()]
        self._steps_out.clear()
        for flight in owed:
            self._free(flight)
            self._fail_request(flight.req, exc,
                               tokens=len(flight.produced))
        for slot in list(self._prefilling):
            st = self._prefilling.pop(slot)
            self._prefix.release(st.all_blocks())
            self._release_sp_staging(st)
            self._fail_request(st.req, exc, tokens=0)

    # -- introspection -------------------------------------------------------
    @property
    def active_slots(self) -> int:
        return len(self._inflight)

    def trace(self, request_id: int) -> "list[dict]":
        """Every finished span of one request's trace (queue wait,
        prefill, its decode-step dispatches via links, the terminal
        ``serving.request``). Empty with tracing off."""
        return tracing.spans_for_trace(request_id)

    def inflight_request_ids(self) -> "list[int]":
        """Ids of queued + prefilling + decoding requests (postmortem
        input). Best-effort: read without the engine lock."""
        out = self.queue.pending_request_ids()
        try:
            out.extend(f.req.request_id
                       for f in list(self._inflight.values()))
            out.extend(s.req.request_id
                       for s in list(self._prefilling.values()))
        except RuntimeError:  # pragma: no cover - mutation race
            pass
        return out

    def _kv_snapshot(self) -> "dict[str, Any]":
        return {
            "block_size": self._kv_bs,
            "blocks_total": self._pool.n_blocks,
            "blocks_used": self._pool.used_count,
            "blocks_used_peak": self._pool.used_peak,
            "blocks_spare": self._pool.spare_count,
            "blocks_cached": self._prefix.cached_blocks,
            "prefix_hits": self._prefix.hit_tokens,
            "prefix_misses": self._prefix.miss_tokens,
            "prefix_evictions": self._prefix.evictions,
            "prefill_chunk": self.prefill_chunk,
            "prefill_chunks": self._prefill_chunks,
            "deferrals_total": self._deferrals,
            # the MAX of decode + staging streaks: /healthz reads this
            # as degraded, and a staging-only stall must degrade too
            "exhausted_streak": max(
                self._pool.deferral_streak,
                self._sp_pool.deferral_streak if self.sp > 1 else 0),
            "dtype": self.kv_dtype,
            "bytes_per_token": kv_bytes_per_token(
                self.config, self.kv_dtype),
            # what a family with state layers holds beside the pool: by
            # slot, whatever the contexts' lengths (0 for any other)
            "state_bytes_per_slot": self._family.state_bytes_per_slot,
            "state_bytes": self.n_slots * self._family.state_bytes_per_slot,
            "prefix_passed_up": self._prefix_passed_up,
            "capacity_ratio_vs_fp32": round(kv_capacity_ratio(
                self.config, self.kv_dtype), 4),
            **({"sp": {
                "axis": self.sp,
                "staging_blocks_total": self._sp_pool.n_blocks,
                "staging_blocks_used": self._sp_pool.used_count,
                "staging_streak": self._sp_pool.deferral_streak,
                "shard_used": self._sp_pool.shard_used_counts(),
                "handoffs": self._sp_handoffs,
            }} if self.sp > 1 else {}),
            # host/disk tier occupancy rides the same snapshot into
            # the flight recorder's pool-pressure context and healthz
            **({"tiers": {
                **(self._prefix.tier_stats() or {}),
                "park_fallbacks": self._park_fallbacks,
                "unpark_reserved": self._pool.unpark_reserved,
            }} if self._kv_tiers is not None else {}),
        }

    def _spec_snapshot(self) -> "dict[str, Any] | None":
        if self.spec_k is None:
            return None
        return {
            "spec_k": self.spec_k,
            "dispatches": self._spec_dispatches,
            "fallbacks": self._spec_fallbacks,
            "proposed": self._spec_proposed,
            "accepted": self._spec_accepted,
            "acceptance_rate": (
                round(self._spec_accepted / self._spec_proposed, 4)
                if self._spec_proposed else None),
            "tokens": self._spec_tokens,
            "tokens_per_dispatch": (
                round(self._spec_tokens / self._spec_dispatches, 4)
                if self._spec_dispatches else None),
        }

    def _flight_context(self) -> dict:
        out = self.metrics.snapshot(self.queue)
        out["active_slots"] = self.active_slots
        out["prefilling_slots"] = len(self._prefilling)
        out["inflight_request_ids"] = self.inflight_request_ids()
        # healthz_report aggregates this shape: a nonzero
        # exhaustion streak reads as degraded (self-recovering)
        out["kv_pool"] = self._kv_snapshot()
        spec = self._spec_snapshot()
        if spec is not None:
            out["spec"] = spec
        ctrl = tenancy.process_overload()
        if ctrl is not None:
            out["overload"] = ctrl.snapshot()
        reg = self.queue.tenants
        if reg is not None:
            out["tenants"] = reg.snapshot()
        return out

    def kv_autoscale_binding(self) -> "tuple[Any, Any]":
        """``(pool, lock)`` for the elastic autoscaler's KV actuator
        (ISSUE 15): the block pool whose serving/spare split the
        controller resizes, plus the engine lock that guards every
        pool mutation — ``AutoScaler(kv_pool=pool, kv_lock=lock)``
        then grows/shrinks without racing admission."""
        return self._pool, self._lock

    def capacity(self) -> "dict[str, Any]":
        """The one structure a router's weighting reads (ISSUE 14):
        identity + room, instead of poking queue, pool, and slot state
        separately. Best-effort reads (no engine lock): routing weights
        tolerate a tick of staleness."""
        # parkable pressure split (ROADMAP item 1): cold = refcount-0
        # cached blocks that COULD page out on demand, parked = blocks
        # already in the host/disk tiers. A router that reads only
        # kv_blocks_free scores a host full when its pressure is
        # actually idle sessions — the headroom policy folds these in.
        cold = parked = sessions = None
        try:
            cold = self._prefix.cold_blocks()
        except RuntimeError:
            cold = None  # racing registration: stale next refresh
        if self._kv_tiers is not None:
            s = self._kv_tiers.stats()
            parked = s["host_blocks"] + s["disk_blocks"]
            try:
                sessions = self._prefix.parked_sessions()
            except RuntimeError:
                sessions = None
        return {
            "host_id": self.host_id,
            "replica_count": 1,
            "n_slots": self.n_slots,
            "free_slots": (self.n_slots - len(self._inflight)
                           - len(self._prefilling)),
            "kv_blocks_free": self._pool.free_count,
            "kv_blocks_total": self._pool.n_blocks,
            "kv_bytes_per_token": kv_bytes_per_token(
                self.config, self.kv_dtype),
            "state_bytes": self.n_slots * self._family.state_bytes_per_slot,
            "kv_blocks_cold": cold,
            "kv_parked_blocks": parked,
            "kv_parked_sessions": sessions,
            "queue_depth": self.queue.depth,
            "max_queue_depth": self.queue.max_depth,
            "draining": self.queue.closed,
            # brownout level (ISSUE 20): a router discounts a
            # browned-out host's headroom so the fleet routes around
            # local overload while the ladder sheds it
            "overload_level": tenancy.overload_level(),
        }

    def snapshot(self) -> dict[str, Any]:
        # between ticks this reads what a synchronous loop would show;
        # mid-tick it reads a live engine as it always did, and does not
        # wait for the lock (the caller may be a Future's callback on the
        # engine's own thread, inside the tick)
        if not self._lock.locked():
            self._settle()
        out = self.metrics.snapshot(self.queue)
        out["host_id"] = self.host_id
        out["capacity"] = self.capacity()
        out["active_slots"] = self.active_slots
        out["n_slots"] = self.n_slots
        out["prefill_seconds"] = self._prefill_seconds
        out["kv"] = self._kv_snapshot()
        out["spec"] = self._spec_snapshot()
        out["slo"] = (self.slo_tracker.sample()
                      if self.slo_tracker is not None else None)
        return out

    def __enter__(self) -> "ContinuousGPTEngine":
        return self

    def __exit__(self, *exc) -> None:
        self.close(drain=exc == (None, None, None))
