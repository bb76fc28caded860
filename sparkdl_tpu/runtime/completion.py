"""Asynchronous device→host completion: overlap readback with dispatch.

PR 3 amortized *launch* overhead (one dispatch per K fused steps), but
every hot path still ended in a host-blocking ``np.asarray(out)``: the
device→host copy of batch i serialized with the dispatch of batch i+1.
This module is the software-pipelining half of that argument (tf.data, Murray et al.): a
result's D2H copy is *started* the moment its dispatch is enqueued
(``jax.Array.copy_to_host_async``) and *collected* only when the caller
actually needs the host value — by which point the next dispatch is
already running and the copy has landed underneath it.

Three pieces:

* :func:`start_fetch` — begin a non-blocking D2H copy of one output
  pytree and return a :class:`FetchTicket`; ``ticket.result()`` blocks
  only for whatever copy time is *left* (metered as
  ``sparkdl_fetch_wait_seconds{path=...}`` — the number that must drop
  when overlap works).
* :class:`AsyncFetcher` — the windowed form: ``submit()`` up to
  ``window`` outputs in flight (device memory stays capped at ``window``
  result buffers), ``stream()`` maps a device-output iterator to host
  results with submission order preserved and a device error surfacing
  on the result index of the batch that caused it, never at the window
  edge.
* a bounded readback thread pool (``SPARKDL_TPU_FETCH_THREADS``) as the
  fallback for leaves without ``copy_to_host_async`` — same window
  bound, same ordering contract.

Wired into every production hot path: ``BatchedRunner.run`` (results
stream out while the next chained dispatch runs),
``BatchedRunner.run_batch_async`` (the future-returning serving variant
the micro-batcher pipelines on), ``finetune`` host-metric reads, and the
continuous-GPT token readback.
"""

from __future__ import annotations

import collections
import os
import threading
import time
from concurrent.futures import (
    ThreadPoolExecutor,
    TimeoutError as FuturesTimeoutError,
)
from typing import Any, Callable, Iterable, Iterator

import numpy as np

from sparkdl_tpu.observability import tracing
from sparkdl_tpu.observability.registry import registry
from sparkdl_tpu.reliability.faults import fault_point

__all__ = [
    "AsyncFetcher",
    "FetchTicket",
    "fetch_metrics",
    "fetch_wait_seconds",
    "start_fetch",
]

_METRICS = None


def fetch_metrics():
    """Lazy handles for the completion spine (one tuple per process):
    (fetches counter by path, host-blocked-wait histogram by path,
    in-flight gauge)."""
    global _METRICS
    if _METRICS is None:
        _METRICS = (
            registry().counter(
                "sparkdl_fetches_total",
                "device->host result fetches started", labels=("path",)),
            registry().histogram(
                "sparkdl_fetch_wait_seconds",
                "host time blocked collecting an async D2H result "
                "(0-ish = the copy hid behind the next dispatch)",
                labels=("path",)),
            registry().gauge(
                "sparkdl_fetch_inflight",
                "async fetches currently in flight, all paths"),
        )
    return _METRICS


def fetch_wait_seconds(path: "str | None" = None) -> float:
    """Total host seconds blocked in ``result()`` (summed over paths when
    ``path`` is None) — the benches' ``fetch_wait_share`` numerator."""
    fam = registry().get("sparkdl_fetch_wait_seconds")
    if fam is None:
        return 0.0
    values = fam.snapshot_values()
    if path is not None:
        series = values.get(f'path="{path}"')
        return float(series["sum"]) if series else 0.0
    return float(sum(v["sum"] for v in values.values()))


_POOL: "ThreadPoolExecutor | None" = None
_POOL_LOCK = threading.Lock()


def _readback_pool() -> ThreadPoolExecutor:
    """Bounded fallback pool for leaves without ``copy_to_host_async``.

    Bounded (default 2 workers) so a burst of fallback fetches can never
    fan out into unbounded host threads — the window, not the pool,
    is the in-flight control; the pool only provides *a* background
    thread for the copy."""
    global _POOL
    with _POOL_LOCK:
        if _POOL is None:
            _POOL = ThreadPoolExecutor(
                max_workers=max(
                    1, int(os.environ.get("SPARKDL_TPU_FETCH_THREADS", "2"))
                ),
                thread_name_prefix="sparkdl-fetch",
            )
        return _POOL


def _tree_leaves(tree: Any) -> "list[Any]":
    import jax

    return jax.tree.leaves(tree)


def _to_host(tree: Any) -> Any:
    """Materialize every leaf on the host (np.asarray is a no-op for
    leaves already there). Raises the deferred device error, if any."""
    import jax

    return jax.tree.map(np.asarray, tree)


class FetchTicket:
    """One in-flight device→host fetch. ``result()`` blocks for whatever
    copy time is left, converts to host arrays, and raises the device
    error of THIS batch if its computation failed. Thread-safe and
    idempotent (the resolution is memoized)."""

    __slots__ = ("_path", "_value", "_exc", "_done", "_lock", "_future",
                 "_tree")

    def __init__(self, tree: Any, path: str, future=None):
        self._tree = tree
        self._path = path
        self._future = future  # fallback-pool future, else None
        self._value: Any = None
        self._exc: "BaseException | None" = None
        self._done = False
        self._lock = threading.Lock()

    def result(self, timeout: "float | None" = None) -> Any:
        """Host pytree of this fetch. A timeout raises
        ``concurrent.futures.TimeoutError`` and is NOT terminal — the
        fetch stays collectable (the direct path polls ``is_ready`` to
        honor the deadline; leaves without it block on the runtime)."""
        with self._lock:
            if not self._done:
                _, wait_hist, inflight = fetch_metrics()
                t0 = time.monotonic()
                finished = True
                try:
                    if self._future is not None:
                        self._value = self._future.result(timeout)
                    else:
                        if timeout is not None:
                            self._wait_ready(t0 + timeout)
                        self._value = _to_host(self._tree)
                except FuturesTimeoutError:
                    # the copy is merely not done yet: surface the
                    # timeout but leave the ticket pending/collectable
                    finished = False
                    raise
                except BaseException as e:
                    self._exc = e
                finally:
                    if finished:
                        self._done = True
                        self._tree = None  # release the device refs
                        now = time.monotonic()
                        wait_hist.observe(now - t0, path=self._path)
                        inflight.dec()
                        tracing.record_span(
                            "fetch.wait", t0, now, path=self._path)
            if self._exc is not None:
                raise self._exc
            return self._value

    def _wait_ready(self, deadline: float) -> None:
        """Poll leaf readiness until ``deadline`` so a timed ``result()``
        is honored on the direct (copy_to_host_async) path too — jax has
        no timed blocking wait, so this is a coarse is_ready poll; leaves
        without is_ready fall through to the blocking conversion."""
        leaves = [l for l in _tree_leaves(self._tree)
                  if hasattr(l, "is_ready")]
        while leaves:
            leaves = [l for l in leaves if not l.is_ready()]
            if not leaves:
                return
            if time.monotonic() >= deadline:
                raise FuturesTimeoutError(
                    f"fetch not ready within deadline "
                    f"({len(leaves)} leaf buffer(s) still in flight)"
                )
            time.sleep(0.001)

    def _release(self) -> None:
        """Abandonment path (GC of an unresolved ticket): the fetch will
        never be collected — the in-flight gauge must not leak."""
        with self._lock:
            if not self._done:
                self._done = True
                self._tree = None
                fetch_metrics()[2].dec()

    def __del__(self):  # pragma: no cover - GC timing
        try:
            self._release()
        except Exception:
            pass


def start_fetch(tree: Any, *, path: str = "default") -> FetchTicket:
    """Begin a non-blocking D2H copy of ``tree`` and return the ticket.

    Every jax-array leaf gets ``copy_to_host_async()`` — a pure hint that
    enqueues the transfer behind the leaf's computation, so the copy
    begins the moment compute finishes instead of after the host comes
    back asking. Leaves without the method (older runtimes, alternative
    array types) ride the bounded readback thread pool instead; plain
    host arrays pass through untouched either way.
    """
    fault_point("fetch")
    fetches, _, inflight = fetch_metrics()
    fetches.inc(path=path)
    inflight.inc()
    needs_pool = False
    for leaf in _tree_leaves(tree):
        if isinstance(leaf, np.ndarray) or np.isscalar(leaf):
            continue
        copy_async = getattr(leaf, "copy_to_host_async", None)
        if copy_async is None:
            needs_pool = True
            continue
        try:
            copy_async()
        except Exception:
            # the hint must never fail a fetch the blocking path could
            # serve — result() falls back to a plain np.asarray wait
            needs_pool = True
    future = _readback_pool().submit(_to_host, tree) if needs_pool else None
    return FetchTicket(tree, path, future)


class AsyncFetcher:
    """Windowed async completion: at most ``window`` results in flight.

    ``submit()`` starts one fetch; the caller keeps the returned tickets
    and resolves them in submission order (the window bound is then the
    caller's deque length — :mod:`~sparkdl_tpu.train.finetune` does
    this). :meth:`stream` is the iterator form the batch path uses::

        for host_out in AsyncFetcher(window=8, path="batch").stream(outs):
            ...  # device outputs of up to 8 batches are in flight

    Ordering/error contract (pinned by tests/runtime/test_completion.py):
    results come back in submission order, and an error raised by batch
    i's computation or readback surfaces when result i is collected —
    after results 0..i-1 were delivered, never early at the window edge.
    """

    def __init__(self, *, window: int = 2, path: str = "default"):
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
        self.window = window
        self.path = path

    def submit(self, tree: Any) -> FetchTicket:
        return start_fetch(tree, path=self.path)

    def stream(self, outputs: Iterable[Any]) -> Iterator[Any]:
        """Map a device-output iterator to host results, ``window`` deep.

        Pulling from ``outputs`` is what issues the NEXT dispatch (the
        ScanChainer/jit call lives inside the source iterator), so a
        window of W keeps W results' D2H copies overlapping the following
        dispatches while device memory holds at most W result buffers.
        A source-side error (a failed dispatch) is delivered after the
        results submitted before it, on its own batch index.
        """
        pending: "collections.deque[FetchTicket]" = collections.deque()
        it = iter(outputs)
        source_exc: "BaseException | None" = None
        while True:
            try:
                out = next(it)
            except StopIteration:
                break
            except BaseException as e:
                # batches already in flight precede the failed dispatch:
                # deliver them first, then surface the error at ITS index
                source_exc = e
                break
            pending.append(self.submit(out))
            if len(pending) >= self.window:
                yield pending.popleft().result()
        while pending:
            yield pending.popleft().result()
        if source_exc is not None:
            raise source_exc
