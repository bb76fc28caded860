"""Profiler hookup: device traces (jax.profiler) + host stack sampling.

SURVEY.md §5 "Tracing / profiling": the reference has nothing in-repo; the
TPU equivalent is ``jax.profiler`` — XPlane/Perfetto traces showing XLA op
timing, infeed gaps and ICI collective overlap. Device-side entry points:

* :func:`trace` — capture a trace of a code block to a logdir (viewable in
  TensorBoard's profile plugin / Perfetto);
* :func:`start_trace_server` — long-lived per-host server so an operator
  can attach and sample a live job (the TPURunner worker starts one when
  ``SPARKDL_TPU_PROFILER_PORT`` is set).

Host-side (ISSUE 9): the device trace shows what XLA did, not what the
*host* threads were doing while the chip starved — :func:`profile_block`
samples every Python thread's stack at a fixed cadence
(``sys._current_frames``, no instrumentation, a few µs per sample) and
writes a **collapsed-stack** file (``stack;frames;leaf count`` lines, the
format flamegraph.pl / speedscope / inferno eat directly). Benches wire
it behind ``SPARKDL_TPU_PROFILE=1`` via :func:`maybe_profile`, so "why is
the feed thread blocked" is one env var away on any bench run.
"""

from __future__ import annotations

import collections
import contextlib
import os
import sys
import threading
import time
from typing import Iterator

import jax

#: Truthy -> benches run under profile_block (see maybe_profile).
PROFILE_ENV = "SPARKDL_TPU_PROFILE"
#: Where maybe_profile writes its .folded files (default: cwd).
PROFILE_DIR_ENV = "SPARKDL_TPU_PROFILE_DIR"
#: Sampling cadence override, Hz (default 99 — deliberately not a round
#: 100 so the sampler cannot alias against 10ms-periodic work).
PROFILE_HZ_ENV = "SPARKDL_TPU_PROFILE_HZ"

#: Frames kept per stack (deeper tails are truncated at the root end).
_MAX_DEPTH = 128


@contextlib.contextmanager
def trace(logdir: str | os.PathLike,
          create_perfetto_trace: bool = False) -> Iterator[None]:
    """Capture a ``jax.profiler`` trace of the enclosed block into ``logdir``.

    Remember to ``jax.block_until_ready`` the last output inside the block,
    otherwise async dispatch leaks device work past the capture window.
    With tracing on (``observability.tracing``), every live span of the
    program lies in the capture's host plane under its own name, on the
    clock of the device operations.
    """
    jax.profiler.start_trace(
        os.fspath(logdir), create_perfetto_trace=create_perfetto_trace
    )
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def start_trace_server(port: int = 9999):
    """Start the live profiling server on this host (one per process)."""
    return jax.profiler.start_server(port)


class StackProfile:
    """Wall-clock sampler of every Python thread's stack.

    A daemon thread wakes every ``interval_s`` and snapshots
    ``sys._current_frames()`` — sampling, not tracing: zero cost between
    samples, a few µs per live thread per sample, and the result is a
    statistical flame graph of where host threads actually sit (queue
    waits, decode loops, GIL-held numpy stacking, ...). The sampler
    excludes itself.
    """

    def __init__(self, interval_s: float = 0.0101):
        if interval_s <= 0:
            raise ValueError(f"interval_s must be > 0, got {interval_s}")
        self.interval_s = interval_s
        #: collapsed stack (root-first, ';'-joined) -> sample count
        self.samples: "collections.Counter[str]" = collections.Counter()
        self.n_samples = 0
        self._stop = threading.Event()
        self._thread: "threading.Thread | None" = None

    def start(self) -> "StackProfile":
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._loop, name="sparkdl-stack-sampler", daemon=True
            )
            self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2)

    def _loop(self) -> None:
        me = threading.get_ident()
        while not self._stop.wait(self.interval_s):
            self.sample_once(_skip_ident=me)

    def sample_once(self, _skip_ident: "int | None" = None) -> None:
        """Take one sample of every live thread (public for tests)."""
        names = {t.ident: t.name for t in threading.enumerate()}
        for ident, frame in sys._current_frames().items():
            if ident == _skip_ident:
                continue
            stack = []
            f = frame
            while f is not None and len(stack) < _MAX_DEPTH:
                co = f.f_code
                stack.append(
                    f"{os.path.basename(co.co_filename)}:{co.co_name}"
                )
                f = f.f_back
            stack.append(names.get(ident, f"thread-{ident}"))
            self.samples[";".join(reversed(stack))] += 1
        self.n_samples += 1

    def write_collapsed(self, path: "str | os.PathLike") -> int:
        """Write the ``stack count`` lines flamegraph.pl / speedscope /
        inferno consume. Returns the number of distinct stacks."""
        with open(path, "w") as f:
            for stack, count in sorted(self.samples.items()):
                f.write(f"{stack} {count}\n")
        return len(self.samples)


@contextlib.contextmanager
def profile_block(path: "str | os.PathLike | None" = None, *,
                  interval_s: float = 0.0101) -> Iterator[StackProfile]:
    """Sample thread stacks for the duration of the block; write the
    collapsed-stack file to ``path`` on exit (skip the write with
    ``path=None`` and read ``.samples`` directly)."""
    prof = StackProfile(interval_s=interval_s).start()
    try:
        yield prof
    finally:
        prof.stop()
        if path is not None:
            prof.write_collapsed(path)


def maybe_profile(name: str):
    """The bench hook: a no-op context unless ``SPARKDL_TPU_PROFILE`` is
    truthy, in which case the block runs under :func:`profile_block`
    writing ``sparkdl-profile-<name>-<pid>.folded`` into
    ``SPARKDL_TPU_PROFILE_DIR`` (default cwd). The path is announced on
    stderr — bench stdout must stay one JSON line."""
    if os.environ.get(PROFILE_ENV, "") in ("", "0"):
        return contextlib.nullcontext(None)
    directory = os.environ.get(PROFILE_DIR_ENV) or "."
    path = os.path.join(
        directory, f"sparkdl-profile-{name}-{os.getpid()}.folded"
    )
    hz = float(os.environ.get(PROFILE_HZ_ENV, "99"))
    if hz <= 0:
        raise ValueError(
            f"{PROFILE_HZ_ENV} must be > 0, got {hz} (unset "
            f"{PROFILE_ENV} to disable profiling instead)"
        )

    @contextlib.contextmanager
    def _ctx():
        t0 = time.perf_counter()
        with profile_block(path, interval_s=1.0 / hz) as prof:
            yield prof
        print(
            f"[profile] {prof.n_samples} samples over "
            f"{time.perf_counter() - t0:.1f}s -> {path} "
            "(flamegraph.pl / speedscope-compatible collapsed stacks)",
            file=sys.stderr,
        )

    return _ctx()
