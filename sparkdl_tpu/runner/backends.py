"""Cluster backends for TPURunner: local processes and Spark barrier jobs.

Reference parity (SURVEY.md 2.13/3.4): HorovodRunner's two regimes —
``np < 0`` local debug processes, ``np > 0`` Spark barrier tasks with an
MPI rendezvous — map here to :class:`LocalProcessBackend` (subprocesses on
this host) and :class:`SparkBarrierBackend` (one barrier task per TPU host,
rendezvous via ``BarrierTaskContext.allGather``). Both end in
``jax.distributed.initialize``: in-step gradient comm is XLA collectives
over ICI/DCN compiled into the program, so there is no user-space ring to
bootstrap — only the coordinator address exchange.
"""

from __future__ import annotations

import logging
import os
import pickle
import shutil
import socket
import subprocess
import sys
import tempfile
import threading
from typing import Any, Callable

logger = logging.getLogger(__name__)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("", 0))
        return s.getsockname()[1]


def virtual_cpu_overrides(n_devices: int, existing_flags: str = "") -> dict:
    """Env overrides forcing an ``n_devices``-way virtual CPU platform.

    The single source of truth for the "fake mesh" env contract used by the
    test conftest, LocalProcessBackend children, and the graft-entry
    dry-run re-exec: ``JAX_PLATFORMS=cpu`` plus
    ``--xla_force_host_platform_device_count`` (any existing count flag in
    ``existing_flags`` is replaced, not duplicated). Overrides must be in
    place before the target process initializes a jax backend.
    """
    flags = [
        f
        for f in existing_flags.split()
        if not f.startswith("--xla_force_host_platform_device_count")
    ]
    flags.append(f"--xla_force_host_platform_device_count={n_devices}")
    return {"JAX_PLATFORMS": "cpu", "XLA_FLAGS": " ".join(flags)}


def tpu_chip_pin_overrides(chip: int) -> dict:
    """Env overrides pinning a child process to ONE local TPU chip.

    The companion of :func:`virtual_cpu_overrides` for real hardware:
    concurrent single-host child interpreters (process trial runners,
    per-chip workers) must each see a disjoint chip, or they deadlock on
    the libtpu lock. Must be in the child env before it imports jax.
    """
    return {
        "TPU_VISIBLE_DEVICES": str(chip),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1",
    }


#: ``TPU_PROCESS_BOUNDS`` for a single-host job of one process per chip,
#: by chip count: the process grid must match the host's physical chip
#: grid (a four-chip v5e host is a 2x2 mesh). Only layouts that have run
#: on real hardware are listed; anything else raises.
_HOST_PROCESS_BOUNDS = {1: "1,1,1", 4: "2,2,1"}


def tpu_rank_overrides(rank: int, nprocs: int, ports: "list[int]") -> dict:
    """Env overrides making ``nprocs`` one-chip processes on ONE host
    form a single ``nprocs``-device TPU job: rank r owns chip r, and
    libtpu is told the process grid and every peer's address (the
    chip-to-chip runtime rendezvous — separate from
    ``jax.distributed``'s coordinator). ``ports``: one free port per
    rank. Must be in the child env before it imports jax. Inside the
    job ``jax.process_index()`` follows the chip grid and need not
    equal the launcher's rank (measured on the four-chip v5e host)."""
    try:
        bounds = _HOST_PROCESS_BOUNDS[nprocs]
    except KeyError:
        raise ValueError(
            f"no single-host TPU process layout on record for {nprocs} "
            f"ranks (known: {sorted(_HOST_PROCESS_BOUNDS)}): the process "
            "grid must match the host's chip grid, and only layouts that "
            "have run on hardware are listed"
        ) from None
    return {
        "TPU_VISIBLE_DEVICES": str(rank),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": bounds,
        "TPU_PROCESS_ADDRESSES": ",".join(
            f"localhost:{p}" for p in ports),
        "TPU_PROCESS_PORT": str(ports[rank]),
        "CLOUD_TPU_TASK_ID": str(rank),
    }


def require_parent_off_chip(what: str) -> None:
    """Raise when THIS process has already initialised a non-CPU jax
    backend. A chip belongs to one process at a time: a parent that has
    touched jax holds every local chip, and children that need one then
    fail or hang until their timeout. Never initialises a backend
    itself (a jax-free parent stays jax-free)."""
    jax = sys.modules.get("jax")
    if jax is None:
        return
    from jax._src import xla_bridge

    if not xla_bridge.backends_are_initialized():
        return
    backend = jax.default_backend()
    if backend != "cpu":
        raise RuntimeError(
            f"{what}: this process has already initialised the {backend} "
            "backend and holds its chip(s); a chip belongs to one process "
            "at a time, so child processes that need one would hang. "
            "Launch from a process that has not run anything on jax yet "
            "(build arrays inside the objective / train_fn, not before "
            "the launch)."
        )


def local_pinnable_chips() -> "list[int]":
    """Chip indices available for per-process pinning on this host.

    MUST NOT touch jax: initializing a backend here would make the
    DRIVER process acquire every chip and starve the very children the
    pins are for. Detection is chip-granular (TPU_VISIBLE_DEVICES takes
    chip ids, and jax device counts are CORES — 2x the chips on some
    generations): an existing TPU_VISIBLE_DEVICES restriction is
    respected, else the host's /dev/accel* entries (one per chip on TPU
    VMs) are counted. Empty on chipless/CPU hosts — fresh interpreters
    don't contend there, so no pinning is needed.
    """
    import glob
    import re

    env = os.environ.get("TPU_VISIBLE_DEVICES")
    if env is not None:
        try:
            return [int(x) for x in env.split(",") if x.strip() != ""]
        except ValueError:
            logger.warning(
                "unparseable TPU_VISIBLE_DEVICES=%r; falling back to "
                "device-file chip detection", env,
            )
    # /dev/accel<N>: N IS the chip index
    chips = sorted(
        int(m.group(1))
        for m in (re.fullmatch(r"accel(\d+)", os.path.basename(p))
                  for p in glob.glob("/dev/accel*"))
        if m
    )
    if chips:
        return chips
    # vfio-exposed hosts: /dev/vfio/<N> are IOMMU GROUP numbers, not
    # chip ids — TPU_VISIBLE_DEVICES wants logical chip indices, so
    # return 0..count-1 and only the numeric entries (skips the
    # /dev/vfio/vfio control node). vfio entries alone are NOT a TPU
    # signal — GPUs and NICs passthrough the same way — so demand a
    # second, independent one (libtpu on the path, or a Google PCI
    # device) before pinning; on mismatch fall back to unpinned rather
    # than pin children to nonexistent chip indices.
    n = sum(
        1 for p in glob.glob("/dev/vfio/*")
        if re.fullmatch(r"\d+", os.path.basename(p))
    )
    if n and not _vfio_is_tpu():
        logger.warning(
            "%d /dev/vfio entries but no TPU signal (no libtpu, no Google "
            "PCI vendor id): not pinning chips — trials run unpinned", n,
        )
        return []
    return list(range(n))


#: Google's PCI vendor id; TPU boards enumerate under it on vfio hosts.
_GOOGLE_PCI_VENDOR = "0x1ae0"


def _vfio_is_tpu() -> bool:
    """Second TPU signal for the vfio fallback (jax-free, like the caller):
    libtpu importable, or any PCI device with Google's vendor id."""
    import glob
    import importlib.util

    try:
        if importlib.util.find_spec("libtpu") is not None:
            return True
    except (ImportError, ValueError):
        pass
    for p in glob.glob("/sys/bus/pci/devices/*/vendor"):
        try:
            with open(p) as f:
                if f.read().strip().lower() == _GOOGLE_PCI_VENDOR:
                    return True
        except OSError:
            continue
    return False


class LocalProcessBackend:
    """Run n ranks as subprocesses of this host (HorovodRunner np<0 mode).

    Each rank is a fresh interpreter (env must precede jax import). By
    default ranks run on CPU with ``devices_per_process`` fake devices each,
    so multi-process collective code is debuggable on one machine with (or
    without) a single TPU chip. ``platform="tpu"`` pins rank r to local
    chip r and joins the ranks into one job over the host's chips
    (:func:`tpu_rank_overrides`); the parent must not hold the chips
    (:func:`require_parent_off_chip`).
    """

    def __init__(self, devices_per_process: int = 1, platform: "str | None" = "cpu",
                 timeout_s: float = 600.0,
                 straggler_grace_s: "float | None" = None):
        self.devices_per_process = devices_per_process
        self.platform = platform
        self.timeout_s = timeout_s
        #: Rank watchdog (reliability layer): once the FIRST rank exits,
        #: surviving ranks get this many extra seconds before they are
        #: torn down as hung. An SPMD job's ranks finish near-together;
        #: a rank still running long after its peers is wedged in a
        #: collective its peers already left (e.g. the metrics rollup
        #: when a sibling died uncleanly) and would otherwise block the
        #: job for the full timeout_s. None disables (default: legit
        #: skew — rank 0 pickling a large result — must not be killed
        #: by an over-eager default).
        self.straggler_grace_s = straggler_grace_s

    def run(self, nprocs: int, fn: Callable, kwargs: dict,
            verbosity: str = "all") -> Any:
        import cloudpickle

        env_overrides = {}
        if self.platform == "cpu":
            # always pin the child's device count — devices_per_process=1
            # must MEAN one device even when the parent env carries a
            # --xla_force_host_platform_device_count (the test harness
            # does), else children silently inherit the parent's topology
            env_overrides = virtual_cpu_overrides(
                self.devices_per_process, os.environ.get("XLA_FLAGS", "")
            )
        elif self.platform:
            require_parent_off_chip(
                f"LocalProcessBackend(platform={self.platform!r})")
            env_overrides["JAX_PLATFORMS"] = self.platform
        rank_envs: "list[dict]" = [{}] * nprocs
        if self.platform == "tpu":
            ports = [free_port() for _ in range(nprocs)]
            rank_envs = [tpu_rank_overrides(r, nprocs, ports)
                         for r in range(nprocs)]

        workdir = tempfile.mkdtemp(prefix="sparkdl_tpu_run_")
        payload_path = os.path.join(workdir, "payload.pkl")
        result_path = os.path.join(workdir, "result.pkl")
        with open(payload_path, "wb") as f:
            cloudpickle.dump({"fn": fn, "kwargs": kwargs}, f)

        coordinator = f"localhost:{free_port()}"
        # children must resolve the same modules as the parent (the user fn
        # may be pickled by reference to a module only on the parent's path)
        child_env = os.environ.copy()
        child_env["PYTHONPATH"] = os.pathsep.join(
            [p for p in sys.path if p] + [child_env.get("PYTHONPATH", "")]
        ).rstrip(os.pathsep)
        # Env overrides ride the process env, not the payload: jax reads
        # them at import, before the worker unpickles anything.
        child_env.update(env_overrides)
        procs: list[subprocess.Popen] = []
        streams: list[threading.Thread] = []
        try:
            for rank in range(nprocs):
                p = subprocess.Popen(
                    [
                        sys.executable, "-m", "sparkdl_tpu.runner._worker",
                        payload_path, str(rank), str(nprocs), coordinator,
                        result_path,
                    ],
                    stdout=subprocess.PIPE,
                    stderr=subprocess.STDOUT,
                    text=True,
                    env={**child_env, **rank_envs[rank]},
                )
                procs.append(p)
                t = threading.Thread(
                    target=_stream_output, args=(p, rank, verbosity), daemon=True
                )
                t.start()
                streams.append(t)

            failed = _wait_all(procs, self.timeout_s,
                               self.straggler_grace_s)
            for t in streams:
                t.join(timeout=5)
            if failed:
                ranks = ", ".join(str(r) for r in failed)
                raise RuntimeError(
                    f"TPURunner local job failed on rank(s) {ranks} "
                    f"(barrier semantics: whole job aborted)"
                )
            return _load_result(result_path)
        finally:
            for p in procs:
                if p.poll() is None:
                    p.kill()
            shutil.rmtree(workdir, ignore_errors=True)


def _stream_output(proc: subprocess.Popen, rank: int, verbosity: str) -> None:
    assert proc.stdout is not None
    for line in proc.stdout:
        if verbosity == "all":
            print(f"[rank {rank}] {line}", end="", flush=True)
        else:
            logger.debug("[rank %d] %s", rank, line.rstrip())


def _wait_all(procs: list[subprocess.Popen], timeout_s: float,
              straggler_grace_s: "float | None" = None) -> list[int]:
    """Wait for every rank; on first failure or timeout kill the rest.

    ``straggler_grace_s`` is the rank watchdog: once the first rank has
    exited (cleanly), ranks still running past the grace window are
    declared hung and torn down — without it a single wedged rank holds
    the job until the global ``timeout_s``.

    Returns the list of failed ranks (empty on success).
    """
    import time

    deadline = time.monotonic() + timeout_s
    pending = dict(enumerate(procs))
    failed: list[int] = []
    first_exit_at: "float | None" = None
    while pending and not failed:
        for rank, p in list(pending.items()):
            rc = p.poll()
            if rc is None:
                continue
            del pending[rank]
            if rc != 0:
                failed.append(rank)
        now = time.monotonic()
        if (pending and first_exit_at is None
                and len(pending) < len(procs)):
            first_exit_at = now
        if (pending and not failed
                and straggler_grace_s is not None
                and first_exit_at is not None
                and now > first_exit_at + straggler_grace_s):
            logger.error(
                "rank watchdog: rank(s) %s still running %.1fs after "
                "the first rank exited; tearing down as hung",
                sorted(pending), straggler_grace_s,
            )
            failed.extend(pending.keys())
            break
        if now > deadline:
            failed.extend(pending.keys())
            break
        time.sleep(0.05)
    for p in pending.values():
        p.kill()
    return sorted(failed)


def _load_result(result_path: str) -> Any:
    if not os.path.exists(result_path):
        raise RuntimeError("rank 0 produced no result file")
    with open(result_path, "rb") as f:
        status, value = pickle.load(f)
    if status == "unpicklable":
        raise RuntimeError(
            f"rank 0's return value could not be pickled: {value}"
        )
    return value


def _host_sort_key(hostname: str) -> tuple:
    """Natural sort key: digit runs compare numerically.

    TPU-VM worker hostnames carry the worker index as a trailing integer
    (``...-w-0``, ``...-w-1``, ... ``...-w-10``); natural order makes
    rank assignment follow the TPU process topology, and plain string sort
    would put ``-w-10`` before ``-w-2``.
    """
    import re

    return tuple(
        int(part) if part.isdigit() else part
        for part in re.split(r"(\d+)", hostname)
    )


def resolve_ranks(addrs: list[str]) -> tuple[list[int], str]:
    """Map barrier-task rendezvous addresses to stable JAX process ids.

    ``addrs[i]`` is partition *i*'s ``host:port``. Returns
    ``(rank_of_partition, coordinator_address)`` where
    ``rank_of_partition[i]`` is the jax process_id partition *i* must use.

    Ranks are assigned by natural-sorted hostname, NOT by Spark partition
    id (SURVEY.md §7 hard part 2): a barrier stage retry may land
    partitions on different executors, but a given TPU host always
    resolves to the same rank as long as the host set is unchanged — so
    rank↔chip binding (and any rank-keyed checkpoint state) survives
    retries. The coordinator is whichever host sorts first.

    Exactly one task per host is enforced here: two barrier tasks on one
    host would each grab the host's TPU runtime and deadlock it. The fix
    on a real cluster is one executor per TPU host (spark.task.cpus =
    executor cores, or spark.executor.cores tuned so one slot per host).
    """
    hosts = [a.rsplit(":", 1)[0] for a in addrs]
    dupes = sorted({h for h in hosts if hosts.count(h) > 1})
    if dupes:
        raise RuntimeError(
            f"barrier placement error: multiple tasks on host(s) "
            f"{', '.join(dupes)} — TPURunner needs exactly one barrier "
            f"task per TPU host (set spark.task.cpus == executor cores so "
            f"each executor runs one task, one executor per host)"
        )
    order = sorted(range(len(addrs)), key=lambda i: _host_sort_key(hosts[i]))
    rank_of_partition = [0] * len(addrs)
    for rank, part in enumerate(order):
        rank_of_partition[part] = rank
    return rank_of_partition, addrs[order[0]]


def run_barrier_task(
    ctx,
    payload: bytes,
    nprocs: int,
    preflight_opts: dict,
    log_addr: "str | None" = None,
    hostname: "str | None" = None,
    distributed_init: "Callable | None" = None,
) -> bytes:
    """Body of one Spark barrier task, extracted so a faked
    BarrierTaskContext (``partitionId()`` + ``allGather(str)``) can drive
    it in-suite without pyspark (SURVEY.md §4: test semantics locally).

    ``distributed_init(coordinator, nprocs, rank)`` defaults to
    ``jax.distributed.initialize``; tests inject a recorder. Returns rank
    0's pickled result (b"" on other ranks).
    """
    import cloudpickle

    hostname = hostname or socket.gethostname()
    port = free_port()
    addrs = list(ctx.allGather(f"{hostname}:{port}"))
    if len(addrs) != nprocs:
        raise RuntimeError(
            f"rendezvous returned {len(addrs)} addresses for {nprocs} tasks"
        )
    rank_of_partition, coordinator = resolve_ranks(addrs)
    rank = rank_of_partition[ctx.partitionId()]

    with _ShipOutput(log_addr, rank):
        if distributed_init is None:
            import jax

            try:
                jax.distributed.initialize(
                    coordinator_address=coordinator,
                    num_processes=nprocs,
                    process_id=rank,
                )
            except Exception as e:
                # Most likely cause: the coordinator port advertised at
                # rendezvous got taken between free_port() and the bind
                # here. Barrier stages are all-or-nothing — failing the
                # task makes Spark retry the whole stage, which re-runs
                # the rendezvous with a fresh port.
                raise RuntimeError(
                    f"jax.distributed.initialize failed on rank {rank} "
                    f"(coordinator {coordinator}): {e}. If this is a port "
                    f"collision the stage retry re-rendezvouses cleanly."
                ) from e
        else:
            distributed_init(coordinator, nprocs, rank)
        # Slice health probe before the user fn compiles anything: a bad
        # chip fails this barrier task now, and Spark's stage retry plus
        # checkpoint resume (sparkdl_tpu.checkpoint) handle the rest.
        from sparkdl_tpu.observability.health import preflight

        preflight(rank=rank, **preflight_opts)
        p = cloudpickle.loads(payload)
        out = p["fn"](**p["kwargs"])
    return pickle.dumps(out) if rank == 0 else b""


def _get_barrier_context():
    """Executor-side hook returning the live barrier context; module-level
    so suites without pyspark can monkeypatch a fake in under the REAL
    ``SparkBarrierBackend.run`` body."""
    from pyspark import BarrierTaskContext

    return BarrierTaskContext.get()


class _LogRelay:
    """Driver-side TCP line sink for executor stdout (HorovodRunner's
    ``driver_log_verbosity`` equivalent, SURVEY.md 2.13).

    Executors already need driver connectivity in Spark (block manager,
    barrier coordination), so a plain listening socket on the driver is
    reachable wherever Spark itself works. Each task connects once and
    streams ``[rank N] ...`` lines; the relay prints them into the driver
    log as they arrive.
    """

    def __init__(self, sink: "Callable[[str], None] | None" = None,
                 keep_lines: int = 10_000):
        import collections

        self._sink = sink or (lambda line: print(line, flush=True))
        #: bounded tail of forwarded lines (test/inspection hook; the full
        #: stream goes to the sink) — unbounded would leak driver memory
        #: over a long job's worth of executor output.
        self.lines: "collections.deque[str]" = collections.deque(
            maxlen=keep_lines)
        self._srv = socket.socket()
        self._srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._srv.bind(("", 0))
        self._srv.listen(64)
        self._srv.settimeout(0.2)
        self.address = f"{socket.gethostname()}:{self._srv.getsockname()[1]}"
        self._closing = threading.Event()
        #: live pump threads only — each pump removes itself on disconnect,
        #: so a long job's worth of short-lived connections does not
        #: accumulate one dead Thread object per connection
        self._pumps: "set[threading.Thread]" = set()
        self._pumps_lock = threading.Lock()
        self._accept_thread = threading.Thread(
            target=self._accept_loop, daemon=True)
        self._accept_thread.start()

    @property
    def live_pumps(self) -> int:
        """Number of currently-connected executor streams."""
        with self._pumps_lock:
            return len(self._pumps)

    def _accept_loop(self) -> None:
        while not self._closing.is_set():
            try:
                conn, _ = self._srv.accept()
            except socket.timeout:
                continue
            except OSError:
                return
            t = threading.Thread(
                target=self._pump, args=(conn,), daemon=True
            )
            with self._pumps_lock:
                self._pumps.add(t)
            t.start()

    def _pump(self, conn: socket.socket) -> None:
        try:
            with conn, conn.makefile("r", errors="replace") as f:
                for line in f:
                    line = line.rstrip("\n")
                    self.lines.append(line)
                    self._sink(line)
        finally:
            with self._pumps_lock:
                self._pumps.discard(threading.current_thread())

    def close(self) -> None:
        self._closing.set()
        try:
            self._srv.close()
        except OSError:
            pass
        self._accept_thread.join(timeout=2)
        with self._pumps_lock:
            pumps = list(self._pumps)
        for t in pumps:
            t.join(timeout=2)


class _ShipOutput:
    """Executor-side context manager: tee this process's stdout/stderr to
    the driver's :class:`_LogRelay` while the user fn runs.

    File-descriptor level (dup2), so native prints (XLA, C++ bridge) ship
    too, not just Python ``print``. Lines still reach the executor's own
    log via the tee. No-op when ``addr`` is None (verbosity 'none') or the
    relay is unreachable — log forwarding must never fail the job.
    """

    def __init__(self, addr: "str | None", rank: int):
        self.addr = addr
        self.rank = rank
        self._sock = None
        self._saved: list[tuple[int, int]] = []
        self._pump_thread = None

    def __enter__(self):
        if self.addr is None:
            return self
        try:
            host, port = self.addr.rsplit(":", 1)
            self._sock = socket.create_connection((host, int(port)), timeout=5)
        except OSError:
            self._sock = None
            return self
        r, w = os.pipe()
        self._saved = [(1, os.dup(1)), (2, os.dup(2))]
        os.dup2(w, 1)
        os.dup2(w, 2)
        os.close(w)
        self._pump_thread = threading.Thread(
            target=self._pump, args=(r,), daemon=True
        )
        self._pump_thread.start()
        return self

    def _pump(self, rfd: int) -> None:
        orig_out = self._saved[0][1]
        buf = b""
        with os.fdopen(rfd, "rb", closefd=True) as r:
            while True:
                chunk = r.read1(65536)
                if not chunk:
                    break
                os.write(orig_out, chunk)  # tee to the executor's own log
                buf += chunk
                *lines, buf = buf.split(b"\n")
                for line in lines:
                    self._send(line)
        if buf:
            self._send(buf)

    def _send(self, line: bytes) -> None:
        if self._sock is None:
            return
        try:
            self._sock.sendall(b"[rank %d] %s\n" % (self.rank, line))
        except OSError:
            try:
                self._sock.close()
            finally:
                self._sock = None

    def __exit__(self, *exc):
        if not self._saved:
            if self._sock is not None:
                self._sock.close()
            return False
        sys.stdout.flush()
        sys.stderr.flush()
        # Restore first: dropping the last write-end refs of the pipe EOFs
        # the pump; only close the saved duplicates after the pump (which
        # tees through one of them) has drained.
        for fd, saved in self._saved:
            os.dup2(saved, fd)
        if self._pump_thread is not None:
            self._pump_thread.join(timeout=5)
        for _, saved in self._saved:
            os.close(saved)
        self._saved = []
        if self._sock is not None:
            self._sock.close()
            self._sock = None
        return False


class SparkBarrierBackend:
    """np>0 mode: one barrier task per TPU host via a live SparkSession.

    The task body (:func:`run_barrier_task`) rendezvouses through
    ``BarrierTaskContext.allGather`` (each task publishes ``host:port``),
    resolves stable hostname-ordered ranks, calls
    ``jax.distributed.initialize`` with the coordinator, runs the user fn
    with stdout teed to the driver, and returns rank 0's result — the
    reference's mpirun bootstrap replaced by coordinator address exchange
    (SURVEY.md §5 "Distributed communication backend").
    """

    def __init__(self, spark_session=None):
        if spark_session is None:
            from pyspark.sql import SparkSession

            spark_session = SparkSession.getActiveSession()
        if spark_session is None:
            raise RuntimeError(
                "no active SparkSession; np>0 needs a cluster (or use np<0 "
                "local mode)"
            )
        self.spark = spark_session

    def run(self, nprocs: int, fn: Callable, kwargs: dict,
            verbosity: str = "all") -> Any:
        import cloudpickle

        payload = cloudpickle.dumps({"fn": fn, "kwargs": kwargs})
        sc = self.spark.sparkContext
        # Preflight knobs resolve on the DRIVER (executor environments don't
        # inherit the driver's env) and ride the task closure.
        from sparkdl_tpu.observability.health import preflight_env_opts

        preflight_opts = preflight_env_opts()
        relay = _LogRelay() if verbosity == "all" else None
        log_addr = relay.address if relay is not None else None

        def barrier_task(it):
            ctx = _get_barrier_context()
            yield run_barrier_task(
                ctx, payload, nprocs, preflight_opts, log_addr=log_addr
            )

        try:
            results = (
                sc.parallelize(range(nprocs), nprocs)
                .barrier()
                .mapPartitions(barrier_task)
                .collect()
            )
        finally:
            if relay is not None:
                relay.close()
        ranked = [r for r in results if r]
        if not ranked:
            raise RuntimeError("no rank returned a result")
        return pickle.loads(ranked[0])
