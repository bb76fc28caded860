"""The harness behind ``python -m benchmark.run``: one run of one cell, from its data files to the result line."""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import importlib.util
import json
import os
import shutil
import sys
import threading
import time

from benchmark import manifest as mf
from benchmark import trace_reduce

#: the traced part of a ``--trace 1`` window: it starts this long after the
#: window opens and lasts this long; both are capped by the window's length
TRACE_AFTER_S = 2.0
TRACE_FOR_S = 3.0


@dataclasses.dataclass
class Comparison:
    """One number the correctness check compared, beside its limit."""

    name: str
    value: float
    limit: float
    higher_is_worse: bool = True

    @property
    def ok(self) -> bool:
        if self.value != self.value:  # NaN never passes
            return False
        return (self.value <= self.limit if self.higher_is_worse
                else self.value >= self.limit)

    def said(self) -> str:
        return (f"compared {self.name} = {self.value:.6g} against limit "
                f"{'<=' if self.higher_is_worse else '>='} {self.limit:.6g}: "
                f"{'ok' if self.ok else 'NOT CORRECT'}")


@dataclasses.dataclass
class Run:
    """What a runner is given and what it fills in; what a per-layer reader
    reads."""

    cell: mf.Cell
    seed: int
    seconds: float
    trace: bool
    rehearse: bool
    t_process: float
    window: "tuple[float, float] | None" = None   # time.monotonic() seconds
    counters: dict = dataclasses.field(default_factory=dict)
    raw: dict = dataclasses.field(default_factory=dict)
    spans: list = dataclasses.field(default_factory=list)
    trace_summary: "dict | None" = None
    traced_window: "tuple[float, float] | None" = None  # monotonic seconds
    device_kind: str = ""
    attempted: int = 0
    failed: int = 0
    window_opened: threading.Event = dataclasses.field(
        default_factory=threading.Event)

    def sizes(self) -> dict:
        """The mix as run: its ``rehearse`` overrides applied on the CPU."""
        mix = dict(self.cell.mix)
        over = mix.pop("rehearse", {})
        return {**mix, **over} if self.rehearse else mix

    def config(self) -> dict:
        """The configuration as run: on the CPU its ``rehearse`` overrides
        are laid over it, one level deep (a nested group is merged)."""
        cfg = dict(self.cell.config)
        over = cfg.pop("rehearse", {})
        if self.rehearse:
            for k, v in over.items():
                cfg[k] = {**cfg.get(k, {}), **v} if isinstance(v, dict) else v
        return cfg

    def open_window(self) -> float:
        """Called by the runner at the first instant of the window."""
        t = time.monotonic()
        self.window_opened.set()
        return t


def say(msg: str) -> None:
    print(f"[benchmark] {msg}", flush=True)


@contextlib.contextmanager
def after_window(phase: str):
    """Times one phase of what a run does once its window has closed and
    prints it as the phase ends, with the counts that set its cost (the
    caller fills them into the dict this yields): a run stopped at its time
    limit has then said which phases it got through, and the next is where
    it was."""
    counts: dict = {}
    t0 = time.monotonic()
    yield counts
    detail = "; ".join(f"{k} {v:,}" if isinstance(v, int) else f"{k} {v}"
                       for k, v in counts.items())
    say(f"after the window: {phase} {time.monotonic() - t0:.3f} s"
        + (f" ({detail})" if detail else ""))


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise mf.ManifestError(f"cannot load {path}")
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


def load_runner(cell: mf.Cell):
    manifest = mf.load_manifest(cell.root)
    kind = str(cell.mix.get("runner"))
    path = os.path.join(mf.harness_dir(manifest, cell.root), "runners",
                        kind + ".py")
    if not os.path.isfile(path):
        raise mf.ManifestError(f"mix {cell.traffic_name!r} names runner "
                               f"{kind!r}: no {path}")
    return load_module(path, f"benchmark_runner_{kind}")


class CompileWatch:
    """Counts what jax compiles or loads, so a run can say that nothing
    compiled inside its window."""

    def __init__(self):
        import jax.monitoring as mon

        self.events: "list[float]" = []   # when each program was compiled
        self.compile_s = 0.0
        self.retrieval_s = 0.0   # reading cached programs and loading them
        self.counts: "dict[str, int]" = {}
        mon.register_event_duration_secs_listener(self._on)
        mon.register_event_listener(self._count)

    def _on(self, name: str, secs: float, **kw) -> None:
        if name.endswith("backend_compile_duration"):
            self.events.append(time.monotonic())
            self.compile_s += secs
        elif name.endswith("cache_retrieval_time_sec"):
            self.retrieval_s += secs

    def _count(self, name: str, **kw) -> None:
        if "compilation_cache" in name:
            key = name.rsplit("/", 1)[-1]
            self.counts[key] = self.counts.get(key, 0) + 1

    def summary(self) -> str:
        return (f"{len(self.events)} programs compiled or loaded in "
                f"{self.compile_s:.1f} s, of which {self.retrieval_s:.1f} s "
                f"reading and loading cached ones; cache {self.counts}")

    def inside(self, window: "tuple[float, float]") -> int:
        return sum(1 for t in self.events if window[0] <= t <= window[1])


class DeviceTracer(threading.Thread):
    """Wraps a few seconds of the steady window in ``jax.profiler``, with one
    host annotation over the traced stretch so that the program's span clock
    and the profiler's can be laid over each other."""

    def __init__(self, run: Run, out_dir: str):
        super().__init__(name="benchmark-tracer", daemon=True)
        self.run_, self.out_dir = run, out_dir
        self.error: "Exception | None" = None
        self.mark: "tuple[float, float] | None" = None
        self.stop_s: "float | None" = None   # what stop_trace itself took

    def run(self) -> None:
        import jax

        try:
            self.run_.window_opened.wait()
            secs = self.run_.seconds
            time.sleep(min(TRACE_AFTER_S, secs / 4))
            # the Python tracer slows the host it is meant to observe; the
            # program's own spans say what the host was doing
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 2
            jax.profiler.start_trace(self.out_dir, profiler_options=options)
            try:
                t0 = time.monotonic()
                with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_MARK):
                    time.sleep(min(TRACE_FOR_S, secs / 2))
                self.mark = (t0, time.monotonic())
            finally:
                t_stop = time.monotonic()
                jax.profiler.stop_trace()
                self.stop_s = round(time.monotonic() - t_stop, 3)
        except Exception as e:  # a thread boundary: the main thread reports it
            self.error = e


def read_through(cache_dir: "str | None") -> "tuple[float, int]":
    """Read every file of the compile cache once, before jax does. Loading a
    cell's cached programs took 16 s in some runs and 44 s in others of the
    same code (PERF.md, section 6); with the files read here first, the time
    jax then takes is the loading alone, and this line shows the reading."""
    t0, n = time.monotonic(), 0
    if cache_dir and os.path.isdir(cache_dir):
        for entry in os.scandir(cache_dir):
            if entry.is_file():
                with open(entry.path, "rb") as f:
                    while chunk := f.read(1 << 24):
                        n += len(chunk)
    return time.monotonic() - t0, n


def device_report(peak_bytes: "int | None") -> dict:
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices()), "memory_peak_bytes": peak_bytes}


def memory_peak() -> "int | None":
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def program_spans(run: Run) -> "list[dict]":
    """The program's finished spans that lie inside the window, with their
    start and end as ``time.monotonic()`` seconds (``t0``/``t1``)."""
    from sparkdl_tpu.observability import tracing

    epoch = time.monotonic() - tracing.trace_clock_us() / 1e6
    w0, w1 = run.window
    out = []
    for ev in tracing.trace_events():
        t0 = epoch + ev["ts"] / 1e6
        t1 = t0 + ev["dur"] / 1e6
        if t1 >= w0 and t0 <= w1:
            out.append({"name": ev["name"], "t0": t0, "t1": t1,
                        "args": ev.get("args", {})})
    return out


def reduce_device_trace(run: Run, tracer: DeviceTracer, trace_dir: str) -> None:
    path = trace_reduce.find_xplane(trace_dir)
    with after_window("load_xplane") as n:
        n["bytes"] = os.path.getsize(path)
        planes = trace_reduce.load_xplane(path)
        n["events kept"] = sum(len(ln["events"]) for p in planes
                               for ln in p["lines"])
    mark = trace_reduce.find_mark(planes)
    if mark is None or tracer.mark is None:
        raise RuntimeError("the trace holds no window annotation")
    m0 = tracer.mark[0]

    def to_ns(t: float) -> int:
        return int(mark[0] + (t - m0) * 1e9)

    with after_window("reduce_trace") as n:
        host = [(s["name"], to_ns(s["t0"]), to_ns(s["t1"]))
                for s in run.spans]
        run.trace_summary, counts = trace_reduce.reduce_trace_counted(
            planes, mark, host)
        n.update({"device operations": counts["device_ops"],
                  "idle gaps": counts["gaps"], "spans": counts["spans"],
                  "spans touching the traced stretch":
                      counts["spans_in_window"]})
    run.traced_window = tracer.mark


def layer_metrics(run: Run) -> dict:
    """Each per-layer metric of the cell from its own reader; a reader that
    finds nothing to read returns None and the metric is left out."""
    manifest = mf.load_manifest(run.cell.root)
    out = {}
    for m in run.cell.per_layer:
        path = mf.reader_file(manifest, run.cell.root, m["name"])
        reader = load_module(path, "benchmark_reader_"
                             + m["name"].replace(".", "_").replace("-", "_"))
        value = reader.compute(run)
        if value is None:
            say(f"per-layer {m['name']}: nothing to read, left out")
            continue
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def main(argv: "list[str] | None" = None,
         t_process: "float | None" = None) -> int:
    ap = argparse.ArgumentParser(prog="python -m benchmark.run")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")

    cell = mf.resolve_cell(args.workload)
    runner = load_runner(cell)

    # the program under test; in a directory that holds only the benchmark
    # this import fails and the run ends with no result line
    from sparkdl_tpu.runtime import chip

    if args.rehearse:
        if not chip.explicit_cpu():
            say("--rehearse needs an exported JAX_PLATFORMS=cpu")
            return 2
    else:
        chip.require_tpu()
    import jax

    if not args.rehearse and len(jax.devices()) < cell.chips:
        say(f"cell {cell.name} needs {cell.chips} chips, jax sees "
            f"{len(jax.devices())}")
        return 2
    cache_dir = chip.configure_compile_cache()
    read_s, read_bytes = read_through(cache_dir)
    # a machine may cap the cache's size; a cell's programs can be larger
    # than the cap, and an LRU cache that is a little too small misses on
    # every program of every run
    jax.config.update("jax_compilation_cache_max_size", -1)
    say(f"cell {cell.name} seed {args.seed} seconds {args.seconds:g} trace "
        f"{args.trace}; device {jax.devices()[0].device_kind!r} x"
        f"{len(jax.devices())}; compile cache {cache_dir} "
        f"({chip.cache_entry_count(cache_dir)} entries, {read_bytes / 1e6:.0f} "
        f"MB read through in {read_s:.1f} s)")

    run = Run(cell=cell, seed=args.seed, seconds=args.seconds,
              trace=bool(args.trace), rehearse=args.rehearse,
              t_process=(t_process if t_process is not None
                         else time.monotonic()),
              device_kind=jax.devices()[0].device_kind)
    compiles = CompileWatch()
    tracer = None
    trace_dir = os.path.join(cell.root, ".benchmark_runs", "trace-" + cell.name)
    if run.trace:
        from sparkdl_tpu.observability import tracing

        tracing.enable_tracing()
        if not args.rehearse:
            shutil.rmtree(trace_dir, ignore_errors=True)
            os.makedirs(trace_dir, exist_ok=True)
            tracer = DeviceTracer(run, trace_dir)
            tracer.start()

    cpu0, wall0 = time.process_time(), time.monotonic()
    state = runner.setup(run)
    say(f"set-up: {compiles.summary()}; {time.monotonic() - wall0:.1f} s of "
        f"wall clock, {time.process_time() - cpu0:.1f} s of this process's "
        "CPU")
    try:
        runner.window(run, state)
        drain_s = time.monotonic() - run.window[1]
        setup_s = run.window[0] - run.t_process
        say(f"window {run.window[1] - run.window[0]:.3f} s after set-up "
            f"{setup_s:.3f} s; compiled inside the window: "
            f"{compiles.inside(run.window)} programs; cache now "
            f"{chip.cache_entry_count(cache_dir)} entries")
        say(f"after the window: drain {drain_s:.3f} s (requests attempted "
            f"{run.attempted:,})")
        if tracer is not None:
            t_join = time.monotonic()
            tracer.join(timeout=300)
            say(f"after the window: stop_trace {time.monotonic() - t_join:.3f}"
                " s (the tracer thread's join; the call itself was made "
                f"inside the window and took {tracer.stop_s} s)")
            if tracer.error is not None or tracer.is_alive():
                raise RuntimeError(f"device trace failed: {tracer.error!r}")
        if run.trace:
            run.spans = program_spans(run)
        metrics = runner.end_to_end(run, state)
        metrics["setup_s"] = setup_s
        peak = memory_peak()  # before the reference puts anything on the chip
        with after_window("correctness check"):
            checks = runner.check(run, state)
    finally:
        runner.teardown(state)

    correct = all(c.ok for c in checks)
    for c in checks:
        say(c.said())

    units = {m["name"]: m["unit"] for m in cell.end_to_end}
    line = {"correct": bool(correct), "attempted": int(run.attempted),
            "failed": int(run.failed), "device": device_report(peak),
            "compiles_in_window": compiles.inside(run.window)}
    if run.trace:
        if tracer is None:
            say("after the window: stop_trace, load_xplane and reduce_trace "
                "not run (a rehearsal takes no device trace)")
        else:
            reduce_device_trace(run, tracer, trace_dir)
            s = run.trace_summary
            line["device"].update(busy_s=s["busy_s"], window_s=s["window_s"])
            line["breakdown"] = {"device_ops": s["device_ops"],
                                 "idle_gaps": s["idle_gaps"]}
        with after_window("per-layer readers") as n:
            line["metrics"] = layer_metrics(run)
            n.update({"metrics": len(line["metrics"]),
                      "spans": len(run.spans)})
    else:
        missing = set(units) - set(metrics)
        if missing:
            raise RuntimeError(f"runner reported no {sorted(missing)}")
        line["metrics"] = {n: {"value": float(metrics[n]), "unit": units[n]}
                           for n in units}
    if args.rehearse:
        # a CPU run is never written under the name of a device metric
        for m in line["metrics"].values():
            m["value"] = None
        line["rehearsal"] = "CPU run at rehearsal sizes: not measured"
    say(f"after the window: {time.monotonic() - run.window[1]:.3f} s in all, "
        "to the result line")
    # each number compared beside its limit: last in the line, and the last
    # lines of standard error (what the driver keeps of a run not correct)
    line["compared"] = {c.name: {
        "value": None if c.value != c.value else c.value,   # NaN is no JSON
        "limit": c.limit, "ok": c.ok} for c in checks}
    for c in checks:
        print(c.said(), file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0

