"""The benchmark's own tests: CPU only, one file.

What is checked here: the manifest against the contract's static rules; that
the seeded generators repeat; that each runner's rehearsal ends in the
contract's result line and that a run without a TPU ends without one; that a
cell, configuration, mix, kind of runner and per-layer metric can be ADDED
as files and entries, and that this file's and its neighbours' tests of the
manifest then pass on the copy that gained them; the trace reduction on a
trace built by hand; the needed-FLOP and
needed-byte counts against hand counts; and that the correctness comparison
fails the lower-precision controls and a broken timed path.

Child processes run under an exported ``JAX_PLATFORMS=cpu`` (jax then never
touches the TPU's library); nothing here describes or opens a chip.

NINE tests, each walking its cases in a loop, and not some forty parametrised
ones: under ``--dist loadfile`` xdist hands out files in the order of their
number of tests. With 36 this file ran first, its rehearsals beside the
hot-tenant storm of tests/serving/test_tenancy.py (a wall-clock test: p95
under a storm within a tenth of p95 alone), and the storm failed in 3 of 5
whole runs. With nine it starts after that file has finished.
"""

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from benchmark import manifest as mf
from benchmark import needs, peaks, reference, trace_reduce, traffic

ROOT = mf.repo_root()
MANIFEST = mf.load_manifest(ROOT)
CELLS = [w["name"] for w in MANIFEST["workloads"]]
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def on_one_core(cmd):
    """Children of this file run on one core. A rehearsal compiles with as
    many threads as the machine has cores, and wall-clock tests of the
    repository that xdist runs beside this file (the hot-tenant storm of
    tests/serving/test_tenancy.py) then miss their limits."""
    taskset = shutil.which("taskset")
    if taskset is None:
        return cmd
    return [taskset, "-c", str(max(os.sched_getaffinity(0))), *cmd]


def child_env(*pythonpath):
    """A child's environment: the CPU, none of this process's device count,
    of the driver's ``BENCH_RUN`` or of pytest's own variables."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "BENCH_RUN")
           and not k.startswith("PYTEST")}
    env.update(JAX_PLATFORMS="cpu", TF_CPP_MIN_LOG_LEVEL="2",
               PYTHONPATH=os.pathsep.join(map(str, pythonpath)))
    return env


def run_cell(args, cwd=ROOT, timeout=600):
    return subprocess.run(
        on_one_core([sys.executable, "-m", "benchmark.run", *args]),
        cwd=cwd, env=child_env(cwd, ROOT),
        capture_output=True, text=True, timeout=timeout)


def last_json(proc):
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    assert lines, proc.stderr[-2000:]
    return json.loads(lines[-1])


# -- the manifest ------------------------------------------------------------------

def check_manifest_passes_the_static_rules():
    assert mf.validate(ROOT) == []


def check_paths_and_command_are_exact():
    assert MANIFEST["paths"] == ["benchmark", "tests/benchmark"]
    assert MANIFEST["command"] == ["python3", "-m", "benchmark.run"]
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}


def check_the_accepted_names_are_there_and_lead_their_lists():
    # what was accepted is PRESENT and comes first, in the order it was
    # accepted; what a later PR adds comes after it and fails nothing here
    assert CELLS[0] == "gpt2xl-backlog"
    assert {"tokens_per_s", "setup_s"} <= {
        e["name"] for e in MANIFEST["end_to_end"]}
    assert [c["name"] for c in MANIFEST["configs"]][:1] == ["gpt2-xl-serve"]


def check_cell_resolves_to_its_files_and_metrics(cell):
    c = mf.resolve_cell(cell, ROOT)
    assert c.chips == 1 and c.config and c.mix["runner"]
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer, "a cell reports at least one per-layer metric"
    for m in c.per_layer:
        assert m["moves"] in names, (m["name"], m["moves"])
        assert os.path.isfile(mf.reader_file(MANIFEST, ROOT, m["name"]))


READERS = sorted(f[:-3] for f in os.listdir(
    os.path.join(ROOT, "benchmark", "layer_metrics")) if f.endswith(".py"))


def check_every_per_layer_metric_of_the_manifest_has_a_reader_file():
    assert {m["name"] for m in MANIFEST["per_layer"]} <= set(READERS)


def check_per_layer_metric_has_a_reader(metric):
    from benchmark import harness

    mod = harness.load_module(mf.reader_file(MANIFEST, ROOT, metric),
                              "reader_under_test")
    assert callable(mod.compute)
    # a reader that finds nothing to read returns nothing
    silent = harness.Run(cell=mf.resolve_cell(CELLS[0], ROOT), seed=0,
                         seconds=1.0, trace=True, rehearse=True,
                         t_process=0.0, window=(0.0, 1.0))
    assert mod.compute(silent) is None


def test_the_manifest_its_cells_and_its_readers():
    check_manifest_passes_the_static_rules()
    check_paths_and_command_are_exact()
    check_the_accepted_names_are_there_and_lead_their_lists()
    for cell in CELLS:
        check_cell_resolves_to_its_files_and_metrics(cell)
    check_every_per_layer_metric_of_the_manifest_has_a_reader_file()
    for metric in READERS:
        check_per_layer_metric_has_a_reader(metric)


BREACHES = [
    (lambda m: m["workloads"][0].update(name="has space"), "bad name"),
    (lambda m: m["end_to_end"][0].update(unit="tokens per s"), "bad unit"),
    (lambda m: m["end_to_end"][0].update(bound=0.5), "bound"),
    (lambda m: m["per_layer"][0].update(why="x"), "keys"),
    (lambda m: m["per_layer"][0].update(moves="nothing"), "moves unknown"),
    (lambda m: m["workloads"][0].update(chips=2), "chips"),
    (lambda m: m["workloads"].append(dict(m["workloads"][0])), "twice"),
    (lambda m: m.update(run_seconds=52), "run_seconds"),
]


def test_validate_names_each_breach(tmp_path):
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.makedirs(tmp_path / "tests" / "benchmark")
    for breach, message in BREACHES:
        m = json.loads(json.dumps(MANIFEST))
        breach(m)
        (tmp_path / "BENCHMARK.json").write_text(json.dumps(m))
        assert any(message in b for b in mf.validate(str(tmp_path))), message


# -- seeded generators ---------------------------------------------------------------

SERVE_MIXES = sorted({w["traffic"] for w in MANIFEST["workloads"]
                      if mf.resolve_cell(w["name"], ROOT).mix["runner"]
                      == "serve"})


def check_serve_traffic_repeats_and_differs_by_seed(mix_name):
    with open(mf.traffic_file(MANIFEST, ROOT, mix_name)) as f:
        mix = json.load(f)
    big = 2**31 + 12345
    a = traffic.serve_requests(mix, 50257, 200, big)
    b = traffic.serve_requests(mix, 50257, 200, big)
    c = traffic.serve_requests(mix, 50257, 200, big + 1)
    assert all(np.array_equal(x.prompt, y.prompt) and x.n_out == y.n_out
               and x.due_s == y.due_s for x, y in zip(a, b))
    assert any(len(x.prompt) != len(y.prompt) for x, y in zip(a, c))
    # every seed does the same work in another order: block by block the
    # same set of (prompt, output) sizes
    block = int(mix["block"])
    sizes = lambda rs: sorted((len(r.prompt), r.n_out) for r in rs[:block])
    assert sizes(a) == sizes(c) == sizes(a[block:2 * block])
    lo, hi = mix["prompt"]["min"], mix["prompt"]["max"]
    assert all(lo <= len(r.prompt) <= hi for r in a)
    if mix["loop"] == "open":
        due = np.array([r.due_s for r in a])
        assert (np.diff(due) > 0).all()
        rate = mix["arrivals"]["rate_per_s"]
        assert abs(len(a) / due[-1] - rate) < 0.05 * rate


def check_open_loop_arrivals_repeat_and_keep_the_rate():
    mix = {"block": 64, "loop": "open", "arrivals": {"rate_per_s": 10.0}}
    due = traffic.arrival_times(mix, 2000, 2**31 + 3)
    assert np.array_equal(due, traffic.arrival_times(mix, 2000, 2**31 + 3))
    assert not np.array_equal(due, traffic.arrival_times(mix, 2000, 4))
    assert (np.diff(due) > 0).all()
    assert abs(2000 / due[-1] - 10.0) < 0.5


def check_seeded_gpt2_weights_repeat_and_take_a_large_seed():
    hf = {"vocab_size": 64, "n_embd": 16, "n_layer": 2, "n_head": 2,
          "n_positions": 8}
    a = reference.gpt2_weights(2**31 + 99, hf, "float32")
    b = reference.gpt2_weights(2**31 + 99, hf, "float32")
    c = reference.gpt2_weights(2**31 + 100, hf, "float32")
    assert np.array_equal(a["wte"], b["wte"])
    assert not np.array_equal(a["wte"], c["wte"])
    assert a["blocks"]["up.kernel"].shape == (2, 16, 64)


def test_the_seeded_generators_repeat_and_differ_by_seed():
    for mix_name in SERVE_MIXES:
        check_serve_traffic_repeats_and_differs_by_seed(mix_name)
    check_open_loop_arrivals_repeat_and_keep_the_rate()
    check_seeded_gpt2_weights_repeat_and_take_a_large_seed()


# -- the result line, end to end, at rehearsal sizes --------------------------------------

def check_rehearsal_ends_in_the_contracts_line(cell, trace):
    proc = run_cell(["--workload", cell, "--seed", str(2**31 + 17),
                     "--seconds", "2", "--trace", str(trace), "--rehearse"])
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = last_json(proc)
    assert RESULT_KEYS <= set(line)
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert line["device"]["platform"] == "cpu"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["metrics"]
    # a CPU run never writes a number under a device metric's name
    assert all(m["value"] is None for m in line["metrics"].values())
    assert "compared " in proc.stdout   # each number beside its limit


def check_without_a_tpu_a_run_fails_and_prints_no_result():
    proc = run_cell(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                     "--trace", "0"])
    assert proc.returncode != 0
    assert not any(ln.startswith("{") for ln in proc.stdout.splitlines())


def check_in_a_directory_with_only_the_benchmark_a_run_fails(tmp_path):
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    os.makedirs(tmp_path / "tests" / "benchmark")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        on_one_core([sys.executable, "-m", "benchmark.run", "--workload",
                     CELLS[0], "--seed", "1", "--seconds", "1", "--trace",
                     "0", "--rehearse"]),
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert not any(ln.startswith("{") for ln in proc.stdout.splitlines())


def test_a_run_ends_in_the_contracts_line_or_in_none(tmp_path):
    for cell in CELLS:
        for trace in (0, 1):
            check_rehearsal_ends_in_the_contracts_line(cell, trace)
    check_without_a_tpu_a_run_fails_and_prints_no_result()
    check_in_a_directory_with_only_the_benchmark_a_run_fails(tmp_path)


#: a second KIND of cell as a ``model_config`` PR brings it: a new file that
#: imports what it shares with the serving runner and has a set-up and a
#: check of its own
OTHER_RUNNER = '''"""A runner kind added by a test."""
from benchmark.harness import Comparison, say
from benchmark.runners import serve
from benchmark.runners.serve import end_to_end, teardown, window  # noqa: F401


def setup(run):
    say("set-up of the runner kind serve_other")
    return serve.setup(run)


def check(run, state):
    return serve.check(run, state) + [
        Comparison("the_new_kinds_own_number", 0.0, 0.0)]
'''
NEW_READER = '''def compute(run):
    ticks = [s for s in run.spans if s["name"] == "serving.decode_step"]
    return float(len(ticks)) if ticks else None
'''
TESTS_DIR = os.path.join(ROOT, "tests", "benchmark")
#: the manifest-level tests of the three files that used to pin the manifest
#: to one cell, one configuration and eighteen metrics, as ``pytest -k``
#: finds them in this tree and found them in PR 27's
PINNING_FILES = ["test_benchmark.py", "test_engine_readers.py",
                 "test_engine_rehearsal.py"]
PINNING_TESTS = "test_the_manifest or rehearsal"


def run_the_copied_tests(tmp_path):
    """The copy's own tests, in a child whose ``benchmark`` is the copy's
    (every ``ROOT`` there is the copy): the static ones and the traced
    rehearsal of the accepted cell."""
    return subprocess.run(
        on_one_core([sys.executable, "-m", "pytest", "-q", "-p",
                     "no:cacheprovider", "-p", "no:xdist", "-k", PINNING_TESTS,
                     *(os.path.join("tests", "benchmark", f)
                       for f in PINNING_FILES)]),
        cwd=tmp_path, env=child_env(tmp_path), capture_output=True,
        text=True, timeout=900)


def test_a_cell_config_mix_and_metric_are_added_as_files(
        tmp_path, tests_from=TESTS_DIR):
    """Later PRs may add files and entries and edit no file that is there:
    a copy of the repository gains what a ``model_config`` PR brings (a
    configuration, a mix, a KIND of runner, a per-layer reader that only
    the new cell reports, an end-to-end metric and a cell, whose name is
    APPENDED to the lists of metrics that are there), runs the cell, and
    the copy's own tests of the manifest stay green (``tests_from``: PR
    28's proof that they did not was this test on PR 27's three files)."""
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copytree(tests_from, tmp_path / "tests" / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    # the program under test is in the copy as it is in a checkout
    os.symlink(os.path.join(ROOT, "sparkdl_tpu"), tmp_path / "sparkdl_tpu")
    before = {p: p.read_bytes() for d in ("benchmark", "tests")
              for p in (tmp_path / d).rglob("*") if p.is_file()}
    assert any(p.name == "test_benchmark.py" for p in before)
    bench = tmp_path / "benchmark"
    cfg = json.loads((bench / "configs" / "gpt2-xl-serve.json").read_text())
    cfg["rehearse"]["hf_config"]["n_layer"] = 3
    (bench / "configs" / "gpt2-three-layers.json").write_text(json.dumps(cfg))
    mix = json.loads((bench / "traffic" / "backlog-chat.json").read_text())
    mix.update(runner="serve_other", loop="open",
               arrivals={"rate_per_s": 20.0})
    (bench / "traffic" / "open-loop.json").write_text(json.dumps(mix))
    (bench / "runners" / "serve_other.py").write_text(OTHER_RUNNER)
    (bench / "layer_metrics" / "decode_ticks.new.py").write_text(NEW_READER)
    m = json.loads(json.dumps(MANIFEST))
    m["configs"].append({
        "name": "gpt2-three-layers", "source": "a test's own",
        "file": "benchmark/configs/gpt2-three-layers.json", "reduced": [],
        "why": "added by a test"})
    m["workloads"].append({
        "name": "new.cell", "config": "gpt2-three-layers",
        "traffic": "open-loop", "chips": 1, "why": "added by a test"})
    m["end_to_end"].append({
        "name": "latency_per_token_p50_ms", "unit": "ms/token",
        "better": "lower", "bound": 0.1, "source": "host_clock",
        "workloads": ["new.cell"]})
    m["per_layer"].append({
        "name": "decode_ticks.new", "unit": "ticks", "better": "higher",
        "source": "program_span", "layer": "Serving engine",
        "moves": "latency_per_token_p50_ms", "workloads": ["new.cell"]})
    # the new cell reports three metrics that are there, one of them read
    # from the device trace: its name goes at the end of their lists
    shared = ["batch_occupancy.backlog", "device_idle_share.backlog"]
    for e in m["end_to_end"] + m["per_layer"]:
        if e["name"] in ["tokens_per_s", *shared]:
            e["workloads"].append("new.cell")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(m))
    assert mf.validate(str(tmp_path)) == []
    proc = run_cell(["--workload", "new.cell", "--seed", "5", "--seconds",
                     "2", "--trace", "1", "--rehearse"], cwd=str(tmp_path))
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "set-up of the runner kind serve_other" in proc.stdout
    line = last_json(proc)
    assert line["correct"] is True
    assert "the_new_kinds_own_number" in line["compared"]
    # a rehearsal has no device trace, so that reader is silent
    assert set(line["metrics"]) == {"decode_ticks.new", shared[0]}
    assert "per-layer " + shared[1] + ": nothing to read" in proc.stdout
    proc = run_cell(["--workload", "new.cell", "--seed", "5", "--seconds",
                     "2", "--trace", "0", "--rehearse"], cwd=str(tmp_path))
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert set(last_json(proc)["metrics"]) == {
        "tokens_per_s", "latency_per_token_p50_ms", "setup_s"}
    # the accepted cell reports what it reported
    names = lambda root: [e["name"] for e in mf.resolve_cell(
        CELLS[0], root).per_layer]
    assert names(str(tmp_path)) == names(ROOT)
    proc = run_the_copied_tests(tmp_path)
    assert proc.returncode == 0, proc.stdout[-6000:] + proc.stderr[-2000:]
    assert "3 passed" in proc.stdout and "failed" not in proc.stdout
    assert all(p.read_bytes() == b for p, b in before.items())


# -- the trace reduction, on a trace built by hand -----------------------------------------

SLAB = "bf16[1,512,16,25,64]"
STORED = "{1,4,3,2,0:T(8,128)(2,1)}"


def slab_copy(serial):
    """A device operation as the chip's trace names it."""
    return (f"%copy.{serial} = {SLAB}{{4,3,2,1,0}} copy({SLAB}{STORED} "
            f"%slab.{serial})")


def synthetic_planes():
    ms = 1_000_000
    dev0_ops = [("%fusion.1", 10 * ms, 20 * ms),
                (slab_copy(8), 30 * ms, 10 * ms),
                ("%fusion.4", 60 * ms, 20 * ms)]
    dev1_ops = [("%fusion.1", 10 * ms, 40 * ms),
                (slab_copy(3), 70 * ms, 20 * ms)]
    return [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": dev0_ops},
            {"name": "XLA Modules", "events": [
                ("jit__paged_step(1)", 10 * ms, 30 * ms),
                ("jit__chunk_one(2)", 60 * ms, 20 * ms),
                # cut by the end of the window: 10 of its 30 ms are inside
                ("jit__paged_step(1)", 90 * ms, 30 * ms)]},
            {"name": "Steps", "events": [("0", 0, 100 * ms)]}]},
        {"name": "/device:TPU:1", "lines": [
            {"name": "XLA Ops", "events": dev1_ops},
            {"name": "XLA Modules", "events": [
                ("jit__paged_step(1)", 10 * ms, 40 * ms)]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": [
                (trace_reduce.WINDOW_MARK, 0, 100 * ms),
                ("something else", 5 * ms, 1 * ms)]}]},
    ]


def check_trace_reduction_on_a_hand_built_trace():
    from benchmark import harness, readers

    ms = 1_000_000
    host = [("serving.prefill_chunk", 40 * ms, 60 * ms),
            ("outer", 0, 90 * ms)]    # the last 10 ms are under no span
    s = trace_reduce.reduce_trace(synthetic_planes(), host_spans=host)
    assert s["window_s"] == pytest.approx(0.1)
    assert s["devices"] == 2
    # device 0 busy 10-40 and 60-80 (50 ms), device 1 busy 10-50 and 70-90
    # (60 ms): mean 55 ms
    assert s["busy_s"] == pytest.approx(0.055)
    # three executions of the decode program touch the window, two are whole
    step = "jit__paged_step(1)"
    assert s["programs"][step] == {"seconds": pytest.approx(0.08), "count": 3}
    assert s["whole_programs"][step] == {"seconds": pytest.approx(0.07),
                                         "count": 2}
    assert trace_reduce.program_seconds(s, "paged_step") == (
        pytest.approx(0.08), 3)
    assert trace_reduce.program_seconds(s, "paged_step", whole=True) == (
        pytest.approx(0.07), 2)
    assert trace_reduce.program_seconds(s, "no_such_program") == (0.0, 0)
    # decode_device_ms is the whole executions' 70 ms over their count, not
    # 80 ms over the three that touch the stretch
    run = harness.Run(cell=mf.resolve_cell(CELLS[0], ROOT), seed=0,
                      seconds=1.0, trace=True, rehearse=True, t_process=0.0,
                      window=(0.0, 1.0), trace_summary=s)
    assert readers.decode_device_ms(run) == pytest.approx(35.0)
    # operations by KIND: fusion.1 and fusion.4 are one kind (3 runs on 2
    # devices, 80 ms), the two slab copies another (2 runs, 30 ms)
    assert s["device_ops"] == [
        ["2x fusion in 1.5 runs", pytest.approx(0.04)],
        [f"2x copy {SLAB}{{4,3,2,1,0}} in 1 runs", pytest.approx(0.015)]]
    # a tuple of results: its index remarks dropped, a run of one shape once
    assert trace_reduce.op_kind(
        f"%fusion.2951 = ({SLAB}{STORED}, {SLAB}{STORED}, /*index=2*/{SLAB}"
        f"{{4,3,2,1,0}}) fusion(bf16[48,512,16,25,64]{STORED} %pool), "
        "kind=kLoop, calls=%fused.7") == (
            f"fusion (2x {SLAB}{STORED}, {SLAB}{{4,3,2,1,0}})")
    assert trace_reduce.op_kind("%while.14.clone") == "while"
    gaps = dict(s["idle_gaps"])
    # device 0 idle 0-10, 40-60, 80-100; device 1 idle 0-10, 50-70, 90-100.
    # Each part of a gap goes to the innermost span over it: 50-70 is half
    # the chunk's and half outer's, 80-100 half outer's and half no span's
    assert gaps["serving.prefill_chunk"] == pytest.approx(0.015)
    assert gaps["outer"] == pytest.approx(0.02)
    assert gaps["no span"] == pytest.approx(0.01)
    assert s["longest_gap_s"] == pytest.approx(0.02)
    assert sum(gaps.values()) + s["busy_s"] == pytest.approx(s["window_s"])


def check_trace_reduction_clips_to_a_window_and_refuses_an_empty_one():
    ms = 1_000_000
    s = trace_reduce.reduce_trace(synthetic_planes(), window=(20 * ms, 50 * ms))
    assert s["window_s"] == pytest.approx(0.03)
    assert s["busy_s"] == pytest.approx(0.025)   # 20 ms and 30 ms
    with pytest.raises(ValueError, match="no operation"):
        trace_reduce.reduce_trace(synthetic_planes(),
                                  window=(95 * ms, 99 * ms))
    assert "XLA Ops" in trace_reduce.describe(synthetic_planes())


# -- needed FLOPs and bytes, against hand counts --------------------------------------------

def check_gpt2_xl_decode_bytes_per_tick_against_a_hand_count():
    hf = mf.resolve_cell("gpt2xl-backlog", ROOT).config["hf_config"]
    h, f, n, v = 1600, 6400, 48, 50257
    block = 2 * (4 * h * h + 4 * h + 2 * h * f + f + h) + 4 * 4 * h
    params = n * block + 4 * v * h + 4 * 2 * h
    assert needs.gpt2_param_bytes(hf) == params
    assert 3.2e9 < params < 3.6e9
    assert needs.gpt2_kv_bytes_per_token(hf) == 2 * 48 * 1600 * 2 == 307200
    assert needs.gpt2_decode_bytes(hf, rows=4, context_tokens=1000) == (
        params + 307200 * 1000 + 307200 * 4)
    peak = peaks.peak_for("TPU v5 lite")
    secs, bound = needs.roofline_seconds(
        needs.gpt2_decode_flops(hf, 4, 1000),
        needs.gpt2_decode_bytes(hf, 4, 1000), peak)
    assert bound == "memory" and 4e-3 < secs < 5e-3


def check_the_peak_table_raises_on_an_unknown_device():
    assert peaks.peak_for("TPU v5 lite").bf16_flops_per_s == 197e12
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peak_for("TPU v9 imaginary")


def test_the_yardsticks_arithmetic_against_hand_counts():
    check_trace_reduction_on_a_hand_built_trace()
    check_trace_reduction_clips_to_a_window_and_refuses_an_empty_one()
    check_gpt2_xl_decode_bytes_per_tick_against_a_hand_count()
    check_the_peak_table_raises_on_an_unknown_device()


# -- the comparison: the control fails, a broken path fails ----------------------------------

TINY_GPT = {"vocab_size": 512, "n_embd": 64, "n_layer": 2, "n_head": 4,
            "n_positions": 128, "layer_norm_epsilon": 1e-5}


def test_the_lower_precision_control_fails_the_serving_limits():
    for seed in (3, 2**31 + 4, 5):
        check_the_lower_precision_control_fails_the_serving_limits(seed)


def check_the_lower_precision_control_fails_the_serving_limits(seed):
    """The reference decodes greedily (a sound program's stand-in: every gap
    0); the same forward with float8 matmul operands is over the limits. At
    this size (two layers, 31 served tokens a row) it is judged at every
    position of the prompts and tokens; on the chip at the served ones."""
    import jax
    import jax.numpy as jnp

    from benchmark.runners import serve

    w = reference.gpt2_weights(seed, TINY_GPT, "float32")
    seqs = np.array(traffic.rng_for(seed, 0).integers(0, 512, (4, 128)),
                    np.int32)
    with jax.default_matmul_precision("highest"):
        step = jax.jit(lambda s: jax.vmap(
            lambda ids: reference.gpt2_logits(w, ids, TINY_GPT))(s))
        for t in range(96, 127):     # 31 greedy tokens after a 96-token prompt
            seqs[:, t + 1] = np.asarray(jnp.argmax(step(seqs)[:, t], -1))
    gaps, std = reference.gpt2_token_gaps(w, seqs, TINY_GPT)
    sound = gaps[:, 96:] / std
    low, _ = reference.gpt2_token_gaps(w, seqs, TINY_GPT, "float8")
    assert sound.max() <= serve.TOKEN_GAP_MAX_LIMIT["cpu"]
    assert sound.mean() <= serve.TOKEN_GAP_MEAN_LIMIT["cpu"]
    assert (low / std).max() > 3 * serve.TOKEN_GAP_MAX_LIMIT["cpu"]
    assert (low / std).mean() > 3 * serve.TOKEN_GAP_MEAN_LIMIT["cpu"]
    # the int8 reference rounds less than the float8 one
    mid, _ = reference.gpt2_token_gaps(w, seqs, TINY_GPT, "int8")
    assert mid.mean() < low.mean()


def test_the_probe_reads_the_control_engine_and_the_engine_as_configured():
    """``python -m benchmark.probe`` at rehearsal sizes: one reading per
    engine and seed, the configured engine's beside both lower-precision
    references; a float32 engine reads 0 against the float32 reference."""
    proc = subprocess.run(
        on_one_core([sys.executable, "-m", "benchmark.probe", "--workloads",
                     "gpt2xl-backlog", "--seeds", str(2**31 + 21),
                     "--control-seeds", "22", "--seconds", "1.5",
                     "--rehearse"]), cwd=ROOT, env=child_env(ROOT),
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [json.loads(ln) for ln in proc.stdout.splitlines()
             if ln.startswith("{")]
    assert [(r["engine"], r["seed"]) for r in lines] == [
        ("control", 22), ("configured", 2**31 + 21)]
    for r in lines:
        assert r["failed"] == 0 and r["tokens_compared"] > 0
        assert r["program_gap_max"] >= 0 and r["tokens_per_s"] > 0
    assert lines[1]["program_gap_max"] == 0
    assert {"int8_reference_gap_mean", "float8_reference_gap_max"} <= set(
        lines[1])


def test_a_broken_timed_path_comes_out_not_correct(monkeypatch, capsys):
    """The rest of a run, driven in this process past the look for a chip
    (the rehearsal's CPU), with the engine's answers altered where they are
    handed out: one token of every completion is another token."""
    from concurrent.futures import Future

    from benchmark import harness
    from sparkdl_tpu.serving.continuous import ContinuousGPTEngine

    real_submit = ContinuousGPTEngine.submit

    def altered(self, prompt_ids, max_new_tokens, **kw):
        inner = real_submit(self, prompt_ids, max_new_tokens, **kw)
        outer: Future = Future()

        def relay(f):
            if f.exception() is not None:
                outer.set_exception(f.exception())
                return
            toks = np.array(f.result())
            toks[-1] = (toks[-1] + 1) % self.config.vocab_size
            outer.set_result(toks)

        inner.add_done_callback(relay)
        return outer

    monkeypatch.setattr(ContinuousGPTEngine, "submit", altered)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    rc = harness.main(["--workload", "gpt2xl-backlog", "--seed", "9",
                       "--seconds", "1.5", "--trace", "0", "--rehearse"])
    out = capsys.readouterr().out
    line = json.loads([ln for ln in out.splitlines() if ln.strip()][-1])
    assert rc == 0 and line["correct"] is False
    assert "NOT CORRECT" in out
