"""Test harness configuration.

Reference-parity test strategy (SURVEY.md §4): the reference tests on
``local[*]`` Spark; we test on a virtual 8-device CPU mesh
(``JAX_PLATFORMS=cpu`` + ``--xla_force_host_platform_device_count=8``) so
every DP/TP/SP collective path is unit-testable on the CPU. jax reads both
from the environment when it is first imported, hence top of conftest.
"""

import os

# Single source of truth for the fake-mesh env contract (stdlib-only import
# chain, so no jax backend is touched here).
from sparkdl_tpu.runner.backends import virtual_cpu_overrides

os.environ.update(virtual_cpu_overrides(8, os.environ.get("XLA_FLAGS", "")))
# Keep TF (used only for ingestion tests) off any accelerator and quiet.
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "2")
os.environ.setdefault("CUDA_VISIBLE_DEVICES", "-1")

# tests/lint_fixtures/ holds DELIBERATE rule violations for the linter's
# own suite: never collected, never scanned by the guards below (the
# linter's default walk skips the directory too — lint.core.EXCLUDED_DIRS)
collect_ignore = ["lint_fixtures"]

import functools  # noqa: E402
import time  # noqa: E402

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    # pytest-xdist hands ``--dist loadfile`` files out by their COUNT of
    # tests, largest first (``--loadscope-reorder``, on by default), which
    # leaves the longest file of few tests (tests/benchmark/
    # test_benchmark.py: nine tests, a third of the run's wall clock) for
    # the middle of the run, to end alone. In collection order it starts
    # with the run. A run without xdist has no such option and is untouched.
    if hasattr(config.option, "loadscopereorder"):
        config.option.loadscopereorder = False


def wait_until(predicate, *, timeout_s=10.0, interval_s=0.01,
               desc="condition"):
    """Deadline-bounded polling: the ONE sanctioned way to wait for an
    asynchronous condition in tests. Returns the first truthy value of
    ``predicate()``; raises AssertionError naming ``desc`` at
    ``timeout_s`` — a stuck predicate fails the test instead of hanging
    the suite (the flaky-soak trap sparkdl-lint's ``sleep-poll`` rule
    and the collection guard below reject)."""
    deadline = time.monotonic() + timeout_s
    while True:
        value = predicate()
        if value:
            return value
        if time.monotonic() >= deadline:
            raise AssertionError(
                f"timed out after {timeout_s}s waiting for {desc}")
        # sparkdl-lint: disable=sleep-poll -- this IS the deadline helper; the bound is enforced two lines above the sleep
        time.sleep(interval_s)


@pytest.fixture(name="wait_until", scope="session")
def wait_until_fixture():
    return wait_until


@functools.cache
def _generate_program():
    import jax

    from sparkdl_tpu.models.gpt import generate

    # model and token budget are static: one compile a (model, prompt
    # length, budget) a process, where the eager call traces its scan anew
    # at every call
    return jax.jit(generate, static_argnums=(0, 3))


def oracle(model, variables, prompt, max_new):
    """THE reference of the serving engine's parity suites: one prompt's
    greedy tokens from the unbatched ``models.gpt.generate``, which shares
    no scheduler, admission or cache code with the engine (prompt not
    included, as an engine's Future resolves)."""
    import jax.numpy as jnp

    out = _generate_program()(
        model, variables, jnp.asarray([prompt], jnp.int32), max_new
    )
    return np.asarray(out[0, len(prompt):])


def fail_on_sleep_polls(root):
    """Collection-time twin of the basename guard below: a test file
    with a ``while`` loop that ``time.sleep``-polls WITHOUT a deadline
    in its condition hangs the whole suite when the predicate wedges.
    Fail the run loudly at conftest import, pointing at the loop — use
    the ``wait_until`` fixture (or bound the loop on time.monotonic()).
    Suppressible per line with justification:
    ``# sparkdl-lint: disable=sleep-poll -- <why>``."""
    import pathlib

    from sparkdl_tpu.lint.core import SourceFile
    from sparkdl_tpu.lint.rules import scan_sleep_polls

    bad = []
    for path in sorted(pathlib.Path(root).rglob("test_*.py")):
        if "lint_fixtures" in path.parts:
            continue
        text = path.read_text()
        if "time.sleep" not in text and "sleep(" not in text:
            continue  # cheap pre-filter: no parse for sleep-free files
        src = SourceFile(str(path), text,
                         rel=str(path.relative_to(root)))
        if src.tree is None:
            continue  # pytest will surface the syntax error itself
        for finding in scan_sleep_polls(src.tree, src.rel):
            hit, why = src.suppression_for("sleep-poll", finding.line)
            if hit and why:
                continue
            if hit:  # suppressed WITHOUT the required justification
                bad.append(f"{finding.path}:{finding.line} "
                           "(suppression lacks '-- <why>' justification)")
            else:
                bad.append(f"{finding.path}:{finding.line}")
    if bad:
        raise pytest.UsageError(
            "time.sleep polling loop(s) with no deadline in the loop "
            "condition (a stuck predicate hangs the suite): "
            + ", ".join(bad)
            + " — use the wait_until fixture from conftest, or bound "
            "the loop on time.monotonic()"
        )


def fail_on_duplicate_test_basenames(root):
    """tests/ has no ``__init__.py``, so pytest imports each test file as
    a top-level module named after its BASENAME — two ``test_pipeline.py``
    in different subdirs collide and collection silently drops (or
    errors on) one of them (bit PR 8). Fail the whole run loudly
    instead, at conftest import, before any test collects."""
    import pathlib

    seen: "dict[str, list]" = {}
    for path in sorted(pathlib.Path(root).rglob("test_*.py")):
        if "lint_fixtures" in path.parts:
            continue
        seen.setdefault(path.name, []).append(path)
    dups = {name: paths for name, paths in seen.items() if len(paths) > 1}
    if dups:
        detail = "; ".join(
            f"{name}: "
            + ", ".join(str(p.relative_to(root)) for p in paths)
            for name, paths in sorted(dups.items())
        )
        raise pytest.UsageError(
            "duplicate test-file basenames under tests/ (no __init__.py "
            "-> module names collide and pytest drops files): " + detail
            + " — rename one of each pair (e.g. test_<subdir>_<name>.py)"
        )


fail_on_duplicate_test_basenames(os.path.dirname(os.path.abspath(__file__)))
fail_on_sleep_polls(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(42)


@pytest.fixture(scope="session")
def eight_device_mesh():
    import jax
    from sparkdl_tpu.runtime.mesh import data_parallel_mesh

    assert len(jax.devices()) == 8, "conftest must set up 8 fake CPU devices"
    return data_parallel_mesh()
