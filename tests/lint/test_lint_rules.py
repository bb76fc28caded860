"""Per-rule fixture tests: every rule has a positive (fires), a negative
(stays quiet), and — via the framework suite — a suppressed form. The
fixture corpus lives in tests/lint_fixtures/ (excluded from collection
and from the linter's default directory walk)."""

import os

import pytest

from sparkdl_tpu.lint.core import SourceFile
from sparkdl_tpu.lint.rules import (
    BlockingInHotLoopRule,
    DonationSafetyRule,
    EnvPinRule,
    FaultCoverageRule,
    LockDisciplineRule,
    MetricDriftRule,
    SleepPollRule,
)
from sparkdl_tpu.lint.core import Project

FIXTURES = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "lint_fixtures")


def load(name, rel=None):
    path = os.path.join(FIXTURES, name)
    with open(path, encoding="utf-8") as fh:
        return SourceFile(path, fh.read(), rel=rel or name)


def run_rule(rule, *files, docs=""):
    findings = []
    for f in files:
        if rule.wants(f):
            findings.extend(rule.check(f))
    findings.extend(rule.finalize(Project(list(files), {}, docs)))
    return findings


class TestLockDiscipline:
    def test_positive_mixed_mutation(self):
        found = run_rule(LockDisciplineRule(), load("lock_bad.py"))
        assert len(found) == 1
        assert found[0].line == 16
        assert "'self.depth'" in found[0].message

    def test_negative_propagation_and_locked_suffix(self):
        assert run_rule(LockDisciplineRule(), load("lock_ok.py")) == []

    def test_acquisition_cycle(self):
        found = run_rule(LockDisciplineRule(), load("lock_cycle.py"))
        assert len(found) == 1
        assert "cycle" in found[0].message
        assert "Pool._route_lock" in found[0].message
        assert "Pool._state_lock" in found[0].message


class TestDonationSafety:
    def test_positive_read_after_donation(self):
        found = run_rule(DonationSafetyRule(), load("donation_bad.py"))
        lines = sorted(f.line for f in found)
        assert len(found) == 3, found
        # read of `state` after chained(); read of self._cache after the
        # donated step; loop body that never rebinds
        assert lines == [13, 28, 34]

    def test_negative_rebind_idioms(self):
        assert run_rule(DonationSafetyRule(), load("donation_ok.py")) == []

    def test_a_bound_module_function_jitted_on_self_is_seen(self):
        """The engine's binding form since ISSUE 31, ``self._x_fn =
        jax.jit(bound(f, a, b), donate_argnums=(1,))``: the handle
        donates, so the one call that does not rebind the pool is the one
        finding; the rebinding call and the undonating handle are clean."""
        found = run_rule(DonationSafetyRule(), load("donation_bound.py"))
        assert [f.line for f in found] == [26], found
        assert "'self._pool_kv'" in found[0].message
        assert "self._step_fn" in found[0].message

    def test_rebind_inside_compound_statements_is_clean(self):
        """The documented same-statement rebind idiom must stay clean
        inside if/for/try suites — the call is judged at ITS statement,
        not the enclosing compound one."""
        src = SourceFile("m.py", (
            "import jax\n"
            "\n"
            "step = jax.jit(lambda s, x: s, donate_argnums=(0,))\n"
            "\n"
            "\n"
            "def run(cond, state, xs):\n"
            "    if cond:\n"
            "        state = step(state, xs)\n"
            "    for x in xs:\n"
            "        try:\n"
            "            state = step(state, x)\n"
            "        finally:\n"
            "            pass\n"
            "    return state\n"))
        assert run_rule(DonationSafetyRule(), src) == [], \
            [f.render() for f in run_rule(DonationSafetyRule(), src)]

    def test_lock_graph_nodes_are_file_qualified(self):
        """Same-named classes in different files must not merge into
        one lock node (phantom ABBA cycles)."""
        a = SourceFile("a.py", (
            "import threading\n"
            "class Pool:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self._cv = threading.Lock()\n"
            "    def route(self):\n"
            "        with self._lock:\n"
            "            with self._cv:\n"
            "                pass\n"))
        b = SourceFile("b.py", (
            "import threading\n"
            "class Pool:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self._cv = threading.Lock()\n"
            "    def route(self):\n"
            "        with self._cv:\n"
            "            with self._lock:\n"
            "                pass\n"))
        assert run_rule(LockDisciplineRule(), a, b) == []

    def test_self_attr_bindings_are_class_scoped(self):
        """Two classes reusing an attribute name must not contaminate
        each other: only the class whose attr is bound to a donating
        jit sees donation semantics on it."""
        src = SourceFile("m.py", (
            "import functools\n"
            "import jax\n"
            "\n"
            "\n"
            "@functools.partial(jax.jit, donate_argnums=(1,))\n"
            "def _donating(params, cache):\n"
            "    return cache\n"
            "\n"
            "\n"
            "def _plain(params, cache):\n"
            "    return cache\n"
            "\n"
            "\n"
            "class Donates:\n"
            "    def __init__(self):\n"
            "        self._step_fn = _donating\n"
            "\n"
            "    def run(self, params, x):\n"
            "        out = self._step_fn(params, x)\n"
            "        return out, x  # read of donated x: flagged\n"
            "\n"
            "\n"
            "class DoesNot:\n"
            "    def __init__(self):\n"
            "        self._step_fn = _plain\n"
            "\n"
            "    def run(self, params, x):\n"
            "        out = self._step_fn(params, x)\n"
            "        return out, x  # _plain donates nothing: clean\n"))
        found = run_rule(DonationSafetyRule(), src)
        assert len(found) == 1, found
        assert found[0].line == 20


class TestBlockingInHotLoop:
    def test_positive_including_transitive_helper(self):
        found = run_rule(BlockingInHotLoopRule(), load("hotloop_bad.py"))
        msgs = sorted(f.message for f in found)
        assert len(found) == 4, found
        assert any("time.sleep" in m for m in msgs)
        assert any(".result()" in m for m in msgs)
        assert any(".join()" in m for m in msgs)
        assert any("device_get" in m for m in msgs)

    def test_negative_timed_waits(self):
        assert run_rule(
            BlockingInHotLoopRule(), load("hotloop_ok.py")) == []


class TestMetricDrift:
    def test_conflicting_shapes_and_missing_doc(self):
        found = run_rule(MetricDriftRule(), load("metric_bad.py"),
                         docs="sparkdl_lintfixture_total is documented")
        conflict = [f for f in found if "conflicting" in f.message]
        undoc = [f for f in found if "not documented" in f.message]
        assert len(conflict) == 2  # one per declaration site
        assert len(undoc) == 1
        assert "sparkdl_lintfixture_undocumented" in undoc[0].message

    def test_documented_consistent_family_is_clean(self):
        src = SourceFile("m.py", (
            "from sparkdl_tpu.observability.registry import registry\n"
            "_A = registry().counter('sparkdl_ok_total', 'x',"
            " labels=('site',))\n"
            "_B = registry().counter('sparkdl_ok_total', 'x',"
            " labels=('site',))\n"))
        assert run_rule(MetricDriftRule(), src,
                        docs="`sparkdl_ok_total` counter") == []


class TestFaultCoverage:
    def test_unexercised_site_and_ghost_plan(self):
        found = run_rule(
            FaultCoverageRule(),
            load("fault_bad.py"),
            load("fault_ok.py"),
            load("fault_plans_testfile.py",
                 rel="tests/fault_plans_testfile.py"),
        )
        orphan = [f for f in found if "fixture.orphan" in f.message]
        ghost = [f for f in found if "fixture.ghost" in f.message]
        covered = [f for f in found if "fixture.covered" in f.message]
        assert len(orphan) == 1 and "no test fault plan" in \
            orphan[0].message
        assert len(ghost) == 1 and "no fault_point" in ghost[0].message
        assert covered == []


class TestEnvPin:
    def test_positive_direct_reads(self):
        found = run_rule(EnvPinRule(), load("env_bad.py"))
        assert len(found) == 2, found
        assert any("SPARKDL_TPU_PREFILL_CHUNK" in f.message
                   and "pin-managed" in f.message for f in found)
        assert any("SPARKDL_TPU_MADE_UP_KNOB" in f.message
                   for f in found)

    def test_negative_resolver_and_allowlist(self):
        assert run_rule(EnvPinRule(), load("env_ok.py")) == []


class TestSleepPoll:
    def test_positive_negative_and_suppression_scope(self):
        src = load("sleep_poll_testfile.py",
                   rel="tests/sleep_poll_testfile.py")
        found = list(SleepPollRule().check(src))
        # two loops fire at the rule level (line 8 bad, line 20
        # suppressed); the deadlined loop stays quiet
        assert sorted(f.line for f in found) == [8, 20]
        assert src.suppression_for("sleep-poll", 20)[0]
        assert not src.suppression_for("sleep-poll", 9)[0]


def test_every_rule_has_positive_and_negative_fixture_coverage():
    """Meta: the table above keeps one fixture pair per shipped rule —
    a rule without a firing fixture can silently rot."""
    from sparkdl_tpu.lint.rules import ALL_RULES

    covered = {
        "lock-discipline", "donation-safety", "blocking-in-hot-loop",
        "metric-drift", "fault-coverage", "env-pin", "sleep-poll",
    }
    assert {cls.name for cls in ALL_RULES} == covered
