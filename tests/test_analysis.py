"""dttlint analyzer tests: each rule family catches its seeded fixture
at the right rule id and line, suppressions and the baseline round-trip
work, and — the tier-1 gate — the repo itself is clean."""

import json
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from distributed_tensorflow_tpu.analysis import (
    collect_files,
    default_rules,
    load_baseline,
    load_modules,
    render_baseline,
    run_rules,
    split_findings,
)
from distributed_tensorflow_tpu.analysis.baseline import BaselineError
from distributed_tensorflow_tpu.analysis.core import Finding, Module

REPO_ROOT = Path(__file__).resolve().parent.parent


def lint_source(tmp_path, source, filename="fixture.py", repo_root=None):
    """Write a fixture, run the full default rule set, return findings."""
    path = tmp_path / filename
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(textwrap.dedent(source))
    root = repo_root or tmp_path
    modules, errors = load_modules([path], root)
    assert not errors, errors
    return run_rules(modules, default_rules())


def by_rule(findings, rule):
    return [f for f in findings if f.rule == rule]


class TestJitPurity:
    def test_decorated_function_impurities(self, tmp_path):
        findings = lint_source(tmp_path, """\
            import time
            import logging
            import jax

            logger = logging.getLogger(__name__)

            @jax.jit
            def step(x):
                t0 = time.time()
                logger.info("tick")
                print(x)
                return x + t0
            """)
        purity = by_rule(findings, "jit-purity")
        assert [f.line for f in purity] == [9, 10, 11]
        assert "time.time" in purity[0].message
        assert "logger.info" in purity[1].message
        assert "print" in purity[2].message

    def test_call_graph_walk_reaches_helper(self, tmp_path):
        findings = lint_source(tmp_path, """\
            import random
            import jax

            def helper(x):
                return x * random.random()

            def outer(x):
                return helper(x)

            fn = jax.jit(outer)
            """)
        purity = by_rule(findings, "jit-purity")
        assert len(purity) == 1
        assert purity[0].line == 5
        assert "random.random" in purity[0].message

    def test_jax_random_is_pure(self, tmp_path):
        findings = lint_source(tmp_path, """\
            import jax

            @jax.jit
            def step(key, x):
                noise = jax.random.normal(key, x.shape)
                return x + noise
            """)
        assert by_rule(findings, "jit-purity") == []

    def test_numpy_random_alias_resolved(self, tmp_path):
        findings = lint_source(tmp_path, """\
            import numpy as np
            import jax

            @jax.jit
            def step(x):
                return x + np.random.rand()
            """)
        purity = by_rule(findings, "jit-purity")
        assert len(purity) == 1 and purity[0].line == 6
        assert "numpy.random" in purity[0].message

    def test_obs_instrument_handle_flagged(self, tmp_path):
        findings = lint_source(tmp_path, """\
            import jax

            class Engine:
                def _step(self, x):
                    self._obs["steps"].inc()
                    return x

                def compile(self):
                    return jax.jit(self._step)
            """)
        purity = by_rule(findings, "jit-purity")
        assert len(purity) == 1 and purity[0].line == 5


class TestRecompileHazard:
    def test_unhashable_static_arg(self, tmp_path):
        findings = lint_source(tmp_path, """\
            import jax

            def f(x, opts=[]):
                return x

            g = jax.jit(f, static_argnums=(1,))
            """)
        hazards = by_rule(findings, "recompile-hazard")
        assert len(hazards) == 1 and hazards[0].line == 6
        assert "opts" in hazards[0].message

    def test_nonfrozen_dataclass_cache_key(self, tmp_path):
        findings = lint_source(tmp_path, """\
            import dataclasses
            import jax

            @dataclasses.dataclass
            class Cfg:
                n: int = 1

            class Engine:
                def __init__(self):
                    self._fns = {}

                def get(self, cfg: Cfg, temp):
                    key = (float(temp), cfg)
                    self._fns[key] = jax.jit(lambda x: x * temp)
                    return self._fns[key]
            """)
        hazards = by_rule(findings, "recompile-hazard")
        assert len(hazards) == 1 and hazards[0].line == 14
        assert "Cfg" in hazards[0].message

    def test_frozen_dataclass_key_is_fine(self, tmp_path):
        findings = lint_source(tmp_path, """\
            import dataclasses
            import jax

            @dataclasses.dataclass(frozen=True)
            class Cfg:
                n: int = 1

            class Engine:
                def __init__(self):
                    self._fns = {}

                def get(self, cfg: Cfg, temp):
                    key = (float(temp), cfg)
                    self._fns[key] = jax.jit(lambda x: x * temp)
                    return self._fns[key]
            """)
        assert by_rule(findings, "recompile-hazard") == []

    def test_sampling_bad_fixture_fires_at_seeded_lines(self):
        """The per-request-scalar-in-key antipattern the vectorized
        sampling path removed: a non-frozen config in the program-cache
        key (or baked into a jitted partial) fires at every SEED line."""
        path = REPO_ROOT / "tests" / "analysis_fixtures" / "sampling_bad.py"
        expected = [i for i, line in
                    enumerate(path.read_text().splitlines(), 1)
                    if "# SEED: recompile-hazard" in line]
        assert expected, f"{path} has no SEED markers"
        modules, errors = load_modules([path], REPO_ROOT)
        assert not errors, errors
        hazards = by_rule(run_rules(modules, default_rules()),
                          "recompile-hazard")
        assert sorted(f.line for f in hazards) == expected

    def test_sampling_clean_twin_is_silent(self):
        """Frozen params + static family keys + runtime vectors — the
        serve.sampling pattern — produce zero findings from ANY rule."""
        path = REPO_ROOT / "tests" / "analysis_fixtures" / "sampling_clean.py"
        modules, errors = load_modules([path], REPO_ROOT)
        assert not errors, errors
        assert run_rules(modules, default_rules()) == []

    def test_mutable_closure_capture(self, tmp_path):
        findings = lint_source(tmp_path, """\
            import jax

            def make(scale0):
                state = [scale0]

                def inner(x):
                    return x * state[0]

                return jax.jit(inner)
            """)
        hazards = by_rule(findings, "recompile-hazard")
        assert len(hazards) == 1 and hazards[0].line == 9
        assert "state" in hazards[0].message


class TestLockDiscipline:
    def test_unlocked_write_flagged(self, tmp_path):
        findings = lint_source(tmp_path, """\
            import threading

            class Stats:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._count = 0

                def inc(self):
                    with self._lock:
                        self._count += 1

                def reset(self):
                    self._count = 0
            """)
        locks = by_rule(findings, "lock-discipline")
        assert len(locks) == 1
        assert locks[0].line == 13
        assert "_count" in locks[0].message
        assert locks[0].symbol == "Stats.reset"

    def test_condition_aliases_wrapped_lock(self, tmp_path):
        findings = lint_source(tmp_path, """\
            import threading

            class Q:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._cv = threading.Condition(self._lock)
                    self._items = []

                def put(self, x):
                    with self._lock:
                        self._items.append(x)
                        self._cv.notify()

                def get(self):
                    with self._cv:
                        return self._items.pop()
            """)
        assert by_rule(findings, "lock-discipline") == []

    def test_init_and_init_reachable_methods_exempt(self, tmp_path):
        findings = lint_source(tmp_path, """\
            import threading

            class D:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._items = []
                    self._replay()

                def _replay(self):
                    self._items.append(1)

                def add(self, x):
                    with self._lock:
                        self._items.append(x)
            """)
        assert by_rule(findings, "lock-discipline") == []

    def test_locked_suffix_means_caller_holds(self, tmp_path):
        findings = lint_source(tmp_path, """\
            import threading

            class S:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._n = 0

                def bump(self):
                    with self._lock:
                        self._bump_locked()

                def _bump_locked(self):
                    self._n += 1
            """)
        assert by_rule(findings, "lock-discipline") == []


class TestLayering:
    def test_obs_core_must_not_import_jax(self, tmp_path):
        pkg = tmp_path / "distributed_tensorflow_tpu" / "obs"
        findings = lint_source(
            tmp_path, """\
            import jax

            def snapshot():
                return jax.device_count()
            """,
            filename="distributed_tensorflow_tpu/obs/metrics.py")
        layer = by_rule(findings, "layering")
        assert len(layer) == 1 and layer[0].line == 1
        assert "jax" in layer[0].message
        assert pkg.joinpath("metrics.py").exists()

    def test_training_must_not_import_serve_even_lazily(self, tmp_path):
        findings = lint_source(
            tmp_path, """\
            def hook():
                from distributed_tensorflow_tpu.serve import engine
                return engine
            """,
            filename="distributed_tensorflow_tpu/training/loop.py")
        layer = by_rule(findings, "layering")
        assert len(layer) == 1 and layer[0].line == 2
        assert "even lazily" in layer[0].message

    def test_a_decoder_family_must_not_import_another(self, tmp_path):
        findings = lint_source(
            tmp_path, """\
            from distributed_tensorflow_tpu.models.decoder_parts import dot
            from distributed_tensorflow_tpu.models.glm4_moe_lite import route
            """,
            filename="distributed_tensorflow_tpu/models/mellum.py")
        layer = by_rule(findings, "layering")
        assert len(layer) == 1 and layer[0].line == 2
        assert "no other decoder family" in layer[0].message

    def test_toplevel_cycle_detected(self, tmp_path):
        a = tmp_path / "distributed_tensorflow_tpu" / "x.py"
        b = tmp_path / "distributed_tensorflow_tpu" / "y.py"
        a.parent.mkdir(parents=True, exist_ok=True)
        a.write_text("from distributed_tensorflow_tpu.y import g\n"
                     "def f():\n    return g()\n")
        b.write_text("from distributed_tensorflow_tpu.x import f\n"
                     "def g():\n    return f()\n")
        modules, errors = load_modules([a, b], tmp_path)
        assert not errors
        findings = run_rules(modules, default_rules())
        cycles = [f for f in by_rule(findings, "layering")
                  if "cycle" in f.message]
        assert len(cycles) == 1

    def test_lazy_import_breaks_cycle(self, tmp_path):
        a = tmp_path / "distributed_tensorflow_tpu" / "x.py"
        b = tmp_path / "distributed_tensorflow_tpu" / "y.py"
        a.parent.mkdir(parents=True, exist_ok=True)
        a.write_text("from distributed_tensorflow_tpu.y import g\n"
                     "def f():\n    return g()\n")
        b.write_text("def g():\n"
                     "    from distributed_tensorflow_tpu.x import f\n"
                     "    return f()\n")
        modules, errors = load_modules([a, b], tmp_path)
        assert not errors
        findings = run_rules(modules, default_rules())
        cycles = [f for f in by_rule(findings, "layering")
                  if "cycle" in f.message]
        assert cycles == []


class TestHygiene:
    def test_unused_import_and_mutable_default(self, tmp_path):
        findings = lint_source(tmp_path, """\
            import itertools
            import json


            def f(xs=[]):
                return json.dumps(xs)
            """)
        unused = by_rule(findings, "unused-import")
        assert len(unused) == 1 and unused[0].line == 1
        assert "itertools" in unused[0].message
        mutable = by_rule(findings, "mutable-default")
        assert len(mutable) == 1 and mutable[0].line == 5


class TestSuppressions:
    SOURCE = """\
        import threading

        class S:
            def __init__(self):
                self._lock = threading.Lock()
                self._n = 0

            def inc(self):
                with self._lock:
                    self._n += 1

            def peek(self):
                return self._n{trailing}
        """

    def test_trailing_comment_suppresses(self, tmp_path):
        findings = lint_source(tmp_path, self.SOURCE.format(
            trailing="  # dttlint: disable=lock-discipline"))
        assert by_rule(findings, "lock-discipline") == []

    def test_other_rule_not_suppressed(self, tmp_path):
        findings = lint_source(tmp_path, self.SOURCE.format(
            trailing="  # dttlint: disable=jit-purity"))
        assert len(by_rule(findings, "lock-discipline")) == 1

    def test_preceding_line_comment_suppresses(self, tmp_path):
        source = textwrap.dedent(self.SOURCE.format(trailing="")).replace(
            "        return self._n",
            "        # dttlint: disable=lock-discipline\n"
            "        return self._n")
        findings = lint_source(tmp_path, source)
        assert by_rule(findings, "lock-discipline") == []

    def test_disable_file(self, tmp_path):
        source = ("# dttlint: disable-file=lock-discipline\n"
                  + textwrap.dedent(self.SOURCE.format(trailing="")))
        findings = lint_source(tmp_path, source)
        assert by_rule(findings, "lock-discipline") == []


class TestBaseline:
    def test_round_trip(self, tmp_path):
        findings = [
            Finding(rule="lock-discipline", path="a/b.py", line=12,
                    message="unlocked read", code="return self._n"),
            Finding(rule="jit-purity", path="c.py", line=3,
                    message="print", code="print(x)"),
        ]
        path = tmp_path / "baseline.json"
        path.write_text(render_baseline(findings, justification="benign"))
        entries = load_baseline(path)
        assert len(entries) == 2
        new, baselined, stale = split_findings(findings, entries)
        assert new == [] and len(baselined) == 2 and stale == []

    def test_line_drift_still_matches(self, tmp_path):
        finding = Finding(rule="lock-discipline", path="a.py", line=40,
                          message="unlocked read", code="return self._n")
        path = tmp_path / "baseline.json"
        path.write_text(render_baseline([finding], justification="benign"))
        drifted = Finding(rule="lock-discipline", path="a.py", line=97,
                          message="unlocked read", code="return self._n")
        new, baselined, stale = split_findings(
            [drifted], load_baseline(path))
        assert new == [] and len(baselined) == 1

    def test_missing_justification_rejected(self, tmp_path):
        path = tmp_path / "baseline.json"
        path.write_text(json.dumps({"entries": [{
            "rule": "lock-discipline", "path": "a.py",
            "code": "return self._n", "justification": "  "}]}))
        with pytest.raises(BaselineError):
            load_baseline(path)

    def test_stale_entry_reported(self, tmp_path):
        path = tmp_path / "baseline.json"
        path.write_text(json.dumps({"entries": [{
            "rule": "lock-discipline", "path": "gone.py",
            "code": "return self._n", "justification": "was removed"}]}))
        new, baselined, stale = split_findings([], load_baseline(path))
        assert new == [] and baselined == [] and len(stale) == 1

    def test_repo_baseline_is_wellformed(self):
        entries = load_baseline(
            REPO_ROOT / "distributed_tensorflow_tpu" / "analysis"
            / "baseline.json")
        for e in entries:
            assert e["justification"].strip()


class TestRepoGate:
    """The self-enforcing tier-1 gate: the tree must be dttlint-clean."""

    def test_repo_has_zero_nonbaselined_findings(self):
        proc = subprocess.run(
            [sys.executable, "-m", "distributed_tensorflow_tpu.analysis",
             "--json"],
            capture_output=True, text=True, cwd=REPO_ROOT, timeout=300)
        assert proc.returncode == 0, (
            "dttlint found non-baselined findings:\n" + proc.stdout[-8000:]
            + proc.stderr[-2000:])
        report = json.loads(proc.stdout)
        assert report["findings"] == []
        assert report["files"] > 50  # the sweep really covered the tree

    def test_runner_flags_seeded_violation(self, tmp_path):
        bad = tmp_path / "seeded.py"
        bad.write_text("import threading\n\n"
                       "class S:\n"
                       "    def __init__(self):\n"
                       "        self._lock = threading.Lock()\n"
                       "        self._n = 0\n\n"
                       "    def inc(self):\n"
                       "        with self._lock:\n"
                       "            self._n += 1\n\n"
                       "    def peek(self):\n"
                       "        return self._n\n")
        proc = subprocess.run(
            [sys.executable, "-m", "distributed_tensorflow_tpu.analysis",
             "--no-baseline", str(bad)],
            capture_output=True, text=True, cwd=REPO_ROOT, timeout=120)
        assert proc.returncode == 1
        assert "lock-discipline" in proc.stdout

    def test_analysis_package_imports_without_jax(self):
        # The analyzer must stay usable in a jax-free interpreter: no
        # analysis module may import jax at module scope.
        proc = subprocess.run(
            [sys.executable, "-c",
             "import sys; sys.modules['jax'] = None; "
             "import distributed_tensorflow_tpu.analysis; "
             "import distributed_tensorflow_tpu.analysis.__main__; "
             "print('ok')"],
            capture_output=True, text=True, cwd=REPO_ROOT, timeout=120)
        assert proc.returncode == 0, proc.stderr[-2000:]
        assert "ok" in proc.stdout


class TestCollectFiles:
    def test_tests_dir_excluded_from_directory_sweep(self, tmp_path):
        (tmp_path / "pkg").mkdir()
        (tmp_path / "pkg" / "a.py").write_text("x = 1\n")
        (tmp_path / "tests").mkdir()
        (tmp_path / "tests" / "test_a.py").write_text("x = 1\n")
        files = collect_files([tmp_path], tmp_path)
        names = {f.name for f in files}
        assert "a.py" in names and "test_a.py" not in names

    def test_module_names_derived_from_repo_root(self, tmp_path):
        p = tmp_path / "distributed_tensorflow_tpu" / "obs" / "metrics.py"
        p.parent.mkdir(parents=True)
        p.write_text("x = 1\n")
        modules, _ = load_modules([p], tmp_path)
        assert modules[0].name == "distributed_tensorflow_tpu.obs.metrics"
