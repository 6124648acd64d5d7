"""dttlint v3 device-boundary rules: each seeded fixture in
``tests/analysis_fixtures/`` is detected at its exact ``path:line``
(markers are rule-specific, ``# SEED: <rule-id>``), each clean twin
stays silent, the real tree is clean end to end, and re-introducing a
donated-cache read in a scratch copy of ``serve/engine.py`` makes
``use-after-donate`` fire — the rule guards the engine's documented
donated-cache chaining idiom, not just the fixture."""

import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from distributed_tensorflow_tpu.analysis import load_modules
from distributed_tensorflow_tpu.analysis.__main__ import default_targets
from distributed_tensorflow_tpu.analysis.concurrency import _FACTS_CACHE
from distributed_tensorflow_tpu.analysis.core import collect_files
from distributed_tensorflow_tpu.analysis.device import (
    _DEVICE_CACHE,
    DonationDisciplineRule,
    HostSyncRule,
    UseAfterDonateRule,
)

REPO_ROOT = Path(__file__).resolve().parent.parent
FIXTURES = REPO_ROOT / "tests" / "analysis_fixtures"


def seeded_lines(path: Path, rule_id: str):
    """Lines carrying this rule's ``# SEED: <rule-id>`` marker."""
    marker = f"# SEED: {rule_id}"
    return [i for i, line in enumerate(path.read_text().splitlines(), 1)
            if marker in line]


def run_rule_on(rule, path: Path, root: Path = REPO_ROOT):
    # Both fact layers cache per module list; stay hermetic.
    _FACTS_CACHE.clear()
    _DEVICE_CACHE.clear()
    modules, errors = load_modules([path], root)
    assert not errors, errors
    return rule.run(modules)


CASES = [
    ("donate", UseAfterDonateRule, "use-after-donate"),
    ("donate", DonationDisciplineRule, "donation-discipline"),
    ("hostsync", HostSyncRule, "host-sync"),
    ("async", UseAfterDonateRule, "use-after-donate"),
    ("async", HostSyncRule, "host-sync"),
    ("asyncring", UseAfterDonateRule, "use-after-donate"),
    ("asyncring", HostSyncRule, "host-sync"),
    ("gateway", HostSyncRule, "host-sync"),
    ("tiering", HostSyncRule, "host-sync"),
    ("lifecycle", HostSyncRule, "host-sync"),
]


class TestSeededFixtures:
    """Each bad fixture fires at exactly its SEED-marked lines; each
    clean twin produces zero findings from the same rule."""

    @pytest.mark.parametrize("stem,rule_cls,rule_id", CASES)
    def test_bad_fixture_fires_at_seeded_lines(self, stem, rule_cls,
                                               rule_id):
        path = FIXTURES / f"{stem}_bad.py"
        expected = seeded_lines(path, rule_id)
        assert expected, f"{path} has no SEED markers for {rule_id}"
        findings = [f for f in run_rule_on(rule_cls(), path)
                    if f.rule == rule_id]
        got = sorted(f.line for f in findings)
        assert got == expected, [f.format() for f in findings]

    @pytest.mark.parametrize("stem,rule_cls,rule_id", CASES)
    def test_clean_twin_is_silent(self, stem, rule_cls, rule_id):
        path = FIXTURES / f"{stem}_clean.py"
        findings = [f for f in run_rule_on(rule_cls(), path)
                    if f.rule == rule_id]
        assert findings == [], [f.format() for f in findings]

    def test_alias_through_self_attr_is_named(self):
        """``refill`` donates ``self._cache`` and re-reads it: the
        finding names the attribute, proving taint follows attribute
        aliases, not just local names."""
        findings = run_rule_on(UseAfterDonateRule(),
                               FIXTURES / "donate_bad.py")
        attr_hits = [f for f in findings if "self._cache" in f.message]
        assert attr_hits, [f.format() for f in findings]

    def test_hot_helper_via_call_graph(self):
        """``_flush_stats`` has no loop of its own — it is hot only
        because ``decode``'s launch loop calls it."""
        findings = run_rule_on(HostSyncRule(),
                               FIXTURES / "hostsync_bad.py")
        helper_hits = [f for f in findings if f.symbol.endswith(
            "_flush_stats")]
        assert helper_hits, [f.format() for f in findings]


class TestRealTreeClean:
    """The three device rules hold over the shipped tree with ZERO
    baseline entries — every real finding was fixed, not suppressed."""

    def test_device_rules_clean_on_default_targets(self):
        _FACTS_CACHE.clear()
        _DEVICE_CACHE.clear()
        files = collect_files(default_targets(REPO_ROOT), REPO_ROOT)
        modules, errors = load_modules(files, REPO_ROOT)
        assert not errors, errors
        for rule_cls in (UseAfterDonateRule, HostSyncRule,
                         DonationDisciplineRule):
            findings = rule_cls().run(modules)
            assert findings == [], [f.format() for f in findings]


class TestDonatedCacheInvariant:
    """Re-introducing the hand-documented hazard — reading ``cache``
    after the donated prefill launch in ``serve/engine.py`` — is caught
    in a scratch copy of the tree."""

    def test_cache_read_after_donated_launch_trips_rule(self, tmp_path):
        scratch = tmp_path / "scratch"
        shutil.copytree(
            REPO_ROOT / "distributed_tensorflow_tpu",
            scratch / "distributed_tensorflow_tpu",
            ignore=shutil.ignore_patterns("__pycache__"))
        engine = scratch / "distributed_tensorflow_tpu" / "serve" / "engine.py"
        src = engine.read_text()
        # The launch rebinds ``cache`` to its result, which is what makes
        # the later reads legal; bind the result elsewhere and the
        # function's own ``return (nxt, cache)`` reads the donated buffer.
        anchor = "nxt, cache, counts = self._generate_fns[key]("
        assert src.count(anchor) == 1
        engine.write_text(src.replace(
            anchor, "nxt, _fresh, counts = self._generate_fns[key]("))

        _FACTS_CACHE.clear()
        _DEVICE_CACHE.clear()
        files = collect_files([scratch / "distributed_tensorflow_tpu"],
                              scratch)
        modules, errors = load_modules(files, scratch)
        assert not errors, errors
        findings = UseAfterDonateRule().run(modules)
        engine_hits = [f for f in findings
                       if f.path == "distributed_tensorflow_tpu/serve/engine.py"]
        assert engine_hits, "donated-cache read in engine.py went undetected"
        _FACTS_CACHE.clear()
        _DEVICE_CACHE.clear()


class TestCli:
    """The device rules ride the existing runner surface:
    --changed-only picks them up from a stdin file list."""

    def _run(self, *argv, stdin=None, cwd=REPO_ROOT):
        return subprocess.run(
            [sys.executable, "-m", "distributed_tensorflow_tpu.analysis",
             *argv],
            input=stdin, capture_output=True, text=True, cwd=cwd,
            timeout=300)

    def test_changed_only_flags_bad_fixture(self):
        proc = self._run(
            "--changed-only", "--no-baseline",
            stdin="tests/analysis_fixtures/donate_bad.py\n")
        assert proc.returncode == 1, proc.stdout + proc.stderr
        assert "use-after-donate" in proc.stdout
        assert "donation-discipline" in proc.stdout

    def test_changed_only_clean_fixture_passes(self):
        proc = self._run(
            "--changed-only", "--no-baseline",
            stdin="tests/analysis_fixtures/donate_clean.py\n")
        assert proc.returncode == 0, proc.stdout + proc.stderr


class TestFileWalk:
    """collect_files never descends into cache directories — a stale
    ``__pycache__``/``.pytest_cache`` artifact must not become a
    finding."""

    def test_cache_dirs_are_skipped(self, tmp_path):
        (tmp_path / "pkg").mkdir()
        (tmp_path / "pkg" / "real.py").write_text("x = 1\n")
        for cache in ("__pycache__", ".pytest_cache"):
            d = tmp_path / "pkg" / cache
            d.mkdir()
            (d / "planted.py").write_text("import os, sys  # junk\n")
        files = collect_files([tmp_path], tmp_path)
        names = sorted(p.name for p in files)
        assert names == ["real.py"], names

    def test_default_targets_exclude_caches(self):
        files = collect_files(default_targets(REPO_ROOT), REPO_ROOT)
        offenders = [p for p in files
                     if "__pycache__" in p.parts
                     or ".pytest_cache" in p.parts]
        assert offenders == []
