"""Smoke test for the bench driver contract: ONE parseable JSON line.

Marked ``slow`` (excluded from tier-1) — it compiles and runs the tiny-CPU
ResNet config in a subprocess, which takes minutes on a cold jit cache.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.slow
def test_bench_prints_one_json_line():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--windows", "1"],
        capture_output=True, text=True, timeout=1200, cwd=REPO, env=env,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [l for l in proc.stdout.strip().splitlines() if l.strip()]
    assert lines, f"no stdout; stderr: {proc.stderr[-2000:]}"
    out = json.loads(lines[-1])  # the contract: last line is the JSON
    for key in ("metric", "value", "unit", "device", "spread"):
        assert key in out, f"missing {key!r} in {out}"
    # A shrunken CPU run names its device and never the chip metric.
    assert out["device"]["platform"] == "cpu"
    assert "cpu_smoke" in out["metric"]
    assert out["value"] > 0
    assert out["spread"]["n"] == 1
