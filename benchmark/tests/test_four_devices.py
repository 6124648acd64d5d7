"""Second rehearsal: a training cell over a data=2 x tensor=2 mesh of four
virtual CPU devices, through the same driver, against the same reference.

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        python3 -m pytest benchmark/tests/test_four_devices.py -q
"""

import os
import time

import jax
import pytest

from benchmark.harness import device, spec
from benchmark.harness.drivers import DRIVERS
from benchmark.tests.conftest import FIXTURES


def test_a_sound_run_over_four_devices_is_correct():
    if len(jax.devices()) < 4:
        pytest.skip("needs --xla_force_host_platform_device_count=4")
    cell = spec.load_cell(
        "train.gpt2-tiny.d2t2",
        manifest=os.path.join(FIXTURES, "BENCHMARK.json"), data_dir=FIXTURES)
    lines = []
    result = DRIVERS["train"](
        cell, seed=5, seconds=1.0, trace=False, devices=jax.devices()[:4],
        peaks=device.load_peaks("cpu", path=os.path.join(FIXTURES, "peaks.json")),
        started=time.perf_counter(),
        say=lambda event, **kw: lines.append({"event": event, **kw}))
    assert result["correct"], [l for l in lines if l["event"] == "compared"]
    assert result["failed"] == 0 and result["attempted"] > 0
