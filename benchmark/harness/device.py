"""The device a run is on: found, checked against the cell, looked up in
the table of peaks.  A run that finds no accelerator, too few chips or a
device kind the table lacks does not fall back: it fails."""

from __future__ import annotations

import json
import os
from typing import Any, Dict

from benchmark.harness.spec import BENCH_DIR


class DeviceError(Exception):
    pass


def load_peaks(device_kind: str, *, path: str | None = None) -> Dict[str, Any]:
    path = path or os.path.join(BENCH_DIR, "peaks.json")
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise DeviceError(
            f"device kind {device_kind!r} is not in {path}; add its "
            "published peaks with their source, do not guess")
    return table[device_kind]


def require_chips(chips: int):
    """The accelerator devices this cell runs on, or DeviceError."""
    import jax

    devices = jax.devices()
    if devices[0].platform == "cpu":
        raise DeviceError(f"JAX found no accelerator: {devices}")
    if len(devices) < chips:
        raise DeviceError(
            f"the cell asks for {chips} chips, JAX found {len(devices)}")
    return devices[:chips]


def describe(devices, **extra) -> Dict[str, Any]:
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices), **extra}


def memory_peak(devices) -> Dict[str, int]:
    """Peak device memory on the fullest chip, from two things JAX reports.

    ``memory_stats()["peak_bytes_in_use"]`` counts live buffers only: on a
    TPU the scratch a running program allocates (activations, logits,
    layout copies) is not in it (a 355M-parameter step read 4.3 GB there,
    state alone).  So the largest scratch among the programs this process
    has loaded (``get_compiled_memory_stats().temp_size_in_bytes``, per
    device) is added: an upper bound on the true peak that is exact when
    the largest program runs while the most buffers are live, as a training
    step or a decode launch does.  The first is a reading and the second the
    compiler's figure, so the result line carries all three numbers.  Call
    it before the reference compiles.
    """
    import jax.extend

    buffers = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                  for d in devices)
    scratch = 0
    for executable in jax.extend.backend.get_backend().live_executables():
        try:
            stats = executable.get_compiled_memory_stats()
        except Exception:      # an executable kind that keeps no stats
            continue
        scratch = max(scratch, int(stats.temp_size_in_bytes))
    return {"buffers_peak_bytes": buffers,
            "largest_program_scratch_bytes": scratch,
            "memory_peak_bytes": buffers + scratch}


def place_compile_cache() -> str:
    """The persistent compile cache: where JAX_COMPILATION_CACHE_DIR says,
    else the program's own fixed directory inside the checkout.  Every
    program is cached, however quickly it compiled, so that only a
    checkout's first run of a cell compiles."""
    import jax
    from distributed_tensorflow_tpu import compile_cache

    placed = compile_cache.configure()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return placed
