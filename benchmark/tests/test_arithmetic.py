"""The FLOP and byte functions against hand-worked numbers, the peaks
table, percentiles and interval arithmetic."""

import json
import os

import pytest

from benchmark.harness import device, flops, spec, stats, xplane
from benchmark.harness import program


def _shape(config_name, traffic_name):
    with open(os.path.join(spec.BENCH_DIR, "configs", f"{config_name}.json")) as f:
        config = json.load(f)
    with open(os.path.join(spec.BENCH_DIR, "traffic", f"{traffic_name}.json")) as f:
        traffic = json.load(f)
    return {**program.shape_of(config), "seq_len": traffic["seq_len"]}


# Hand-worked, per trained token, forward + backward = 3 x forward, a
# multiply-add = 2 operations:
#  gpt2-medium  L=24 d=1024 T=1024 V=50257, causal:
#    layer = 2*(4d^2 + 2*d*4d) + 4*T*d/2 = 25,165,824 + 2,097,152
#    forward = 24*27,262,976 + 2*d*V = 654,311,424 + 102,926,336
#    x3 = 2,271,713,280
#  gpt2-large   L=36 d=1280 T=1024 V=50257, causal:
#    layer = 24*d^2 + 2*T*d = 39,321,600 + 2,621,440
#    forward = 36*41,943,040 + 128,657,920 = 1,638,607,360 ; x3 = 4,915,822,080
@pytest.mark.parametrize("config,traffic,expected", [
    ("gpt2-medium", "lm-seq1024-b32", 2_271_713_280),
    ("gpt2-large", "lm-seq1024-b64", 4_915_822_080),
])
def test_train_flops_per_token(config, traffic, expected):
    got = flops.transformer_train_flops_per_token(_shape(config, traffic))
    assert got == pytest.approx(expected, rel=1e-9)


def test_mfu_is_tokens_times_flops_over_peak():
    # 30,000 tokens/s/chip x 2.27171328 GFLOP / 197 TFLOP/s = 34.59...%
    assert flops.mfu_pct(30_000, 2_271_713_280, 197e12) == pytest.approx(
        34.5946, rel=1e-4)


@pytest.mark.parametrize("kind,causal,flop,byte", [
    # rows=128 (8 x 16 heads), seq=1024, head 64.  One score product is
    # 2*1024*1024*64 = 134,217,728 operations a row; causal halves it.
    ("fwd", True, 2 * 128 * 67_108_864, 4 * 16_777_216 + 524_288),
    ("dq", True, 2 * 128 * 67_108_864, 5 * 16_777_216 + 2 * 524_288),
    ("dkv", False, 2 * 128 * 134_217_728, 6 * 16_777_216 + 2 * 524_288),
])
def test_flash_call_cost(kind, causal, flop, byte):
    cost = flops.flash_call_cost(kind, rows=128, seq=1024, head_dim=64,
                                 causal=causal)
    assert cost == {"flops": flop, "bytes": byte}


def test_roofline_says_which_peak_bounds():
    peaks = device.load_peaks("TPU v5 lite")
    fwd = flops.flash_call_cost("fwd", rows=128, seq=1024, head_dim=64,
                                causal=True)
    floor = flops.least_seconds(fwd, peaks)
    assert floor["bound"] == "compute"
    assert floor["seconds"] == pytest.approx(fwd["flops"] / 197e12)
    short = flops.flash_call_cost("fwd", rows=128, seq=128, head_dim=64,
                                  causal=True)
    assert flops.least_seconds(short, peaks)["bound"] == "memory"


def test_peaks_table_has_v5e_with_its_source():
    with open(os.path.join(spec.BENCH_DIR, "peaks.json")) as f:
        table = json.load(f)
    assert "Google Cloud" in table["_source"]
    v5e = device.load_peaks("TPU v5 lite")
    assert (v5e["bf16_flops_per_s"], v5e["hbm_bytes_per_s"],
            v5e["hbm_bytes"]) == (197e12, 819e9, 16e9)


@pytest.mark.parametrize("kind", ["TPU v4", "cpu", "_source", ""])
def test_unknown_device_kind_is_refused(kind):
    with pytest.raises(device.DeviceError):
        device.load_peaks(kind)


def test_run_refuses_a_machine_without_an_accelerator():
    """The whole command, here on the CPU: exit code 2, no result line."""
    import subprocess
    import sys

    proc = subprocess.run(
        [sys.executable, os.path.join(spec.BENCH_DIR, "run.py"),
         "--workload", "train.gpt2-medium.seq1024", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, cwd=spec.REPO_DIR,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, timeout=300)
    assert proc.returncode == 2
    assert "no accelerator" in proc.stderr
    assert proc.stdout.strip() == ""


def test_percentile_is_nearest_rank():
    values = list(range(1, 101))
    assert stats.percentile(values, 95) == 95
    assert stats.percentile(values, 50) == 50
    assert stats.percentile([3.0], 95) == 3.0
    assert stats.percentile([1, 2, 3, 4], 95) == 4
    assert stats.median([4, 1, 3, 2]) == 2.5
    with pytest.raises(ValueError):
        stats.percentile([], 95)


def test_interval_arithmetic():
    merged = xplane.union([(0, 2), (1, 3), (5, 6), (6, 7), (9, 9)])
    assert merged == [(0, 3), (5, 7)]
    assert xplane.total(merged) == 5
    assert xplane.clip(merged, (2, 6)) == [(2, 3), (5, 6)]
    assert xplane.subtract([(0, 10)], merged) == [(3, 5), (7, 10)]
    assert xplane.subtract([(0, 3), (5, 7)], [(1, 6)]) == [(0, 1), (6, 7)]
    assert xplane.subtract([(0, 1)], []) == [(0, 1)]


def test_idle_gaps_go_to_the_innermost_host_span():
    host = [xplane.Event("bench/window", 0.0, 10.0),
            xplane.Event("bench/wait_request", 1.0, 4.0),
            xplane.Event("bench/submit", 2.0, 2.5)]
    got = xplane.attribute_gaps([(1.5, 3.0), (8.0, 9.0)], host)
    assert got == pytest.approx({"submit": 0.5, "wait_request": 1.0,
                                 "window": 1.0})


def test_every_piece_of_a_gap_goes_to_the_shortest_span_over_it():
    """The sweep against the definition, point by point on a fine grid,
    over spans that nest, overlap across threads and leave holes."""
    import random

    rng = random.Random(5)
    for _ in range(30):
        host = [xplane.Event("other", 0.0, 10.0)]
        for _ in range(rng.randint(0, 10)):
            start = rng.randint(0, 90) / 10
            host.append(xplane.Event("bench/" + rng.choice("abc"), start,
                                     start + rng.randint(1, 60) / 10))
        edges = sorted(rng.sample(range(0, 100), 2 * rng.randint(0, 6)))
        gaps = [(a / 10, b / 10) for a, b in zip(edges[::2], edges[1::2])]
        want = {}
        for a, b in gaps:
            for tick in range(round(a * 10), round(b * 10)):
                at = (tick + 0.5) / 10
                over = [e for e in host[1:] if e.start <= at <= e.end]
                key = (min(over, key=lambda e: e.seconds).name[6:]
                       if over else "unattributed")
                want[key] = want.get(key, 0.0) + 0.1
        got = xplane.attribute_gaps(gaps, host)
        assert set(got) == set(want)
        # spans of one length over one piece: either may have it
        if len({e.seconds for e in host[1:]}) == len(host) - 1:
            assert got == pytest.approx(want)
        assert sum(got.values()) == pytest.approx(sum(want.values()))


def test_exposed_collective_time_is_what_compute_does_not_cover():
    op = lambda name, code, a, b: xplane.Event(
        f"%{name} = f32[8]{{0}} {code}(f32[8]{{0}} %x)", a, b)
    lines = {
        xplane.OPS_LINE: [
            op("w", "while", 0.0, 10.0),              # container: not compute
            op("fusion.1", "fusion", 0.0, 2.0),
            op("all-reduce.1", "all-reduce", 2.0, 3.0),       # exposed
            op("fusion.2", "fusion", 4.0, 6.0),
            op("all-reduce-done.2", "all-reduce-done", 6.0, 6.5),  # exposed
        ],
        xplane.ASYNC_LINE: [
            op("all-reduce-start.2", "all-reduce-start", 4.5, 6.5),
            op("copy-start.9", "copy-start", 0.0, 9.0),       # not a collective
        ],
    }
    got = xplane.exposed_collective_seconds(lines, (0.0, 10.0))
    assert got == pytest.approx({"collective_s": 3.0, "exposed_s": 1.5})
