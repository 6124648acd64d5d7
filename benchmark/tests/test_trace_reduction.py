"""The reduction from a profiler trace to numbers, on small traces recorded
on a TPU v5e by ``tools/record_test_trace.py`` and kept in ``data/``: three
steps of a toy GPT-2 training step (2 layers, width 256, 8 x 256 tokens,
accumulation 2, flash attention) on one chip, and the same step over
``data=2 x tensor=2`` on four."""

import os

import pytest

from benchmark.harness import flops, modules, profile, xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
ONE = os.path.join(DATA, "toy_train_1chip.xplane.pb.gz")
FOUR = os.path.join(DATA, "toy_train_d2t2.xplane.pb.gz")


@pytest.fixture(scope="module")
def one():
    return profile.reduce(ONE)


@pytest.fixture(scope="module")
def four():
    return profile.reduce(FOUR)


def test_planes_lines_and_window(one, four):
    assert sorted(one["trace"].devices) == [0]
    assert sorted(four["trace"].devices) == [0, 1, 2, 3]
    for reduced in (one, four):
        lines = reduced["trace"].devices[0]
        assert {xplane.OPS_LINE, xplane.MODULES_LINE} <= set(lines)
        lo, hi = reduced["window"]
        assert 0 < reduced["busy_s"] < reduced["window_s"] == pytest.approx(hi - lo)
        spans = {e.name for e in reduced["trace"].host}
        assert {"bench/window", "bench/next_batch"} <= spans


def test_busy_is_the_union_of_leaf_ops_and_idle_its_complement(one):
    lines, window = one["trace"].devices[0], one["window"]
    leaves = xplane.leaf_events(lines[xplane.OPS_LINE])
    assert len(leaves) < len(lines[xplane.OPS_LINE])        # whiles left out
    assert not any(xplane.parse_op(e.name)["opcode"] == "while" for e in leaves)
    busy = xplane.total(xplane.clip(xplane.busy_intervals(lines), window))
    idle = xplane.total(xplane.idle_gaps(lines, window))
    assert busy + idle == pytest.approx(one["window_s"], rel=1e-9)
    assert busy == pytest.approx(one["busy_s"], rel=1e-9)
    # Never more than the sum of the ops, never less than the longest one.
    assert max(e.seconds for e in leaves) <= busy <= sum(e.seconds for e in leaves)
    # The three launches of the step lie inside the window and hold
    # nearly all of the busy time.
    steps = [e for e in xplane.module_events(lines, "jit_step")
             if window[0] <= e.start and e.end <= window[1]]
    assert len(steps) == 3
    assert sum(e.seconds for e in steps) == pytest.approx(busy, rel=0.1)


def test_idle_gaps_are_attributed_to_host_spans(one):
    lines, window = one["trace"].devices[0], one["window"]
    gaps = xplane.idle_gaps(lines, window)
    by_span = xplane.attribute_gaps(
        gaps, [e for e in one["trace"].host if e.name != "bench/window"])
    assert sum(by_span.values()) == pytest.approx(xplane.total(gaps), rel=1e-9)
    report = profile.breakdown(one)
    assert 1 <= len(report["device_ops"]) <= 10
    assert 1 <= len(report["idle_gaps"]) <= 10
    assert report["device_ops"] == sorted(
        report["device_ops"], key=lambda kv: -kv[1])


def test_flash_kernel_calls_by_kind(one):
    """2 layers x 2 microbatches x 3 steps: 12 dQ, 12 dK/dV, and 24
    forward calls (each forward runs again when its block is
    rematerialised); rows = 4 sequences x 4 heads, 256 x 64."""
    lines, window = one["trace"].devices[0], one["window"]
    kinds = {}
    for event, op in xplane.kernel_calls(lines, window):
        kind = xplane.flash_kind(op["type"])
        assert kind is not None and event.seconds > 0
        kinds.setdefault(kind, []).append(event.seconds)
    assert {k: len(v) for k, v in kinds.items()} == {
        ("fwd", 16, 256, 64): 24, ("dq", 16, 256, 64): 12,
        ("dkv", 16, 256, 64): 12}
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    for (kind, rows, seq, dim), seconds in kinds.items():
        floor = flops.least_seconds(flops.flash_call_cost(
            kind, rows=rows, seq=seq, head_dim=dim, causal=True), peaks)
        assert floor["bound"] == "memory"          # 256-long tiles
        assert 0 < floor["seconds"] < min(seconds)   # no share over 100%


def test_the_heaviest_program_is_the_step(one):
    ctx = {"profile": one, "cell": None}
    events = modules.launches(ctx, "heaviest")
    assert len(events) == 3 and all(
        e.name.startswith("jit_step(") for e in events)


def test_exposed_collective_time_on_four_chips(four, one):
    window = four["window"]
    per_chip = [xplane.exposed_collective_seconds(lines, window)
                for lines in four["trace"].devices.values()]
    for chip in per_chip:
        assert 0 < chip["exposed_s"] <= chip["collective_s"] < four["window_s"]
    assert xplane.exposed_collective_seconds(
        one["trace"].devices[0], one["window"]) == {
            "collective_s": 0, "exposed_s": 0}
    names = {xplane.parse_op(e.name)["opcode"]
             for e in four["trace"].devices[0][xplane.OPS_LINE]
             if xplane.is_collective(e)}
    assert any(n.startswith("all-reduce") for n in names)
