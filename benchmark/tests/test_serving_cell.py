"""The serving cells' files against one another and against the program:
what can drift apart without a chip to notice it.  The slot count sits in
the cell's file, the rule that finds the decode launch in the trace beside
it, the lead-in and the reordering of arrivals in the traffic file, and
prose about all of them in three places."""

import json
import os
import re
import time

import jax
import numpy as np
import pytest

from benchmark.harness import modules, serve, spec, traffic, xplane
from benchmark.tests.conftest import FIXTURES


def _serving_cells():
    with open(os.path.join(spec.REPO_DIR, "BENCHMARK.json")) as f:
        names = [w["name"] for w in json.load(f)["workloads"]]
    cells = [spec.load_cell(name) for name in names]
    return [c for c in cells if c.cell["kind"] == "serve"]


SERVING = _serving_cells()
IDS = [c.name for c in SERVING]


def _tiny(num_slots):
    cell = spec.load_cell(
        "serve.gpt2-tiny", manifest=os.path.join(FIXTURES, "BENCHMARK.json"),
        data_dir=FIXTURES)
    cell.cell["scheduler"]["num_slots"] = num_slots
    return cell


@pytest.mark.parametrize("cell", SERVING, ids=IDS)
def test_the_decode_rule_finds_the_decode_launch_at_the_cells_slot_count(cell):
    """A made-up trace that holds what the program would put there at this
    cell's own slot count: launches under the names the engine gives its
    programs (read off an engine that ran, at a tiny width), each with an
    instruction on its activations, (slots, 1, width) in a decode step and
    (1, prompt, width) in a prefill.  The cell's ``trace_modules`` rules,
    by name or by content, have to tell the two apart."""
    slots = int(cell.cell["scheduler"]["num_slots"])
    steps = int(cell.cell["scheduler"]["megastep"])
    width = int(cell.config["n_embd"])
    tiny = _tiny(slots)
    engine, sched, _ = serve.build(tiny, 3, jax.devices()[:1])
    try:
        serve.warm_up(tiny, sched, 3)
    finally:
        sched.close()
    # The engine keeps its jitted programs by kind; the name of each is
    # what the trace's XLA Modules line shows after "jit_".
    names = {key[0]: "jit_" + fn.__name__
             for key, fn in engine._generate_fns.items()
             if isinstance(key, tuple) and hasattr(fn, "__name__")}
    decode_name = names["slot_megastep"]
    prefill_name = next(n for k, n in names.items() if "prefill" in k)

    op = lambda shape, a, b: xplane.Event(
        f"%fusion.7 = bf16[{shape}]{{2,1,0}} fusion(%p)", a, b)
    lines = {
        xplane.MODULES_LINE: [
            xplane.Event(f"{prefill_name}(11)", 1.0, 1.1),
            xplane.Event(f"{decode_name}(7)", 1.1, 1.5),
            xplane.Event(f"{prefill_name}(12)", 1.5, 1.7),
            xplane.Event(f"{decode_name}(7)", 1.7, 2.1),
        ],
        xplane.OPS_LINE: [
            op(f"1,128,{width}", 1.0, 1.1),
            op(f"{slots},1,{width}", 1.1, 1.5),
            op(f"1,768,{width}", 1.5, 1.7),
            op(f"{slots},1,{width}", 1.7, 2.1),
        ],
    }
    ctx = {"cell": cell, "profile": {
        "trace": xplane.Trace({0: lines}, []), "window": (0.5, 2.5)}}
    decode = modules.launches(ctx, "decode")
    prefill = modules.launches(ctx, "prefill")
    assert [e.start for e in decode] == [1.1, 1.7]
    assert [e.start for e in prefill] == [1.0, 1.5]
    # One launch fuses ``megastep`` token steps: the decode metric divides
    # by the scheduler's own number.
    reader = cell.reader({"name": "decode", "reader": "module_device_ms"})
    assert reader(ctx, module="decode", per="megastep") == pytest.approx(
        1e3 * 0.4 / steps)


@pytest.mark.parametrize("cell", SERVING, ids=IDS)
def test_slots_lead_in_and_reordering_agree_with_their_why_texts(cell):
    """The numbers, and the sentences that explain them, in the cell's
    file, the traffic file and BENCHMARK.json."""
    mix, sched = cell.traffic, cell.cell["scheduler"]
    slots = int(sched["num_slots"])
    lead_in = float(mix["lead_in_s"])
    rate = float(mix["arrivals"]["rate_per_s"])
    knee = float(mix["arrivals"]["knee_per_s"])
    fill = float(mix["slots_full_after_s"])

    # "one request a slot": the seed reorders arrivals within a run of as
    # many as there are slots.
    assert mix["shuffle_block"] == slots
    assert f"run of {slots} " in mix["shuffle_block_why"]
    # 1.3 x the swept knee, rounded to a quarter.
    assert rate == pytest.approx(round(1.3 * knee * 4) / 4)
    assert f"{knee:g} requests/s" in mix["arrivals"]["knee"]
    # The lead-in is at least twice what the slots took to fill in the
    # sweep, and by then, for any seed, more requests have arrived than
    # there are slots: every slot live, the queue not empty.
    assert lead_in >= 2 * fill
    assert f"{lead_in:g} s" in mix["lead_in_why"]
    assert f"{slots} slots" in mix["lead_in_why"]
    assert f"{fill:g} s" in mix["lead_in_why"]
    for seed in (1, 2**31 + 9, 77):
        requests = traffic.open_loop_requests(mix, seed, 30.0)
        assert sum(r.due_s < 0 for r in requests) >= 1.5 * slots
    # The cell's why and its arithmetic speak of this slot count.
    assert f"{slots} slots" in cell.why
    assert f"{lead_in:g} s lead-in" in cell.why
    assert f"{slots} slots" in cell.cell["num_slots_arithmetic"]
    block, total = int(sched["block_size"]), int(sched["max_total_len"])
    assert f"{slots} x {total // block} + 1 blocks" in \
        cell.cell["num_slots_arithmetic"]
    # The sweep the knee was read from is committed, at this slot count.
    record = re.search(r"benchmark/records/[\w.\-]+\.json",
                       mix["arrivals"]["knee"]).group(0)
    with open(os.path.join(spec.REPO_DIR, record)) as f:
        sweep = json.load(f)
    assert sweep["scheduler"]["num_slots"] == slots
    points = {p["rate_per_s"]: p for p in sweep["points"]}
    assert points[knee]["backlog_at_window_end"] < slots
    above = min(r for r in points if r > knee)
    assert above - knee <= 1.0
    assert points[above]["backlog_at_window_end"] >= slots


def test_rows_padded_to_their_own_length_read_the_same_gaps():
    """The reference over rows padded to the shortest stated length that
    holds them compares every request and reads what it reads over rows
    padded to the slot length."""
    cell = _tiny(4)
    seed = 21
    engine, sched, abstract = serve.build(cell, seed, jax.devices()[:1])
    requests = traffic.open_loop_requests(cell.traffic, seed, 2.0)
    from benchmark.harness.spans import Spans
    served = serve.offer(requests, sched, Spans(),
                         time.monotonic() + cell.traffic["lead_in_s"])
    serve.drain(served, Spans(), time.monotonic() + 60.0)
    sched.close()
    prompts = [r.request.prompt for r in served]
    tokens = [r.tokens for r in served]
    whole = serve.reference_gaps(cell, seed, abstract, prompts, tokens)
    cell.cell["correct"]["reference_padded_lengths"] = [32, 64, 128]
    padded = serve.reference_gaps(cell, seed, abstract, prompts, tokens)
    needed = [len(p) + len(t) - 1 for p, t in zip(prompts, tokens)]
    assert min(needed) <= 32 and max(needed) > 64    # all three lengths used
    assert len(padded) == len(whole) == len(prompts)
    for a, b, t in zip(whole, padded, tokens):
        assert a.shape == b.shape == t.shape
        np.testing.assert_allclose(a, b, atol=2e-5)
    cell.cell["correct"]["reference_padded_lengths"] = [32, 64]
    with pytest.raises(ValueError, match="slot length"):
        serve.reference_gaps(cell, seed, abstract, prompts, tokens)
