"""The rest of a run without the look for a chip, at the tiny fixtures on
the CPU: a sound system comes out correct; a timed path broken underneath
comes out not correct; so does the float8 control."""

import os
import time

import jax
import pytest

from benchmark.harness import device, spec
from benchmark.harness.drivers import DRIVERS
from benchmark.tests.conftest import FIXTURES


def _cell(name):
    return spec.load_cell(
        name, manifest=os.path.join(FIXTURES, "BENCHMARK.json"),
        data_dir=FIXTURES)


def _drive(name, seed=2**31 + 7, seconds=1.5, metrics_every=None):
    cell = _cell(name)
    if metrics_every is not None:
        cell.cell["trainer"]["metrics_every"] = metrics_every
    lines = []
    result = DRIVERS[cell.cell["kind"]](
        cell, seed=seed, seconds=seconds, trace=False,
        devices=jax.devices()[:1],
        peaks=device.load_peaks("cpu", path=os.path.join(FIXTURES, "peaks.json")),
        started=time.perf_counter(),
        say=lambda event, **kw: lines.append({"event": event, **kw}))
    return result, lines


def _compared(lines):
    return {l["number"]: l for l in lines if l["event"] == "compared"}


@pytest.mark.parametrize("every", [None, 1, 4])
@pytest.mark.parametrize("name", ["train.gpt2-tiny"])
def test_a_sound_training_run_is_correct(name, every):
    """Whatever the depth of the dispatch queue (``metrics_every``), every
    step the window dispatched is counted and one loss in so many is read."""
    result, lines = _drive(name, metrics_every=every)
    assert result["correct"], _compared(lines)
    assert result["attempted"] > 3 and result["failed"] == 0
    window = next(l for l in lines if l["event"] == "window")
    every = every or 1
    assert window["metrics_every"] == every
    assert window["steps"] == result["attempted"]
    assert window["steps"] // every - 1 <= window["losses_read"] \
        <= window["steps"] // every + 1
    assert len(window["step_s_between_fetches"]) == window["losses_read"]
    assert window["host_stall_s_longest"] >= 0
    assert 0 <= window["host_stall_ended_at_s"] <= window["window_s"] + 0.1
    assert result["end_to_end"]["train_tokens_per_s_per_chip"] > 0
    assert result["end_to_end"]["setup_s"] > 0
    numbers = _compared(lines)
    assert set(numbers) == {"loss_gap_max", "first_grad_global_norm_gap",
                            "first_grad_norm_gap_worst_leaf",
                            "param_change_norm_gap_worst_leaf"}
    assert all("limit" in row and "value" in row for row in numbers.values())


def test_a_step_that_leaves_its_state_unchanged_is_not_correct(monkeypatch):
    from distributed_tensorflow_tpu.training.train_state import TrainState

    monkeypatch.setattr(
        TrainState, "apply_gradients",
        lambda self, grads, new_model_state=None:
            self.replace(step=self.step + 1))
    result, lines = _drive("train.gpt2-tiny")
    assert not result["correct"]
    numbers = _compared(lines)
    assert numbers["loss_gap_max"]["ok"]            # the forward is sound
    assert not numbers["first_grad_norm_gap_worst_leaf"]["ok"]
    assert numbers["param_change_norm_gap_worst_leaf"]["value"] == pytest.approx(1.0, abs=1e-3)


def test_a_batch_with_part_left_out_is_not_correct(monkeypatch):
    """Half of every microbatch dropped before the loss: the loss and the
    gradient are those of other data."""
    import importlib

    from benchmark.harness import train

    real = importlib.import_module(
        "distributed_tensorflow_tpu.models").get_workload

    def halved(*args, **kwargs):
        workload = real(*args, **kwargs)
        loss_fn = workload.loss_fn
        workload.loss_fn = lambda p, b, rng: loss_fn(
            p, jax.tree.map(lambda x: x[: x.shape[0] // 2], b), rng)
        return workload

    monkeypatch.setattr(
        "distributed_tensorflow_tpu.models.get_workload", halved)
    result, lines = _drive("train.gpt2-tiny")
    assert not result["correct"], _compared(lines)


def test_the_float8_control_fails_the_training_limits():
    from benchmark.harness import train

    cell = _cell("train.gpt2-tiny")
    devices = jax.devices()[:1]
    from benchmark.harness import program
    from distributed_tensorflow_tpu.models import get_workload

    workload = get_workload(
        cell.config["program"]["model"],
        config=program.program_config(cell.config),
        batch_size=cell.traffic["batch_size"], seq_len=cell.traffic["seq_len"])
    abstract = jax.eval_shape(lambda: workload.module.init(
        jax.random.key(0), workload.init_batch["tokens"]))["params"]
    for seed in (3, 4, 5):
        exact = train.reference_side(cell, seed, abstract, devices)
        control = train.reference_side(cell, seed, abstract, devices, "fp8")
        verdict = train.compare(control, exact, cell.cell["correct"]["limits"])
        assert not verdict["correct"], verdict


def test_a_sound_serving_run_is_correct():
    result, lines = _drive("serve.gpt2-tiny", seconds=2.0)
    assert result["correct"], _compared(lines)
    window = next(l for l in lines if l["event"] == "window")
    assert window["offered"] == 20 and window["lead_in_requests"] >= 1
    # Where in the window the tokens fell, and what stopped the host.
    assert sum(window["tokens_by_second"]) == window["tokens_in_window"]
    assert window["gc_pause_s_longest"] >= 0 <= window["host_stall_s_longest"]
    assert isinstance(window["xla_compiles_after_warmup"], list)
    assert result["attempted"] == 20 - window["withdrawn_at_close"]
    assert result["failed"] == 0
    for name in ("serve_tokens_per_s", "setup_s"):
        assert result["end_to_end"][name] > 0
    # Every finished request is held against the reference, not a sample.
    compared = _compared(lines)["served_logit_gap_max"]
    assert compared["requests"] == result["attempted"]
    assert compared["served_tokens"] > 10 * compared["requests"] / 4


def test_requests_without_a_token_at_the_close_are_withdrawn_not_failed():
    """Far above capacity the queue is long at the close: what the client
    withdraws is neither attempted nor failed, and the rest is correct."""
    cell = _cell("serve.gpt2-tiny")
    cell.traffic["arrivals"]["rate_per_s"] = 1000
    lines = []
    result = DRIVERS["serve"](
        cell, seed=5, seconds=1.0, trace=False, devices=jax.devices()[:1],
        peaks=None, started=time.perf_counter(),
        say=lambda event, **kw: lines.append({"event": event, **kw}))
    window = next(l for l in lines if l["event"] == "window")
    assert window["withdrawn_at_close"] > 0
    assert result["attempted"] == window["offered"] - window["withdrawn_at_close"]
    assert result["correct"] and result["failed"] == 0, _compared(lines)


def test_a_token_altered_where_it_is_produced_is_not_correct(monkeypatch):
    from distributed_tensorflow_tpu.serve import engine

    real = engine._select_next
    monkeypatch.setattr(
        engine, "_select_next",
        lambda *a, **kw: (real(*a, **kw) + 1) % 256)
    result, lines = _drive("serve.gpt2-tiny", seconds=2.0)
    assert not result["correct"]
    assert not _compared(lines)["served_logit_gap_max"]["ok"]


def test_the_float8_control_fails_the_serving_limit():
    """At every position of served prompts and tokens, the token float8
    puts first lies further below the reference's best than the limit."""
    from benchmark.harness import serve, traffic
    from benchmark.harness.spans import Spans

    cell = _cell("serve.gpt2-tiny")
    limit = cell.cell["correct"]["limits"]["served_logit_gap_max"]
    devices = jax.devices()[:1]
    worst = lambda gaps: max(float(g.max()) for g in gaps)
    for seed in (11, 12, 13):
        engine, sched, abstract = serve.build(cell, seed, devices)
        requests = traffic.open_loop_requests(cell.traffic, seed, 3.0)
        served = serve.offer(requests, sched, Spans(),
                             time.monotonic() + cell.traffic["lead_in_s"])
        serve.drain(served, Spans(), time.monotonic() + 60.0)
        sched.close()
        prompts = [r.request.prompt for r in served]
        tokens = [r.tokens for r in served]
        sound = worst(serve.reference_gaps(cell, seed, abstract, prompts, tokens))
        low = serve.reference_gaps(cell, seed, abstract, prompts, tokens,
                                   "fp8", pick_own=True)
        control = worst(serve.reference_gaps(cell, seed, abstract, prompts, low))
        assert sound <= limit < control, (seed, sound, limit, control)


def test_the_result_line_ends_with_each_number_compared_beside_its_limit(
        monkeypatch, capsys):
    """``run.py`` itself, past its look for a chip: the numbers compared
    come last in the result line and are the last lines of stderr."""
    import importlib.util
    import json

    path = os.path.join(spec.BENCH_DIR, "run.py")
    module_spec = importlib.util.spec_from_file_location("bench_run", path)
    run = importlib.util.module_from_spec(module_spec)
    module_spec.loader.exec_module(run)
    monkeypatch.setattr(device, "require_chips",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(device, "load_peaks", lambda kind: {})
    monkeypatch.setattr(device, "place_compile_cache", lambda: "off")
    cell = _cell("serve.gpt2-tiny")
    monkeypatch.setattr(spec, "load_cell", lambda name: cell)
    assert run.main(["--workload", "serve.gpt2-tiny", "--seed", "7",
                     "--seconds", "1.5"]) == 0
    out, err = capsys.readouterr()
    line = json.loads(out.splitlines()[-1])
    assert list(line)[-1] == "compared" and line["correct"]
    assert set(line["compared"]) == {
        "served_logit_gap_max", "compile_post_warmup",
        "requests_not_answered_in_full"}
    limit = cell.cell["correct"]["limits"]
    gap = line["compared"]["served_logit_gap_max"]
    assert gap["limit"] == limit["served_logit_gap_max"] >= gap["value"] >= 0
    last = err.splitlines()[-len(line["compared"]):]
    assert [l.split()[:2] for l in last] == [
        ["compared", number] for number in line["compared"]]
    assert all(l.split()[3] == "limit" for l in last)
