"""The window-latent configuration (dots3-note-prev's share) and its serving
cell, as far as the CPU can check them: the plain reference against the
program at the tiny fixture, the float8 control, a whole run of the tiny
cell through the three pools, the configuration file against the catalog's
row, the cell's and the traffic's parameters, the byte and the FLOP function
against hand counts, the readers on a made-up trace, the window's controls,
and the trace-module names against an engine that ran."""

import dataclasses
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import (decode_bytes_wlatent, device,
                               prefill_flops_wlatent, program, serve, spec,
                               traffic, weights, xplane)
from benchmark.harness.drivers import DRIVERS
from benchmark.reference import precision
from benchmark.tests.conftest import FIXTURES

CELL = "serve.dots3-note-prev.notes-mixed-saturated"
TINY = "serve.dots3-note-tiny"
DOTS_FIXTURES = os.path.join(FIXTURES, "dots")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = {"num_hidden_layers": (46, 5), "n_routed_experts": (256, 8),
           "vocab_size": (152064, 19008)}
NEW = {"decode_wlatent_roofline_pct.serve",
       "window_latent_share_of_decode_bytes_pct.serve",
       "prefill_wlatent_mfu_pct.serve"}


def _tiny_cell():
    return spec.load_cell(
        TINY, manifest=os.path.join(DOTS_FIXTURES, "BENCHMARK.json"),
        data_dir=DOTS_FIXTURES)


def _tiny_system(seed=5):
    """The program's module in float32, seeded weights, rows longer than the
    window and the selection."""
    from distributed_tensorflow_tpu.models import get_workload

    config = _tiny_cell().config
    cfg = dataclasses.replace(program.program_config(config),
                              dtype=jnp.float32)
    module = get_workload(config["program"]["model"], config=cfg).module
    tokens = jnp.asarray(np.random.default_rng(seed).integers(
        0, config["vocab_size"], (3, 72)), jnp.int32)
    abstract = jax.eval_shape(
        lambda: module.init(jax.random.key(0), tokens))["params"]
    config = dict(config, parameter_dtype="float32")
    return config, module, weights.make_params(seed, abstract), tokens


def test_reference_logits_match_the_program():
    config, module, params, tokens = _tiny_system()
    ref = program.reference_module(config)
    got = module.apply({"params": params}, tokens)
    want = ref.logits(precision.Exact(), config, params, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=5e-5)


def test_the_fp8_control_is_a_different_forward():
    config, _, params, tokens = _tiny_system()
    ref = program.reference_module(config)
    exact = ref.logits(precision.Exact(), config, params, tokens)
    low = ref.logits(precision.Fp8(), config, params, tokens)
    assert float(jnp.abs(exact - low).max()) > 1e-3


def test_the_reference_reads_two_kinds_of_mask_and_holds_the_share():
    config, _, params, tokens = _tiny_system()
    ref = program.reference_module(config)
    masks = []
    ref.logits(precision.Exact(), config, params, tokens, masks)
    t = np.arange(72)
    reads = [np.asarray(m).sum(-1) for m in masks]
    for layer in (0, 1):
        assert (reads[layer] == np.minimum(t + 1, 24)).all()
    for layer in (2, 3, 4):
        assert (reads[layer] == np.minimum(t + 1, 25)).all()
    assert (np.asarray(masks[0]) != np.asarray(masks[1])).any()
    assert (config["n_routed_experts"], config["first_expert_held"],
            config["n_routed_experts_published"]) == (4, 2, 8)


def test_a_whole_run_of_the_tiny_cell_is_correct_and_counts_three_pools():
    cell = _tiny_cell()
    lines = []
    result = DRIVERS["serve"](
        cell, seed=2**31 + 7, seconds=1.5, trace=False,
        devices=jax.devices()[:1],
        peaks=device.load_peaks("cpu", path=os.path.join(FIXTURES, "peaks.json")),
        started=time.perf_counter(),
        say=lambda event, **kw: lines.append({"event": event, **kw}))
    compared = {l["number"]: l for l in lines if l["event"] == "compared"}
    assert result["correct"], compared
    assert result["attempted"] > 4 and result["failed"] == 0
    end = result["context"]["stats_end"]
    assert end["moe_experts_held"] == 4 and end["moe_layer_steps"] > 0
    assert end["window_blocks_recycled"] > 0
    assert (0 < end["decode_live_positions_window"]
            < end["decode_live_positions"])
    assert (0 < end["decode_selected_positions"]
            < end["decode_live_positions"])
    by_name = {m["name"]: m for m in cell.per_layer}
    ctx = dict(result["context"], say=lambda *a, **kw: None)
    read = lambda name: cell.reader(by_name[name])(
        ctx, **by_name[name]["args"])
    assert 0 < read("window_latent_share_of_decode_bytes_pct.serve") < 100
    assert 0 < read("sparse_read_share_pct.serve") < 100
    held = read("window_cache_held_pct.serve")      # None: nothing held
    assert held is None or 0 < held <= 100


# -- the cell's own files ------------------------------------------------------

def test_configuration_holds_every_number_of_the_catalog_row():
    if not os.path.isfile(CATALOG):
        pytest.skip("no catalog of architectures here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "dots3-note-prev")
    with open(os.path.join(spec.REPO_DIR, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "dots3-note-prev")
    assert entry["source"] == row["source_url"]
    assert len(entry["why"]) <= 200
    config = spec.load_cell(CELL).config
    assert config["source"] == row["source_url"]
    assert sorted(entry["reduced"]) == sorted(config["reduced"]) == sorted(
        list(REDUCED) + ["layer_types"])
    for key, value in row["config"].items():
        if key not in entry["reduced"]:
            assert config[key] == value, key
    for key, (published, run) in REDUCED.items():
        assert (row["config"][key], config[key]) == (published, run), key
        assert config[f"{key}_published"] == published, key
        assert f"published {published}" in config["reduced"][key], key
    assert config["layer_types"] == row["config"]["layer_types"][:5]
    assert config["layers_published_run"] == [0, 1, 2, 3, 4]
    # Inside the guide's floors: the dense layer and a whole period, 8
    # experts, an eighth of the vocabulary.
    assert config["n_routed_experts"] >= 8
    assert config["vocab_size"] * 8 >= config["vocab_size_published"]
    for key in ("attention_gate", "mla_qkv_lora_rescale", "window",
                "indexer_layers", "rope_pairing", "softmax_scale",
                "index_norm_eps", "parameter_dtype", "weights"):
        assert key in config["assumed"], key
    assert "v5e-256" in config["stands_for"]
    assert "32 chips" in config["stands_for"]
    assert "float32 copy" in config["reduced"]["num_hidden_layers"]
    assert "1,822M" in config["parameters"]["sum"]
    from distributed_tensorflow_tpu.models.dots3_note import Dots3NoteConfig
    assert program.program_config(config) == Dots3NoteConfig.v5e256_share()


def test_the_byte_functions_parameters_are_the_programs_own_count():
    from distributed_tensorflow_tpu.models import get_workload

    config = spec.load_cell(CELL).config
    module = get_workload(config["program"]["model"],
                          config=program.program_config(config)).module
    abstract = jax.eval_shape(lambda: module.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    count = lambda tree: sum(int(np.prod(l.shape))
                             for l in jax.tree.leaves(tree))
    p = decode_bytes_wlatent.weight_parameters(program.shape_of(config))
    assert (sum(v for k, v in p.items() if k != "one_routed_expert")
            + count(abstract["embed"])) == count(abstract)
    assert count(abstract) == pytest.approx(1822e6, rel=1e-3)


def test_cell_and_traffic_carry_the_parameters_the_issue_names():
    cell = spec.load_cell(CELL)
    assert cell.chips == 1 and len(cell.why) <= 200
    sched = cell.cell["scheduler"]
    assert {k: sched[k] for k in (
        "max_total_len", "cache_mode", "block_size", "prefill_budget",
        "megastep", "async_decode")} == {
        "max_total_len": 8192, "cache_mode": "paged", "block_size": 16,
        "prefill_budget": 1024, "megastep": 4, "async_decode": True}
    assert sched["num_slots"] in (16, 32, 64)
    assert cell.cell["trace_seconds"] == 2
    correct = cell.cell["correct"]
    assert correct["reference_padded_lengths"] == [8192]
    for key in ("limits_why", "reference_why"):
        assert "chip run" in correct[key], key
    mix = cell.traffic
    assert mix["kind"] == "open_loop_requests" and mix["sampling"] == "greedy"
    assert mix["vocab_size"] == 19008 == cell.config["vocab_size"]
    assert mix["arrivals"]["process"] == "poisson"
    assert mix["lead_in_s"] >= 2 * mix["slots_full_after_s"]
    assert mix["shuffle_block"] == 1
    a, b = (traffic.open_loop_requests(mix, seed, 30.0)
            for seed in (2147483659, 3141592653))
    assert [r.due_s for r in a] == [r.due_s for r in b]
    assert [(len(r.prompt), r.max_new_tokens) for r in a] \
        == [(len(r.prompt), r.max_new_tokens) for r in b]
    assert any((x.prompt != y.prompt).any() for x, y in zip(a, b))
    assert mix["prompt_tokens"] == {
        "median": 3072, "sigma": 0.7, "min": 513, "max": 7168,
        "round_up_to": [1024, 2048, 3072, 4096, 5120, 6144, 7168]}
    assert mix["output_tokens"] == {"median": 384, "sigma": 0.5, "min": 128,
                                    "max": 1024}
    assert all(n % sched["prefill_budget"] == 0
               for n in mix["prompt_tokens"]["round_up_to"])
    assert 7168 + 1024 == sched["max_total_len"]
    # The larger of 1.3 x the knee and 1.5 x the capacity, up to a quarter.
    arrivals = mix["arrivals"]
    assert arrivals["over_capacity"] == 1.5
    wanted = max(1.5 * arrivals["capacity_per_s"], 1.3 * arrivals["knee_per_s"])
    assert 0 <= arrivals["rate_per_s"] - wanted < 0.25 + 1e-9
    assert (4 * arrivals["rate_per_s"]) % 1 == 0
    # Every prompt passes the window in its first chunk; some end at or
    # under index_topk and some pass it.
    lengths = [len(r.prompt) for r in a]
    assert min(lengths) > cell.config["sliding_window_size"]
    assert min(lengths) <= cell.config["index_topk"] < max(lengths)
    assert traffic.prompt_lengths(mix) == mix["prompt_tokens"]["round_up_to"]
    assert {m["name"] for m in cell.end_to_end} == {"serve_tokens_per_s",
                                                    "setup_s"}
    # Sets, not tails: a later PR may append to BENCHMARK.json's lists.
    names = {m["name"] for m in cell.per_layer}
    glm = {m["name"] for m in spec.load_cell(
        "serve.glm-5.2.longdoc-saturated").per_layer}
    assert names - glm == NEW | {"window_cache_held_pct.serve"}
    assert glm - names == {"decode_dsa_roofline_pct.serve",
                           "prefill_mfu_pct.serve"}
    with open(os.path.join(spec.REPO_DIR, "BENCHMARK.json")) as f:
        listed = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name in NEW:
        assert listed[name]["workloads"] == [CELL]


def test_slot_arithmetic_quotes_the_engines_cache_geometry():
    from distributed_tensorflow_tpu.models import PagedKVConfig, get_workload

    cell = spec.load_cell(CELL)
    sched = cell.cell["scheduler"]
    slots = sched["num_slots"]
    per_slot = sched["max_total_len"] // sched["block_size"]
    ring = -(-(513 + sched["prefill_budget"] + sched["megastep"])
             // sched["block_size"]) + 1
    assert ring == 98
    workload = get_workload(cell.config["program"]["model"],
                            config=program.program_config(cell.config))
    geometry = workload.cache_geometry(PagedKVConfig(
        block_size=16, num_blocks=slots * per_slot + 1,
        window_blocks=slots * ring + 1, window_ring=ring))
    assert geometry["kind"] == "latent_indexed_window"
    assert (geometry["bytes_per_token"],
            geometry["bytes_per_token_past_window"]) == (9984, 3072)
    assert geometry["window_ring_positions"] == 1568
    per_slot_bytes = 8192 * 3072 + 1568 * 6912
    assert per_slot_bytes == 36003840
    text = cell.cell["num_slots_arithmetic"]
    for quoted in ("3,072 B", "6,912 B", "1,568 positions", f"{slots} slots",
                   f"{geometry['pool_bytes']:,} B"):
        assert quoted in text, quoted


# -- the byte and the FLOP function against hand counts ------------------------

def test_decode_step_bytes_against_a_hand_count():
    shape = program.shape_of(spec.load_cell(CELL).config)
    d = 5120
    full = (d * 1024 + 1024 + 1024 * 128 * 192 + d * 576 + 512
            + 512 * 128 * 256 + 128 * 128 * d + d * 128)
    window = (d * 1024 + 1024 + 1024 * 64 * 256 + d * 1088 + 1024
              + 1024 * 64 * 320 + 64 * 128 * d + d * 64)
    indexer = 1024 * 64 * 128 + d * 128 + 2 * 128 + d * 64
    expert = 3 * d * 1536
    shared = (2 * (full + 2 * d + indexer) + 3 * (window + 2 * d)
              + 3 * d * 13824 + 4 * (d * 256 + 256 + expert)
              + d * 19008 + d)
    cost = decode_bytes_wlatent.decode_step_bytes(
        shape, active_experts_per_layer=3.5, live_positions=64000.0,
        selected_positions=30000.0, window_positions=8208.0)
    assert cost["shared_weights"] == 2 * shared
    assert cost["routed_experts"] == 2 * 4 * 3.5 * expert
    assert cost["selected_latent"] == 2 * 2 * 576 * 30000.0
    assert cost["index_keys"] == 2 * 2 * 128 * 64000.0
    assert cost["window_latent"] == 2 * 3 * 1088 * 8208.0
    assert cost["total"] == sum(v for k, v in cost.items() if k != "total")
    # The issue's reckoning: 1.94 GB of weights every token uses.
    assert cost["shared_weights"] == pytest.approx(1.94e9, rel=0.01)
    with pytest.raises(ValueError, match="active experts"):
        decode_bytes_wlatent.decode_step_bytes(
            shape, active_experts_per_layer=9, live_positions=9,
            selected_positions=1, window_positions=1)
    with pytest.raises(ValueError, match="window positions"):
        decode_bytes_wlatent.decode_step_bytes(
            shape, active_experts_per_layer=1, live_positions=9,
            selected_positions=1, window_positions=10)


def test_prefill_chunk_flops_against_a_hand_count():
    shape = program.shape_of(spec.load_cell(CELL).config)
    d, t, off = 5120, 1024, 1024
    got = prefill_flops_wlatent.prefill_chunk_flops(
        shape, offset=off, tokens=t, assignments_here_share=0.03125)
    expert = 3 * d * 1536
    selected = sum(min(off + i + 1, 2048) for i in range(t))
    seen = sum(off + i + 1 for i in range(t))
    assert got["projections_full"] == 2 * 2 * t * (
        d * 1024 + 1024 * 128 * 192 + d * 576 + 512 * 128 * 256 + d * 128
        + 128 * 128 * d)
    assert got["projections_window"] == 2 * 3 * t * (
        d * 1024 + 1024 * 64 * 256 + d * 1088 + 1024 * 64 * 320 + d * 64
        + 64 * 128 * d)
    assert got["attention_full"] == 2 * 2 * selected * 128 * 320
    assert got["attention_window"] == 2 * 3 * t * 513 * 64 * 384
    assert got["indexer_projections"] == 2 * 2 * t * (
        1024 * 64 * 128 + d * 128 + d * 64)
    assert got["index_scores"] == 2 * 2 * seen * 64 * 128
    assert got["dense_mlp"] == 2 * t * 3 * d * 13824
    assert got["router"] == 2 * 4 * t * d * 256
    assert got["shared_experts"] == 2 * 4 * t * expert
    assert got["routed_experts"] == 2 * 4 * t * 8 * 0.03125 * expert
    assert got["head"] == 2 * d * 19008
    assert got["total"] == sum(v for k, v in got.items() if k != "total")
    late = prefill_flops_wlatent.prefill_chunk_flops(
        shape, offset=6144, tokens=t, assignments_here_share=0.03125)
    assert late["attention_full"] == 2 * 2 * t * 2048 * 128 * 320
    assert late["attention_window"] == got["attention_window"]
    assert late["index_scores"] > got["index_scores"]
    with pytest.raises(ValueError, match="a chunk of"):
        prefill_flops_wlatent.prefill_chunk_flops(
            shape, offset=0, tokens=0, assignments_here_share=0.5)


# -- the readers on a made-up trace --------------------------------------------

def _context(cell, stats_start, stats_end, decode=(), prefill=()):
    lines = {xplane.MODULES_LINE: [
        xplane.Event(f"jit_decode_megastep({i})", a, b)
        for i, (a, b) in enumerate(decode)] + [
        xplane.Event(f"jit_prefill_slots({i})", a, b)
        for i, (a, b) in enumerate(prefill)]}
    said = []
    return {"cell": cell,
            "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
            "stats_start": stats_start, "stats_end": stats_end,
            "profile": {"trace": xplane.Trace({0: lines}, []),
                        "window": (0.0, 10.0)},
            "say": lambda event, **kw: said.append((event, kw))}, said


STATS = {"moe_active_experts_per_step": 4.0, "moe_layer_steps": 300.0,
         "decode_live_positions": 70000.0,
         "decode_selected_positions": 30000.0,
         "decode_live_positions_window": 8208.0, "iterations": 30.0,
         "moe_assignments_here": 250.0, "moe_assignments_absent": 7750.0}


def _readers(cell):
    by_name = {m["name"]: m for m in cell.per_layer}
    reader = lambda name: (lambda ctx: cell.reader(by_name[name])(
        ctx, **by_name[name]["args"]))
    return (reader("decode_wlatent_roofline_pct.serve"),
            reader("window_latent_share_of_decode_bytes_pct.serve"),
            reader("prefill_wlatent_mfu_pct.serve"))


def test_decode_roofline_reader_divides_the_floor_by_the_step():
    cell = spec.load_cell(CELL)
    read, read_share, _ = _readers(cell)
    start = dict(STATS, moe_active_experts_per_step=3.0, moe_layer_steps=100.0,
                 decode_live_positions=60000.0,
                 decode_selected_positions=28000.0, iterations=10.0)
    launches = [(1.0, 1.04), (2.0, 2.04), (3.0, 3.06)]   # median 40 ms, 4 steps
    ctx, said = _context(cell, start, STATS, decode=launches)
    value = read(ctx)
    cost = decode_bytes_wlatent.decode_step_bytes(
        program.shape_of(cell.config), active_experts_per_layer=4.5,
        live_positions=75000.0, selected_positions=31000.0,
        window_positions=8208.0)
    assert value == pytest.approx(100 * cost["total"] / 819e9 / 0.010)
    assert 0 < value < 100
    (event, fields), = said
    assert event == "decode_floor"
    assert fields["bytes_module"] == "decode_bytes_wlatent"
    assert fields["window_positions"] == pytest.approx(8208.0)
    share = read_share(ctx)
    assert share == pytest.approx(100 * cost["window_latent"] / cost["total"])
    assert 0 < share < 10


def test_prefill_mfu_reader_counts_the_chunks_the_spans_name():
    cell = spec.load_cell(CELL)
    _, _, read = _readers(cell)
    launches = [(1.0, 1.08), (2.0, 2.10), (3.0, 3.09)]
    ctx, said = _context(cell, STATS, STATS, prefill=launches)
    chunk = lambda off: (xplane.Event("dtt/serve/prefill_chunk", 1.0, 1.01),
                         {"offset": off, "chunk_tokens": 1024})
    ctx["program_spans"] = {"ended": {
        "dtt/serve/prefill_chunk": [chunk(0), chunk(5120)]}}
    value = read(ctx)
    shape = program.shape_of(cell.config)
    mean = sum(prefill_flops_wlatent.prefill_chunk_flops(
        shape, offset=off, tokens=1024, assignments_here_share=0.03125)["total"]
        for off in (0, 5120)) / 2
    assert value == pytest.approx(100 * 3 * mean / 197e12 / 0.27)
    assert 0 < value < 100
    (event, fields), = said
    assert event == "prefill_wlatent_mfu"
    assert (fields["launches"], fields["chunk_spans"]) == (3, 2)
    assert fields["assignments_here_share"] == pytest.approx(0.03125)


@pytest.mark.parametrize("case", ["another_family", "no_launch", "no_spans"])
def test_the_new_readers_read_nothing_where_there_is_nothing(case):
    cell = spec.load_cell(CELL)
    decode, share, prefill = _readers(cell)
    start, end = dict(STATS, moe_layer_steps=100.0, iterations=10.0), STATS
    launches = [(1.0, 1.04)]
    spans = {"ended": {"dtt/serve/prefill_chunk": [(
        xplane.Event("dtt/serve/prefill_chunk", 1.0, 1.01),
        {"offset": 0, "chunk_tokens": 1024})]}}
    if case == "another_family":        # no ring: no window counter
        drop = lambda s: {k: v for k, v in s.items()
                          if k != "decode_live_positions_window"}
        start, end = drop(start), drop(end)
    elif case == "no_launch":
        launches = []
    else:
        spans = None
    ctx, _ = _context(cell, start, end, decode=launches, prefill=launches)
    ctx["program_spans"] = spans
    if case != "no_spans":
        assert decode(ctx) is None
    if case == "another_family":
        assert share(ctx) is None
    assert prefill(ctx) is None


# -- the controls --------------------------------------------------------------

@pytest.mark.parametrize("mechanism", ["half_window", "without_gate"])
def test_the_window_control_serves_another_model(monkeypatch, tmp_path,
                                                 mechanism):
    """``tools/window_control.py`` on the tiny cell: the program whose
    window layers read half their window, or whose gates are left out, is
    held to the reference and reads far over what the sound program
    reads."""
    from benchmark.tools import limits, window_control
    from distributed_tensorflow_tpu.models import dots3_note

    cell = _tiny_cell()
    sound = limits.serve_seed(cell, 21, jax.devices()[:1], False, 1.0)
    for name in ("attention_mask", "mla_output"):
        monkeypatch.setattr(dots3_note, name, getattr(dots3_note, name))
    monkeypatch.setattr(spec, "load_cell", lambda name: cell)
    monkeypatch.setattr(device, "require_chips",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.chdir(tmp_path)
    window_control.main(["--workload", TINY, "--seeds", "21", "--seconds",
                         "1", "--mechanism", mechanism])
    with open(tmp_path / "chiprun_out" / "limits"
              / f"{TINY}.window_control.jsonl") as f:
        (row,) = [json.loads(line) for line in f]
    assert row["seed"] == 21 and row["served_tokens"] > 0
    wrong = row[mechanism]["served_logit_gap_max"]
    assert wrong > 10 * sound["sound"]["served_logit_gap_max"]
    assert wrong > cell.cell["correct"]["limits"]["served_logit_gap_max"]


# -- the names the trace is read by --------------------------------------------

def test_trace_module_names_are_the_names_an_engine_that_ran_gives():
    from distributed_tensorflow_tpu.obs.exporters import render_prometheus
    from distributed_tensorflow_tpu.obs.trace import default_tracer

    cell = _tiny_cell()
    tracer = default_tracer()
    was = tracer.enabled
    tracer.enable()
    try:
        engine, sched, _ = serve.build(cell, 3, jax.devices()[:1])
        try:
            serve.warm_up(cell, sched, 3)
        finally:
            sched.close()
        chunks = [args for *_, args in tracer.spans(
            name="dtt/serve/prefill_chunk")]
    finally:
        tracer.enabled = was
    names = {getattr(fn, "__name__", "") or getattr(
        getattr(fn, "__wrapped__", None), "__name__", "")
        for fn in engine._generate_fns.values()}
    for kind, rule in spec.load_cell(CELL).cell["trace_modules"].items():
        assert rule["prefix"][len("jit_"):-1] in names, (kind, names)
    paths = engine.attention_paths()
    assert set(paths["slot_prefill"]) == {"latent_sparse_masked",
                                          "latent_window_chunk"}
    assert set(paths["slot_megastep"]) == {"latent_sparse_selected",
                                           "latent_window_step"}
    assert chunks and all(
        {"offset", "chunk_tokens", "context_tokens"} <= set(c) for c in chunks)
    exported = render_prometheus()
    assert 'dtt_serve_kv_blocks_held{kind="window"}' in exported
    assert 'dtt_serve_kv_blocks_held{kind="index"}' in exported
    assert "dtt_serve_window_blocks_recycled_total" in exported
