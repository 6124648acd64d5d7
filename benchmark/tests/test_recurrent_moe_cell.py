"""The linear-attention configuration (Solar-Open2-250B's share) and its
serving cell, as far as the CPU can check them: the plain reference against
the program at the tiny fixture, the float8 control, a whole run of the tiny
cell through the pool and the state, the configuration file against the
catalog's row, the cell's and the traffic's parameters, the byte and the
FLOP function against hand counts, the new readers on a made-up trace, and
the trace-module names against an engine that ran."""

import dataclasses
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import (decode_bytes_kda, device, prefill_flops_kda,
                               program, program_spans, serve, spec, traffic,
                               weights, xplane)
from benchmark.harness.drivers import DRIVERS
from benchmark.reference import precision
from benchmark.tests.conftest import FIXTURES

CELL = "serve.solar-open2-250b.report-saturated"
SOLAR_FIXTURES = os.path.join(FIXTURES, "solar")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"

# Solar-Open2-250B's config.json, the widths and what else the cut leaves
# alone.
PUBLISHED = {
    "hidden_size": 4096, "intermediate_size": 10240,
    "moe_intermediate_size": 1280, "num_attention_heads": 64,
    "num_key_value_heads": 8, "head_dim": 128,
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 128,
                           "num_heads": 64, "num_kv_heads": None},
    "n_shared_experts": 1, "num_experts_per_tok": 8,
    "routed_scaling_factor": 1, "rms_norm_eps": 1e-5,
    "max_position_embeddings": 1048576, "norm_topk_prob": True,
    "tie_word_embeddings": False, "model_type": "solar_open2",
    "use_rope": False, "use_gqa_gate": True, "kda_use_full_proj": False,
    "kda_allow_neg_eigval": True, "first_k_dense_replace": 0,
    "gqa_interval": 3, "rope_theta": 10000, "partial_rotary_factor": 1,
}
REDUCED = {"num_hidden_layers": (48, 4), "n_routed_experts": (320, 20),
           "vocab_size": (196608, 24576)}


def _tiny_cell():
    return spec.load_cell(
        "serve.solar-open2-tiny",
        manifest=os.path.join(SOLAR_FIXTURES, "BENCHMARK.json"),
        data_dir=SOLAR_FIXTURES)


def _tiny_system(seed=5):
    """The program's module in float32, seeded weights, a batch of rows
    longer than a chunk of the rule."""
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
    # The linear layers are in the logits: at the harness's own draw the
    # heads' outputs stand over the head norm's eps (the convolution's taps
    # are drawn near 1; the configuration's ``assumed.weights``).
    silenced = jax.tree_util.tree_map_with_path(
        lambda path, leaf: leaf * 0 if "kda" in str(path[0]) else leaf,
        params)
    without = module.apply({"params": silenced}, tokens)
    assert float(jnp.abs(got - without).mean()) > 0.1 * float(
        jnp.abs(got).mean())


def test_the_fp8_control_is_a_different_forward():
    config, _, params, tokens = _tiny_system()
    ref = program.reference_module(config)
    exact = ref.logits(precision.Exact(), config, params, tokens)
    low = ref.logits(precision.Fp8(), config, params, tokens)
    assert float(jnp.abs(exact - low).max()) > 1e-3


def test_the_reference_scans_the_rule_and_holds_the_share():
    """No chunk-wise form in the reference: its rule is one ``scan`` over
    the positions.  And the share: experts 2-5 of 8, the others' part left
    out."""
    config, _, params, tokens = _tiny_system()
    ref = program.reference_module(config)
    text = str(jax.make_jaxpr(lambda p, t: ref.logits(
        precision.Exact(), config, p, t))(params, tokens))
    assert "triangular_solve" not in text and "scan" in text
    assert (config["n_routed_experts"], config["first_expert_held"],
            config["n_routed_experts_published"]) == (4, 2, 8)
    layer = jax.tree.map(lambda w: w[1], params["layers"])
    x = jnp.asarray(np.random.default_rng(1).normal(size=(40, 64)),
                    jnp.float32)
    dot = precision.Exact()
    weights_all = ref._route(dot, config, x, layer["router"])
    assert weights_all.shape == (40, 8)
    got = ref.expert_ffn(dot, config, x, layer)
    routed = sum(weights_all[:, 2 + e, None] * ref._mlp(
        dot, x, jax.tree.map(lambda w: w[e], layer["experts"]))
        for e in range(4))
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(routed + ref._mlp(dot, x, layer["shared"])),
        atol=1e-5)
    assert 0 < (weights_all[:, 2:6] > 0).sum() < (weights_all > 0).sum()


def test_a_whole_run_of_the_tiny_cell_is_correct_and_counts_its_state():
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
    checked = compared["served_logit_gap_max"]
    assert 0 < checked["requests"] < checked["requests_answered_in_full"]
    end = result["context"]["stats_end"]
    assert end["moe_experts_held"] == 4 and end["moe_layer_steps"] > 0
    assert end["moe_assignments_here"] > 0 and end["moe_assignments_absent"] > 0
    assert end["state_bytes_per_slot"] == 3 * (4 * 16 * 16 * 4 + 3 * 192 * 2)
    assert 0 < end["state_slots_live"] <= 4
    assert end["state_resets"] == end["admitted"] > 4
    share = _floor_readers(cell)[1](result["context"])
    assert 0 < share < 100
    assert end["prefill_chunks"] > result["attempted"]     # chunks of 16


# -- the cell's own files ------------------------------------------------------

def test_configuration_keeps_every_published_width_and_states_its_cuts():
    cell = spec.load_cell(CELL)
    config = cell.config
    for key, value in PUBLISHED.items():
        assert config[key] == value, key
    assert sorted(config["reduced"]) == sorted(list(REDUCED) + ["gqa_layers"])
    for key, (published, run) in REDUCED.items():
        assert config[key] == run, key
        assert config[f"{key}_published"] == published, key
        assert f"published {published}" in config["reduced"][key], key
    assert config["gqa_layers"] == [0]
    assert config["gqa_layers_published"] == list(range(0, 48, 4))
    assert config["layers_published_run"] == [0, 1, 2, 3]
    # Inside the guide's floors: a whole period (four layers, none of them a
    # leading dense one), 8 experts, an eighth of the vocabulary.
    assert config["num_hidden_layers"] >= 4
    assert config["n_routed_experts"] >= 8
    assert config["vocab_size"] * 8 >= config["vocab_size_published"]
    for key in ("gqa_gate", "kda_gate_rank", "kda_form", "router",
                "precision_of_the_state", "parameter_dtype", "weights"):
        assert key in config["assumed"], key
    assert "v5e-128" in config["stands_for"]
    assert "16 chips" in config["stands_for"]
    assert "float32 copy" in config["reduced"]["num_hidden_layers"]
    cfg = program.program_config(config)
    assert (cfg.n_routed_experts, cfg.held, cfg.first_expert) == (320, 20, 0)
    assert (cfg.n_gqa_layers, cfg.n_kda_layers, cfg.period) == (1, 3, 4)
    assert (cfg.kda_num_heads, cfg.kda_head_dim, cfg.kda_conv_size,
            cfg.kda_gate_rank) == (64, 128, 4, 128)
    from distributed_tensorflow_tpu.models.solar_open2 import SolarOpen2Config
    assert cfg == SolarOpen2Config.v5e128_share()


def test_configuration_holds_every_number_of_the_catalog_row():
    if not os.path.isfile(CATALOG):
        pytest.skip("no catalog of architectures here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Solar-Open2-250B")
    with open(os.path.join(spec.REPO_DIR, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "solar-open2-250b")
    assert entry["source"] == row["source_url"]
    assert len(entry["why"]) <= 200
    config = spec.load_cell(CELL).config
    assert config["source"] == row["source_url"]
    assert sorted(entry["reduced"]) == sorted(config["reduced"])
    for key, value in row["config"].items():
        if key in entry["reduced"]:
            continue
        assert config[key] == value, key
    assert config["gqa_layers_published"] == row["config"]["gqa_layers"]


def test_the_parameter_table_is_the_programs_own_count():
    """ISSUE 42's table, by the module's own shapes: 109.1M and 137.7M a
    mixer, in all 2,050M, 4.10 GB in bfloat16 and 8.20 GB in float32; and
    by the byte function's."""
    from distributed_tensorflow_tpu.models import get_workload

    config = spec.load_cell(CELL).config
    module = get_workload(config["program"]["model"],
                          config=program.program_config(config)).module
    abstract = jax.eval_shape(lambda: module.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    count = lambda tree: sum(int(np.prod(l.shape))
                             for l in jax.tree.leaves(tree))
    assert count(abstract["gqa"]) == pytest.approx(109.1e6, rel=1e-3)
    assert count(abstract["kda"]) + count(abstract["kda_decay"]) \
        == pytest.approx(3 * 137.7e6, rel=1e-3)
    assert count(abstract["layers"]) == pytest.approx(
        4 * (1.31e6 + 15.73e6 + 20 * 15.73e6), rel=1e-3)
    assert count(abstract["embed"]) + count(abstract["head"]) \
        == pytest.approx(201.3e6, rel=1e-3)
    assert count(abstract) == pytest.approx(2050e6, rel=1e-2)
    assert "2,050M" in config["parameters"]["sum"]
    p = decode_bytes_kda.weight_parameters(program.shape_of(config))
    assert (sum(v for k, v in p.items() if k != "one_routed_expert")
            + count(abstract["embed"])) == count(abstract)


def test_cell_and_traffic_carry_the_parameters_the_issue_names():
    cell = spec.load_cell(CELL)
    assert cell.chips == 1 and len(cell.why) <= 200
    sched = cell.cell["scheduler"]
    assert {k: sched[k] for k in (
        "max_total_len", "cache_mode", "block_size", "prefill_budget",
        "megastep", "async_decode")} == {
        "max_total_len": 5120, "cache_mode": "paged", "block_size": 16,
        "prefill_budget": 1024, "megastep": 4, "async_decode": True}
    assert sched["num_slots"] in (16, 32, 64)
    assert cell.cell["trace_seconds"] == 2
    assert cell.cell["trace_modules"]["decode"]["prefix"] == "jit_decode_megastep("
    assert cell.cell["trace_modules"]["prefill"]["prefix"] == "jit_prefill_slots("
    correct = cell.cell["correct"]
    assert correct["reference_padded_lengths"] == [5120]
    assert correct["reference_rows_per_forward"] == 1
    assert correct["reference_every"] >= 1
    for key in ("limits_why", "reference_why"):
        assert "chip run" in correct[key], key
    mix = cell.traffic
    assert mix["kind"] == "open_loop_requests" and mix["sampling"] == "greedy"
    assert mix["vocab_size"] == 24576 == cell.config["vocab_size"]
    assert mix["arrivals"]["process"] == "poisson"
    assert mix["lead_in_s"] >= 12.0
    assert mix["lead_in_s"] >= 2 * mix["slots_full_after_s"]
    assert mix["shuffle_block"] == 1
    a, b = (traffic.open_loop_requests(mix, seed, 30.0)
            for seed in (2147483659, 3141592653))
    assert [r.due_s for r in a] == [r.due_s for r in b]
    assert [(len(r.prompt), r.max_new_tokens) for r in a] \
        == [(len(r.prompt), r.max_new_tokens) for r in b]
    assert any((x.prompt != y.prompt).any() for x, y in zip(a, b))
    assert mix["prompt_tokens"] == {
        "median": 2048, "sigma": 0.5, "min": 513, "max": 4096,
        "round_up_to": [1024, 2048, 3072, 4096]}
    assert mix["output_tokens"] == {"median": 512, "sigma": 0.5, "min": 256,
                                    "max": 1024}
    # Whole chunks of the one prefill program, and the longest request a
    # whole slot.
    assert all(n % sched["prefill_budget"] == 0
               for n in mix["prompt_tokens"]["round_up_to"])
    assert 4096 + 1024 == sched["max_total_len"]
    # 1.5 x the swept capacity and at least 1.3 x the knee, up to a quarter.
    arrivals = mix["arrivals"]
    assert arrivals["over_capacity"] == 1.5
    wanted = max(1.5 * arrivals["capacity_per_s"], 1.3 * arrivals["knee_per_s"])
    assert 0 <= arrivals["rate_per_s"] - wanted < 0.25 + 1e-9
    assert (4 * arrivals["rate_per_s"]) % 1 == 0
    reported = {m["name"] for m in cell.end_to_end}
    assert reported == {"serve_tokens_per_s", "setup_s"}
    # Sets, not tails: a later PR may append to BENCHMARK.json's lists.
    names = {m["name"] for m in cell.per_layer}
    glm = {m["name"] for m in spec.load_cell(
        "serve.glm-4.7-flash.reason-saturated").per_layer}
    assert names - glm == {"decode_state_roofline_pct.serve",
                           "prefill_state_mfu_pct.serve",
                           "state_share_of_decode_bytes_pct.serve"}
    assert glm - names == {"tpot_p95_ms.serve",
                           "decode_hbm_roofline_pct.serve"}


def test_slot_arithmetic_quotes_the_engines_cache_geometry():
    from distributed_tensorflow_tpu.models import PagedKVConfig, get_workload

    cell = spec.load_cell(CELL)
    sched = cell.cell["scheduler"]
    slots = sched["num_slots"]
    per_slot = sched["max_total_len"] // sched["block_size"]
    blocks = slots * per_slot + 1
    workload = get_workload(cell.config["program"]["model"],
                            config=program.program_config(cell.config))
    geometry = workload.cache_geometry(PagedKVConfig(
        block_size=sched["block_size"], num_blocks=blocks))
    assert geometry["kind"] == "recurrent_state_and_key_value"
    assert (geometry["kv_layers"], geometry["state_layers"]) == (1, 3)
    assert geometry["bytes_per_token"] == 4096
    assert geometry["state_bytes_per_slot_layer"] == 64 * 128 * 128 * 4
    assert geometry["conv_bytes_per_slot_layer"] == 3 * 24576 * 2
    assert geometry["state_bytes_per_slot"] == 13025280
    assert geometry["pool_bytes"] == blocks * 16 * 4096
    text = cell.cell["num_slots_arithmetic"]
    for quoted in ("4,096 B", "13,025,280 B", f"{slots} slots",
                   f"{slots} x {per_slot} + 1 blocks",
                   f"{geometry['pool_bytes']:,} B",
                   f"{slots * geometry['state_bytes_per_slot']:,} B"):
        assert quoted in text, quoted


@pytest.mark.parametrize("seed", [1, 2**31 + 9, 3000000017])
def test_traffic_draws_its_lengths_and_its_slice(seed):
    cell = spec.load_cell(CELL)
    mix = cell.traffic
    requests = traffic.open_loop_requests(mix, seed, 30.0)
    rate = mix["arrivals"]["rate_per_s"]
    assert len(requests) == round(rate * (30.0 + mix["lead_in_s"]))
    assert {len(r.prompt) for r in requests} == {1024, 2048, 3072, 4096}
    assert all(256 <= r.max_new_tokens <= 1024 for r in requests)
    assert all(0 <= r.prompt.min() and r.prompt.max() < 24576
               for r in requests)
    assert max(len(r.prompt) + r.max_new_tokens for r in requests) <= 5120
    assert traffic.prompt_lengths(mix) == [1024, 2048, 3072, 4096]


# -- the byte and the FLOP function against hand counts --------------------------

def test_decode_step_bytes_against_a_hand_count():
    shape = program.shape_of(spec.load_cell(CELL).config)
    d, f = 4096, 8192
    gqa = d * f * 3 + 2 * d * 1024
    kda = (d * 3 * f + 4 * 3 * f + 2 * (d * 128 + 128 * f) + d * 64 + 64 + f
           + 128 + f * d)
    expert = 3 * d * 1280
    shared = (gqa + 3 * kda + 4 * (2 * d + d * 320 + 320 + expert)
              + d * 24576 + d)
    cost = decode_bytes_kda.decode_step_bytes(
        shape, active_experts_per_layer=11.5, live_rows=30.0,
        live_positions=90000.0)
    assert cost["shared_weights"] == 2 * shared
    assert cost["routed_experts"] == 2 * 4 * 11.5 * expert
    assert cost["state"] == 2 * 4 * 3 * 64 * 128 * 128 * 30.0
    assert cost["conv_tails"] == 2 * 2 * 3 * 3 * 3 * f * 30.0
    assert cost["kv_cache"] == 2 * 1 * 2 * 8 * 128 * 90000.0
    assert cost["total"] == sum(v for k, v in cost.items() if k != "total")
    # The issue's reckoning: about 4 GB a step of 32 rows.
    assert 3.5e9 < cost["total"] < 4.5e9
    with pytest.raises(ValueError, match="active experts"):
        decode_bytes_kda.decode_step_bytes(
            shape, active_experts_per_layer=21, live_rows=1, live_positions=9)
    with pytest.raises(ValueError, match="live rows"):
        decode_bytes_kda.decode_step_bytes(
            shape, active_experts_per_layer=1, live_rows=9, live_positions=1)


def test_prefill_chunk_flops_against_a_hand_count():
    shape = program.shape_of(spec.load_cell(CELL).config)
    d, f, t, off = 4096, 8192, 1024, 2048
    got = prefill_flops_kda.prefill_chunk_flops(
        shape, offset=off, tokens=t, assignments_here_share=0.0625)
    seen = sum(off + i + 1 for i in range(t))
    expert = 3 * d * 1280
    assert got["gqa_projections"] == 2 * t * (3 * d * f + 2 * d * 1024)
    assert got["gqa_attention"] == 2 * seen * 64 * 2 * 128
    assert got["kda_projections"] == 2 * 3 * t * (
        3 * d * f + 2 * (d * 128 + 128 * f) + d * 64 + f * d)
    assert got["kda_rule"] == 2 * 3 * t * 64 * (4 * 64 * 128 + 3 * 128 * 128)
    assert got["router"] == 2 * 4 * t * d * 320
    assert got["shared_experts"] == 2 * 4 * t * expert
    assert got["routed_experts"] == 2 * 4 * t * 8 * 0.0625 * expert
    assert got["head"] == 2 * d * 24576
    assert got["total"] == sum(v for k, v in got.items() if k != "total")
    assert 1.2e12 < got["total"] < 1.5e12      # the issue's 1.3 TFLOP a chunk
    with pytest.raises(ValueError, match="a chunk of"):
        prefill_flops_kda.prefill_chunk_flops(
            shape, offset=0, tokens=0, assignments_here_share=0.5)


# -- the new readers on a made-up trace ------------------------------------------

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


STATS = {"moe_active_experts_per_step": 10.0, "moe_layer_steps": 300.0,
         "decode_live_positions": 80000.0, "state_slots_live": 28.0,
         "state_bytes_per_slot": 13025280.0, "iterations": 30.0,
         "moe_assignments_here": 625.0, "moe_assignments_absent": 9375.0}


def _floor_readers(cell):
    """The two metrics of the decode step's floor as the harness calls them:
    the one reader under each metric file's own arguments."""
    by_name = {m["name"]: m for m in cell.per_layer}

    def reader(name):
        metric = by_name[name]
        assert metric["reader"] == "decode_floor_pct"
        return lambda ctx: cell.reader(metric)(ctx, **metric["args"])

    return (reader("decode_state_roofline_pct.serve"),
            reader("state_share_of_decode_bytes_pct.serve"))


def test_decode_roofline_reader_divides_the_floor_by_the_step():
    cell = spec.load_cell(CELL)
    read, read_share = _floor_readers(cell)
    start = dict(STATS, moe_active_experts_per_step=8.0, moe_layer_steps=100.0,
                 decode_live_positions=60000.0, state_slots_live=24.0,
                 iterations=10.0)
    launches = [(1.0, 1.06), (2.0, 2.06), (3.0, 3.09)]   # median 60 ms, 4 steps
    ctx, said = _context(cell, start, STATS, decode=launches)
    value = read(ctx)
    # Within the window: 200 layer-steps at 11 experts, 20 launches of 30
    # rows and 90,000 positions.
    cost = decode_bytes_kda.decode_step_bytes(
        program.shape_of(cell.config), active_experts_per_layer=11.0,
        live_rows=30.0, live_positions=90000.0)
    assert value == pytest.approx(100 * cost["total"] / 819e9 / 0.015)
    assert 0 < value < 100
    (event, fields), = said
    assert event == "decode_floor"
    assert fields["bytes_module"] == "decode_bytes_kda"
    assert fields["live_rows"] == pytest.approx(30.0)
    assert fields["step_ms"] == pytest.approx(15.0)
    share = read_share(ctx)
    assert share == pytest.approx(
        100 * (cost["state"] + cost["conv_tails"]) / cost["total"])
    assert 15 < share < 30


def test_prefill_mfu_reader_counts_the_chunks_the_spans_name():
    cell = spec.load_cell(CELL)
    read = cell.reader({"name": "m", "reader": "prefill_state_mfu_pct"})
    launches = [(1.0, 1.05), (2.0, 2.07), (3.0, 3.06)]
    ctx, said = _context(cell, STATS, STATS, prefill=launches)
    chunk = lambda off: (xplane.Event("dtt/serve/prefill_chunk", 1.0, 1.01),
                         {"offset": off, "chunk_tokens": 1024})
    ctx["program_spans"] = {"ended": {
        "dtt/serve/prefill_chunk": [chunk(0), chunk(3072)]}}
    value = read(ctx)
    shape = program.shape_of(cell.config)
    mean = sum(prefill_flops_kda.prefill_chunk_flops(
        shape, offset=off, tokens=1024, assignments_here_share=0.0625)["total"]
        for off in (0, 3072)) / 2
    assert value == pytest.approx(100 * 3 * mean / 197e12 / 0.18)
    assert 0 < value < 100
    (event, fields), = said
    assert event == "prefill_state_mfu"
    assert (fields["launches"], fields["chunk_spans"]) == (3, 2)
    assert fields["assignments_here_share"] == pytest.approx(0.0625)


@pytest.mark.parametrize("case", ["parent_without_the_counter", "no_launch",
                                  "nothing_counted_in_the_window",
                                  "no_spans"])
def test_the_new_readers_read_nothing_where_there_is_nothing(case):
    cell = spec.load_cell(CELL)
    decode, share = _floor_readers(cell)
    prefill = cell.reader({"name": "m", "reader": "prefill_state_mfu_pct"})
    start = dict(STATS, moe_layer_steps=100.0, iterations=10.0)
    end, launches = STATS, [(1.0, 1.04)]
    spans = {"ended": {"dtt/serve/prefill_chunk": [(
        xplane.Event("dtt/serve/prefill_chunk", 1.0, 1.01),
        {"offset": 0, "chunk_tokens": 1024})]}}
    if case == "parent_without_the_counter":
        start, end = {"iterations": 10.0}, {"iterations": 30.0}
    elif case == "no_launch":
        launches = []
    elif case == "nothing_counted_in_the_window":
        start = dict(STATS)
        end = dict(STATS, moe_assignments_here=0.0, moe_assignments_absent=0.0)
    else:
        spans = None
    ctx, said = _context(cell, start, end, decode=launches, prefill=launches)
    ctx["program_spans"] = spans
    if case != "no_spans":
        assert decode(ctx) is None
    if case in ("parent_without_the_counter",
                "nothing_counted_in_the_window"):
        assert share(ctx) is None
    assert prefill(ctx) is None
    assert not [event for event, _ in said if event != "decode_floor"]


def test_the_new_metrics_read_nothing_from_the_other_families():
    """A cell of a family without a state: no ``state_slots_live`` in its
    scheduler's stats, so the three read ``None`` and the line leaves them
    out."""
    cell = spec.load_cell("serve.glm-4.7-flash.reason-saturated")
    stats = {k: v for k, v in STATS.items() if not k.startswith("state_")}
    ctx, _ = _context(cell, dict(stats, iterations=10.0), stats,
                      decode=[(1.0, 1.04)], prefill=[(2.0, 2.04)])
    ctx["program_spans"] = {"ended": {"dtt/serve/prefill_chunk": [(
        xplane.Event("dtt/serve/prefill_chunk", 1.0, 1.01),
        {"offset": 0, "chunk_tokens": 1024})]}}
    new = spec.load_cell(CELL)
    decode, share = _floor_readers(new)
    assert decode(ctx) is None and share(ctx) is None
    assert new.reader({"name": "m", "reader": "prefill_state_mfu_pct"})(
        ctx) is None


def test_layer_metric_files_name_readers_and_arguments_that_exist():
    cell = spec.load_cell(CELL)
    by_name = {m["name"]: m for m in cell.per_layer}
    for name in ("decode_state_roofline_pct.serve",
                 "prefill_state_mfu_pct.serve",
                 "state_share_of_decode_bytes_pct.serve"):
        metric = by_name[name]
        assert callable(cell.reader(metric))
        assert metric["layer"] == "engine and model step"
        assert metric["moves"] == "serve_tokens_per_s"
    assert by_name["prefill_state_mfu_pct.serve"]["args"]["span"] \
        == "dtt/serve/prefill_chunk"
    roofline = by_name["decode_state_roofline_pct.serve"]["args"]
    share = by_name["state_share_of_decode_bytes_pct.serve"]["args"]
    assert share["share_of"] == ["state", "conv_tails"]
    assert "share_of" not in roofline
    for args in (roofline, share):
        assert args["bytes_module"] == "decode_bytes_kda"
        assert set(args["counts"]) == {
            "active_experts_per_layer", "live_rows", "live_positions"}


# -- the names the trace is read by ----------------------------------------------

def test_trace_module_names_are_the_names_an_engine_that_ran_gives():
    """The tiny cell's engine, run: its two programs are jitted under the
    names the cell's ``trace_modules`` look for, it is on record with the
    two forms of the rule, its prefill chunks' spans carry what the FLOP
    reader takes from them, and the registry exports the state's gauge and
    counter."""
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
    real = spec.load_cell(CELL).cell["trace_modules"]
    for kind, rule in real.items():
        wanted = rule["prefix"][len("jit_"):-1]
        assert wanted in names, (kind, names)
    paths = engine.attention_paths()
    assert set(paths["slot_prefill"]) == {"gqa_gather_full", "kda_chunk"}
    assert set(paths["slot_megastep"]) == {"gqa_gather_full", "kda_step"}
    assert chunks and all(
        {"offset", "chunk_tokens", "context_tokens"} <= set(c) for c in chunks)
    assert program_spans.PREFIX == "dtt/"
    exported = render_prometheus()
    assert "dtt_serve_state_bytes_held" in exported
    assert "dtt_serve_state_resets_total" in exported


@pytest.mark.parametrize("mechanism", ["without_decay", "state_bfloat16"])
def test_the_state_control_serves_another_model(monkeypatch, tmp_path,
                                                mechanism):
    """``tools/state_control.py`` on the tiny cell: the program with the
    decay left out is held to the reference and reads far over what the
    sound program reads; the program with its state kept in bfloat16 leaves
    a state that bfloat16 holds, and its reading is recorded (it is NOT
    over the sound program's in bfloat16 operands: the comparison cannot
    see it, tests/test_solar_open2.py's float32 tolerances do)."""
    from benchmark.tools import limits, state_control
    from distributed_tensorflow_tpu.models import solar_open2

    cell = _tiny_cell()
    sound = limits.serve_seed(cell, 21, jax.devices()[:1], False, 1.0)
    for rule in ("kda_project", "kda_step", "kda_chunk"):
        monkeypatch.setattr(solar_open2, rule, getattr(solar_open2, rule))
    monkeypatch.setattr(spec, "load_cell", lambda name: cell)
    monkeypatch.setattr(device, "require_chips",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.chdir(tmp_path)
    state_control.main(["--workload", "serve.solar-open2-tiny", "--seeds",
                        "21", "--seconds", "1", "--mechanism", mechanism])
    with open(tmp_path / "chiprun_out" / "limits"
              / "serve.solar-open2-tiny.state_control.jsonl") as f:
        (row,) = [json.loads(line) for line in f]
    assert row["seed"] == 21 and row["served_tokens"] > 0
    wrong = row[mechanism]["served_logit_gap_max"]
    if mechanism == "state_bfloat16":
        assert wrong != sound["sound"]["served_logit_gap_max"]
        draw = lambda i, *shape: jax.random.normal(
            jax.random.PRNGKey(i), shape)
        _, state = solar_open2.kda_step(
            draw(0, 2, 4, 16, 16), draw(1, 2, 4, 16), draw(2, 2, 4, 16),
            draw(3, 2, 4, 16), -jnp.abs(draw(4, 2, 4, 16)),
            jax.nn.sigmoid(draw(5, 2, 4)))
        assert state.dtype == jnp.float32
        assert (state == state.astype(jnp.bfloat16).astype(jnp.float32)).all()
        return
    assert wrong > 10 * sound["sound"]["served_logit_gap_max"]
    assert wrong > cell.cell["correct"]["limits"]["served_logit_gap_max"]
