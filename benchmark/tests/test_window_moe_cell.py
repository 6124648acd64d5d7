"""The window-and-full-attention, sparse-expert configuration and its serving
cell, as far as the CPU can check them: the plain reference against the
program at the tiny fixture, the float8 control, a whole run of the tiny
cell, the traffic file's lengths and slice, the configuration file against
the published keys, the byte function against a hand count, the slot
arithmetic against the engine's cache geometry, and the two new readers on
a made-up trace."""

import dataclasses
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import decode_bytes_gqa, device, program, spec, traffic
from benchmark.harness import weights, xplane
from benchmark.harness.drivers import DRIVERS
from benchmark.reference import precision
from benchmark.tests.conftest import FIXTURES

CELL = "serve.mellum2-12b-a2.5b.code-mixed-saturated"
MELLUM_FIXTURES = os.path.join(FIXTURES, "mellum")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"

# Mellum2-12B-A2.5B-Instruct's config.json, as the catalog of architectures
# has it (the nested groups and the three cut keys are compared apart).
PUBLISHED = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 7168,
    "max_position_embeddings": 131072, "max_window_layers": 0,
    "model_type": "mellum", "moe_intermediate_size": 896,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 8, "num_key_value_heads": 4,
    "rms_norm_eps": 1e-6, "sliding_window": 1024,
    "tie_word_embeddings": False, "use_sliding_window": True,
}
PUBLISHED_ROPE = {
    "full_attention": {
        "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
        "original_max_position_embeddings": 8192, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 1.2772588722239782},
    "sliding_attention": {"rope_type": "default", "rope_theta": 500000}}


def _tiny_cell():
    return spec.load_cell(
        "serve.mellum-tiny",
        manifest=os.path.join(MELLUM_FIXTURES, "BENCHMARK.json"),
        data_dir=MELLUM_FIXTURES)


def _tiny_system(seed=5):
    """The program's module in float32, seeded weights, a batch of rows."""
    from distributed_tensorflow_tpu.models import get_workload

    config = _tiny_cell().config
    cfg = dataclasses.replace(program.program_config(config),
                              dtype=jnp.float32)
    module = get_workload(config["program"]["model"], config=cfg).module
    tokens = jnp.asarray(np.random.default_rng(seed).integers(
        0, config["vocab_size"], (3, 80)), jnp.int32)
    abstract = jax.eval_shape(
        lambda: module.init(jax.random.key(0), tokens))["params"]
    config = dict(config, parameter_dtype="float32")
    return config, module, weights.make_params(seed, abstract), tokens


def test_reference_logits_match_the_program():
    """Rows of 80 positions, past the fixture's window of 24."""
    config, module, params, tokens = _tiny_system()
    ref = program.reference_module(config)
    got = module.apply({"params": params}, tokens)
    want = ref.logits(precision.Exact(), config, params, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=1e-5)


def test_the_fp8_control_is_a_different_forward():
    config, _, params, tokens = _tiny_system()
    ref = program.reference_module(config)
    exact = ref.logits(precision.Exact(), config, params, tokens)
    low = ref.logits(precision.Fp8(), config, params, tokens)
    assert 1e-3 < float(jnp.max(jnp.abs(exact - low))) < 1.0


def test_the_reference_reads_a_window_on_window_layers_alone():
    """A change to a token more than a window back reaches a position's
    logits through the full layers (and through window layers stacked on
    one another); with every layer a window layer and one layer deep it
    cannot."""
    config, _, params, tokens = _tiny_system()
    ref = program.reference_module(config)
    one = dict(config, num_hidden_layers=1)
    first = {**params, "layers": jax.tree.map(lambda w: w[:1],
                                              params["layers"])}
    changed = tokens.at[:, 10].set((tokens[:, 10] + 1) % 256)
    a = ref.logits(precision.Exact(), one, first, tokens)
    b = ref.logits(precision.Exact(), one, first, changed)
    moved = np.abs(np.asarray(a - b)).max(axis=(0, 2))
    window = config["sliding_window"]
    assert (moved[:10] == 0).all() and (moved[10:10 + window] > 0).all()
    assert (moved[10 + window:] == 0).all()
    full = dict(one, layer_types=["full_attention"])
    a = ref.logits(precision.Exact(), full, first, tokens)
    b = ref.logits(precision.Exact(), full, first, changed)
    assert (np.abs(np.asarray(a - b)).max(axis=(0, 2))[10:] > 0).all()


def test_the_reference_holds_the_share_the_configuration_names():
    """Held experts 2..5 of 8: the reference's layer is the held experts'
    weighted parts and nothing more (no shared expert)."""
    config, _, params, _ = _tiny_system()
    ref = program.reference_module(config)
    dot = precision.Exact()
    layer = jax.tree.map(lambda w: w[0], params["layers"])
    first, held = config["first_expert_held"], config["num_experts"]
    assert (first, held, config["num_experts_published"]) == (2, 4, 8)
    x = jnp.asarray(np.random.default_rng(1).normal(size=(40, 64)),
                    jnp.float32)
    share = ref.expert_ffn(dot, config, x, layer)
    widths = np.asarray(ref._route(dot, config, x, layer["router"]))
    assert (widths > 0).sum(-1).tolist() == [config["num_experts_per_tok"]] * 40
    np.testing.assert_allclose(widths.sum(-1), 1.0, rtol=1e-5)
    routed = sum(widths[:, first + e, None] * ref._mlp(
        dot, x, jax.tree.map(lambda w: w[e], layer["experts"]))
        for e in range(held))
    np.testing.assert_allclose(np.asarray(share), np.asarray(routed),
                               atol=1e-5)
    assert 0 < (widths[:, first:first + held] > 0).sum() < (widths > 0).sum()


def test_a_whole_run_of_the_tiny_cell_is_correct_and_counts_both_pools():
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
    assert end["moe_assignments_here"] > 0 and end["moe_assignments_absent"] > 0
    assert 0 < end["decode_live_positions_window"] \
        < end["decode_live_positions"]
    assert end["window_ring_blocks"] == -(-(24 + 16 + 4) // 8) + 1
    assert end["window_blocks_recycled"] > 0    # some row outgrew its ring
    read = cell.reader({"name": "m", "reader": "scheduler_stat"})
    assert read(result["context"], key="moe_load_max_over_mean",
                nonzero_key="moe_layer_steps") == end["moe_load_max_over_mean"]


# -- the cell's own files ------------------------------------------------------

def test_configuration_keeps_every_published_width_and_states_its_cuts():
    cell = spec.load_cell(CELL)
    config = cell.config
    for key, value in PUBLISHED.items():
        assert config[key] == value, key
    assert config["rope_parameters"] == PUBLISHED_ROPE
    period = ["sliding_attention"] * 3 + ["full_attention"]
    assert config["layer_types"] == period * 7      # the published list, whole
    assert config["mlp_layer_types"] == ["sparse"] * 28
    assert sorted(config["reduced"]) == [
        "num_experts", "num_hidden_layers", "vocab_size"]
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (16, 16, 24576)
    assert (config["num_hidden_layers_published"],
            config["num_experts_published"],
            config["vocab_size_published"]) == (28, 64, 98304)
    # Inside the guide's floors: whole periods, 16 layers, 16 experts, a
    # quarter of the vocabulary.
    assert config["num_hidden_layers"] % 4 == 0
    assert config["num_experts"] >= 8
    assert config["vocab_size"] * 4 == config["vocab_size_published"]
    for key in ("parameter_dtype", "rope_pairing", "yarn", "sliding_window",
                "no_qk_norm_no_sink", "router", "multi_token_head", "weights"):
        assert key in config["assumed"], key
    assert "v5e-4" in config["stands_for"] and "4 chips" in config["stands_for"]
    assert "6.98 GB" in config["stands_for"]
    cfg = program.program_config(config)
    assert (cfg.num_experts, cfg.held, cfg.first_expert) == (64, 16, 0)
    assert (cfg.n_window_layers, cfg.n_full_layers, cfg.kv_row) == (12, 4, 1024)
    shape = program.shape_of(config)
    assert (shape["full_attention_layers"],
            shape["sliding_attention_layers"]) == (4, 12)
    from distributed_tensorflow_tpu.models.mellum import MellumConfig
    assert cfg == MellumConfig.v5e4_share()


@pytest.mark.skipif(not os.path.isfile(CATALOG),
                    reason="no catalog of architectures beside the guide here")
def test_configuration_holds_every_number_of_the_catalog_row():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Mellum2-12B-A2.5B-Instruct")
    config = spec.load_cell(CELL).config
    assert config["source"] == row["source_url"]
    cut = set(config["reduced"])
    for key, value in row["config"].items():
        if key not in cut:
            assert config[key] == value, key
    with open(os.path.join(spec.REPO_DIR, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "mellum2-12b-a2.5b")
    assert set(entry["reduced"]) == cut and entry["source"] == row["source_url"]


def test_cell_and_traffic_carry_the_parameters_the_issue_names():
    cell = spec.load_cell(CELL)
    assert cell.chips == 1
    sched = {k: cell.cell["scheduler"][k] for k in (
        "max_total_len", "cache_mode", "block_size", "prefill_budget",
        "megastep", "async_decode")}
    assert sched == {"max_total_len": 4096, "cache_mode": "paged",
                     "block_size": 16, "prefill_budget": 512, "megastep": 4,
                     "async_decode": True}
    assert cell.cell["scheduler"]["num_slots"] in (8, 12, 16)
    assert cell.cell["trace_seconds"] == 2
    assert cell.cell["trace_modules"]["decode"]["prefix"] == "jit_decode_megastep("
    assert cell.cell["trace_modules"]["prefill"]["prefix"] == "jit_prefill_slots("
    assert cell.cell["correct"]["reference_padded_lengths"] == [1024, 2048, 4096]
    mix = cell.traffic
    assert mix["kind"] == "open_loop_requests" and mix["sampling"] == "greedy"
    # ISSUE 35 reckoned a lead-in of 10 s before the sweep; the sweep's
    # slots took 6.27 s to fill at the cell's load and over, and the
    # lead-in is at least twice that (test_serving_cell.py holds every
    # cell to it).
    assert (mix["lead_in_s"], mix["vocab_size"]) == (13.0, 24576)
    assert mix["lead_in_s"] >= 2 * mix["slots_full_after_s"] > 10.0
    assert mix["shuffle_block"] == cell.cell["scheduler"]["num_slots"]
    assert mix["arrivals"]["process"] == "poisson"
    assert mix["prompt_tokens"] == {
        "median": 1024, "sigma": 0.8, "min": 256, "max": 3584,
        "round_up_to": [512, 1024, 2048, 3584]}
    assert mix["output_tokens"] == {"median": 256, "sigma": 0.5, "min": 64,
                                    "max": 512}
    # Every prompt is whole chunks of the one prefill program.
    budget = cell.cell["scheduler"]["prefill_budget"]
    assert all(n % budget == 0 for n in mix["prompt_tokens"]["round_up_to"])
    reported = {m["name"] for m in cell.end_to_end}
    assert reported == {"serve_tokens_per_s", "setup_s"}
    names = [m["name"] for m in cell.per_layer]
    glm = [m["name"] for m in spec.load_cell(
        "serve.glm-4.7-flash.reason-saturated").per_layer]
    # The other serving cells' metrics but the two whose readers find
    # nothing to read here, and the two this configuration brings.
    left_out = {"tpot_p95_ms.serve", "decode_hbm_roofline_pct.serve"}
    assert names[:10] == [n for n in glm if n not in left_out]
    assert names[10:] == ["decode_kv_roofline_pct.serve",
                          "window_cache_held_pct.serve"]
    with open(os.path.join(spec.REPO_DIR, "BENCHMARK.json")) as f:
        listed = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name in names:
        assert CELL in listed[name]["workloads"], name
    for name in left_out:
        assert CELL not in listed[name]["workloads"], name
    for name in names[10:]:
        assert listed[name]["workloads"] == [CELL]


def test_slot_arithmetic_quotes_the_engines_cache_geometry():
    """The cell's prose, the scheduler's ring rule and the model's
    ``cache_geometry`` against one another."""
    from distributed_tensorflow_tpu.models import PagedKVConfig, get_workload

    cell = spec.load_cell(CELL)
    sched = cell.cell["scheduler"]
    slots, block = sched["num_slots"], sched["block_size"]
    per_slot = sched["max_total_len"] // block
    ring = -(-(1024 + sched["prefill_budget"] + sched["megastep"])
             // block) + 1
    assert (per_slot, ring) == (256, 98)
    paged = PagedKVConfig(block_size=block, num_blocks=slots * per_slot + 1,
                          window_blocks=slots * ring + 1, window_ring=ring)
    workload = get_workload(cell.config["program"]["model"],
                            config=program.program_config(cell.config))
    g = workload.cache_geometry(paged)
    assert g["kind"] == "key_value_grouped"
    assert (g["values_per_token_layer"], g["pool_width"],
            g["padding_values"]) == (1024, 1024, 0)
    assert g["bytes_per_token_layer"] == 2048
    assert (g["bytes_per_token"], g["bytes_per_token_past_window"]) \
        == (32768, 8192)
    assert (g["full_layers"], g["window_layers"], g["window_positions"]) \
        == (4, 12, 1024)
    assert (g["window_ring_blocks"], g["window_ring_positions"]) == (98, 1568)
    assert g["full_pool_bytes"] == 4 * (slots * 256 + 1) * 16 * 2048
    assert g["window_pool_bytes"] == 12 * (slots * 98 + 1) * 16 * 2048
    # A slot of 4,096 positions: 33.6 MB in the full layers' pool and a
    # ring of 38.5 MB in the window layers', where one geometry for all 16
    # layers would hold 134.2 MB.
    slot_full = 256 * g["full_block_bytes"]
    slot_ring = ring * g["window_block_bytes"]
    assert (slot_full, slot_ring) == (33554432, 38535168)
    assert 256 * (g["full_block_bytes"] + g["window_block_bytes"]) == 134217728
    text = cell.cell["num_slots_arithmetic"]
    for quoted in ("1,024 values", "2,048 B", "98 blocks", "1,568 positions",
                   f"{slots} x 256 + 1 blocks", f"{slots} x 98 + 1 blocks",
                   f"{g['full_pool_bytes']:,} B",
                   f"{g['window_pool_bytes']:,} B", "33.6 MB", "38.5 MB",
                   "134.2 MB"):
        assert quoted in text, quoted
    # The scheduler sizes the ring by the same rule (no engine needed: the
    # rule is the ISSUE's, and tests/test_mellum.py holds the scheduler to
    # it at the tiny preset).
    assert f"{slots} slots" in cell.why and "13 s lead-in" in cell.why


@pytest.mark.parametrize("seed", [1, 2**31 + 9, 3000000017])
def test_traffic_draws_its_lengths_and_its_slice(seed):
    cell = spec.load_cell(CELL)
    requests = traffic.open_loop_requests(cell.traffic, seed, 30.0)
    rate = cell.traffic["arrivals"]["rate_per_s"]
    assert len(requests) == round(rate * 43.0)
    assert {len(r.prompt) for r in requests} == {512, 1024, 2048, 3584}
    assert all(64 <= r.max_new_tokens <= 512 for r in requests)
    longest = max(len(r.prompt) + r.max_new_tokens for r in requests)
    assert 1024 < longest <= cell.cell["scheduler"]["max_total_len"]
    ids = np.concatenate([r.prompt for r in requests])
    assert ids.min() >= 0 and ids.max() < 24576
    assert ids.max() > 24000       # the whole slice, not a corner of it
    assert traffic.prompt_lengths(cell.traffic) == [512, 1024, 2048, 3584]
    other = traffic.open_loop_requests(cell.traffic, seed + 1, 30.0)
    assert [len(r.prompt) for r in other] == [len(r.prompt) for r in requests]


def test_the_mix_is_short_and_long_in_one_queue():
    """About 19 / 31 / 31 / 19% of a long draw of the mix's prompts."""
    cell = spec.load_cell(CELL)
    mix = json.loads(json.dumps(cell.traffic))
    mix["arrivals"]["rate_per_s"] = 100.0
    lengths = [len(r.prompt) for r in traffic.open_loop_requests(mix, 1, 30.0)]
    share = {n: lengths.count(n) / len(lengths)
             for n in (512, 1024, 2048, 3584)}
    for n, want in ((512, 0.19), (1024, 0.31), (2048, 0.31), (3584, 0.19)):
        assert share[n] == pytest.approx(want, abs=0.03), share


# -- the byte function -----------------------------------------------------------

def test_decode_step_bytes_against_a_hand_count():
    """The issue's arithmetic, in parameters: attention 21.23M a layer,
    router 0.147M, one expert 6.19M, the head's 24,576 rows 56.6M; a step
    with every held expert live reads 3.97 GB, 80% of it experts."""
    shape = program.shape_of(spec.load_cell(CELL).config)
    p = decode_bytes_gqa.weight_parameters(shape)
    attention = 2304 * 4096 + 2 * 2304 * 512 + 4096 * 2304
    assert attention == pytest.approx(21.23e6, rel=1e-3)
    assert p["attention"] == 16 * (attention + 2 * 2304)
    assert p["router"] == 16 * 2304 * 64
    assert p["one_routed_expert"] == 3 * 2304 * 896 == pytest.approx(
        6.193e6, rel=1e-3)
    assert p["routed_experts_held"] == 16 * 16 * p["one_routed_expert"]
    assert p["head"] == 2304 * 24576 + 2304
    assert decode_bytes_gqa.kv_values_per_position(shape) == 1024
    full = decode_bytes_gqa.decode_step_bytes(
        shape, active_experts_per_layer=16, live_positions_full=0,
        live_positions_window=0)
    assert full["routed_experts"] == 2 * p["routed_experts_held"] \
        == pytest.approx(3.17e9, rel=2e-3)
    assert full["total"] == pytest.approx(3.97e9, rel=5e-3)
    assert full["routed_experts"] / full["total"] == pytest.approx(0.80, abs=0.01)
    # Experts with no token are not read; a row's K and V are, whole in the
    # 4 full layers and up to the window in the 12 window layers, at
    # 2,048 B a position and layer.
    some = decode_bytes_gqa.decode_step_bytes(
        shape, active_experts_per_layer=10.5, live_positions_full=16000,
        live_positions_window=7000)
    assert some["routed_experts"] == pytest.approx(
        full["routed_experts"] * 10.5 / 16)
    assert some["kv_full_layers"] == 2048 * 4 * 16000
    assert some["kv_window_layers"] == 2048 * 12 * 7000
    assert some["shared_weights"] == full["shared_weights"]
    assert some["total"] == pytest.approx(sum(some[k] for k in (
        "shared_weights", "routed_experts", "kv_full_layers",
        "kv_window_layers")))
    for bad in (dict(active_experts_per_layer=16.5, live_positions_full=0,
                     live_positions_window=0),
                dict(active_experts_per_layer=-1, live_positions_full=0,
                     live_positions_window=0),
                dict(active_experts_per_layer=1, live_positions_full=5,
                     live_positions_window=6),
                dict(active_experts_per_layer=1, live_positions_full=5,
                     live_positions_window=-1)):
        with pytest.raises(ValueError):
            decode_bytes_gqa.decode_step_bytes(shape, **bad)
    with pytest.raises(ValueError, match="window layers"):
        decode_bytes_gqa.decode_step_bytes(
            dict(shape, full_attention_layers=5), active_experts_per_layer=1,
            live_positions_full=5, live_positions_window=5)


# -- the new readers on a made-up trace ------------------------------------------

def _context(cell, stats_start, stats_end, launches):
    lines = {xplane.MODULES_LINE: [
        xplane.Event(f"jit_decode_megastep({i})", a, b)
        for i, (a, b) in enumerate(launches)]}
    said = []
    return {"cell": cell, "peaks": {"hbm_bytes_per_s": 819e9},
            "stats_start": stats_start, "stats_end": stats_end,
            "profile": {"trace": xplane.Trace({0: lines}, []),
                        "window": (0.0, 10.0)},
            "say": lambda event, **kw: said.append((event, kw))}, said


STATS_START = {"moe_active_experts_per_step": 8.0, "moe_layer_steps": 100.0,
               "decode_live_positions": 4000.0,
               "decode_live_positions_window": 2000.0, "iterations": 10.0}
STATS_END = {"moe_active_experts_per_step": 10.0, "moe_layer_steps": 300.0,
             "decode_live_positions": 12000.0,
             "decode_live_positions_window": 6000.0, "iterations": 30.0}


def test_roofline_reader_divides_the_floor_by_the_step():
    cell = spec.load_cell(CELL)
    read = cell.reader({"name": "m", "reader": "decode_kv_roofline_pct"})
    # Within the window: 200 layer-steps at 11 experts live, 20 launches of
    # 16,000 full and 8,000 window positions.
    launches = [(1.0, 1.04), (2.0, 2.04), (3.0, 3.06)]   # median 40 ms, 4 steps
    ctx, said = _context(cell, STATS_START, STATS_END, launches)
    value = read(ctx, module="decode", per="megastep")
    cost = decode_bytes_gqa.decode_step_bytes(
        program.shape_of(cell.config), active_experts_per_layer=11.0,
        live_positions_full=16000.0, live_positions_window=8000.0)
    assert value == pytest.approx(100 * cost["total"] / 819e9 / 0.010)
    assert 0 < value < 100
    (event, fields), = said
    assert event == "decode_kv_floor"
    assert fields["active_experts_per_layer"] == pytest.approx(11.0)
    assert fields["live_positions_full"] == pytest.approx(16000.0)
    assert fields["live_positions_window"] == pytest.approx(8000.0)
    assert fields["step_ms"] == pytest.approx(10.0)


@pytest.mark.parametrize("case", [
    "parent_without_the_counters", "a_family_with_one_kind_of_cache",
    "no_launch", "nothing_counted_in_the_window"])
def test_roofline_reader_reads_nothing_where_there_is_nothing(case):
    cell = spec.load_cell(CELL)
    read = cell.reader({"name": "m", "reader": "decode_kv_roofline_pct"})
    start, end, launches = dict(STATS_START), dict(STATS_END), [(1.0, 1.04)]
    if case == "parent_without_the_counters":
        start, end = {"iterations": 10.0}, {"iterations": 30.0}
    elif case == "a_family_with_one_kind_of_cache":
        for stats in (start, end):
            del stats["decode_live_positions_window"]
    elif case == "no_launch":
        launches = []
    else:
        start = dict(end)
    ctx, said = _context(cell, start, end, launches)
    assert read(ctx, module="decode", per="megastep") is None
    assert not said


def test_held_share_reader():
    cell = spec.load_cell(CELL)
    read = cell.reader({"name": "m", "reader": "scheduler_stat_ratio_pct"})
    with open(os.path.join(spec.BENCH_DIR, "layer_metrics",
                           "window_cache_held_pct.serve.json")) as f:
        args = json.load(f)["args"]
    assert args == dict(key="kv_bytes_held", over="kv_bytes_held_uniform")
    end = {"kv_bytes_held": 300.0, "kv_bytes_held_uniform": 400.0}
    assert read({"stats_end": end}, **args) == 75.0
    assert read({"stats_end": dict(end, kv_bytes_held_uniform=0.0)},
                **args) is None
    assert read({"stats_end": {"iterations": 3.0}}, **args) is None
    assert read({}, **args) is None


def test_the_new_metrics_read_nothing_from_the_other_families():
    """Run on the GLM fixture's context: no window counters, so both new
    readers leave their metric out and do not raise."""
    cell = spec.load_cell(CELL)
    stats = {"moe_active_experts_per_step": 4.0, "moe_layer_steps": 300.0,
             "decode_live_positions": 3000.0, "iterations": 30.0}
    ctx, said = _context(cell, dict(stats, iterations=10.0), stats,
                         [(1.0, 1.04)])
    for metric in cell.per_layer[10:]:
        assert cell.reader(metric)(ctx, **metric["args"]) is None
    assert not said
