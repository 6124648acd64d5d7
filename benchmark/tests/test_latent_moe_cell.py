"""The latent-attention, sparse-expert configuration and its serving cell,
as far as the CPU can check them: the plain reference against the program at
the tiny fixture, the float8 control, a whole run of the tiny cell, the
traffic file's lengths and slice, the configuration file against the
published keys, the byte function against a hand count, and the two new
readers on a made-up trace."""

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import decode_bytes, device, program, spec, traffic
from benchmark.harness import weights, xplane
from benchmark.harness.drivers import DRIVERS
from benchmark.reference import precision
from benchmark.tests.conftest import FIXTURES

CELL = "serve.glm-4.7-flash.reason-saturated"
GLM_FIXTURES = os.path.join(FIXTURES, "glm")

# GLM-4.7-Flash's config.json, as the catalog of architectures has it.
PUBLISHED = {
    "hidden_size": 2048, "intermediate_size": 10240,
    "moe_intermediate_size": 1536, "num_attention_heads": 20,
    "num_key_value_heads": 20, "q_lora_rank": 768, "kv_lora_rank": 512,
    "qk_nope_head_dim": 192, "qk_rope_head_dim": 64, "v_head_dim": 256,
    "n_shared_experts": 1, "num_experts_per_tok": 4, "n_group": 1,
    "topk_group": 1, "routed_scaling_factor": 1.8, "first_k_dense_replace": 1,
    "rms_norm_eps": 1e-5, "rope_theta": 1000000,
    "max_position_embeddings": 202752, "norm_topk_prob": True,
    "tie_word_embeddings": False, "model_type": "glm4_moe_lite",
}


def _tiny_cell():
    return spec.load_cell(
        "serve.glm-tiny", manifest=os.path.join(GLM_FIXTURES, "BENCHMARK.json"),
        data_dir=GLM_FIXTURES)


def _tiny_system(seed=5):
    """The program's module in float32, seeded weights, a batch of rows."""
    import dataclasses

    from distributed_tensorflow_tpu.models import get_workload

    config = _tiny_cell().config
    cfg = dataclasses.replace(program.program_config(config),
                              dtype=jnp.float32)
    module = get_workload(config["program"]["model"], config=cfg).module
    tokens = jnp.asarray(np.random.default_rng(seed).integers(
        0, config["vocab_size"], (3, 48)), jnp.int32)
    abstract = jax.eval_shape(
        lambda: module.init(jax.random.key(0), tokens))["params"]
    config = dict(config, parameter_dtype="float32")
    return config, module, weights.make_params(seed, abstract), tokens


def test_reference_logits_match_the_program():
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


def test_the_reference_holds_the_share_the_configuration_names():
    """Held experts 2..5 of 8: the reference's layer with every expert
    held, less what experts 0, 1, 6 and 7 add, is the share's layer."""
    config, _, params, _ = _tiny_system()
    ref = program.reference_module(config)
    dot = precision.Exact()
    layer = jax.tree.map(lambda w: w[0], params["moe_layers"])
    first, held = config["first_expert_held"], config["n_routed_experts"]
    assert (first, held, config["n_routed_experts_published"]) == (2, 4, 8)
    x = jnp.asarray(np.random.default_rng(1).normal(size=(40, 64)),
                    jnp.float32)
    share = ref.expert_ffn(dot, config, x, layer)
    widths = np.asarray(ref._route(dot, config, x, layer["router"]))
    assert (widths > 0).sum(-1).tolist() == [config["num_experts_per_tok"]] * 40
    routed = sum(widths[:, first + e, None] * ref._mlp(
        dot, x, jax.tree.map(lambda w: w[e], layer["experts"]))
        for e in range(held))
    np.testing.assert_allclose(
        np.asarray(share), np.asarray(routed + ref._mlp(dot, x, layer["shared"])),
        atol=1e-5)
    assert 0 < (widths[:, first:first + held] > 0).sum() < (widths > 0).sum()


def test_a_whole_run_of_the_tiny_cell_is_correct_and_counts_its_experts():
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
    assert end["decode_live_positions"] > 0
    read = cell.reader({"name": "m", "reader": "scheduler_stat"})
    assert read(result["context"], key="moe_load_max_over_mean",
                nonzero_key="moe_layer_steps") == end["moe_load_max_over_mean"]


# -- the cell's own files ------------------------------------------------------

def test_configuration_keeps_every_published_width_and_states_its_cuts():
    cell = spec.load_cell(CELL)
    config = cell.config
    for key, value in PUBLISHED.items():
        assert config[key] == value, key
    assert sorted(config["reduced"]) == [
        "n_routed_experts", "num_hidden_layers", "num_nextn_predict_layers",
        "vocab_size"]
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"], config["num_nextn_predict_layers"]) \
        == (21, 8, 19360, 0)
    assert (config["num_hidden_layers_published"],
            config["n_routed_experts_published"],
            config["vocab_size_published"],
            config["num_nextn_predict_layers_published"]) == (47, 64, 154880, 1)
    # Inside the guide's floors: a whole period and four more expert layers,
    # 8 experts, an eighth of the vocabulary.
    assert config["num_hidden_layers"] - config["first_k_dense_replace"] >= 4
    assert config["n_routed_experts"] >= 8
    assert config["vocab_size"] * 8 >= config["vocab_size_published"]
    for key in ("parameter_dtype", "rope_pairing", "weights", "router"):
        assert key in config["assumed"], key
    assert "v5e-8" in config["stands_for"] and "8 chips" in config["stands_for"]
    cfg = program.program_config(config)
    assert (cfg.n_routed_experts, cfg.held, cfg.first_expert) == (64, 8, 0)
    assert (cfg.latent_width, cfg.pool_width) == (576, 640)
    from distributed_tensorflow_tpu.models.glm4_moe_lite import (
        Glm4MoeLiteConfig)
    assert cfg == Glm4MoeLiteConfig.v5e8_share()


def test_cell_and_traffic_carry_the_parameters_the_issue_names():
    cell = spec.load_cell(CELL)
    assert cell.chips == 1
    sched = {k: cell.cell["scheduler"][k] for k in (
        "num_slots", "max_total_len", "cache_mode", "block_size", "megastep",
        "async_decode")}
    assert sched == {"num_slots": 16, "max_total_len": 1024,
                     "cache_mode": "paged", "block_size": 16, "megastep": 4,
                     "async_decode": True}
    assert cell.cell["trace_seconds"] == 2
    assert cell.cell["trace_modules"]["decode"]["prefix"] == "jit_decode_megastep("
    assert cell.cell["trace_modules"]["prefill"]["prefix"] == "jit_prefill_slots("
    mix = cell.traffic
    assert mix["kind"] == "open_loop_requests" and mix["sampling"] == "greedy"
    assert (mix["lead_in_s"], mix["shuffle_block"], mix["vocab_size"]) \
        == (10.0, 16, 19360)
    assert mix["arrivals"]["process"] == "poisson"
    assert mix["prompt_tokens"] == {"median": 192, "sigma": 0.6, "min": 64,
                                    "max": 384, "round_up_to": [128, 256, 384]}
    assert mix["output_tokens"] == {"median": 256, "sigma": 0.5, "min": 128,
                                    "max": 512}
    assert len(cell.cell["correct"]["reference_padded_lengths"]) <= 2
    reported = {m["name"] for m in cell.end_to_end}
    assert reported == {"serve_tokens_per_s", "setup_s"}
    names = [m["name"] for m in cell.per_layer]
    gpt2 = spec.load_cell("serve.gpt2-medium.chat-saturated")
    assert names[:10] == [m["name"] for m in gpt2.per_layer]
    assert names[10:] == ["decode_hbm_roofline_pct.serve",
                          "moe_load_max_over_mean.serve"]


def test_slot_arithmetic_quotes_the_engines_cache_geometry():
    from distributed_tensorflow_tpu.models import get_workload
    from distributed_tensorflow_tpu.models.gpt2 import PagedKVConfig

    cell = spec.load_cell(CELL)
    sched = cell.cell["scheduler"]
    blocks = sched["num_slots"] * sched["max_total_len"] // sched["block_size"] + 1
    workload = get_workload(cell.config["program"]["model"],
                            config=program.program_config(cell.config))
    geometry = workload.cache_geometry(PagedKVConfig(
        block_size=sched["block_size"], num_blocks=blocks))
    assert geometry["kind"] == "latent"
    assert (geometry["values_per_token_layer"], geometry["pool_width"],
            geometry["padding_values"]) == (576, 640, 64)
    assert geometry["bytes_per_token_layer"] == 1280
    assert geometry["bytes_per_token"] == 26880
    assert geometry["pool_bytes"] == 440832000
    text = cell.cell["num_slots_arithmetic"]
    for quoted in ("576 values", "640 wide", "1,280 B", "26,880 B",
                   "440,832,000 B", "16 x 64 + 1 blocks"):
        assert quoted in text, quoted


@pytest.mark.parametrize("seed", [1, 2**31 + 9, 3000000017])
def test_traffic_draws_its_lengths_and_its_slice(seed):
    cell = spec.load_cell(CELL)
    requests = traffic.open_loop_requests(cell.traffic, seed, 30.0)
    rate = cell.traffic["arrivals"]["rate_per_s"]
    assert len(requests) == round(rate * 40.0)
    assert {len(r.prompt) for r in requests} == {128, 256, 384}
    assert all(128 <= r.max_new_tokens <= 512 for r in requests)
    longest = max(len(r.prompt) + r.max_new_tokens for r in requests)
    assert longest <= cell.cell["scheduler"]["max_total_len"]
    ids = np.concatenate([r.prompt for r in requests])
    assert ids.min() >= 0 and ids.max() < 19360
    assert ids.max() > 19000       # the whole slice, not a corner of it
    assert traffic.prompt_lengths(cell.traffic) == [128, 256, 384]
    # The same work for every seed: lengths in the same order.
    other = traffic.open_loop_requests(cell.traffic, seed + 1, 30.0)
    assert [len(r.prompt) for r in other] == [len(r.prompt) for r in requests]


# -- the byte function -----------------------------------------------------------

def test_decode_step_bytes_against_a_hand_count():
    """The issue's arithmetic, in parameters: attention 21.76M a layer,
    dense MLP 62.91M, router 0.13M, one expert 9.44M, the head's 19,360
    rows 39.6M; a step with every held expert live reads 4.52 GB."""
    shape = program.shape_of(spec.load_cell(CELL).config)
    p = decode_bytes.weight_parameters(shape)
    attention = (2048 * 768 + 768 + 768 * 20 * 256 + 2048 * 576 + 512
                 + 512 * 20 * 448 + 20 * 256 * 2048)
    assert attention == pytest.approx(21.76e6, rel=2e-3)
    assert p["attention"] == 21 * (attention + 2 * 2048)
    assert p["dense_mlp"] == 3 * 2048 * 10240 == pytest.approx(62.91e6, rel=1e-3)
    assert p["router"] == 20 * (2048 * 64 + 64)
    assert p["one_routed_expert"] == 3 * 2048 * 1536
    assert p["shared_experts"] == 20 * p["one_routed_expert"]
    assert p["routed_experts_held"] == 20 * 8 * p["one_routed_expert"]
    assert p["head"] == 2048 * 19360 + 2048
    full = decode_bytes.decode_step_bytes(
        shape, active_experts_per_layer=8, live_positions=0)
    assert full["routed_experts"] == 2 * p["routed_experts_held"] \
        == pytest.approx(3.02e9, rel=2e-3)
    assert full["total"] == pytest.approx(4.52e9, rel=5e-3)
    assert full["routed_experts"] / full["total"] == pytest.approx(0.67, abs=0.01)
    # Experts with no token are not read; the live rows' latents are, at
    # 576 values a token and layer.
    some = decode_bytes.decode_step_bytes(
        shape, active_experts_per_layer=2.5, live_positions=6000)
    assert some["routed_experts"] == pytest.approx(
        full["routed_experts"] * 2.5 / 8)
    assert some["latent_cache"] == 2 * 21 * 576 * 6000
    assert some["shared_weights"] == full["shared_weights"]
    assert some["total"] == pytest.approx(sum(
        some[k] for k in ("shared_weights", "routed_experts", "latent_cache")))
    for bad in (dict(active_experts_per_layer=8.5, live_positions=0),
                dict(active_experts_per_layer=-1, live_positions=0),
                dict(active_experts_per_layer=1, live_positions=-5)):
        with pytest.raises(ValueError):
            decode_bytes.decode_step_bytes(shape, **bad)


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


def test_roofline_reader_divides_the_floor_by_the_step():
    cell = spec.load_cell(CELL)
    read = cell.reader({"name": "m", "reader": "decode_hbm_roofline_pct"})
    # Before the window 100 layer-steps with 2 experts live a layer and
    # step and 10 launches of 1,000 positions; by its close 300 and 30, so
    # within it 200 layer-steps at 5 and 20 launches of 4,000.
    start = {"moe_active_experts_per_step": 2.0, "moe_layer_steps": 100.0,
             "decode_live_positions": 1000.0, "iterations": 10.0}
    end = {"moe_active_experts_per_step": 4.0, "moe_layer_steps": 300.0,
           "decode_live_positions": 3000.0, "iterations": 30.0}
    launches = [(1.0, 1.04), (2.0, 2.04), (3.0, 3.06)]   # median 40 ms, 4 steps
    ctx, said = _context(cell, start, end, launches)
    value = read(ctx, module="decode", per="megastep")
    cost = decode_bytes.decode_step_bytes(
        program.shape_of(cell.config), active_experts_per_layer=5.0,
        live_positions=4000.0)
    assert value == pytest.approx(100 * cost["total"] / 819e9 / 0.010)
    assert 0 < value < 100
    (event, fields), = said
    assert event == "decode_hbm_floor"
    assert fields["active_experts_per_layer"] == pytest.approx(5.0)
    assert fields["live_positions"] == pytest.approx(4000.0)
    assert fields["step_ms"] == pytest.approx(10.0)


@pytest.mark.parametrize("case", ["parent_without_the_counter", "no_launch",
                                  "nothing_counted_in_the_window"])
def test_roofline_reader_reads_nothing_where_there_is_nothing(case):
    cell = spec.load_cell(CELL)
    read = cell.reader({"name": "m", "reader": "decode_hbm_roofline_pct"})
    stats = {"moe_active_experts_per_step": 4.0, "moe_layer_steps": 300.0,
             "decode_live_positions": 3000.0, "iterations": 30.0}
    start, end, launches = dict(stats, moe_layer_steps=100.0, iterations=10.0), \
        stats, [(1.0, 1.04)]
    if case == "parent_without_the_counter":
        start, end = {"iterations": 10.0}, {"iterations": 30.0}
    elif case == "no_launch":
        launches = []
    else:
        start = dict(stats)
    ctx, said = _context(cell, start, end, launches)
    assert read(ctx, module="decode", per="megastep") is None
    assert not said


def test_scheduler_stat_reader():
    cell = spec.load_cell(CELL)
    read = cell.reader({"name": "m", "reader": "scheduler_stat"})
    end = {"moe_load_max_over_mean": 1.75, "moe_layer_steps": 12.0}
    args = dict(key="moe_load_max_over_mean", nonzero_key="moe_layer_steps")
    assert read({"stats_end": end}, **args) == 1.75
    assert read({"stats_end": dict(end, moe_layer_steps=0.0)}, **args) is None
    assert read({"stats_end": {"iterations": 3.0}}, **args) is None
    assert read({}, **args) is None
    with open(os.path.join(spec.BENCH_DIR, "layer_metrics",
                           "moe_load_max_over_mean.serve.json")) as f:
        assert json.load(f)["args"] == args
