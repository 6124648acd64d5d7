"""The learned-sparse-attention configuration (GLM-5.2's share) and its
serving cell, as far as the CPU can check them: the plain reference against
the program at the tiny fixture, the float8 control, a whole run of the
tiny cell through both pools, the configuration file against the catalog's
row, the cell's and the traffic's parameters, the byte and the FLOP
function against hand counts, the new readers on a made-up trace, and the
trace-module names against an engine that ran."""

import dataclasses
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import (decode_bytes_dsa, device, prefill_flops_dsa,
                               program, program_spans, serve, spec, traffic,
                               weights, xplane)
from benchmark.harness.drivers import DRIVERS
from benchmark.reference import precision
from benchmark.tests.conftest import FIXTURES

CELL = "serve.glm-5.2.longdoc-saturated"
DSA_FIXTURES = os.path.join(FIXTURES, "glm_dsa")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"

# GLM-5.2's config.json, the widths and what else the cut leaves alone.
PUBLISHED = {
    "hidden_size": 6144, "intermediate_size": 12288,
    "moe_intermediate_size": 2048, "num_attention_heads": 64,
    "num_key_value_heads": 64, "head_dim": 192, "q_lora_rank": 2048,
    "kv_lora_rank": 512, "qk_head_dim": 256, "qk_nope_head_dim": 192,
    "qk_rope_head_dim": 64, "v_head_dim": 256, "index_n_heads": 32,
    "index_head_dim": 128, "index_topk": 2048, "index_topk_freq": 4,
    "n_shared_experts": 1, "num_experts_per_tok": 8, "n_group": 1,
    "topk_group": 1, "routed_scaling_factor": 2.5, "rms_norm_eps": 1e-5,
    "max_position_embeddings": 1048576, "norm_topk_prob": True,
    "scoring_func": "sigmoid", "topk_method": "noaux_tc",
    "tie_word_embeddings": False, "model_type": "glm_moe_dsa",
    "rope_interleave": True, "indexer_rope_interleave": True,
    "rope_parameters": {"rope_theta": 8000000, "rope_type": "default"},
}
REDUCED = {"num_hidden_layers": (78, 5), "first_k_dense_replace": (3, 1),
           "n_routed_experts": (256, 8), "vocab_size": (154880, 19360),
           "num_nextn_predict_layers": (1, 0)}


def _tiny_cell():
    return spec.load_cell(
        "serve.glm-dsa-tiny",
        manifest=os.path.join(DSA_FIXTURES, "BENCHMARK.json"),
        data_dir=DSA_FIXTURES)


def _tiny_system(seed=5):
    """The program's module in float32, seeded weights, a batch of rows
    longer than the selection."""
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
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=3e-5, rtol=1e-5)


def test_the_fp8_control_is_a_different_forward():
    config, _, params, tokens = _tiny_system()
    ref = program.reference_module(config)
    exact = ref.logits(precision.Exact(), config, params, tokens)
    low = ref.logits(precision.Fp8(), config, params, tokens)
    assert 1e-3 < float(jnp.max(jnp.abs(exact - low))) < 1.0


def test_the_reference_selects_on_full_layers_and_hands_the_set_down():
    """72 positions against a selection of 24: every layer's mask holds
    min(t + 1, 24) positions a query, none after it; the shared layers'
    is the full layer's before them, the second full layer's its own."""
    config, _, params, tokens = _tiny_system()
    ref = program.reference_module(config)
    masks = []
    ref.logits(precision.Exact(), config, params, tokens, masks)
    first, *shared, last = [np.asarray(m) for m in masks]
    assert first.shape == (3, 72, 72)
    assert (first.sum(-1) == np.minimum(np.arange(72) + 1, 24)).all()
    assert not np.triu(first, 1).any()
    for mask in shared:
        assert (mask == first).all()
    assert (last != first).any()
    # A selection is not the newest positions: old ones stay in it.
    assert first[:, -1, :24].any()


def test_the_reference_holds_the_share_the_configuration_names():
    """Held experts 2..5 of 8: the share's layer is its own experts' part
    plus the shared expert, and the router still chooses among all 8."""
    config, _, params, _ = _tiny_system()
    ref = program.reference_module(config)
    dot = precision.Exact()
    layer = params["layer_1"]
    first, held = config["first_expert_held"], config["n_routed_experts"]
    assert (first, held, config["n_routed_experts_published"]) == (2, 4, 8)
    x = jnp.asarray(np.random.default_rng(1).normal(size=(40, 64)),
                    jnp.float32)
    share = ref.expert_ffn(dot, config, x, layer)
    widths = np.asarray(ref._route(dot, config, x, layer["router"]))
    assert (widths > 0).sum(-1).tolist() == [config["num_experts_per_tok"]] * 40
    routed = sum(widths[:, first + e, None] * ref._mlp(
        dot, config, x, jax.tree.map(lambda w: w[e], layer["experts"]))
        for e in range(held))
    np.testing.assert_allclose(
        np.asarray(share),
        np.asarray(routed + ref._mlp(dot, config, x, layer["shared"])),
        atol=1e-5)
    assert 0 < (widths[:, first:first + held] > 0).sum() < (widths > 0).sum()


def test_a_whole_run_of_the_tiny_cell_is_correct_and_counts_what_it_reads():
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
    # Every second answer and the longest were checked, not all.
    checked = compared["served_logit_gap_max"]
    assert 0 < checked["requests"] < checked["requests_answered_in_full"]
    end = result["context"]["stats_end"]
    assert end["moe_experts_held"] == 4 and end["moe_layer_steps"] > 0
    assert end["moe_assignments_here"] > 0 and end["moe_assignments_absent"] > 0
    # Prompts of 32 to 80 against a selection of 24: every decode launch's
    # rows are longer than the selection.
    assert 24 <= end["decode_selected_positions"] < end["decode_live_positions"]
    share = cell.reader({"name": "m", "reader": "scheduler_stat_ratio_pct"})(
        result["context"], key="decode_selected_positions",
        over="decode_live_positions")
    assert share == pytest.approx(100 * end["decode_selected_positions"]
                                  / end["decode_live_positions"])
    assert 0 < share < 100
    assert end["prefill_chunks"] > result["attempted"]     # chunks of 16


# -- the cell's own files ------------------------------------------------------

def test_configuration_keeps_every_published_width_and_states_its_cuts():
    cell = spec.load_cell(CELL)
    config = cell.config
    for key, value in PUBLISHED.items():
        assert config[key] == value, key
    assert sorted(config["reduced"]) == sorted(
        list(REDUCED) + ["indexer_types", "mlp_layer_types"])
    for key, (published, run) in REDUCED.items():
        assert config[key] == run, key
        assert config[f"{key}_published"] == published, key
        assert f"published {published}" in config["reduced"][key], key
    assert config["indexer_types"] == ["full", "shared", "shared", "shared",
                                       "full"]
    assert config["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    # Inside the guide's floors: a whole period and four layers after the
    # leading dense ones, 8 experts, an eighth of the vocabulary.
    assert config["num_hidden_layers"] - config["first_k_dense_replace"] >= 4
    assert config["indexer_types"][1:] == ["shared"] * 3 + ["full"]
    assert config["n_routed_experts"] >= 8
    assert config["vocab_size"] * 8 >= config["vocab_size_published"]
    for key in ("shared_indexer", "selection", "indexer_precision",
                "index_norm_eps", "parameter_dtype", "rope_pairing",
                "weights", "router"):
        assert key in config["assumed"], key
    assert "v5e-256" in config["stands_for"]
    assert "32 chips" in config["stands_for"]
    assert "float32 copy" in config["reduced"]["num_hidden_layers"]
    cfg = program.program_config(config)
    assert (cfg.n_routed_experts, cfg.held, cfg.first_expert) == (256, 8, 0)
    assert (cfg.latent_width, cfg.pool_width) == (576, 640)
    assert (cfg.n_full_layers, cfg.n_moe_layers) == (2, 4)
    from distributed_tensorflow_tpu.models.glm_moe_dsa import GlmMoeDsaConfig
    assert cfg == GlmMoeDsaConfig.v5e256_share()


def test_configuration_holds_every_number_of_the_catalog_row():
    if not os.path.isfile(CATALOG):
        pytest.skip("no catalog of architectures here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "GLM-5.2")
    with open(os.path.join(spec.REPO_DIR, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "glm-5.2")
    assert entry["source"] == row["source_url"]
    config = spec.load_cell(CELL).config
    assert config["source"] == row["source_url"]
    assert sorted(entry["reduced"]) == sorted(config["reduced"])
    for key, value in row["config"].items():
        if key in entry["reduced"]:
            continue
        assert config[key] == value, key
    # The two layer lists are the published lists' entries 2-6.
    assert config["indexer_types"] == row["config"]["indexer_types"][2:7]
    assert config["mlp_layer_types"] == row["config"]["mlp_layer_types"][2:7]


def test_the_parameter_table_is_the_programs_own_count():
    """ISSUE 40's table, by the module's own shapes: 400.9M, 3 x 505.9M...
    in all 2,673M, 5.35 GB in bfloat16 and 10.69 GB in float32."""
    from distributed_tensorflow_tpu.models import get_workload

    config = spec.load_cell(CELL).config
    module = get_workload(config["program"]["model"],
                          config=program.program_config(config)).module
    abstract = jax.eval_shape(lambda: module.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    count = lambda tree: sum(int(np.prod(l.shape))
                             for l in jax.tree.leaves(tree))
    assert count(abstract["layer_0"]) == pytest.approx(400.9e6, rel=1e-3)
    for l in (1, 2, 3):
        assert count(abstract[f"layer_{l}"]) == pytest.approx(
            204.3e6 + 8 * 37.7e6, rel=1e-3)
    assert count(abstract["layer_4"]) == pytest.approx(515.7e6, rel=1e-3)
    assert count(abstract["embed"]) + count(abstract["head"]) \
        == pytest.approx(237.9e6, rel=1e-3)
    assert count(abstract) == pytest.approx(2673e6, rel=1e-3)
    assert "2,673M" in config["parameters"]["sum"]


def test_cell_and_traffic_carry_the_parameters_the_issue_names():
    cell = spec.load_cell(CELL)
    assert cell.chips == 1
    sched = {k: cell.cell["scheduler"][k] for k in (
        "num_slots", "max_total_len", "cache_mode", "block_size", "megastep",
        "async_decode")}
    assert sched == {"num_slots": 16, "max_total_len": 8192,
                     "cache_mode": "paged", "block_size": 16, "megastep": 4,
                     "async_decode": True}
    budget = cell.cell["scheduler"]["prefill_budget"]
    assert budget in (512, 1024)
    assert cell.cell["trace_seconds"] == 2
    assert cell.cell["trace_modules"]["decode"]["prefix"] == "jit_decode_megastep("
    assert cell.cell["trace_modules"]["prefill"]["prefix"] == "jit_prefill_slots("
    correct = cell.cell["correct"]
    # One padded length where ISSUE 40 named three: each is a program to
    # compile in a first run (the cell's ``reference_why``).
    assert correct["reference_padded_lengths"] == [8192]
    assert correct["reference_rows_per_forward"] == 1
    assert correct["reference_every"] == 8
    assert "342 s" in correct["reference_why"]
    mix = cell.traffic
    assert mix["kind"] == "open_loop_requests" and mix["sampling"] == "greedy"
    assert mix["vocab_size"] == 19360
    assert mix["arrivals"]["process"] == "poisson"
    assert mix["lead_in_s"] >= 12.0
    assert mix["lead_in_s"] >= 2 * mix["slots_full_after_s"]
    # One sequence of arrival instants for every seed: the order of the
    # first sixteen gaps moved this cell's window by a second of a sequence
    # of work that swings threefold (the mix's ``shuffle_block_why``).
    assert mix["shuffle_block"] == 1
    a, b = (traffic.open_loop_requests(mix, seed, 30.0)
            for seed in (2147483659, 3141592653))
    assert [r.due_s for r in a] == [r.due_s for r in b]
    assert [(len(r.prompt), r.max_new_tokens) for r in a] \
        == [(len(r.prompt), r.max_new_tokens) for r in b]
    assert any((x.prompt != y.prompt).any() for x, y in zip(a, b))
    assert mix["prompt_tokens"] == {
        "median": 4608, "sigma": 0.35, "min": 2561, "max": 7168,
        "round_up_to": [3072, 4096, 5120, 6144, 7168]}
    assert mix["output_tokens"] == {"median": 256, "sigma": 0.5, "min": 128,
                                    "max": 512}
    # Every prompt is longer than the selection, and whole chunks of the
    # one prefill program.
    index_topk = cell.config["index_topk"]
    assert all(n > index_topk and n % budget == 0
               for n in mix["prompt_tokens"]["round_up_to"])
    # 1.5 x the capacity swept at 16 slots, to a quarter request a second.
    arrivals = mix["arrivals"]
    assert arrivals["over_capacity"] == 1.5
    assert arrivals["rate_per_s"] == pytest.approx(
        round(4 * 1.5 * arrivals["capacity_per_s"]) / 4, abs=0.25)
    reported = {m["name"] for m in cell.end_to_end}
    assert reported == {"serve_tokens_per_s", "setup_s"}
    # Sets, not tails: a later PR may append to BENCHMARK.json's lists.
    names = {m["name"] for m in cell.per_layer}
    glm = {m["name"] for m in spec.load_cell(
        "serve.glm-4.7-flash.reason-saturated").per_layer}
    assert names - glm == {"decode_dsa_roofline_pct.serve",
                           "prefill_mfu_pct.serve",
                           "sparse_read_share_pct.serve"}
    assert glm - names == {"tpot_p95_ms.serve",
                           "decode_hbm_roofline_pct.serve"}


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
    assert geometry["kind"] == "latent_indexed"
    assert (geometry["values_per_token_layer"], geometry["pool_width"],
            geometry["padding_values"]) == (576, 640, 64)
    assert geometry["bytes_per_token_layer"] == 1280
    assert geometry["index_bytes_per_token_layer"] == 256
    assert geometry["bytes_per_token"] == 5 * 1280 + 2 * 256 == 6912
    assert geometry["latent_pool_bytes"] == 838963200
    assert geometry["index_pool_bytes"] == 67117056
    assert geometry["pool_bytes"] == 906080256
    text = cell.cell["num_slots_arithmetic"]
    for quoted in ("576 values", "640 wide", "1,280 B", "256 B", "6,912 B",
                   "906,080,256 B", "16 x 512 + 1 blocks", "16 slots"):
        assert quoted in text, quoted


@pytest.mark.parametrize("seed", [1, 2**31 + 9, 3000000017])
def test_traffic_draws_its_lengths_and_its_slice(seed):
    cell = spec.load_cell(CELL)
    mix = cell.traffic
    requests = traffic.open_loop_requests(mix, seed, 30.0)
    rate = mix["arrivals"]["rate_per_s"]
    assert len(requests) == round(rate * (30.0 + mix["lead_in_s"]))
    assert {len(r.prompt) for r in requests} <= {3072, 4096, 5120, 6144, 7168}
    assert len({len(r.prompt) for r in requests}) >= 4
    assert all(128 <= r.max_new_tokens <= 512 for r in requests)
    longest = max(len(r.prompt) + r.max_new_tokens for r in requests)
    assert longest <= cell.cell["scheduler"]["max_total_len"]
    ids = np.concatenate([r.prompt for r in requests])
    assert ids.min() >= 0 and ids.max() < 19360
    assert ids.max() > 19000       # the whole slice, not a corner of it
    assert traffic.prompt_lengths(mix) == [3072, 4096, 5120, 6144, 7168]
    # The same work for every seed: lengths in the same order.
    other = traffic.open_loop_requests(mix, seed + 1, 30.0)
    assert [len(r.prompt) for r in other] == [len(r.prompt) for r in requests]
    # The guard's arithmetic: a selection of 2,048 of a mean row of some
    # 5,000 positions.
    mean = np.mean([len(r.prompt) + r.max_new_tokens / 2 for r in requests])
    assert 35 < 100 * 2048 / mean < 45


# -- the byte and the FLOP function ----------------------------------------------

def test_decode_step_bytes_against_a_hand_count():
    """ISSUE 40's arithmetic, in parameters: attention 165.0M a layer, an
    indexer 9.4M, the dense MLP 226.5M, a router 1.6M, one expert 37.7M,
    the head's 19,360 rows 119M."""
    shape = program.shape_of(spec.load_cell(CELL).config)
    assert decode_bytes_dsa.layer_counts(shape) == {
        "layers": 5, "full": 2, "dense": 1, "sparse": 4}
    p = decode_bytes_dsa.weight_parameters(shape)
    attention = (6144 * 2048 + 2048 + 2048 * 64 * 256 + 6144 * 576 + 512
                 + 512 * 64 * 448 + 64 * 256 * 6144)
    assert attention == pytest.approx(165.0e6, rel=2e-3)
    assert p["attention"] == 5 * (attention + 2 * 6144)
    indexer = 2048 * 32 * 128 + 6144 * 128 + 2 * 128 + 6144 * 32
    assert indexer == pytest.approx(9.4e6, rel=5e-3)
    assert p["indexer"] == 2 * indexer
    assert p["dense_mlp"] == 3 * 6144 * 12288 == pytest.approx(226.5e6, rel=1e-3)
    assert p["router"] == 4 * (6144 * 256 + 256)
    assert p["one_routed_expert"] == 3 * 6144 * 2048 == pytest.approx(
        37.7e6, rel=2e-3)
    assert p["shared_experts"] == 4 * p["one_routed_expert"]
    assert p["routed_experts_held"] == 4 * 8 * p["one_routed_expert"]
    assert p["head"] == 6144 * 19360 + 6144
    full = decode_bytes_dsa.decode_step_bytes(
        shape, active_experts_per_layer=8, live_positions=0,
        selected_positions=0)
    assert full["shared_weights"] == pytest.approx(2.69e9, rel=5e-3)
    assert full["routed_experts"] == 2 * p["routed_experts_held"] \
        == pytest.approx(2.42e9, rel=5e-3)
    # 16 rows of 5,400 positions: 2,048 latent rows a row on each of the 5
    # layers, 1,152 B each; every index key on each of the 2 full layers.
    some = decode_bytes_dsa.decode_step_bytes(
        shape, active_experts_per_layer=3.2, live_positions=16 * 5400,
        selected_positions=16 * 2048)
    assert some["routed_experts"] == pytest.approx(
        full["routed_experts"] * 3.2 / 8)
    assert some["latent_cache"] == 5 * 1152 * 16 * 2048
    assert some["index_keys"] == 2 * 256 * 16 * 5400
    assert some["shared_weights"] == full["shared_weights"]
    assert some["total"] == pytest.approx(sum(
        some[k] for k in ("shared_weights", "routed_experts", "latent_cache",
                          "index_keys")))
    assert some["total"] == pytest.approx(3.9e9, rel=0.02)
    for bad in (dict(active_experts_per_layer=8.5, live_positions=0,
                     selected_positions=0),
                dict(active_experts_per_layer=1, live_positions=10,
                     selected_positions=11),
                dict(active_experts_per_layer=1, live_positions=10,
                     selected_positions=-1)):
        with pytest.raises(ValueError):
            decode_bytes_dsa.decode_step_bytes(shape, **bad)


def test_prefill_chunk_flops_against_a_hand_count():
    shape = program.shape_of(spec.load_cell(CELL).config)
    flops = prefill_flops_dsa.prefill_chunk_flops
    # The sums of keys: a chunk below the selection rises with t, one past
    # it reads index_topk a position, one across it both.
    assert prefill_flops_dsa._sum_min(0, 4, 10) == 1 + 2 + 3 + 4
    assert prefill_flops_dsa._sum_min(8, 4, 10) == 9 + 10 + 10 + 10
    assert prefill_flops_dsa._sum_min(20, 4, 10) == 40
    first = flops(shape, offset=0, tokens=1024, assignments_here_share=0.03)
    late = flops(shape, offset=4096, tokens=1024, assignments_here_share=0.03)
    projections = (6144 * 2048 + 2048 * 64 * 256 + 6144 * 576
                   + 512 * 64 * 448 + 64 * 256 * 6144)
    assert first["projections"] == 2 * 5 * 1024 * projections
    assert first["attention"] == 2 * 5 * 64 * 512 * (1024 * 1025 // 2)
    assert late["attention"] == 2 * 5 * 64 * 512 * 1024 * 2048
    assert first["index_scores"] == 2 * 2 * 32 * 128 * (1024 * 1025 // 2)
    assert late["index_scores"] == 2 * 2 * 32 * 128 * sum(
        range(4097, 4097 + 1024))
    assert late["indexer_projections"] == 2 * 2 * 1024 * (
        2048 * 4096 + 6144 * 128 + 6144 * 32)
    assert late["dense_mlp"] == 2 * 1024 * 3 * 6144 * 12288
    assert late["shared_experts"] == 2 * 4 * 1024 * 3 * 6144 * 2048
    # Each assignment to a held expert once: 8 choices a token, 3% of them
    # here, not 8 held experts over every position.
    assert late["routed_experts"] == pytest.approx(
        late["shared_experts"] * 8 * 0.03)
    assert late["head"] == 2 * 6144 * 19360
    assert late["total"] == pytest.approx(sum(
        v for k, v in late.items() if k != "total"))
    # About 3 GFLOP a position past the selection.
    assert late["total"] / 1024 == pytest.approx(3.2e9, rel=0.1)
    assert late["total"] > first["total"]
    for bad in (dict(offset=-1, tokens=4, assignments_here_share=0.1),
                dict(offset=0, tokens=0, assignments_here_share=0.1),
                dict(offset=0, tokens=4, assignments_here_share=1.5)):
        with pytest.raises(ValueError):
            flops(shape, **bad)


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


STATS = {"moe_active_experts_per_step": 4.0, "moe_layer_steps": 300.0,
         "decode_live_positions": 60000.0, "decode_selected_positions": 30000.0,
         "iterations": 30.0, "moe_assignments_here": 300.0,
         "moe_assignments_absent": 9700.0}


def test_decode_roofline_reader_divides_the_floor_by_the_step():
    cell = spec.load_cell(CELL)
    read = cell.reader({"name": "m", "reader": "decode_dsa_roofline_pct"})
    start = dict(STATS, moe_active_experts_per_step=2.0, moe_layer_steps=100.0,
                 decode_live_positions=30000.0,
                 decode_selected_positions=24000.0, iterations=10.0)
    launches = [(1.0, 1.06), (2.0, 2.06), (3.0, 3.09)]   # median 60 ms, 4 steps
    ctx, said = _context(cell, start, STATS, decode=launches)
    value = read(ctx, module="decode", per="megastep")
    # Within the window: 200 layer-steps at 5 experts, 20 launches of
    # 75,000 live and 33,000 selected positions.
    cost = decode_bytes_dsa.decode_step_bytes(
        program.shape_of(cell.config), active_experts_per_layer=5.0,
        live_positions=75000.0, selected_positions=33000.0)
    assert value == pytest.approx(100 * cost["total"] / 819e9 / 0.015)
    assert 0 < value < 100
    (event, fields), = said
    assert event == "decode_dsa_floor"
    assert fields["selected_positions"] == pytest.approx(33000.0)
    assert fields["step_ms"] == pytest.approx(15.0)


def test_prefill_mfu_reader_counts_the_chunks_the_spans_name():
    cell = spec.load_cell(CELL)
    read = cell.reader({"name": "m", "reader": "prefill_mfu_pct"})
    launches = [(1.0, 1.05), (2.0, 2.07), (3.0, 3.06)]
    ctx, said = _context(cell, STATS, STATS, prefill=launches)
    chunk = lambda off: (xplane.Event("dtt/serve/prefill_chunk", 1.0, 1.01),
                         {"offset": off, "chunk_tokens": 1024})
    ctx["program_spans"] = {"ended": {
        "dtt/serve/prefill_chunk": [chunk(0), chunk(4096)]}}
    value = read(ctx)
    shape = program.shape_of(cell.config)
    mean = sum(prefill_flops_dsa.prefill_chunk_flops(
        shape, offset=off, tokens=1024, assignments_here_share=0.03)["total"]
        for off in (0, 4096)) / 2
    assert value == pytest.approx(100 * 3 * mean / 197e12 / 0.18)
    assert 0 < value < 100
    (event, fields), = said
    assert event == "prefill_mfu"
    assert (fields["launches"], fields["chunk_spans"]) == (3, 2)
    assert fields["assignments_here_share"] == pytest.approx(0.03)


@pytest.mark.parametrize("case", ["parent_without_the_counter", "no_launch",
                                  "nothing_counted_in_the_window",
                                  "no_spans"])
def test_the_new_readers_read_nothing_where_there_is_nothing(case):
    cell = spec.load_cell(CELL)
    decode = cell.reader({"name": "m", "reader": "decode_dsa_roofline_pct"})
    prefill = cell.reader({"name": "m", "reader": "prefill_mfu_pct"})
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
        assert decode(ctx, module="decode", per="megastep") is None
    assert prefill(ctx) is None
    assert not [event for event, _ in said if event != "decode_dsa_floor"]


def test_the_new_metrics_read_nothing_from_the_other_families():
    """A cell of a family without an indexer: no ``decode_selected_
    positions`` in its scheduler's stats, so the share and the roofline
    read ``None`` and the line leaves them out."""
    cell = spec.load_cell("serve.glm-4.7-flash.reason-saturated")
    stats = {k: v for k, v in STATS.items()
             if k != "decode_selected_positions"}
    ctx, _ = _context(cell, dict(stats, iterations=10.0), stats,
                      decode=[(1.0, 1.04)])
    new = spec.load_cell(CELL)
    assert new.reader({"name": "m", "reader": "decode_dsa_roofline_pct"})(
        ctx, module="decode", per="megastep") is None
    assert new.reader({"name": "m", "reader": "scheduler_stat_ratio_pct"})(
        ctx, key="decode_selected_positions",
        over="decode_live_positions") is None


def test_layer_metric_files_name_readers_and_arguments_that_exist():
    cell = spec.load_cell(CELL)
    by_name = {m["name"]: m for m in cell.per_layer}
    for name in ("decode_dsa_roofline_pct.serve", "prefill_mfu_pct.serve",
                 "sparse_read_share_pct.serve"):
        metric = by_name[name]
        assert callable(cell.reader(metric))
        assert metric["layer"] == "engine and model step"
        assert metric["moves"] == "serve_tokens_per_s"
    assert by_name["sparse_read_share_pct.serve"]["args"] == {
        "key": "decode_selected_positions", "over": "decode_live_positions"}
    assert by_name["prefill_mfu_pct.serve"]["args"]["span"] \
        == "dtt/serve/prefill_chunk"


# -- the names the trace is read by ----------------------------------------------

def test_trace_module_names_are_the_names_an_engine_that_ran_gives():
    """The tiny cell's engine, run: its two programs are jitted under the
    names the cell's ``trace_modules`` look for, it is on record with the
    two sparse attention paths, and its prefill chunks' spans carry what
    the FLOP reader takes from them."""
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
    assert set(paths["slot_prefill"]) == {"latent_sparse_masked"}
    assert set(paths["slot_megastep"]) == {"latent_sparse_selected"}
    assert chunks and all(
        {"offset", "chunk_tokens", "context_tokens"} <= set(c) for c in chunks)
    assert program_spans.PREFIX == "dtt/"


def test_seed_phase_offers_every_seed_under_every_block(monkeypatch, tmp_path):
    """``tools/seed_phase.py`` on the tiny cell: one point a seed and a
    ``shuffle_block``, the window's tokens second by second, nothing
    compiled after the warm-up, the points kept in a file."""
    from benchmark.tools import seed_phase

    cell = _tiny_cell()
    monkeypatch.setattr(spec, "load_cell", lambda name: cell)
    monkeypatch.setattr(device, "require_chips",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.chdir(tmp_path)
    seed_phase.main(["--workload", "serve.glm-dsa-tiny", "--seeds", "11,12",
                     "--shuffle-blocks", "8,1", "--seconds", "2"])
    with open(tmp_path / "chiprun_out" / "seed_phase"
              / "serve.glm-dsa-tiny.json") as f:
        points = json.load(f)["points"]
    assert [(p["shuffle_block"], p["traffic_seed"]) for p in points] \
        == [(8, 11), (8, 12), (1, 11), (1, 12)]
    for p in points:
        assert p["failed"] == 0 and p["compile_post_warmup"] == 0
        assert sum(p["tokens_by_second"]) == p["tokens_in_window"] > 0
