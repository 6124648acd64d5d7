"""Smoke tests for the serve entrypoints' driver contract: ONE parseable
JSON line from ``serve.py`` and from ``bench.py --mode=serve``.

Marked ``slow`` (excluded from tier-1, like test_bench_smoke.py) — each
subprocess compiles the tiny GPT-2 prefill + decode programs cold.  The
continuous-batching entrypoint smokes additionally carry ``serve_slow``
(they compile one slot-prefill program per distinct prompt length on top
of the decode step), so either marker alone keeps them out of tier-1.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(cmd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    proc = subprocess.run(
        [sys.executable] + cmd,
        capture_output=True, text=True, timeout=1200, cwd=REPO, env=env,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [l for l in proc.stdout.strip().splitlines() if l.strip()]
    assert lines, f"no stdout; stderr: {proc.stderr[-2000:]}"
    return json.loads(lines[-1])  # the contract: last line is the JSON


@pytest.mark.slow
def test_serve_entrypoint_prints_one_json_line():
    out = _run([os.path.join(REPO, "serve.py"), "--model=gpt2",
                "--steps=16", "--prompt_len=8", "--max_new_tokens=4",
                "--max_batch_size=8"])
    for key in ("model", "requests", "completed", "tokens_per_sec",
                "p50_latency_ms", "p99_latency_ms", "avg_batch_occupancy",
                "batches", "checkpoint_step"):
        assert key in out, f"missing {key!r} in {out}"
    assert out["completed"] == 16
    assert out["tokens_per_sec"] > 0
    assert out["p99_latency_ms"] >= out["p50_latency_ms"]
    assert out["checkpoint_step"] is None  # fresh-init smoke path


@pytest.mark.slow
@pytest.mark.serve_slow
def test_serve_entrypoint_continuous_prints_one_json_line():
    out = _run([os.path.join(REPO, "serve.py"), "--model=gpt2",
                "--continuous", "--num_slots=8", "--steps=16",
                "--prompt_lens=6,8", "--max_new_tokens=6",
                "--min_new_tokens=2"])
    assert out["scheduler"] == "continuous"
    for key in ("tokens_per_sec", "slot_occupancy", "iterations",
                "admissions_per_iter", "retirements_per_iter",
                "ttft_p50_ms", "ttft_p99_ms", "tpot_mean_ms",
                "p50_latency_ms", "p99_latency_ms"):
        assert key in out, f"missing {key!r} in {out}"
    assert out["completed"] == 16
    assert 0.0 < out["slot_occupancy"] <= 1.0
    assert out["ttft_p99_ms"] >= out["ttft_p50_ms"]


@pytest.mark.slow
@pytest.mark.serve_slow
def test_serve_entrypoint_paged_int8_prints_one_json_line():
    out = _run([os.path.join(REPO, "serve.py"), "--model=gpt2",
                "--continuous", "--cache_mode=paged", "--block_size=8",
                "--kv_dtype=int8", "--num_slots=8", "--steps=16",
                "--prompt_lens=6,8", "--max_new_tokens=6",
                "--min_new_tokens=2"])
    assert out["scheduler"] == "continuous"
    assert out["cache_mode"] == "paged"
    assert out["kv_dtype"] == "int8"
    assert out["completed"] == 16
    assert out["kv_hbm_bytes"] > 0
    assert out["block_size"] == 8
    assert 0 < out["blocks_high_water"] <= out["blocks_total"]
    assert out["blocks_per_request_mean"] > 0


@pytest.mark.slow
@pytest.mark.serve_slow
def test_serve_entrypoint_prefix_cache_prints_one_json_line():
    out = _run([os.path.join(REPO, "serve.py"), "--model=gpt2",
                "--continuous", "--cache_mode=paged", "--block_size=4",
                "--prefix_cache", "--shared_prefix_len=16",
                "--shared_prefix_groups=2", "--num_slots=8", "--steps=16",
                "--prompt_lens=6,8", "--max_new_tokens=6",
                "--min_new_tokens=2"])
    assert out["scheduler"] == "continuous"
    assert out["prefix_cache"] is True
    assert out["completed"] == 16
    assert out["prefix_hit_rate"] > 0
    assert out["prefill_tokens_skipped"] > 0
    assert out["prefix_cached_blocks"] >= 0
    assert len(out["tokens_checksum"]) == 16


@pytest.mark.slow
@pytest.mark.serve_slow
def test_serve_entrypoint_chunked_prints_one_json_line():
    out = _run([os.path.join(REPO, "serve.py"), "--model=gpt2",
                "--continuous", "--prefill_budget=8", "--num_slots=8",
                "--steps=12", "--prompt_lens=6,8,40", "--max_new_tokens=6",
                "--min_new_tokens=2"])
    assert out["scheduler"] == "continuous"
    assert out["completed"] == 12
    assert out["prefill_budget"] == 8
    # Every 40-token prompt takes 5 chunks, so chunks > requests.
    assert out["prefill_chunks"] > 12
    assert out["tpot_p99_ms"] >= out["tpot_p50_ms"] >= 0
    assert len(out["tokens_checksum"]) == 16


@pytest.mark.slow
@pytest.mark.serve_slow
def test_serve_entrypoint_megastep_prints_one_json_line():
    out = _run([os.path.join(REPO, "serve.py"), "--model=gpt2",
                "--continuous", "--megastep=4", "--num_slots=8",
                "--steps=12", "--prompt_lens=6,8", "--max_new_tokens=6",
                "--min_new_tokens=2"])
    assert out["scheduler"] == "continuous"
    assert out["completed"] == 12
    assert out["megastep"] == 4
    # One fused launch covers up to K tokens per slot: strictly fewer
    # launches than decoded tokens.
    assert 0 < out["megastep_launches"] < out["megastep_tokens"]
    assert out["tpot_p99_ms"] >= out["tpot_p50_ms"] >= 0
    assert len(out["tokens_checksum"]) == 16


@pytest.mark.slow
@pytest.mark.serve_slow
def test_serve_entrypoint_spec_prints_one_json_line():
    out = _run([os.path.join(REPO, "serve.py"), "--model=gpt2",
                "--continuous", "--spec_k=4", "--prompt_period=4",
                "--num_slots=8", "--steps=12", "--prompt_lens=8,12",
                "--max_new_tokens=8", "--min_new_tokens=4"])
    assert out["scheduler"] == "continuous"
    assert out["completed"] == 12
    assert out["spec_k"] == 4
    # The repetitive (motif-tiled) mix makes drafts land: accepted
    # tokens and launch amortization both show up in the counters.
    assert out["spec_launches"] > 0
    assert out["spec_acceptance_rate"] > 0
    assert 0 < out["megastep_launches"] < out["megastep_tokens"]
    assert len(out["tokens_checksum"]) == 16


@pytest.mark.slow
@pytest.mark.serve_slow
def test_serve_entrypoint_sampling_mix_prints_one_json_line():
    out = _run([os.path.join(REPO, "serve.py"), "--model=gpt2",
                "--continuous",
                "--sampling_mix=greedy:0.5,t0.8k40:0.3,t1.0p0.9:0.2",
                "--num_slots=8", "--steps=16", "--prompt_lens=6,8",
                "--max_new_tokens=6", "--min_new_tokens=2"])
    assert out["scheduler"] == "continuous"
    assert out["completed"] == 16
    assert out["sampling_mix"] == "greedy:0.5,t0.8k40:0.3,t1.0p0.9:0.2"
    assert out["sampling_configs"] == 3
    # The tentpole claim at the entrypoint: a heterogeneous mix shares
    # ONE compiled program set — nothing compiles after warmup, and the
    # cache holds the per-family programs, not one per config.
    assert out["compile_post_warmup"] == 0
    assert 0 < out["programs_cached"] <= 4
    assert out["compile_total"] == out["programs_cached"]


@pytest.mark.slow
@pytest.mark.serve_slow
def test_bench_serve_mode_prints_one_json_line():
    out = _run([os.path.join(REPO, "bench.py"), "--mode=serve",
                "--serve_requests=16"])
    for key in ("metric", "value", "unit", "device", "preset",
                "p50_latency_ms", "p99_latency_ms",
                "ttft_p50_ms", "tpot_mean_ms", "slot_occupancy",
                "fixed_tokens_per_sec", "continuous_speedup",
                "paged_tokens_per_sec", "paged_speedup",
                "paged_int8_tokens_per_sec", "kv_hbm_bytes",
                "kv_hbm_ratio_paged", "kv_hbm_ratio_paged_int8",
                "block_size", "num_blocks", "block_utilization",
                "queue_wait_p50_ms", "queue_wait_p99_ms", "trace_events"):
        assert key in out, f"missing {key!r} in {out}"
    assert out["unit"] == "tokens/sec"
    assert out["value"] > 0
    assert out["fixed_tokens_per_sec"] > 0
    assert out["paged_tokens_per_sec"] > 0
    assert "serve_tokens_per_sec" in out["metric"]
    # the trace-export smoke: the bench runs with the flight recorder on,
    # so the continuous runs must have recorded per-request spans
    assert out["trace_events"] > 0
    assert out["queue_wait_p99_ms"] >= out["queue_wait_p50_ms"] >= 0
    # the memory claim: paged <= 0.5x dense cache bytes, int8 <= 0.25x
    assert out["kv_hbm_bytes"]["paged"] < out["kv_hbm_bytes"]["dense"]
    assert out["kv_hbm_ratio_paged"] <= 0.5
    assert out["kv_hbm_ratio_paged_int8"] <= 0.25
    # the prefix-caching claim: shared-prefix traffic hits the cache and
    # the warm run's greedy tokens are bit-identical to the cold run's
    for key in ("prefix_hit_rate", "prefill_tokens_skipped",
                "ttft_speedup_prefix", "prefix_parity"):
        assert key in out, f"missing {key!r} in {out}"
    assert out["prefix_hit_rate"] > 0
    assert out["prefill_tokens_skipped"] > 0
    assert out["prefix_parity"] is True
    # the chunked-prefill claim: the skewed whale mix's inter-token gap
    # p99 improves (or at worst matches), the whale actually chunked, and
    # greedy output is bit-identical budget on vs off — alone and
    # composed with the prefix cache and the per-shard pool
    for key in ("tpot_p99_unchunked", "tpot_p99_chunked",
                "unchunked_tokens_per_sec", "chunked_tokens_per_sec",
                "chunked_prefill_budget"):
        assert key in out, f"missing {key!r} in {out}"
    assert out["tpot_p99_speedup_chunked"] >= 1.0
    assert out["chunked_prefill_chunks"] > 0
    assert out["chunked_parity"] is True
    assert out["chunked_prefix_parity"] is True
    assert out["chunked_prefix_skip_parity"] is True
    assert out["chunked_pershard_parity"] is True
    # the megastep claim: K fused decode steps per dispatch beat (or at
    # worst match) the per-token launch on the same traffic, at the same
    # greedy checksum
    for key in ("megastep", "megastep_tokens_per_sec",
                "megastep_base_tokens_per_sec", "megastep_launches",
                "megastep_base_launches"):
        assert key in out, f"missing {key!r} in {out}"
    assert out["megastep"] == 8
    assert out["megastep_parity"] is True
    assert out["megastep_speedup"] >= 1.0
    assert out["megastep_launches"] < out["megastep_base_launches"]
    # the speculative-decoding claim: on the repetitive mix the drafter
    # lands, the verifier emits more than one token per launch
    # (steps-per-token speedup > 1), and greedy output stays
    # bit-identical spec on vs off — alone and composed with chunked
    # prefill, the megastep, and the prefix cache
    for key in ("spec_k", "spec_steps_per_token",
                "spec_base_steps_per_token", "spec_launches",
                "spec_drafted", "spec_accepted"):
        assert key in out, f"missing {key!r} in {out}"
    assert out["spec_k"] == 4
    assert out["spec_parity"] is True
    assert out["spec_acceptance_rate"] > 0
    assert out["spec_speedup"] >= 1.0
    assert out["spec_steps_per_token"] < out["spec_base_steps_per_token"]
    assert out["spec_chunked_parity"] is True
    assert out["spec_megastep_parity"] is True
    assert out["spec_prefix_parity"] is True
    # the vectorized-sampling claim: a heterogeneous per-request mix
    # runs on ONE compiled program set (zero post-warmup compiles),
    # while the scalar fixed-batch path pays one program set per config
    for key in ("sampling_mix", "sampling_configs",
                "sampling_tokens_per_sec", "sampling_programs_cached",
                "sampling_compile_post_warmup",
                "sampling_scalar_program_sets"):
        assert key in out, f"missing {key!r} in {out}"
    assert out["sampling_configs"] == 3
    assert out["sampling_compile_post_warmup"] == 0
    assert out["sampling_scalar_program_sets"] == 3
    assert out["sampling_tokens_per_sec"] > 0
