"""Smoke tests for the serve entrypoints' driver contract: ONE parseable
JSON line from ``serve.py``.

Marked ``slow`` (excluded from tier-1) — each
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
