"""BENCHMARK.json against the contract's form, and against the files it
names: what can be checked without a chip."""

import json
import os
import re

import pytest

from benchmark.harness import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(spec.REPO_DIR, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level_keys_and_sizes(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["benchmark"]
    assert bench["command"] == ["python3", "benchmark/run.py"]
    assert isinstance(bench["run_seconds"], int) and 1 <= bench["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(spec.REPO_DIR, "BENCHMARK.json")) < 64 * 1024


def test_entries_have_just_the_contracts_keys(bench):
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("benchmark/")
        assert all(NAME.match(k) for k in c["reduced"]) and len(c["reduced"]) <= 16
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in {"host_clock", "device_trace"}
        assert 0.01 <= m["bound"] <= 0.1
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in SOURCES and "\n" not in m["layer"]
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 4)


def test_every_cell_loads_and_reports_what_the_contract_asks(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    listed = {m["name"]: m for m in bench["per_layer"]}
    pairs = set()
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        pairs.add((w["config"], w["traffic"]))
        reported = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in reported and len(reported) >= 2
        assert cell.per_layer, w["name"]
        for metric in cell.per_layer:
            entry = listed[metric["name"]]
            # The cell's file and BENCHMARK.json agree on who reports what,
            # and a layer metric moves a metric its cell reports.
            assert w["name"] in entry["workloads"]
            assert entry["moves"] in reported
            for key in ("layer", "unit", "better", "source", "moves"):
                assert metric[key] == entry[key], (metric["name"], key)
            assert callable(cell.reader(metric))
        for name, entry in listed.items():
            if w["name"] in entry["workloads"]:
                assert name in {m["name"] for m in cell.per_layer}
    assert len(pairs) == len(bench["workloads"])
    used = {w["config"] for w in bench["workloads"]}
    assert used == {c["name"] for c in bench["configs"]}


def test_configuration_files_state_source_reduced_and_assumed(bench):
    for c in bench["configs"]:
        with open(os.path.join(spec.REPO_DIR, c["file"])) as f:
            config = json.load(f)
        assert config["source"] == c["source"]
        assert sorted(config["reduced"]) == sorted(c["reduced"])
        for key in ("assumed", "precision", "stands_for", "program",
                    "reference", "shape"):
            assert key in config, (c["name"], key)
        widths = {"hidden_size", "intermediate_size", "n_embd", "n_inner",
                  "n_head", "num_attention_heads"}
        assert not any(key in widths or key.endswith(("_dim", "_rank"))
                       for key in c["reduced"])


def test_no_harness_file_names_a_cell_a_configuration_or_a_model(bench):
    words = {w["name"] for w in bench["workloads"]}
    words |= {c["name"] for c in bench["configs"]}
    words |= {w["traffic"] for w in bench["workloads"]}
    words |= {"gpt2", "gpt-2", "bert", "resnet", "dlrm"}
    harness = os.path.join(spec.BENCH_DIR, "harness")
    files = [os.path.join(harness, f) for f in os.listdir(harness)
             if f.endswith(".py")] + [os.path.join(spec.BENCH_DIR, "run.py")]
    for path in files:
        with open(path) as f:
            text = f.read().lower()
        for word in words:
            assert word.lower() not in text, (os.path.basename(path), word)
