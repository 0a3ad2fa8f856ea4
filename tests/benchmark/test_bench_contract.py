"""`BENCHMARK.json` against the contract's rules that can be checked on
paper, and against the files: every entry has its file and every file its
entry."""

import json
import os
import re

import pytest

import bench_paths
from benchmark.lib.readers import READERS
from benchmark.lib.registry import Registry

REG = Registry()
B = REG.bench
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
ALL_METRICS = B["end_to_end"] + B["per_layer"]


def test_top_level_keys_and_sizes():
    assert set(B) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(bench_paths.REPO, "BENCHMARK.json")) <= 64 * 1024
    assert isinstance(B["run_seconds"], int) and 1 <= B["run_seconds"] <= 51
    # a full check with the full 24 cells fits 43,200 s
    assert (2 + 14 * 24) * (B["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200
    assert 1 <= len(B["paths"]) <= 16 and len(B["command"]) <= 32
    for word in B["command"][1:]:
        if "/" in word:
            assert not word.startswith("/") and ".." not in word
            assert any(word.startswith(p + "/") for p in B["paths"])
    assert 1 <= len(B["configs"]) <= 24 and 1 <= len(B["workloads"]) <= 24
    assert 1 <= len(B["end_to_end"]) <= 16 and 1 <= len(B["per_layer"]) <= 128


@pytest.mark.parametrize("entry", B["configs"] + B["workloads"] + ALL_METRICS, ids=lambda e: e["name"])
def test_names_units_and_lines(entry):
    assert NAME.match(entry["name"])
    for key in ("config", "traffic"):
        if key in entry:
            assert NAME.match(entry[key])
    if "unit" in entry:
        assert UNIT.match(entry["unit"]) and entry["better"] in ("lower", "higher")
        assert entry["source"] in SOURCES
    for key in ("why", "layer", "source"):
        if key in entry:
            assert 1 <= len(entry[key]) <= 200 and "\n" not in entry[key] and "\t" not in entry[key]


def test_entries_have_just_the_keys_shown():
    for c in B["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"} and len(c["reduced"]) <= 16
    for w in B["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"} and w["chips"] in (1, 4)
    for m in B["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace") and 0.01 <= m["bound"] <= 0.1
    for m in B["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
    names = [e["name"] for e in ALL_METRICS]
    assert len(set(names)) == len(names)
    assert len({w["name"] for w in B["workloads"]}) == len(B["workloads"])
    assert len({(w["config"], w["traffic"]) for w in B["workloads"]}) == len(B["workloads"])
    assert "setup_s" in [m["name"] for m in B["end_to_end"]]


def test_every_cell_reports_setup_another_metric_and_a_layer_metric():
    for w in B["workloads"]:
        e2e = [m["name"] for m in REG.metrics("end_to_end", w["name"])]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert REG.metrics("per_layer", w["name"])
    cells = {w["name"] for w in B["workloads"]}
    for m in B["per_layer"]:
        target = next(e for e in B["end_to_end"] if e["name"] == m["moves"])
        where = set(m.get("workloads", cells))
        assert where <= set(target.get("workloads", cells)), m["name"]
    assert sum(w["chips"] == 4 for w in B["workloads"]) <= max(1, len(B["workloads"]) // 4)
    used = {w["config"] for w in B["workloads"]}
    assert used == {c["name"] for c in B["configs"]}


def test_every_entry_has_its_file_and_every_file_its_entry():
    d = REG.dir

    def stems(sub):
        return {f[: -len(".json")] for f in os.listdir(os.path.join(d, sub)) if f.endswith(".json")}

    assert stems("workloads") == {w["name"] for w in B["workloads"]}
    assert stems("traffic") == {w["traffic"] for w in B["workloads"]}
    assert stems("layer_metrics") == {m["name"] for m in B["per_layer"]}
    assert {"benchmark/configs/" + s + ".json" for s in stems("configs")} == {c["file"] for c in B["configs"]}
    for m in B["per_layer"]:
        spec = REG.layer_metric(m["name"])
        assert spec["reader"] in READERS
        # the entry lives in BENCHMARK.json alone; the file is how to read it
        assert set(spec) <= {"reader", "args", "note"}, m["name"]
    layers = {m["layer"] for m in B["per_layer"]}
    perf = open(os.path.join(bench_paths.REPO, "PERF.md")).read()
    assert all(f"**{layer}**" in perf for layer in layers), "PERF.md's list of layers names each layer"
    for w in B["workloads"]:
        cell = REG.cell(w["name"])
        assert os.path.exists(os.path.join(d, "lib", f"kind_{cell['kind']}.py"))


def test_files_under_paths_are_named_from_the_allowed_characters():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for p in B["paths"]:
        for root, dirs, files in os.walk(os.path.join(bench_paths.REPO, p)):
            dirs[:] = [x for x in dirs if x != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(root, f), bench_paths.REPO)
                assert ok.match(rel) and len(rel) <= 200, rel


WIDTH = re.compile(r"(hidden|intermediate|latent|state|proj).*size|_dim$|_rank$|head_dim|num_experts_per_tok|expand")


@pytest.mark.parametrize("config", B["configs"], ids=lambda c: c["name"])
def test_configurations_state_their_source_and_cut_no_width(config):
    f = json.load(open(os.path.join(bench_paths.REPO, config["file"])))
    assert f["source"] == config["source"] and f["source"].startswith("https://")
    assert f["reduced"] == config["reduced"] and isinstance(f["assumed"], list)
    assert not [k for k in config["reduced"] if WIDTH.search(k)]
    published = {
        "qwen2.5-0.5b": dict(hidden_size=896, num_hidden_layers=24, num_attention_heads=14,
                             num_key_value_heads=2, intermediate_size=4864),
        "qwen2.5-1.5b": dict(hidden_size=1536, num_hidden_layers=28, num_attention_heads=12,
                             num_key_value_heads=2, intermediate_size=8960),
    }.get(config["name"], {})
    common = dict(vocab_size=151936, tie_word_embeddings=True, rope_theta=1e6, rms_norm_eps=1e-6,
                  max_position_embeddings=32768, model_type="qwen2") if published else {}
    for k, v in {**published, **common}.items():
        assert f[k] == v, (config["name"], k)
