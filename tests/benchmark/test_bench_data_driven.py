"""A cell, a configuration and a per-layer metric are added as files plus
entries, with no edit to a file that is there."""

import json
import os
import shutil

import bench_paths
from benchmark.lib import readers
from benchmark.lib.registry import Registry
from benchmark.lib.spans import Spans
from benchmark.lib.traffic import Traffic


def _write(path, obj):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f)


def test_new_cell_configuration_and_metric_are_found_by_name(tmp_path):
    root = str(tmp_path)
    shutil.copy(os.path.join(bench_paths.REPO, "BENCHMARK.json"), root)
    for sub in ("configs", "workloads", "traffic", "layer_metrics"):
        shutil.copytree(os.path.join(bench_paths.REPO, "benchmark", sub),
                        os.path.join(root, "benchmark", sub))
    before = {p: open(p).read() for p in
              [os.path.join(dp, f) for dp, _, fs in os.walk(os.path.join(root, "benchmark")) for f in fs]}

    bench = json.load(open(os.path.join(root, "BENCHMARK.json")))
    new_cfg = dict(json.load(open(os.path.join(root, "benchmark/configs/qwen2.5-1.5b.json"))),
                   hidden_size=2048, num_attention_heads=16, intermediate_size=11008,
                   num_hidden_layers=36, source="https://huggingface.co/Qwen/Qwen2.5-3B-Instruct")
    _write(os.path.join(root, "benchmark/configs/qwen2.5-3b.json"), new_cfg)
    bench["configs"].append({"name": "qwen2.5-3b", "source": new_cfg["source"],
                             "file": "benchmark/configs/qwen2.5-3b.json", "reduced": [], "why": "test"})
    cell = json.load(open(os.path.join(root, "benchmark/workloads/rollout-1.5b-gsm8k.json")))
    cell["experiment"]["decode"]["max_running_requests"] = 32
    _write(os.path.join(root, "benchmark/workloads/rollout-3b-long.json"), cell)
    _write(os.path.join(root, "benchmark/traffic/long-outputs.json"),
           dict(json.load(open(os.path.join(root, "benchmark/traffic/gsm8k-rollout.json"))),
                output_len={"dist": "lognormal", "median": 512, "sigma": 0.5, "lo": 64, "hi": 1024},
                inflight_groups=8))
    bench["workloads"].append({"name": "rollout-3b-long", "config": "qwen2.5-3b",
                               "traffic": "long-outputs", "chips": 1, "why": "test"})
    metric = {"name": "decode_preemptions_per_request.rollout", "layer": "decode engine", "unit": "1",
              "better": "lower", "source": "program_counter", "moves": "rollout_tokens_per_s",
              "workloads": ["rollout-3b-long"], "reader": "counter_ratio",
              "args": {"num": "preemptions_total", "den": [["prefills_total", "prefix_forks_total"]]}}
    _write(os.path.join(root, "benchmark/layer_metrics", metric["name"] + ".json"),
           {"reader": metric.pop("reader"), "args": metric.pop("args")})
    bench["per_layer"].append({k: metric[k] for k in
                               ("name", "unit", "better", "source", "layer", "moves", "workloads")})
    for m in bench["end_to_end"]:
        if m["name"].startswith("rollout_"):
            m["workloads"].append("rollout-3b-long")
    _write(os.path.join(root, "BENCHMARK.json"), bench)

    reg = Registry(root)
    got = reg.cell("rollout-3b-long")
    assert got["kind"] == "rollout" and got["config_file"]["hidden_size"] == 2048
    assert got["experiment"]["decode"]["max_running_requests"] == 32
    g = Traffic(got["traffic_file"], got["config_file"]["vocab_size"], 11).group(0)
    assert min(g.output_lens) >= 64 and len(g.output_lens) == 8
    names = [m["name"] for m in reg.metrics("per_layer", "rollout-3b-long")]
    assert metric["name"] in names and "decode_queue_ms.rollout" not in names
    assert "rollout_tokens_per_s" in [m["name"] for m in reg.metrics("end_to_end", "rollout-3b-long")]
    ctx = {"spans": Spans(), "window": (0, 1),
           "counters": {"preemptions_total": 3, "prefills_total": 10, "prefix_forks_total": 20}}
    assert readers.read(reg.layer_metric(metric["name"]), ctx) == 0.1
    # nothing that was there was edited
    assert all(open(p).read() == text for p, text in before.items())
    # and the old cells are as they were
    assert reg.cell("train-0.5b-gsm8k")["kind"] == "train"
