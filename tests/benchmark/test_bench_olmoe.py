"""The `olmoe-1b-7b` configuration and its cell, on paper and on a small
hand-made trace: the configuration holds the catalog's published keys and
cuts depth alone; `flops_moe` counts what the program's tree holds; the
`rollout_moe` kind is found by name, fails at once on a program that reads
the configuration as another model, and fills the context its metrics read;
each new metric's file names a reader that exists and reads its number."""

import importlib
import json
import os

import jax
import pytest

import bench_paths
from benchmark.lib import flops, flops_moe, kind_rollout_moe, readers
from benchmark.lib.harness import CONFIG_META_KEYS
from benchmark.lib.registry import Registry
from benchmark.lib.spans import Spans

from areal_tpu.models.qwen2 import ModelConfig, init_params

REG = Registry(bench_paths.REPO)
CELL = "rollout-olmoe-gsm8k"
# the model-configs guide's catalog entry, `config`, every key
CATALOG = {
    "attention_bias": False, "clip_qkv": None, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 1024, "max_position_embeddings": 4096, "model_type": "olmoe",
    "norm_topk_prob": False, "num_attention_heads": 16, "num_experts": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 16, "num_key_value_heads": 16,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "tie_word_embeddings": False, "vocab_size": 50304,
}
NEW_METRICS = {
    "chunk_roofline_moe": ("batch_field", "kernels"),
    "paged_attention_device_ms.rollout": ("device_op_time", "kernels"),
    "moe_expert_load_max_over_mean.rollout": ("counter_ratio", "decode engine"),
    "expert_matmul_device_ms.rollout": ("device_op_time", "kernels"),
    "expert_matmul_roofline": ("batch_field", "kernels"),
}


def _model_config(**over):
    f = REG.cell(CELL)["config_file"]
    hf = {k: v for k, v in f.items() if k not in CONFIG_META_KEYS}
    return ModelConfig.from_hf_config(dict(hf, **over))


def test_configuration_holds_the_catalogs_keys_and_cuts_depth_only():
    entry = next(c for c in REG.bench["configs"] if c["name"] == "olmoe-1b-7b")
    f = REG.cell(CELL)["config_file"]
    differs = sorted(k for k, v in CATALOG.items() if k not in f or f[k] != v)
    assert differs == ["num_hidden_layers"] == entry["reduced"] == f["reduced"]
    assert f["num_hidden_layers"] == 8 and "deployment" in f
    assert f["source"].endswith("OLMoE-1B-7B-0125-Instruct/blob/main/config.json")
    assert f["parameters"] == flops_moe.param_count(_model_config())


@pytest.mark.parametrize("width", ["tiny", "published", "published_full_depth"])
def test_param_count_is_the_trees_leaf_count(width):
    over = {"tiny": dict(vocab_size=256, hidden_size=64, intermediate_size=32,
                         num_attention_heads=4, num_key_value_heads=4, num_hidden_layers=3),
            "published": {}, "published_full_depth": dict(num_hidden_layers=16)}[width]
    cfg = _model_config(**over)
    tree = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    leaves = sum(int(x.size) for x in jax.tree.leaves(tree))
    assert flops_moe.param_count(cfg) == leaves
    if width == "published_full_depth":
        assert leaves == 6_919_161_856  # 6.92B; lib/flops.py would count one expert of 64
        assert flops.param_count(cfg) < leaves / 10
        # 1.18B a token multiplies with; "1.3B active" counts the embedding's 103M too
        assert flops_moe.active_param_count(cfg) == 1_178_994_688
    # one token multiplies with the active parameters but the embedding's rows, twice each
    d = cfg.hidden_size
    matmul = flops_moe.active_param_count(cfg) - d - cfg.num_hidden_layers * (
        2 * d + (cfg.num_attention_heads + cfg.num_key_value_heads) * flops.head_dim(cfg))
    assert flops_moe.forward_flops_per_token(cfg, 0) == 2 * matmul


def test_decode_step_counts_every_touched_experts_weights_once():
    cfg = _model_config()
    full = flops_moe.decode_step_needed_seconds(cfg, 64, 30000, "TPU v5e")
    assert flops_moe.experts_touched(cfg, 64) == 64 and flops_moe.experts_touched(cfg, 3) == 24
    assert full["expert_bytes"] == 8 * 64 * 3 * 2048 * 1024 * 2 == 6_442_450_944
    assert 0.7 < full["expert_bytes"] / full["bytes"] < 0.8 and full["bound"] == "memory"
    assert 0.010 < full["seconds"] < 0.012  # about 8.9 GB at 819 GB/s
    few = flops_moe.decode_step_needed_seconds(cfg, 3, 1500, "TPU v5e")
    assert few["expert_bytes"] == full["expert_bytes"] * 24 / 64
    one_layer = flops_moe.expert_matmuls_needed_seconds(cfg, 64, "TPU v5e")
    assert one_layer["bound"] == "memory" and 8 * one_layer["seconds"] < full["seconds"]
    with pytest.raises(KeyError):
        flops_moe.decode_step_needed_seconds(cfg, 64, 30000, "TPU v9")


def test_kind_is_found_by_name_and_reuses_the_rollout_kinds_parts():
    from benchmark.lib import kind_rollout

    cell = REG.cell(CELL)
    kind = importlib.import_module(f"benchmark.lib.kind_{cell['kind']}")
    assert kind is kind_rollout_moe and callable(kind.run)
    for part in ("build_engine", "warm_engine", "ClosedLoop", "check_sample"):
        assert getattr(kind, part) is getattr(kind_rollout, part)
    assert set(kind_rollout.COUNTERS) < set(kind.COUNTERS)
    assert cell["experiment"]["decode"]["max_running_requests"] == 64
    # rollout-1.5b-gsm8k's cell with the model and the slots changed, nothing else
    big = REG.cell("rollout-1.5b-gsm8k")
    assert cell["traffic"] == big["traffic"]
    assert dict(cell["experiment"]["decode"], max_running_requests=128) == big["experiment"]["decode"]
    for key in ("trace_after_seconds", "trace_seconds", "check_samples", "warmup_groups", "warmup_scale"):
        assert cell[key] == big[key]


def test_a_program_that_reads_the_configuration_as_another_model_fails_at_once(tmp_path):
    f = REG.cell(CELL)["config_file"]
    hf = {k: v for k, v in f.items() if k not in CONFIG_META_KEYS}

    def write(model_dir, **over):
        os.makedirs(model_dir, exist_ok=True)
        with open(os.path.join(model_dir, "config.json"), "w") as fh:
            json.dump(dict(hf, **over), fh)
        return str(model_dir)

    kind_rollout_moe.require_experts(write(tmp_path / "olmoe"), f)
    # what a program without an `olmoe` entry made of it: a dense model
    with pytest.raises(RuntimeError, match="full-width q/k norm"):
        kind_rollout_moe.require_experts(write(tmp_path / "dense", model_type="qwen2"), f)


def _trace(chunks: int, steps_each: int = 128, layers: int = 8):
    """A device plane as the v5e writes it: `chunks` executions of jit_chunk,
    each with a paged-attention call and three grouped matmuls a layer a
    step (named as the compiled program names them), 1 us and 2 us each."""
    ops, t = [], 1000.0
    modules = []
    for _ in range(chunks):
        start = t
        for _ in range(steps_each * layers):
            ops.append(["%paged_attention.9 custom-call bf16[64,16,2048]", t, 1000.0])
            t += 1000.0
            for name in ("%ragged-dot-none.1", "%ragged-dot-none", "%ragged-dot-none.2"):
                ops.append([f"{name} custom-call bf16[512,1024]", t, 2000.0])
                t += 2000.0
            ops.append(["%ragged-dot-metadata custom-call (s32[65], s32[64])", t, 10.0])
            t += 10.0
        modules.append(["jit_chunk(123)", start, t - start])
    return {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": modules}, {"name": "XLA Ops", "events": ops}]}]}, t


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_new_metric_names_a_reader_that_exists_and_reads_the_context(name):
    reader, layer = NEW_METRICS[name]
    spec = REG.layer_metric(name)
    entry = next(m for m in REG.bench["per_layer"] if m["name"] == name)
    assert spec["reader"] == reader and reader in readers.READERS
    assert entry["layer"] == layer and entry["workloads"] == [CELL]
    assert entry["moves"] == "rollout_tokens_per_s"
    cfg = _model_config()
    trace, end = _trace(chunks=2)
    work, fields = kind_rollout_moe.traced_work(trace, (0.0, end), 128, 64.0, 30000.0, cfg, "TPU v5e")
    assert work["steps"] == 256 and work["needed_step"]["bound"] == "memory"
    ctx = {"spans": Spans(), "window": (0, 1), "trace": trace, "trace_window": (0.0, end),
           "work": work, "fields": fields, "model_config": cfg, "device_kind": "TPU v5e",
           "chips": 1, "counters": {"moe_pairs_total": 512 * 8 * 256,
                                    "moe_hot_expert_pairs_total": 16 * 8 * 256}}
    got = readers.read(spec, ctx)
    step_s = 8 * (1000.0 + 3 * 2000.0 + 10.0) / 1e9  # the hand-made trace's token step
    want = {
        "paged_attention_device_ms.rollout": 8 * 1000.0 / 1e6,
        "expert_matmul_device_ms.rollout": 8 * 3 * 2000.0 / 1e6,
        "moe_expert_load_max_over_mean.rollout": 64 * 16 / 512,
        "chunk_roofline_moe": 100 * work["needed_step"]["seconds"] / step_s,
        "expert_matmul_roofline": 100 * flops_moe.expert_matmuls_needed_seconds(
            cfg, 64.0, "TPU v5e")["seconds"] / (3 * 2000.0 / 1e9),
    }[name]
    assert got == pytest.approx(want, rel=1e-9)
    # where the program has no such span, counter or kernel (the parent): nothing, no raise
    bare = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [["jit_chunk(1)", 0.0, 5.0]]},
        {"name": "XLA Ops", "events": [["%fusion.1 fusion f32[8]", 0.0, 5.0]]}]}]}
    empty = dict(ctx, trace=bare, trace_window=(0.0, 10.0), counters={}, fields={})
    if reader != "batch_field":
        assert readers.read(spec, empty) is None
    assert readers.read(spec, dict(empty, trace=None, work=None)) is None


def test_chunk_roofline_is_not_attached_to_the_moe_cell():
    names = [m["name"] for m in REG.metrics("per_layer", CELL)]
    assert "chunk_roofline" not in names and set(NEW_METRICS) <= set(names)
    big = [m["name"] for m in REG.metrics("per_layer", "rollout-1.5b-gsm8k")]
    assert "chunk_roofline" in big and set(big) - {"chunk_roofline"} < set(names)
    e2e = [m["name"] for m in REG.metrics("end_to_end", CELL)]
    assert e2e == ["rollout_tokens_per_s", "setup_s"]
