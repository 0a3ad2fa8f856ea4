"""The `sdar-30b-a3b-chat` configuration and its cell, on paper and on a small
hand-made trace: every catalog key carried and the one cut named, the
configuration's parameter and byte reckoning against the program's tree;
`flops_diffusion` by hand at the cell's sizes; the `rollout_diffusion` kind
found by name, failing at once on a program that does not know the model; the
rebuilt denoise states of a response; the comparison failing on a reading one
precision lower; the cell ISSUE 36's parameter for parameter; each new
metric's file naming a reader that was there and reading its number."""

import importlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_paths
from benchmark.lib import flops_diffusion, kind_rollout_diffusion, readers, weights
from benchmark.lib.harness import CONFIG_META_KEYS
from benchmark.lib.registry import Registry
from benchmark.lib.spans import Spans
from benchmark.lib.traffic import longest_sequence
from benchmark.reference import sdar_ref

from areal_tpu.api.io_struct import ModelResponse
from areal_tpu.models.qwen2 import ModelConfig, forward, init_params, param_shapes

REG = Registry(bench_paths.REPO)
CELL = "rollout-sdar-gsm8k"
NEW_METRICS = {
    "diffusion_forwards_per_token.rollout": ("counter_ratio", "decode engine"),
    "diffusion_commit_forward_share_pct.rollout": ("counter_ratio", "decode engine"),
    "moe_block_expert_load_max_over_mean.rollout": ("counter_ratio", "decode engine"),
    "block_attention_device_ms.rollout": ("device_op_time", "kernels"),
    "block_expert_matmul_device_ms.rollout": ("device_op_time", "kernels"),
    "block_expert_matmul_roofline": ("batch_field", "kernels"),
    "chunk_roofline_diffusion": ("batch_field", "kernels"),
}
# the readers benchmark/lib/readers.py had before this cell: none is added
READERS_THERE = {"counter_ratio", "host_span", "device_module_time", "device_op_time",
                 "device_idle", "roofline", "batch_field"}
# the model-configs guide's catalog entry, `config`, every key
CATALOG = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 6144, "max_position_embeddings": 32768,
    "max_window_layers": 48, "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 768, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts": 128, "num_experts_per_tok": 8, "num_hidden_layers": 48,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06, "rope_scaling": None,
    "rope_theta": 1000000, "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936,
}


def _hf(**over):
    f = REG.cell(CELL)["config_file"]
    return dict({k: v for k, v in f.items() if k not in CONFIG_META_KEYS}, **over)


def _model_config(**over):
    return ModelConfig.from_hf_config(_hf(**over))


TINY = dict(vocab_size=256, hidden_size=64, moe_intermediate_size=32, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, num_experts=16, num_experts_per_tok=4,
            mask_token_id=255)


# -- the configuration ---------------------------------------------------------


def test_configuration_carries_every_catalog_key_and_names_its_cut():
    entry = next(c for c in REG.bench["configs"] if c["name"] == "sdar-30b-a3b-chat")
    f = REG.cell(CELL)["config_file"]
    differs = sorted(k for k, v in CATALOG.items() if k not in f or f[k] != v)
    assert differs == f["reduced"] == entry["reduced"] == ["num_hidden_layers"]
    assert f["num_hidden_layers"] == 5
    assert (f["block_length"], f["mask_token_id"]) == (4, 151669) and f["mask_token_id"] < f["vocab_size"]
    assert f["source"] == entry["source"] and f["source"].endswith(
        "JetLM/SDAR-30B-A3B-Chat/blob/main/config.json")
    assert "first of ten pipeline stages" in f["deployment"]
    said = " ".join(f["assumed"])
    for what in ("block_length 4", "denoising_steps 4", "low_confidence_static",
                 "confidence_threshold 0.9", "mask_token_id 151,669", "no logit shift",
                 "aligned to absolute position 0", "commit pass is a forward of its own"):
        assert what in said, what
    cfg = _model_config()
    assert (cfg.block_length, cfg.mask_token_id, cfg.num_hidden_layers) == (4, 151669, 5)
    assert cfg.qk_norm and not cfg.qk_norm_full and not cfg.mixed and cfg.sliding_window is None


def test_parameter_and_byte_reckoning_against_the_programs_tree():
    """ISSUE 36's reckoning, leaf by leaf of `param_shapes`."""
    cfg = _model_config()
    shapes = param_shapes(cfg)
    size = lambda t: sum(int(np.prod(s)) for s in jax.tree.leaves(  # noqa: E731
        t, is_leaf=lambda x: isinstance(x, tuple)))
    layers = shapes["layers"]
    attn = layers["attn"]
    assert attn["q_kernel"] == (5, 2048, 32, 128) and attn["k_kernel"] == (5, 2048, 4, 128)
    assert attn["q_norm"] == attn["k_norm"] == (5, 128)
    proj = {k: v for k, v in attn.items() if k.endswith("kernel")}
    assert size(proj) == 5 * 18_874_368
    norms = size(attn) - size(proj) + size(layers["input_norm"]) + size(layers["post_attn_norm"])
    assert norms == 5 * 4_352
    mlp = layers["mlp"]
    assert mlp["router_kernel"] == (5, 2048, 128) and mlp["gate_kernel"] == (5, 128, 2048, 768)
    assert size(mlp) == 5 * (262_144 + 128 * 4_718_592)
    assert size(layers) == 5 * 623_120_640
    assert flops_diffusion.layer_params_outside_experts(cfg) == 18_874_368 + 4_352 + 262_144
    assert flops_diffusion.expert_params(cfg) == 4_718_592
    assert size(shapes["embed"]) + size(shapes["lm_head"]) == 622_329_856
    total = size(shapes)
    assert total == 3_737_935_104 == REG.cell(CELL)["config_file"]["parameters"]
    assert total == flops_diffusion.param_count(cfg)
    assert 7.47e9 < 2 * total < 7.48e9  # bf16 bytes
    # the pool: 10 KiB a token, slots x context
    d = REG.cell(CELL)["experiment"]["decode"]
    slots, ctx = d["max_running_requests"], d["context_length"]
    from benchmark.lib.flops import kv_bytes_per_token

    assert kv_bytes_per_token(cfg) == 10 * 1024
    pool = slots * ctx * 10 * 1024
    assert 1.67e9 < pool < 1.68e9
    resident = 2 * total + pool
    assert 9.1e9 < resident < 9.2e9 and resident > 0.25 * 16e9  # the floor: a quarter of the chip


@pytest.mark.parametrize("width", ["tiny", "published", "published_full_depth"])
def test_param_count_is_the_trees_leaf_count(width):
    over = {"tiny": TINY, "published": {}, "published_full_depth": dict(num_hidden_layers=48)}
    cfg = _model_config(**over[width])
    tree = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    assert flops_diffusion.param_count(cfg) == sum(int(x.size) for x in jax.tree.leaves(tree))
    if width == "published_full_depth":
        assert 30e9 < flops_diffusion.param_count(cfg) < 31e9  # "30B"


def test_flops_diffusion_by_hand_at_the_cells_sizes():
    cfg = _model_config()
    running, live = 70.0, 70 * 600.0
    rows = running * 4
    touched = 128 * (1 - (120 / 128) ** rows)
    assert flops_diffusion.experts_touched(cfg, rows) == pytest.approx(touched)
    assert 127.9 < touched <= 128  # 280 rows x top-8 reach every expert
    assert 50 < flops_diffusion.experts_touched(cfg, 8) < 52  # two slots' blocks do not
    fwd = flops_diffusion.forward_needed_seconds(cfg, running, live, "TPU v5e", 0.8)
    outside = 5 * (18_874_368 + 4_352 + 262_144) + 2048 + 151936 * 2048
    experts = 5 * touched * 4_718_592
    kv = 10 * 1024
    assert fwd["bytes"] == pytest.approx(
        2 * (outside + experts) + live * kv + rows * (kv + 2048 * 2))
    assert fwd["expert_bytes"] == pytest.approx(2 * experts) and 6.0e9 < 2 * experts < 6.05e9
    per_position = 5 * (2 * 2048 * 40 * 128 + 2 * 4096 * 2048 + 4 * 604 * 4096 + 2 * 2048 * 128
                        + 8 * 2 * 4_718_592) + 0.8 * 2 * 2048 * 151936
    assert fwd["flops"] == pytest.approx(rows * per_position)
    assert fwd["bound"] == "memory" and fwd["seconds"] == pytest.approx(fwd["bytes"] / 819e9)
    assert 0.008 < fwd["seconds"] < 0.009
    # a commit pass alone reads no head
    commit = flops_diffusion.forward_needed_seconds(cfg, running, live, "TPU v5e", 0.0)
    assert fwd["bytes"] - commit["bytes"] == pytest.approx(2 * 151936 * 2048)
    mm = flops_diffusion.expert_matmuls_needed_seconds(cfg, running, "TPU v5e")
    pairs = rows * 8
    assert mm["bytes"] == pytest.approx(
        2 * (touched * 4_718_592 + pairs * (2 * 2048 + 4 * 768)))
    assert mm["flops"] == pairs * 2 * 4_718_592 and mm["bound"] == "memory"


# -- the kind ------------------------------------------------------------------


def test_kind_is_found_by_name_and_reads_this_models_config(tmp_path):
    assert REG.cell(CELL)["kind"] == "rollout_diffusion"
    kind = importlib.import_module(f"benchmark.lib.kind_{REG.cell(CELL)['kind']}")
    assert kind is kind_rollout_diffusion
    d = tmp_path / "model"
    d.mkdir()
    (d / "config.json").write_text(json.dumps(_hf()))
    mc = kind.require_block_diffusion(str(d), REG.cell(CELL)["config_file"])
    assert mc.num_experts == 128 and mc.block_length == 4
    # a program that reads the model as another one fails before anything is built
    (d / "config.json").write_text(json.dumps(_hf(block_length=8)))
    with pytest.raises(RuntimeError, match="the program read"):
        kind.require_block_diffusion(str(d), REG.cell(CELL)["config_file"])
    d8 = REG.cell(CELL)["experiment"]["decode"]
    from types import SimpleNamespace

    assert kind.forwards_per_chunk(SimpleNamespace(**d8), 4) == 160


def test_a_program_that_does_not_know_the_model_fails_at_once(tmp_path, monkeypatch):
    """What the parent does on this cell: the registry refuses the model type."""
    from areal_tpu.models import qwen2

    d = tmp_path / "model"
    d.mkdir()
    (d / "config.json").write_text(json.dumps(_hf()))
    monkeypatch.setattr(qwen2, "MODEL_TYPES",
                        tuple(t for t in qwen2.MODEL_TYPES if t != "sdar_moe"))
    with pytest.raises(NotImplementedError, match="sdar_moe"):
        kind_rollout_diffusion.require_block_diffusion(str(d), REG.cell(CELL)["config_file"])


def test_block_states_are_rebuilt_from_the_reveal_steps():
    """A prompt of 6 (two of the first block's positions its own), 9 tokens:
    the first block's two states, a whole block's four, and a last block cut
    by `max_new_tokens` left out."""
    resp = ModelResponse(
        input_tokens=[10, 11, 12, 13, 14, 15],
        output_tokens=[20, 21, 30, 31, 32, 33, 40, 41, 42],
        output_logprobs=[-0.1 * i for i in range(9)],
        output_reveal_steps=[1, 0, 2, 0, 3, 1, 0, 1, 2])
    blocks = kind_rollout_diffusion.block_states(resp, 4, 99)
    assert [b["base"] for b in blocks] == [4, 8]  # 12..15 holds three tokens of four
    first, second = blocks
    assert first["context"] == [10, 11, 12, 13] and second["context"] == first["context"] + [
        14, 15, 20, 21]
    assert [s["input"] for s in first["states"]] == [[14, 15, 99, 99], [14, 15, 99, 21]]
    assert [s["revealed"] for s in first["states"]] == [[(3, 21, 1)], [(2, 20, 0)]]
    assert [s["input"] for s in second["states"]] == [
        [99, 99, 99, 99], [99, 31, 99, 99], [99, 31, 99, 33], [30, 31, 99, 33]]
    assert [s["revealed"] for s in second["states"]] == [
        [(1, 31, 3)], [(3, 33, 5)], [(0, 30, 2)], [(2, 32, 4)]]
    many = [{"base": 4 * i} for i in range(40)]
    chosen = kind_rollout_diffusion.chosen_blocks(many)
    assert len(chosen) == 12 and chosen[0] is many[0] and chosen[-1] is many[-1]
    with pytest.raises(ValueError, match="reveal steps"):
        kind_rollout_diffusion.block_states(
            ModelResponse(input_tokens=[1], output_tokens=[2], output_logprobs=[0.0]), 4, 99)


@pytest.mark.parametrize("what", ["bf16_compute", "float8_weights", "float32_other_tokens"])
def test_comparison_with_the_reference_at_a_tiny_width(what):
    """The program in bf16 agrees with the float32 reference under the
    reference's tolerances; the reference one precision lower (weights at
    float8's 3 mantissa bits) fails, and so does a float32 reading of other
    tokens."""
    cfg = ModelConfig.from_hf_config(_hf(**dict(TINY, hidden_size=128, num_hidden_layers=4)),
                                     dtype="bfloat16", param_dtype="bfloat16")
    params = weights.seeded_params(cfg, 7)
    ids = np.random.default_rng(3).integers(1, 250, 200).astype(np.int32)
    ref = sdar_ref.forward_logits(params, cfg, ids)[np.arange(200), ids]
    if what == "bf16_compute":
        lg = forward(params, jnp.asarray(ids), jnp.arange(200), jnp.zeros(200, jnp.int32), cfg)
        got = np.asarray(jax.nn.log_softmax(lg.astype(jnp.float32), axis=-1))[np.arange(200), ids]
    elif what == "float8_weights":
        got = sdar_ref.forward_logits(sdar_ref.round_mantissa(params, 3), cfg, ids)[
            np.arange(200), ids]
    else:
        got = np.roll(ref, 1)
    c = kind_rollout_diffusion.compare_with_reference(what, got, ref, states=200)
    assert c["ok"] == (what == "bf16_compute"), c
    assert not kind_rollout_diffusion.compare_with_reference("none", [], [], 0)["ok"]


def test_round_mantissa_is_float8s_grid():
    x = jnp.asarray([1.0, 1.0625, 1.124, 1.126, -3.3, 0.0, 1e-3], jnp.float32)
    got = np.asarray(sdar_ref.round_mantissa({"a": x}, 3)["a"])
    want = np.asarray(x.astype(jnp.float8_e4m3fn).astype(jnp.float32))
    np.testing.assert_allclose(got[:5], want[:5])  # inside e4m3's exponent range
    assert got[5] == 0 and abs(got[6] - 1e-3) < 1e-3 / 16


# -- the traffic and the cell ----------------------------------------------------


def test_traffic_is_the_other_gsm8k_rollout_cells():
    cell = REG.cell(CELL)
    assert cell["traffic_file"] == REG.cell("rollout-1.5b-gsm8k")["traffic_file"]
    assert cell["traffic_file"] == REG.cell("rollout-olmoe-gsm8k")["traffic_file"]
    t = cell["traffic_file"]
    assert t["n_samples"] * t["inflight_groups"] == 256 and longest_sequence(t) == 1280


def test_cell_is_the_issues_parameter_for_parameter():
    cell = REG.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "sdar-30b-a3b-chat", "gsm8k-rollout", 1)
    d, r = cell["experiment"]["decode"], cell["experiment"]["rollout"]
    assert d == {"context_length": 1280, "max_running_requests": 128, "new_tokens_per_chunk": 128,
                 "page_size": 128, "dtype": "bfloat16", "kv_cache_dtype": "bfloat16",
                 "diffusion_steps": 4, "diffusion_strategy": "low_confidence_static",
                 "diffusion_threshold": 0.9}
    assert r["max_concurrent_rollouts"] == 256
    assert cell["experiment"]["gconfig"]["temperature"] == 1.0
    assert (cell["warmup_groups"], cell["warmup_scale"], cell["trace_after_seconds"],
            cell["trace_seconds"], cell["check_samples"]) == (16, 0.1, 15, 20, 6)
    entry = next(w for w in REG.bench["workloads"] if w["name"] == CELL)
    assert "static" in entry["why"] and "threshold" in entry["why"]
    # it reports the rollout metric and every per-layer metric all rollout cells share
    e2e = {m["name"] for m in REG.metrics("end_to_end", CELL)}
    assert e2e == {"rollout_tokens_per_s", "setup_s"}
    mine = {m["name"] for m in REG.metrics("per_layer", CELL)}
    shared = {m["name"] for m in REG.bench["per_layer"]
              if {"rollout-1.5b-gsm8k", "rollout-olmoe-gsm8k", "rollout-kexaone-mixedlen",
                  "rollout-qwen3next-mixedlen"} <= set(m.get("workloads", []))}
    assert mine == shared | set(NEW_METRICS) and len(shared) == 7
    # appended, nothing before it moved: the new cell and its metrics are the lists' last
    assert REG.bench["workloads"][-1]["name"] == CELL
    assert REG.bench["configs"][-1]["name"] == "sdar-30b-a3b-chat"
    assert [m["name"] for m in REG.bench["per_layer"][-7:]] == list(NEW_METRICS) or set(
        m["name"] for m in REG.bench["per_layer"][-7:]) == set(NEW_METRICS)
    for m in REG.bench["end_to_end"] + REG.bench["per_layer"]:
        if CELL in m.get("workloads", []) and m["name"] not in NEW_METRICS:
            assert m["workloads"][-1] == CELL


# -- the metrics ---------------------------------------------------------------


def _trace(chunks: int, forwards_each: int = 160):
    """A device plane as the v5e writes it (nanoseconds): `chunks` executions
    of jit_chunk_diffusion, a forward of which holds a block read and three
    grouped matmuls in each of five layers, named as the compiled program
    names them."""
    ops, t, modules = [], 1000.0, []
    for _ in range(chunks):
        start = t
        for _ in range(forwards_each):
            for layer in range(5):
                ops.append([f"%paged_attention_block.{layer} custom-call bf16[128,128,512]",
                            t, 80e3])
                t += 80e3
                for rd in ("%ragged-dot-none.1", "%ragged-dot-none", "%ragged-dot-none.2"):
                    ops.append([f"{rd} custom-call bf16[4224,768]", t, 600e3])
                    t += 600e3
        modules.append(["jit_chunk_diffusion(123)", start, t - start])
    return {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": modules}, {"name": "XLA Ops", "events": ops}]}]}, t


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_new_metric_names_a_reader_that_was_there_and_reads_the_context(name):
    reader, layer = NEW_METRICS[name]
    spec = REG.layer_metric(name)
    entry = next(m for m in REG.bench["per_layer"] if m["name"] == name)
    assert spec["reader"] == reader and reader in READERS_THERE and set(readers.READERS) == READERS_THERE
    assert entry["layer"] == layer and entry["workloads"] == [CELL]
    assert entry["moves"] == "rollout_tokens_per_s"
    assert entry["unit"] == ("%" if "roofline" in name or name.endswith("pct.rollout") else
                             "1" if "max_over_mean" in name else
                             "forwards/token" if "per_token" in name else "ms")
    cfg = _model_config()
    trace, end = _trace(chunks=2)
    running, live = 70.0, 70 * 600.0
    work, fields = kind_rollout_diffusion.traced_work(
        trace, (0.0, end), 160, running, live, 0.8, cfg, "TPU v5e")
    assert work["steps"] == 320 and work["needed_forward"]["bound"] == "memory"
    ctx = {"spans": Spans(), "window": (0, 1), "trace": trace, "trace_window": (0.0, end),
           "work": work, "fields": fields, "model_config": cfg, "device_kind": "TPU v5e",
           "chips": 1, "counters": {
               "moe_pairs_total": 70 * 4 * 8 * 5 * 320, "moe_hot_expert_pairs_total": 27 * 5 * 320,
               "generated_tokens_total": 15_000, "diffusion_slot_forwards_total": 70 * 320,
               "diffusion_commit_forwards_total": 14 * 320}}
    got = readers.read(spec, ctx)
    forward_s = 5 * (80e3 + 3 * 600e3) / 1e9  # the hand-made trace's forward
    want = {
        "diffusion_forwards_per_token.rollout": 70 * 320 / 15_000,
        "diffusion_commit_forward_share_pct.rollout": 20.0,
        "moe_block_expert_load_max_over_mean.rollout": 128 * 27 / (70 * 4 * 8),
        "block_attention_device_ms.rollout": 5 * 80e3 / 1e6,
        "block_expert_matmul_device_ms.rollout": 5 * 3 * 600e3 / 1e6,
        "chunk_roofline_diffusion": 100 * work["needed_forward"]["seconds"] / forward_s,
        "block_expert_matmul_roofline": 100 * flops_diffusion.expert_matmuls_needed_seconds(
            cfg, running, "TPU v5e")["seconds"] / (3 * 600e3 / 1e9),
    }[name]
    assert got == pytest.approx(want, rel=1e-9)
    if "roofline" in name:
        assert 0 < got < 100
    # where the program has no such span, counter or kernel (the parent): nothing, no raise
    bare = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [["jit_chunk(1)", 0.0, 5.0]]},
        {"name": "XLA Ops", "events": [["%fusion.1 fusion f32[8]", 0.0, 5.0]]}]}]}
    empty = dict(ctx, trace=bare, trace_window=(0.0, 10.0), counters={}, fields={})
    assert readers.read(spec, empty) is None
    _, none = kind_rollout_diffusion.traced_work(
        bare, (0.0, 10.0), 160, running, live, 0.8, cfg, "TPU v5e")
    assert set(none) <= {"chunk_roofline_diffusion"}  # no kernel of its own to read: no share
