"""The `k-exaone-236b-a23b` configuration and its cell, on paper and on a
small hand-made trace: the configuration's parameter and byte reckoning
against the program's tree; `flops_hybrid` by hand on a small case; the
`rollout_hybrid` kind found by name, failing at once on a program that does
not know the model, its reference comparison failing on a reading one
precision lower; the traffic's 128 mid-quantiles and 16 prompt strata; each
new metric's file naming a reader that exists and reads its number."""

import importlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_paths
from benchmark.lib import flops_hybrid, kind_rollout_hybrid, readers, weights
from benchmark.lib.harness import CONFIG_META_KEYS
from benchmark.lib.registry import Registry
from benchmark.lib.spans import Spans
from benchmark.lib.traffic import Traffic, longest_sequence, output_lengths, prompt_lengths
from benchmark.reference import kexaone_ref

from areal_tpu.models.qwen2 import ModelConfig, forward, init_params, param_shapes

REG = Registry(bench_paths.REPO)
CELL = "rollout-kexaone-mixedlen"
NEW_METRICS = {
    "chunk_roofline_hybrid": ("batch_field", "kernels"),
    "window_attention_device_ms.rollout": ("device_op_time", "kernels"),
    "full_attention_device_ms.rollout": ("device_op_time", "kernels"),
    "held_expert_matmul_device_ms.rollout": ("device_op_time", "kernels"),
    "held_expert_matmul_roofline": ("batch_field", "kernels"),
    "moe_held_expert_load_max_over_mean.rollout": ("counter_ratio", "decode engine"),
    "kv_window_rows_share_pct.rollout": ("counter_ratio", "decode engine"),
}


def _hf(**over):
    f = REG.cell(CELL)["config_file"]
    return dict({k: v for k, v in f.items() if k not in CONFIG_META_KEYS}, **over)


def _model_config(**over):
    return ModelConfig.from_hf_config(_hf(**over))


TINY = dict(vocab_size=256, hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16, sliding_window=16)


# -- the configuration ---------------------------------------------------------


def test_configuration_names_its_cut_and_its_deployment():
    entry = next(c for c in REG.bench["configs"] if c["name"] == "k-exaone-236b-a23b")
    f = REG.cell(CELL)["config_file"]
    assert entry["reduced"] == f["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size", "num_nextn_predict_layers"]
    assert (f["num_hidden_layers"], f["num_experts"], f["vocab_size"]) == (5, 16, 19200)
    assert (f["num_experts_published"], f["expert_first"], f["vocab_size_published"]) == (
        128, 0, 153600)
    assert f["num_nextn_predict_layers"] == 0 and "8 chips share each layer" in f["deployment"]
    assert f["source"].endswith("K-EXAONE-236B-A23B/blob/main/config.json")
    assert sum("[family]" in a for a in f["assumed"]) >= 4
    # the per-layer lists are carried whole; the program reads the first five
    assert len(f["layer_types"]) == 48 and _model_config().layer_types == (
        "sliding_attention",) * 3 + ("full_attention", "sliding_attention")


def test_parameter_and_byte_reckoning_against_the_programs_tree():
    """ISSUE 30's reckoning, leaf by leaf of `param_shapes`."""
    cfg = _model_config()
    shapes = param_shapes(cfg)
    size = lambda t: sum(int(np.prod(s)) for s in jax.tree.leaves(  # noqa: E731
        t, is_leaf=lambda x: isinstance(x, tuple)))
    attn = size(shapes["layers_0"]["attn"])
    assert attn == 113_246_208 + 2 * 128  # q, o 50,331,648 each; k, v 6,291,456 each; two norms
    assert size(shapes["layers_0"]["mlp"]) == 339_738_624  # the dense layer's MLP
    assert size(shapes["layers_0"]) == 452_997_376 == attn + 339_738_624 + 2 * 6144
    mlp = shapes["layers_1"]["mlp"]
    assert int(np.prod(mlp["router_kernel"])) == 786_432 and mlp["router_bias"] == (128,)
    assert int(np.prod(mlp["gate_kernel"])) * 3 == 16 * 37_748_736
    assert size(shapes["layers_1"]) == 755_773_824  # a sparse layer, as the issue reckons it
    assert size(shapes["embed"]) + size(shapes["lm_head"]) == 235_929_600
    total = size(shapes)
    assert total == 3_712_028_416 == REG.cell(CELL)["config_file"]["parameters"]
    assert total == flops_hybrid.param_count(cfg)
    assert 7.42e9 < 2 * total < 7.43e9  # bf16 bytes
    # the caches: 4 KiB a token a layer; the full layer's pool and the four rings
    assert flops_hybrid.kv_row_bytes(cfg) == 4096
    d = REG.cell(CELL)["experiment"]["decode"]
    slots, ctx, page = d["max_running_requests"], d["context_length"], d["page_size"]
    assert slots * ctx * 4096 * 1 == 2_147_483_648
    assert 4 * slots * 2 * page * 4096 == 268_435_456  # layers x slots x ring pages x rows x bytes
    assert 5 * slots * ctx * 4096 > 10.7e9  # one pool for all five layers would not fit


@pytest.mark.parametrize("width", ["tiny", "published", "published_full_depth"])
def test_param_count_is_the_trees_leaf_count(width):
    over = {"tiny": TINY, "published": {},
            "published_full_depth": dict(num_hidden_layers=48, num_experts=128, vocab_size=153600)}
    cfg = _model_config(**over[width])
    tree = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    assert flops_hybrid.param_count(cfg) == sum(int(x.size) for x in jax.tree.leaves(tree))
    if width == "published_full_depth":
        assert 236e9 < flops_hybrid.param_count(cfg) < 237.5e9  # "236B", without its MTP layer


def test_flops_hybrid_by_hand_on_a_small_case():
    cfg = _model_config(**TINY)
    d, hd, nH, nKV, M, E = 64, 16, 4, 2, 32, 16
    kinds = flops_hybrid.layer_kinds(cfg)
    assert kinds == {"dense": 1, "sparse": 4, "window": 4, "full": 1}
    attn = d * (nH + 2 * nKV) * hd + nH * hd * d + 2 * hd
    assert flops_hybrid.attention_params(cfg) == attn
    outside = d * 128 + 128 + 3 * d * M  # router, its bias, the shared expert
    assert flops_hybrid.sparse_layer_params_outside_routed(cfg) == outside
    assert flops_hybrid.held_pairs(cfg, 10) == 10 * 8 * 16 / 128 == 10.0
    assert flops_hybrid.experts_touched(cfg, 10) == 10 and flops_hybrid.experts_touched(cfg, 64) == E
    row = 2 * nKV * hd * 2
    assert flops_hybrid.kv_row_bytes(cfg) == row
    running, live = 10, 10 * 40  # contexts of 40: over the window of 16
    step = flops_hybrid.decode_step_needed_seconds(cfg, running, live, "TPU v5e")
    weights_once = 5 * (attn + 2 * d) + 3 * d * 96 + 4 * outside + d + 256 * d
    experts = 4 * 10 * 3 * d * M
    rows = 1 * live + 4 * running * 16
    want = (weights_once + experts) * 2 + rows * row + running * (5 * row + d * 2)
    assert step["bytes"] == want and step["expert_bytes"] == experts * 2
    assert step["window_rows_bytes"] == 4 * running * 16 * row
    assert step["full_rows_bytes"] == live * row
    flops = 5 * 2 * attn + 4 * nH * hd * (40 + 4 * 16) + 6 * d * 96 + 4 * (
        2 * outside + 1.0 * 2 * 3 * d * M) + 2 * d * 256
    assert flops_hybrid.forward_flops_per_token(cfg, 40) == flops
    assert step["flops"] == running * flops and step["bound"] == "memory"
    one = flops_hybrid.expert_matmuls_needed_seconds(cfg, running, "TPU v5e")
    assert one["bytes"] == (10 * 3 * d * M + 10 * (2 * d + 4 * M)) * 2
    with pytest.raises(KeyError):
        flops_hybrid.decode_step_needed_seconds(cfg, 1, 1, "TPU v9")


def test_a_step_of_the_cell_on_paper():
    """ISSUE 30's arithmetic for 45 requests live at a mean context of 3.8k."""
    cfg = _model_config()
    step = flops_hybrid.decode_step_needed_seconds(cfg, 45, 45 * 3800, "TPU v5e")
    assert step["expert_bytes"] == 4 * 16 * 37_748_736 * 2  # 4.8 GB: all 16 held, four layers
    assert 0.55 < step["expert_bytes"] / step["bytes"] < 0.65
    assert 0.69e9 < step["full_rows_bytes"] < 0.71e9 and step["window_rows_bytes"] < 0.1e9
    assert step["bound"] == "memory" and 0.009 < step["seconds"] < 0.0105


# -- the kind --------------------------------------------------------------------


def test_kind_is_found_by_name_and_reuses_the_rollout_kinds_parts():
    from benchmark.lib import kind_rollout

    cell = REG.cell(CELL)
    kind = importlib.import_module(f"benchmark.lib.kind_{cell['kind']}")
    assert kind is kind_rollout_hybrid and callable(kind.run)
    for part in ("warm_engine", "ClosedLoop", "check_sample"):
        assert getattr(kind, part) is getattr(kind_rollout, part)
    assert set(kind_rollout.COUNTERS) < set(kind.COUNTERS)
    d = cell["experiment"]["decode"]
    assert (d["max_running_requests"], d["context_length"], d["page_size"],
            d["new_tokens_per_chunk"], d["dtype"], d["kv_cache_dtype"]) == (
        64, 8192, 128, 128, "bfloat16", "bfloat16")
    assert "max_prefill_tokens" not in d  # ISSUE 30 names none: the engine's default
    assert cell["experiment"]["rollout"]["max_concurrent_rollouts"] == 128
    assert cell["chips"] == 1 and cell["traffic"] == "agent-mixedlen-rollout"
    assert longest_sequence(cell["traffic_file"]) == d["context_length"]
    e2e = [m["name"] for m in REG.metrics("end_to_end", CELL)]
    assert e2e == ["rollout_tokens_per_s", "setup_s"]


def test_a_program_that_does_not_know_the_model_fails_at_once(tmp_path):
    f = REG.cell(CELL)["config_file"]

    def write(model_dir, **over):
        os.makedirs(model_dir, exist_ok=True)
        with open(os.path.join(model_dir, "config.json"), "w") as fh:
            json.dump(_hf(**over), fh)
        return str(model_dir)

    mc = kind_rollout_hybrid.require_mixed_stack(write(tmp_path / "kexaone"), f)
    assert mc.layer_types[3] == "full_attention"
    # the parent: `exaone_moe` is not in its registry
    with pytest.raises(NotImplementedError, match="not in the registry"):
        kind_rollout_hybrid.require_mixed_stack(write(tmp_path / "other", model_type="exaone5"), f)
    # a program that read it as another model
    with pytest.raises(RuntimeError, match="layer types"):
        kind_rollout_hybrid.require_mixed_stack(write(tmp_path / "dense", model_type="qwen3"), f)


def test_the_router_bias_is_redrawn_from_the_seed_at_a_scale_a_sigmoid_can_bear():
    cfg = _model_config(**TINY)
    seeded = weights.seeded_params(cfg, 7)
    a = kind_rollout_hybrid.redraw_router_bias(seeded, 7)
    b = kind_rollout_hybrid.redraw_router_bias(seeded, 7)
    c = kind_rollout_hybrid.redraw_router_bias(seeded, 2**31 + 7)
    bias = lambda p, i: np.asarray(p[f"layers_{i}"]["mlp"]["router_bias"], np.float32)  # noqa: E731
    assert float(np.std(bias(seeded, 1))) > 0.3  # weights.py's N(0, 0.5^2)
    assert 0.005 < float(np.std(np.r_[bias(a, 1), bias(a, 2), bias(a, 3)])) < 0.015
    np.testing.assert_array_equal(bias(a, 2), bias(b, 2))
    assert (bias(a, 2) != bias(c, 2)).any() and (bias(a, 1) != bias(a, 2)).any()
    # nothing else moves
    same = jax.tree_util.tree_map_with_path(
        lambda path, x, y: str(path[-1].key) == "router_bias" or bool((x == y).all()), seeded, a)
    assert all(jax.tree.leaves(same))


def _program_logprobs(params, cfg, ids):
    T = len(ids)
    logits = forward(params, jnp.asarray(ids), jnp.arange(T), jnp.zeros(T, jnp.int32), cfg)
    lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return np.asarray(lp[jnp.arange(T - 1), jnp.asarray(ids[1:])])


@pytest.mark.parametrize("what", ["bf16_compute", "float8_weights", "rope_on_the_full_layers",
                                  "no_scaling", "window_of_all"])
def test_what_the_kinds_comparison_catches_and_what_it_lets_pass(what):
    """bf16 compute against the float32 reference reading the same bf16
    weights passes; a reading one precision lower (the weights rounded to
    float8's 3 mantissa bits) fails, and so does a wrong piece of the layer."""
    import dataclasses

    cfg = ModelConfig.from_hf_config(_hf(**TINY), dtype="bfloat16", param_dtype="bfloat16")
    params = kind_rollout_hybrid.redraw_router_bias(weights.seeded_params(cfg, 11), 11)
    ids = np.random.default_rng(5).integers(1, 256, 96).astype(np.int32)
    ref, margin = kexaone_ref.token_logprobs(params, cfg, ids, with_margins=True)
    run_cfg, run_params = cfg, params
    if what == "float8_weights":
        run_params = jax.tree.map(
            lambda x: x.astype(jnp.float8_e4m3fn).astype(x.dtype) if x.ndim >= 2 else x, params)
    elif what == "rope_on_the_full_layers":
        run_cfg = dataclasses.replace(cfg, nope_full_layers=False)
    elif what == "no_scaling":
        run_cfg = dataclasses.replace(cfg, routed_scaling_factor=1.0)
    elif what == "window_of_all":
        run_cfg = dataclasses.replace(cfg, layer_types=("sliding_attention",) * 5)
    c = kind_rollout_hybrid.compare_with_reference(
        what, _program_logprobs(run_params, run_cfg, ids), ref, margin)
    assert c["ok"] is (what == "bf16_compute"), c
    assert 0.1 < c["clear_share"] < 0.9 and c["p90_abs"] <= c["max_abs"]


def test_tolerances_are_stated():
    assert 0 < kexaone_ref.MEAN_ABS_TOL < kexaone_ref.P90_ABS_TOL < 1.0
    assert 0 < kexaone_ref.NEAR_TIE_MARGIN < 0.1
    src = open(kexaone_ref.__file__).read()
    assert 'default_matmul_precision("highest")' in src and "pallas" not in src.lower()
    assert src.count("[family]") >= 4  # each departure noted at its line


# -- the traffic ---------------------------------------------------------------


def test_the_traffics_128_mid_quantiles_and_16_prompt_strata():
    t = REG.cell(CELL)["traffic_file"]
    assert (t["n_samples"], t["epoch_groups"], t["inflight_groups"], t["prompt_strata"]) == (
        8, 16, 16, 16)  # ISSUE 30's: 16 groups (128 requests) in flight
    outs = output_lengths(t["output_len"], 128)
    assert len(outs) == 128 and outs == sorted(outs)
    assert min(outs) >= 32 and max(outs) == 2048  # the clip holds the tail's last quantiles
    assert 380 <= outs[63] <= 384 <= outs[64] <= 390  # median 384
    plens = prompt_lengths(t["prompt_len"], 16)
    assert plens[0] == 1184 and plens[-1] == 5984 and len(set(plens)) == 16
    assert all(b - a == 320 for a, b in zip(plens, plens[1:]))
    traffic = Traffic(t, 19200, 2**31 + 3)
    groups = [traffic.group(i) for i in range(16)]  # one epoch, one cycle of prompts
    assert sorted(n for g in groups for n in g.output_lens) == outs
    assert sorted(len(g.prompt) for g in groups) == plens
    assert all(int(g.prompt.max()) < 19200 and int(g.prompt.min()) >= 1 for g in groups)
    assert max(len(g.prompt) + max(g.output_lens) for g in groups) <= 8192
    scales = traffic.cohort_scales(16)
    assert min(scales) > 0.1 and max(scales) < 1.0 and len(set(scales)) == 16


# -- the metrics ---------------------------------------------------------------


def _trace(chunks: int, steps_each: int = 128):
    """A device plane as the v5e writes it: `chunks` executions of jit_chunk,
    a token step of which holds four window reads, one full read and three
    grouped matmuls a sparse layer, named as the compiled program names them."""
    ops, t, modules = [], 1000.0, []
    for _ in range(chunks):
        start = t
        for _ in range(steps_each):
            for layer in range(5):
                name, dur = (("%paged_attention.3", 9000.0) if layer == 3
                             else (f"%paged_attention_window.{layer}", 500.0))
                ops.append([f"{name} custom-call bf16[64,64,1024]", t, dur])
                t += dur
                if layer:
                    for rd in ("%ragged-dot-none.1", "%ragged-dot-none", "%ragged-dot-none.2"):
                        ops.append([f"{rd} custom-call bf16[360,2048]", t, 2000.0])
                        t += 2000.0
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
    work, fields = kind_rollout_hybrid.traced_work(
        trace, (0.0, end), 128, 45.0, 45 * 3800.0, cfg, "TPU v5e")
    assert work["steps"] == 256 and work["needed_step"]["bound"] == "memory"
    ctx = {"spans": Spans(), "window": (0, 1), "trace": trace, "trace_window": (0.0, end),
           "work": work, "fields": fields, "model_config": cfg, "device_kind": "TPU v5e",
           "chips": 1, "counters": {
               "moe_pairs_total": 45 * 4 * 256, "moe_hot_expert_pairs_total": 7 * 4 * 256,
               "moe_absent_pairs_total": 315 * 4 * 256,
               "kv_window_rows_read_total": 4 * 45 * 128 * 256,
               "kv_full_rows_read_total": 45 * 3800 * 256}}
    got = readers.read(spec, ctx)
    step_s = (4 * 500.0 + 9000.0 + 4 * 3 * 2000.0) / 1e9  # the hand-made trace's token step
    want = {
        "window_attention_device_ms.rollout": 4 * 500.0 / 1e6,
        "full_attention_device_ms.rollout": 9000.0 / 1e6,
        "held_expert_matmul_device_ms.rollout": 4 * 3 * 2000.0 / 1e6,
        "moe_held_expert_load_max_over_mean.rollout": 16 * 7 / 45,
        "kv_window_rows_share_pct.rollout": 100 * 4 * 128 / (4 * 128 + 3800),
        "chunk_roofline_hybrid": 100 * work["needed_step"]["seconds"] / step_s,
        "held_expert_matmul_roofline": 100 * flops_hybrid.expert_matmuls_needed_seconds(
            cfg, 45.0, "TPU v5e")["seconds"] / (3 * 2000.0 / 1e9),
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


def test_the_cell_reports_the_rollout_metrics_whose_definitions_carry_over():
    names = [m["name"] for m in REG.metrics("per_layer", CELL)]
    carried = ["decode_slot_occupancy_pct.rollout", "decode_queue_ms.rollout",
               "decode_discarded_pct.rollout", "rollout_tpot_p95_ms", "chunk_device_ms.rollout",
               "prefill_device_ms.rollout", "device_idle_pct.rollout"]
    assert set(carried) | set(NEW_METRICS) == set(names)
    # the two kernels of the two kinds of cache do not read each other's time
    import re

    full = REG.layer_metric("full_attention_device_ms.rollout")["args"]["pattern"]
    window = REG.layer_metric("window_attention_device_ms.rollout")["args"]["pattern"]
    assert re.search(full, "%paged_attention.7 custom-call") and not re.search(
        full, "%paged_attention_window.7 custom-call")
    assert re.search(window, "%paged_attention_window custom-call") and not re.search(
        window, "%paged_attention.7 custom-call")
