"""The `deepseek-v2` configuration and its cell `rollout-dsv2-longctx`: the
configuration's keys against the catalog's row, the parameter count leaf by
leaf, `flops_latent.py` on hand-worked cases, the kind and what it reuses, one
precision lower failing each bound, the traffic, and the data-driven cases
over the new cell and its seven metrics."""

import importlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_paths
from benchmark.lib import flops_latent, kind_rollout_latent, readers, weights
from benchmark.lib.harness import CONFIG_META_KEYS
from benchmark.lib.registry import Registry
from benchmark.lib.spans import Spans
from benchmark.lib.traffic import Traffic, longest_sequence, output_lengths, prompt_lengths
from benchmark.reference import deepseek_v2_ref

from areal_tpu.models import qwen2
from areal_tpu.models.qwen2 import ModelConfig, forward, param_shapes

REG = Registry(bench_paths.REPO)
CELL = "rollout-dsv2-longctx"
NEW_METRICS = {
    "latent_attention_device_ms.rollout": ("device_op_time", "kernels"),
    "latent_attention_roofline": ("batch_field", "kernels"),
    "chunk_roofline_latent": ("batch_field", "kernels"),
    "group_expert_matmul_device_ms.rollout": ("device_op_time", "kernels"),
    "group_expert_matmul_roofline": ("batch_field", "kernels"),
    "moe_group_expert_load_max_over_mean.rollout": ("counter_ratio", "decode engine"),
    "kv_latent_rows_per_slot_step.rollout": ("counter_ratio", "decode engine"),
}
# the model-configs guide's catalog entry for DeepSeek-V2, `config`, every key
CATALOG = {
    "attention_bias": False, "first_k_dense_replace": 1, "hidden_act": "silu",
    "hidden_size": 5120, "intermediate_size": 12288, "kv_lora_rank": 512,
    "max_position_embeddings": 163840, "model_type": "deepseek_v2",
    "moe_intermediate_size": 1536, "moe_layer_freq": 1, "n_group": 8, "n_routed_experts": 160,
    "n_shared_experts": 2, "norm_topk_prob": False, "num_attention_heads": 128,
    "num_experts_per_tok": 6, "num_hidden_layers": 60, "num_key_value_heads": 128,
    "q_lora_rank": 1536, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 0.707,
                     "mscale_all_dim": 0.707, "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 16, "scoring_func": "softmax",
    "seq_aux": True, "tie_word_embeddings": False, "topk_group": 3,
    "topk_method": "group_limited_greedy", "v_head_dim": 128, "vocab_size": 102400,
}
CUT = {"num_hidden_layers": 5, "n_routed_experts": 20, "vocab_size": 12800}


def _hf(**over):
    f = REG.cell(CELL)["config_file"]
    return dict({k: v for k, v in f.items() if k not in CONFIG_META_KEYS}, **over)


def _model_config(**over):
    return ModelConfig.from_hf_config(_hf(**over))


TINY = dict(vocab_size=256, hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
            num_attention_heads=4, num_key_value_heads=4, kv_lora_rank=32, q_lora_rank=48,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, n_routed_experts=4,
            num_experts_published=32, num_experts_per_tok=6)


def test_configuration_carries_every_catalog_key_and_cuts_three():
    entry = next(c for c in REG.bench["configs"] if c["name"] == "deepseek-v2")
    f = REG.cell(CELL)["config_file"]
    assert entry["source"] == f["source"] == (
        "https://huggingface.co/deepseek-ai/DeepSeek-V2/blob/main/config.json")
    assert sorted(entry["reduced"]) == sorted(f["reduced"]) == sorted(CUT)
    for key, value in CATALOG.items():
        assert f[key] == CUT.get(key, value), key
    assert (f["num_experts_published"], f["expert_first"], f["vocab_size_published"]) == (
        160, 0, 102400)
    assert f["parameters"] == 3145466880
    assert "8 chips share each layer" in f["deployment"] and "twelve pipeline" in f["deployment"]
    assert sum("[family]" in a for a in f["assumed"]) >= 6
    # no width among the cuts
    assert not any(k.endswith(("_dim", "_rank", "_size")) and k != "vocab_size" for k in CUT)


def test_the_parameter_count_leaf_by_leaf():
    """ISSUE 38's arithmetic, and the program's own tree."""
    cfg = _model_config()
    leaves = flops_latent.attention_leaves(cfg)
    assert leaves == {
        "q_a_kernel": 5120 * 1536, "q_a_norm": 1536, "q_b_kernel": 1536 * 24576,
        "kv_a_kernel": 5120 * 576, "kv_a_norm": 512, "kv_b_kernel": 512 * 32768,
        "o_kernel": 16384 * 5120}
    assert flops_latent.attention_params(cfg) == 149227520
    assert flops_latent.expert_params(cfg) == 23592960
    assert 3 * 5120 * cfg.shared_expert_intermediate_size == 47185920
    assert 5120 * 160 == 819200 and 3 * 5120 * 12288 == 188743680
    # the dense layer 0 whole: attention, two norms, the MLP
    assert 149227520 + 2 * 5120 + 188743680 == 337981440
    sparse = 149227520 + 2 * 5120 + 819200 + 47185920 + 20 * 23592960
    assert flops_latent.param_count(cfg) == 337981440 + 4 * sparse + 5120 + 2 * 12800 * 5120
    assert flops_latent.param_count(cfg) == 3145466880
    tree = jax.tree.leaves(param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    assert sum(int(np.prod(s)) for s in tree) == 3145466880
    shapes = param_shapes(cfg)["layers_1"]
    got = {k: int(np.prod(v)) for k, v in shapes["attn"].items()}
    assert got == leaves
    assert shapes["mlp"]["gate_kernel"] == (20, 5120, 1536)
    assert shapes["mlp"]["router_kernel"] == (5120, 160)


@pytest.mark.parametrize("width", ["tiny", "published", "published_uncut"])
def test_param_count_is_the_trees_leaf_count(width):
    over = {"tiny": TINY, "published": {}, "published_uncut": dict(
        n_routed_experts=160, vocab_size=102400, num_hidden_layers=60)}[width]
    cfg = _model_config(**over)
    tree = jax.tree.leaves(param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    assert flops_latent.param_count(cfg) == sum(int(np.prod(s)) for s in tree)


def test_flops_latent_by_hand():
    cfg = _model_config()
    assert flops_latent.latent_row_bytes(cfg) == 1152
    assert flops_latent.latent_row_flops(cfg) == 2 * 128 * (576 + 512) == 278528
    # at the ridge: 242 FLOP a byte against 197e12 / 819e9 = 240.5
    assert abs(278528 / 1152 - 241.8) < 0.1
    one = flops_latent.latent_attention_needed_seconds(cfg, 1e6, "TPU v5e")
    assert one["bound"] == "compute"
    assert one["seconds"] == pytest.approx(1e6 * 278528 / 197e12)
    assert one["bytes"] / 819e9 == pytest.approx(1e6 * 1152 / 819e9)
    assert one["seconds"] / (one["bytes"] / 819e9) == pytest.approx(1.0052, abs=1e-3)
    # 48 pairs a layer in expectation at 64 running; the experts they touch are counted
    e = flops_latent.expert_matmuls_needed_seconds(cfg, 48.0, 20.0, "TPU v5e")
    assert e["bytes"] == (20 * 23592960 + 48 * (2 * 5120 + 4 * 1536)) * 2
    assert e["flops"] == 48 * 2 * 23592960 and e["bound"] == "memory"
    few = flops_latent.expert_matmuls_needed_seconds(cfg, 20.0, 13.0, "TPU v5e")
    assert few["bytes"] == (13 * 23592960 + 20 * (2 * 5120 + 4 * 1536)) * 2
    # weights a step reads whatever the routing
    outside = (5 * (149227520 + 10240) + 188743680 + 4 * (819200 + 47185920) + 5120
               + 12800 * 5120)
    assert flops_latent.weights_outside_routed(cfg) == outside


def test_a_step_of_the_cell_on_paper():
    """ISSUE 38's reckoning: 64 slots at 9.7k live rows, 48 pairs a layer."""
    cfg = _model_config()
    rows, pairs, touched = 64 * 9700 * 5, 48.0 * 4, 20.0 * 4
    step = flops_latent.decode_step_needed_seconds(cfg, 64, rows, pairs, touched, "TPU v5e")
    outside = flops_latent.weights_outside_routed(cfg)
    want = ((outside + 4 * 20 * 23592960) * 2 + rows * 1152 + 64 * (5 * 1152 + 5120 * 2))
    assert step["bytes"] == want and step["bound"] == "memory"
    assert 0.0115 < step["seconds"] < 0.0125
    assert step["latent_rows_bytes"] == pytest.approx(3.58e9, rel=0.01)
    assert step["expert_bytes"] == pytest.approx(3.77e9, rel=0.01)
    flops = (64 * (5 * 2 * 149227520 + 6 * 5120 * 12288 + 4 * 2 * (819200 + 47185920)
                   + 3 * 2 * 23592960 + 2 * 5120 * 12800) + rows * 278528)
    assert step["flops"] == pytest.approx(flops)
    # a deeper batch crosses to the compute side: the kernel is at the ridge
    deep = flops_latent.decode_step_needed_seconds(cfg, 64, 64 * 16000 * 5, pairs, touched,
                                                   "TPU v5e")
    assert deep["seconds"] > step["seconds"]


def test_kind_is_found_by_name_and_reuses_the_rollout_kinds_parts():
    from benchmark.lib import kind_rollout

    cell = REG.cell(CELL)
    kind = importlib.import_module(f"benchmark.lib.kind_{cell['kind']}")
    assert kind is kind_rollout_latent and callable(kind.run)
    for part in ("warm_engine", "ClosedLoop", "check_sample", "build_engine"):
        assert getattr(kind, part) is getattr(kind_rollout, part)
    assert set(kind_rollout.COUNTERS) < set(kind.COUNTERS)
    d = cell["experiment"]["decode"]
    assert (d["max_running_requests"], d["context_length"], d["page_size"],
            d["new_tokens_per_chunk"], d["dtype"], d["kv_cache_dtype"],
            d["max_prefill_tokens"]) == (64, 16384, 128, 128, "bfloat16", "bfloat16", 16384)
    assert cell["experiment"]["rollout"]["max_concurrent_rollouts"] == 256
    assert cell["chips"] == 1 and cell["traffic"] == "agent-longctx-queued-rollout"
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

    mc = kind_rollout_latent.require_latent(write(tmp_path / "dsv2"), f)
    assert mc.latent and mc.moe_n_group == 8
    # the parent: `deepseek_v2` is not in its registry
    with pytest.raises(NotImplementedError, match="not in the registry"):
        kind_rollout_latent.require_latent(write(tmp_path / "other", model_type="deepseek_v9"), f)
    # a program that read it as another model
    with pytest.raises(RuntimeError, match="latent rank"):
        kind_rollout_latent.require_latent(
            write(tmp_path / "dense", model_type="qwen3", num_key_value_heads=128), f)


def _program_logprobs(params, cfg, ids):
    T = len(ids)
    logits = forward(params, jnp.asarray(ids), jnp.arange(T), jnp.zeros(T, jnp.int32), cfg)
    lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return np.asarray(lp[jnp.arange(T - 1), jnp.asarray(ids[1:])])


@pytest.mark.parametrize("what", ["bf16_compute", "float8_weights", "float8_pool_rows",
                                  "no_mscale", "no_group_limit", "no_scaling"])
def test_what_the_kinds_comparison_catches_and_what_it_lets_pass(what, monkeypatch):
    """bf16 compute against the float32 reference reading the same bf16
    weights passes; a reading one precision lower fails (the reference with
    its weights at float8's 3 mantissa bits; the latent rows rounded to float8
    as they are cached, here through a decode loop over a pool), and so does a
    wrong piece of the layer."""
    import dataclasses

    # one routing group of eight held, as the cell holds it (a chosen expert weighs 0.1)
    cfg = ModelConfig.from_hf_config(
        _hf(**dict(TINY, n_routed_experts=20, num_experts_published=160)),
        dtype="bfloat16", param_dtype="bfloat16")
    params = weights.seeded_params(cfg, 12)
    ids = np.random.default_rng(5).integers(1, 256, 160).astype(np.int32)
    ref, margin = deepseek_v2_ref.token_logprobs(params, cfg, ids, with_margins=True)
    run_cfg = cfg
    if what == "float8_weights":
        ref = deepseek_v2_ref.token_logprobs(params, cfg, ids, weight_bits=3)
    elif what == "no_mscale":
        run_cfg = dataclasses.replace(cfg, rope_yarn=(32.0, 1.0, 0.0, 0.0))
    elif what == "no_group_limit":
        run_cfg = dataclasses.replace(cfg, moe_n_group=1, moe_topk_group=1)
    elif what == "no_scaling":
        run_cfg = dataclasses.replace(cfg, routed_scaling_factor=1.0)
    if what == "float8_pool_rows":
        pad = qwen2._latent_pool_row
        monkeypatch.setattr(qwen2, "_latent_pool_row", lambda row, lanes: pad(
            row.astype(jnp.float8_e4m3fn).astype(row.dtype), lanes))
        got = _decode_logprobs(params, cfg, ids)
    else:
        got = _program_logprobs(params, run_cfg, ids)
    c = kind_rollout_latent.compare_with_reference(what, got, ref, margin)
    together = kind_rollout_latent.compare_all([(got, ref)])
    assert (c["ok"] and together["ok"]) is (what == "bf16_compute"), (c, together)
    assert c["p90_abs"] == together["p90_abs"] <= c["max_abs"]
    if what.startswith("float8"):  # one precision lower fails EACH bound
        assert not c["ok"] and not together["ok"], (c, together)


def _decode_logprobs(params, cfg, ids):
    """Token by token through a latent pool in the absorbed form."""
    T, bsz = len(ids), 16
    nb = -(-T // bsz)
    pool = {"latent": jnp.zeros((cfg.num_hidden_layers, 1 + nb, bsz, cfg.latent_row_lanes),
                                jnp.bfloat16)}
    table = jnp.arange(1, 1 + nb, dtype=jnp.int32)[None]
    step = jax.jit(lambda t, n, kp: qwen2.decode_step_paged(
        params, t, n, kp, {}, table, cfg, active=jnp.ones(1, bool), attn_impl="xla")[:2])
    out = []
    for t in range(T - 1):
        logits, pool = step(jnp.asarray(ids[t:t + 1]), jnp.asarray([t]), pool)
        out.append(jax.nn.log_softmax(logits[0].astype(jnp.float32))[ids[t + 1]])
    return np.asarray(out)


def test_tolerances_are_stated():
    assert 0 < deepseek_v2_ref.MEAN_ABS_TOL < deepseek_v2_ref.P90_ABS_TOL < 1.0
    assert 0 < deepseek_v2_ref.NEAR_TIE_MARGIN < 0.1
    src = open(deepseek_v2_ref.__file__).read()
    assert 'default_matmul_precision("highest")' in src and "pallas" not in src.lower()
    assert "areal_tpu" not in src.replace("areal_tpu/models", "")  # reads the tree, not the code
    assert src.count("[family]") >= 7  # each departure noted at its line
    assert "absorb" not in src.split('"""')[2]  # the expanded form only


# -- the traffic -----------------------------------------------------------------


def test_the_traffics_128_mid_quantiles_and_16_prompt_strata():
    t = REG.cell(CELL)["traffic_file"]
    assert (t["n_samples"], t["temperature"], t["inflight_groups"], t["epoch_groups"],
            t["prompt_strata"], t["first_cohort_min_scale"]) == (8, 1.0, 32, 16, 16, 0.1)
    assert t["prompt_len"] == {"lo": 4096, "hi": 14336}
    assert t["output_len"] == {"dist": "lognormal", "median": 384, "sigma": 0.7, "lo": 32,
                               "hi": 2048}
    # the lengths are the two agent-mixedlen traffics', drawn the same way
    mixed = REG.cell("rollout-qwen3next-mixedlen")["traffic_file"]
    assert t["output_len"] == mixed["output_len"]
    assert output_lengths(t["output_len"], 128) == output_lengths(mixed["output_len"], 128)
    prompts = prompt_lengths(t["prompt_len"], 16)
    assert (min(prompts), max(prompts), len(set(prompts))) == (4416, 14016, 16)
    a, b = Traffic(t, 12800, 2**31 + 5), Traffic(t, 12800, 7)
    lens = lambda tr: sorted(n for g in range(16) for n in tr.group(g).output_lens)  # noqa: E731
    assert lens(a) == lens(b) == sorted(output_lengths(t["output_len"], 128))
    assert sorted(len(a.group(g).prompt) for g in range(16)) == sorted(prompts)
    assert int(a.group(3).prompt.max()) < 12800


# -- the metrics -------------------------------------------------------------------


def _trace(chunks: int, steps_each: int = 128):
    """A device plane as the v5e writes it: `chunks` executions of jit_chunk,
    a token step of which holds five latent reads and three grouped matmuls a
    sparse layer, named as the compiled program names them."""
    ops, t, modules = [], 1000.0, []
    for _ in range(chunks):
        start = t
        for _ in range(steps_each):
            for layer in range(5):
                ops.append([f"%paged_attention_latent.{layer} custom-call bf16[64,128,512]",
                            t, 1500.0])
                t += 1500.0
                if layer:
                    for rd in ("%ragged-dot-none.1", "%ragged-dot-none", "%ragged-dot-none.2"):
                        ops.append([f"{rd} custom-call bf16[384,1536]", t, 400.0])
                        t += 400.0
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
    # the window's counters: 10 chunks of 128 steps, 60 live slots at 9,000 rows, 45 pairs a layer
    steps = 10 * 128
    counters = {
        "chunks_dispatched_total": 10, "generated_tokens_total": 60 * steps,
        "kv_latent_rows_read_total": 60 * 9000 * 5 * steps,
        "moe_pairs_total": 45 * 4 * steps, "moe_hot_expert_pairs_total": 6 * 4 * steps,
        "moe_group_experts_touched_total": 17 * 4 * steps}
    work, fields = kind_rollout_latent.traced_work(
        trace, (0.0, end), 128, 60.0, counters, cfg, "TPU v5e")
    assert work["steps"] == 256 and work["latent_rows_per_step"] == 60 * 9000 * 5
    assert work["held_pairs_per_step"] == 180 and work["held_experts_touched_per_step"] == 68
    ctx = {"spans": Spans(), "window": (0, 1), "trace": trace, "trace_window": (0.0, end),
           "work": work, "fields": fields, "model_config": cfg, "device_kind": "TPU v5e",
           "chips": 1, "counters": counters}
    got = readers.read(spec, ctx)
    step_s = (5 * 1500.0 + 4 * 3 * 400.0) / 1e9  # the hand-made trace's token step
    want = {
        "latent_attention_device_ms.rollout": 5 * 1500.0 / 1e6,
        "group_expert_matmul_device_ms.rollout": 4 * 3 * 400.0 / 1e6,
        "moe_group_expert_load_max_over_mean.rollout": 20 * 6 / 45,
        "kv_latent_rows_per_slot_step.rollout": 9000.0,
        "chunk_roofline_latent": 100 * work["needed_step"]["seconds"] / step_s,
        "latent_attention_roofline": 100 * flops_latent.latent_attention_needed_seconds(
            cfg, 60 * 9000 * 5, "TPU v5e")["seconds"] / (5 * 1500.0 / 1e9),
        "group_expert_matmul_roofline": 100 * flops_latent.expert_matmuls_needed_seconds(
            cfg, 45.0, 17.0, "TPU v5e")["seconds"] / (3 * 400.0 / 1e9),
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
    assert set(carried) | set(NEW_METRICS) == set(names) and len(names) == 14
    # the latent kernel's metric reads no other paged kernel's time, nor they its
    import re

    latent = REG.layer_metric("latent_attention_device_ms.rollout")["args"]["pattern"]
    full = REG.layer_metric("full_attention_device_ms.rollout")["args"]["pattern"]
    assert re.search(latent, "%paged_attention_latent.7 custom-call") and not re.search(
        latent, "%paged_attention.7 custom-call")
    assert not re.search(full, "%paged_attention_latent.7 custom-call")
    assert not re.search(full, "%paged_attention_latent custom-call")


def test_nothing_the_benchmark_had_is_edited_but_eight_lists():
    """Every accepted metric that lists this cell lists it right after the
    cell before it, and no roofline of another configuration took it."""
    listing = [m["name"] for m in REG.bench["end_to_end"] + REG.bench["per_layer"]
               if CELL in m.get("workloads", [])]
    assert len(listing) == 8 + 7
    # (by position and not "last", so that a later cell need not edit this test)
    for m in REG.bench["end_to_end"] + REG.bench["per_layer"]:
        if CELL in m.get("workloads", []) and m["name"] not in NEW_METRICS:
            assert m["workloads"].index(CELL) == m["workloads"].index("rollout-sdar-gsm8k") + 1
    assert [w["name"] for w in REG.bench["workloads"]].index(CELL) == 7
    assert [c["name"] for c in REG.bench["configs"]].index("deepseek-v2") == 6
    assert sum(1 for w in REG.bench["workloads"] if w["chips"] == 4) == 1
