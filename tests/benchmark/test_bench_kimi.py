"""The `kimi-linear-48b-a3b` configuration and its cell, on paper and on a
small hand-made trace: every catalog key carried and exactly three reduced,
the configuration's parameter and byte reckoning against the program's tree
leaf by leaf; `flops_kda` by hand at the cell's sizes; the `rollout_kda` kind
found by name, failing at once on a program that does not know the model, its
reference comparison failing on a reading one precision lower (matrices at
float8 by the log-probabilities' bounds, the state at bf16 by the state's
own); the cell ISSUE 45's parameter for parameter, its traffic
`rollout-qwen3next-mixedlen`'s file; each new metric's file naming a reader
that was there and reading its number, and nothing where the program has no
such kernel or counter."""

import importlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_paths
from benchmark.lib import flops_kda, kind_rollout_kda, readers, weights
from benchmark.lib.harness import CONFIG_META_KEYS
from benchmark.lib.registry import Registry
from benchmark.lib.spans import Spans
from benchmark.lib.traffic import longest_sequence
from benchmark.reference import kimi_linear_ref

from areal_tpu.models.qwen2 import ModelConfig, forward, init_params, param_shapes
from areal_tpu.ops.gdn_step import gdn_step

REG = Registry(bench_paths.REPO)
CELL = "rollout-kimilinear-mixedlen"
NEW_METRICS = {
    "kda_step_device_ms.rollout": ("device_op_time", "kernels"),
    "kda_step_roofline": ("batch_field", "kernels"),
    "nope_latent_attention_device_ms.rollout": ("device_op_time", "kernels"),
    "nope_latent_attention_roofline": ("batch_field", "kernels"),
    "routed_expert_matmul_device_ms.rollout": ("device_op_time", "kernels"),
    "routed_expert_matmul_roofline": ("batch_field", "kernels"),
    "chunk_roofline_kda": ("batch_field", "kernels"),
    "kda_state_share_of_cache_bytes_pct.rollout": ("counter_ratio", "decode engine"),
    "moe_routed_expert_load_max_over_mean.rollout": ("counter_ratio", "decode engine"),
}
# the readers benchmark/lib/readers.py had before this cell: none is added
READERS_THERE = {"counter_ratio", "host_span", "device_module_time", "device_op_time",
                 "device_idle", "roofline", "batch_field"}
# the model-configs guide's catalog entry, `config`, every key
CATALOG = {
    "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu", "hidden_size": 2304,
    "intermediate_size": 9216, "kv_lora_rank": 512,
    "linear_attn_config": {
        "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
        "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19, 21, 22, 23, 25, 26],
        "num_heads": 32, "short_conv_kernel_size": 4},
    "mla_use_nope": True, "model_max_length": 1048576, "model_type": "kimi_linear",
    "moe_intermediate_size": 1024, "moe_layer_freq": 1, "moe_renormalize": True,
    "moe_router_activation_func": "sigmoid", "num_attention_heads": 32, "num_expert_group": 1,
    "num_experts": 256, "num_experts_per_token": 8, "num_hidden_layers": 27,
    "num_key_value_heads": 32, "num_nextn_predict_layers": 0, "num_shared_experts": 1,
    "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 10000, "routed_scaling_factor": 2.446,
    "tie_word_embeddings": False, "topk_group": 1, "use_grouped_topk": True, "v_head_dim": 128,
    "vocab_size": 163840,
}


def _hf(**over):
    f = REG.cell(CELL)["config_file"]
    return dict({k: v for k, v in f.items() if k not in CONFIG_META_KEYS}, **over)


def _model_config(**over):
    return ModelConfig.from_hf_config(_hf(**over))


TINY = dict(vocab_size=256, hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
            num_attention_heads=4, num_key_value_heads=4, head_dim=16, kv_lora_rank=32,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, num_hidden_layers=4,
            linear_attn_config=dict(CATALOG["linear_attn_config"], head_dim=16, num_heads=4))


# -- the configuration ---------------------------------------------------------


def test_configuration_carries_every_catalog_key_and_names_its_cut():
    entry = next(c for c in REG.bench["configs"] if c["name"] == "kimi-linear-48b-a3b")
    f = REG.cell(CELL)["config_file"]
    differs = sorted(k for k, v in CATALOG.items() if k not in f or f[k] != v)
    assert differs == sorted(f["reduced"]) == sorted(entry["reduced"]) == [
        "num_experts", "num_hidden_layers", "vocab_size"]
    assert (f["num_hidden_layers"], f["num_experts"], f["vocab_size"]) == (8, 32, 20480)
    assert (f["num_experts_published"], f["expert_first"], f["vocab_size_published"]) == (
        256, 0, 163840)
    assert 8 * f["vocab_size"] == 163840 and 8 * f["num_experts"] == 256
    assert f["source"] == entry["source"] and f["source"].endswith(
        "Kimi-Linear-48B-A3B-Instruct/blob/main/config.json")
    assert "8 chips share each layer" in f["deployment"] and "first 8 of 27" in f["deployment"]
    said = " ".join(f["assumed"])
    for what in ("[family] KDA", "[family] MLA", "[family] MLP", "[family] a pre-norm",
                 "A_log = log U(0, 16)", "dt_bias = 1", "SIGMOID", "no rotary table",
                 "4,341,760", "self_attn.{q_proj", "block_sparse_moe"):
        assert what in said, what
    # published layers 1-8 as they are: two whole periods, the dense layer once
    mc = _model_config()
    assert mc.layer_types == (("linear_attention",) * 3 + ("full_attention",)) * 2
    assert [mc.layer_sparse(i) for i in range(8)] == [False] + [True] * 7


def test_parameter_and_byte_reckoning_against_the_programs_tree():
    """ISSUE 45's reckoning, leaf by leaf of `param_shapes`."""
    cfg = _model_config()
    shapes = param_shapes(cfg)
    size = lambda t: sum(int(np.prod(s)) for s in jax.tree.leaves(  # noqa: E731
        t, is_leaf=lambda x: isinstance(x, tuple)))
    kda = shapes["layers_0"]["attn"]
    assert kda == {
        "q_kernel": (2304, 32, 128), "k_kernel": (2304, 32, 128), "v_kernel": (2304, 32, 128),
        "q_conv_kernel": (4096, 4), "k_conv_kernel": (4096, 4), "v_conv_kernel": (4096, 4),
        "f_a_kernel": (2304, 128), "f_b_kernel": (128, 4096), "A_log": (32,),
        "dt_bias": (4096,), "b_kernel": (2304, 32), "g_a_kernel": (2304, 128),
        "g_b_kernel": (128, 4096), "o_norm": (128,), "o_kernel": (32, 128, 2304)}
    assert size(kda) == 39_514_272 == flops_kda.kda_mixer_params(cfg)
    mla = shapes["layers_3"]["attn"]
    assert mla == {"q_kernel": (2304, 32, 192), "kv_a_kernel": (2304, 576), "kv_a_norm": (512,),
                   "kv_b_kernel": (512, 8192), "o_kernel": (32, 128, 2304)}
    assert size(mla) == 29_114_880 == flops_kda.latent_mixer_params(cfg)
    assert size(shapes["layers_0"]["mlp"]) == 63_700_992  # the dense layer
    mlp = shapes["layers_1"]["mlp"]
    assert int(np.prod(mlp["router_kernel"])) + 256 == 590_080 and mlp["router_bias"] == (256,)
    assert size({k: v for k, v in mlp.items() if k.startswith("shared_")}) == 7_077_888
    assert mlp["gate_kernel"] == (32, 2304, 1024) and flops_kda.expert_params(cfg) == 7_077_888
    assert size(shapes["embed"]) == size(shapes["lm_head"]) == 47_185_920
    total = size(shapes)
    assert total == 2_092_550_080 == REG.cell(CELL)["config_file"]["parameters"]
    assert total == flops_kda.param_count(cfg)
    assert 4.18e9 < 2 * total < 4.19e9  # bf16 bytes
    # what the cell keeps resident: the two latent layers' pool, the six KDA layers' state
    d = REG.cell(CELL)["experiment"]["decode"]
    slots, ctx = d["max_running_requests"], d["context_length"]
    assert flops_kda.latent_row_bytes(cfg) == 1152 and cfg.latent_row_lanes * 2 == 1280
    assert 2 * slots * ctx * 1280 == 2_684_354_560
    assert flops_kda.state_bytes(cfg) == 2 * 1024 * 1024
    assert flops_kda.conv_rows_bytes(cfg) == 3 * 12288 * 2 == 73_728
    assert flops_kda.state_update_bytes(cfg) == 4_341_760
    assert 6 * (1 + slots) * (2 * 1024 * 1024 + 73_728) == 1_680_261_120


@pytest.mark.parametrize("width", ["tiny", "published", "published_full_depth"])
def test_param_count_is_the_trees_leaf_count(width):
    cfg = {"tiny": lambda: _model_config(**TINY), "published": _model_config,
           "published_full_depth": lambda: _model_config(
               num_hidden_layers=27, num_experts=256, vocab_size=163840)}[width]()
    tree = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    leaves = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))
    assert leaves == flops_kda.param_count(cfg)
    if width == "published_full_depth":
        assert 49.1e9 < leaves < 49.2e9  # ISSUE 45: 49.12 billion


def test_flops_kda_by_hand_at_the_cells_sizes():
    cfg = _model_config()
    assert flops_kda.layer_kinds(cfg) == {"kda": 6, "latent": 2, "dense": 1, "sparse": 7}
    # a latent row at 32 heads: 1,152 B against 69,632 FLOPs, 60 FLOP/B
    assert flops_kda.latent_row_flops(cfg) == 2 * 32 * (576 + 512) == 69_632
    attn = flops_kda.latent_attention_needed_seconds(cfg, 2 * 128 * 3900.0, "TPU v5e")
    assert attn["bound"] == "memory" and 59 < attn["flops"] / attn["bytes"] < 61
    # ISSUE 45's token step at 128 running: state 3.33 GB, experts 3.17, rows 1.28 (at the
    # pool's 1,280 B a row; 1.15 needed), everything else 0.92
    step = flops_kda.decode_step_needed_seconds(
        cfg, 128.0, 6 * 128.0, 2 * 128 * 3900.0, 128.0 * 7, 7 * 32.0, "TPU v5e")
    assert step["bound"] == "memory"
    assert step["state_bytes"] == 6 * 128 * 4_341_760 == 3_334_471_680
    assert step["expert_bytes"] == 7 * 32 * 14_155_776 == 3_170_893_824
    assert step["latent_rows_bytes"] == 2 * 128 * 3900 * 1152
    assert 0.91e9 < step["weights_outside_routed_bytes"] < 0.93e9
    assert 8.5e9 < step["bytes"] < 8.7e9 and 0.0104 < step["seconds"] < 0.0107
    # the kernel alone: a state in and out and five rows of 4,096 float32 a slot and layer
    kda = flops_kda.kda_step_needed_seconds(cfg, 128.0, "TPU v5e")
    assert kda["bytes"] == 128 * (2 * 2_097_152 + 5 * 4096 * 4)
    # a sparse layer's grouped matmuls with every held expert touched by 128 pairs
    mm = flops_kda.expert_matmuls_needed_seconds(cfg, 128.0, 32.0, "TPU v5e")
    assert mm["bound"] == "memory" and mm["bytes"] == 2 * (
        32 * 7_077_888 + 128 * (2 * 2304 + 4 * 1024))


# -- the kind ------------------------------------------------------------------


def test_kind_is_found_by_name_and_reads_this_models_config(tmp_path):
    assert REG.cell(CELL)["kind"] == "rollout_kda"
    kind = importlib.import_module(f"benchmark.lib.kind_{REG.cell(CELL)['kind']}")
    assert kind is kind_rollout_kda
    d = tmp_path / "model"
    d.mkdir()
    (d / "config.json").write_text(json.dumps(_hf()))
    mc = kind.require_kda(str(d), REG.cell(CELL)["config_file"])
    assert mc.num_experts == 32 and mc.num_experts_published == 256 and mc.linear_decay_lanes
    # a program that reads the model as another one fails before anything is built
    (d / "config.json").write_text(json.dumps(_hf(num_experts_per_token=6)))
    with pytest.raises(RuntimeError, match="the program read"):
        kind.require_kda(str(d), REG.cell(CELL)["config_file"])


def test_a_program_that_does_not_know_the_model_fails_at_once(tmp_path, monkeypatch):
    """What the parent does on this cell: the registry refuses the model type."""
    from areal_tpu.models import qwen2

    d = tmp_path / "model"
    d.mkdir()
    (d / "config.json").write_text(json.dumps(_hf()))
    monkeypatch.setattr(qwen2, "MODEL_TYPES",
                        tuple(t for t in qwen2.MODEL_TYPES if t != "kimi_linear"))
    with pytest.raises(NotImplementedError, match="kimi_linear"):
        kind_rollout_kda.require_kda(str(d), REG.cell(CELL)["config_file"])


def test_the_mixers_own_leaves_are_redrawn_from_the_seed():
    cfg = _model_config(**TINY)
    base = weights.seeded_params(cfg, 2**31 + 5)
    a = kind_rollout_kda.redraw_mixer_leaves(base, 2**31 + 5)
    b = kind_rollout_kda.redraw_mixer_leaves(base, 2**31 + 5)
    c = kind_rollout_kda.redraw_mixer_leaves(base, 2**31 + 6)
    kda = a["layers_0"]["attn"]
    assert float(jnp.abs(kda["dt_bias"].astype(jnp.float32) - 1).max()) == 0
    A = np.exp(np.asarray(kda["A_log"], np.float32))
    assert (A > 0).all() and (A <= 16.1).all()
    for name in ("q", "k", "v"):
        conv = np.asarray(kda[f"{name}_conv_kernel"], np.float32)
        assert np.abs(conv).max() <= 0.5 and conv.std() > 0.2
    bias = np.asarray(a["layers_1"]["mlp"]["router_bias"], np.float32)
    drawn = np.asarray(base["layers_1"]["mlp"]["router_bias"], np.float32)
    assert 0 < np.abs(bias).max() < 0.06 < 0.5 < np.abs(drawn).max()
    jax.tree.map(lambda x, y: np.testing.assert_array_equal(np.asarray(x), np.asarray(y)), a, b)
    assert not np.array_equal(np.asarray(kda["A_log"]), np.asarray(c["layers_0"]["attn"]["A_log"]))
    # every other leaf is weights.py's
    for layer, leaf in (("layers_0", "q_kernel"), ("layers_0", "f_b_kernel"),
                        ("layers_3", "kv_b_kernel")):
        np.testing.assert_array_equal(np.asarray(a[layer]["attn"][leaf]),
                                      np.asarray(base[layer]["attn"][leaf]))


@pytest.mark.parametrize("what", ["bf16_compute", "float8_weights"])
def test_comparison_with_the_reference_at_a_tiny_width(what):
    """The program in bf16 agrees with the float32 reference under the
    reference's tolerances; the reference one precision lower (weights at
    float8's 3 mantissa bits) fails."""
    cfg = ModelConfig.from_hf_config(_hf(**dict(TINY, hidden_size=128)),
                                     dtype="bfloat16", param_dtype="bfloat16")
    params = kind_rollout_kda.redraw_mixer_leaves(weights.seeded_params(cfg, 7), 7)
    ids = np.random.default_rng(3).integers(1, 256, 200).astype(np.int32)
    ref, margin = kimi_linear_ref.token_logprobs(params, cfg, ids, with_margins=True)
    if what == "bf16_compute":
        T = len(ids)
        lg = forward(params, jnp.asarray(ids), jnp.arange(T), jnp.zeros(T, jnp.int32), cfg)
        lp = jax.nn.log_softmax(lg.astype(jnp.float32), axis=-1)
        got = np.asarray(lp[jnp.arange(T - 1), jnp.asarray(ids[1:])])
    else:
        got = kimi_linear_ref.token_logprobs(params, cfg, ids, weight_bits=3)
    c = kind_rollout_kda.compare_with_reference(what, got, ref, margin)
    assert c["ok"] == (what == "bf16_compute"), c


def _rounding_step(S, *a, **kw):
    """The program's state update, its state rounded to bf16 after the step."""
    o, S = gdn_step(S, *a, **kw)
    return o, jax.lax.reduce_precision(S, exponent_bits=8, mantissa_bits=7)


def _a_used_pool(step, dtype=jnp.float32):
    """A small pool [2 layers, 1 + 4 slots, 8 heads, 16, 16] after 12 steps
    of `step` under a vector decay in both layers, slot 2 never active."""
    key = jax.random.PRNGKey(11)
    S = jnp.zeros((2, 5, 8, 16, 16), dtype)
    active = jnp.array([True, True, False, True])
    for t in range(12):
        q, k, v, g, b = (jax.random.normal(jax.random.fold_in(key, 5 * t + i), shape)
                         for i, shape in enumerate([(4, 8, 16)] * 4 + [(4, 8)]))
        k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
        for layer in range(2):
            S = step(S, q / 4, k, v, -jnp.abs(g) / 8, jax.nn.sigmoid(b), layer, active)[1]
    return S


@pytest.mark.parametrize("what", ["float32", "rounded_every_step", "bf16_pool"])
def test_state_one_precision_lower_fails_the_state_checks(what):
    """What the log-probabilities' bounds do not see, `check_state` does: a
    state kept in float32 passes both of its bounds with room; one rounded to
    bf16 after every step, or a pool of bf16, fails each of them."""
    step = gdn_step if what == "float32" else _rounding_step
    S = _a_used_pool(step, jnp.bfloat16 if what == "bf16_pool" else jnp.float32)
    assert float(jnp.abs(S[:, 0]).max()) == 0 and float(jnp.abs(S[:, 3]).max()) == 0
    before = np.asarray(S, np.float32)
    held = kind_rollout_kda.state_storage_check(S)
    replay = kind_rollout_kda.state_step_check(S, 2**31 + 9, step=step)
    np.testing.assert_array_equal(np.asarray(S, np.float32), before)  # the pool is left alone
    assert held["nonzero"] == 2 * 3 * 8 * 16 * 16  # the three active slots' rows
    if what == "float32":
        assert held["ok"] and held["beyond_bf16_share"] > 0.99, held
        assert replay["ok"] and max(replay["state_rel"], replay["out_rel"]) < 5e-6, replay
    else:
        assert not held["ok"] and held["beyond_bf16_share"] == 0, held
        assert not replay["ok"], replay
        assert 20 * kimi_linear_ref.STATE_STEP_REL_TOL < replay["state_rel"] < 1e-2, replay


@pytest.mark.parametrize("rows", ["as_written", "float8"])
def test_check_state_reads_the_engines_pool_after_a_run(rows, monkeypatch):
    """`check_state` on the pool a tiny engine leaves after a group has
    decoded: float32 rows, both bounds met, the same answer twice; and
    `check_latent_rows` on the latent pool beside it: met as the engine
    writes its rows, failed where they are rounded to float8 on the way
    (`bench_artifacts/pr45/lower_precision.py pool`), which the state's
    bounds do not see."""
    from types import SimpleNamespace

    from areal_tpu.models import qwen2

    if rows == "float8":
        pad = qwen2._latent_pool_row
        monkeypatch.setattr(qwen2, "_latent_pool_row", lambda row, lanes: pad(
            row.astype(jnp.float8_e4m3fn).astype(row.dtype), lanes))

    from areal_tpu.api.cli_args import JaxDecodeConfig
    from areal_tpu.engine.jax_decode import JaxDecodeEngine
    from benchmark.lib.kind_rollout import _request

    cfg = _model_config(**TINY)
    params = kind_rollout_kda.redraw_mixer_leaves(weights.seeded_params(cfg, 5), 5)
    engine = JaxDecodeEngine(JaxDecodeConfig(
        context_length=128, max_running_requests=4, new_tokens_per_chunk=8, page_size=4,
        dtype="float32", kv_cache_dtype="float32"))
    engine.set_model(params, cfg)
    engine.initialize()
    try:
        import asyncio

        async def go():
            return await asyncio.gather(*[
                engine.agenerate(_request(list(range(3, 40)), n, 1.0)) for n in (9, 14)])

        assert [r.output_len for r in asyncio.run(go())] == [9, 14]
        engine.pause_generation()
        rt = SimpleNamespace(seed=3200000101)
        checks = kind_rollout_kda.check_state(rt, engine)
        assert [c["ok"] for c in checks] == [True, True], checks
        assert checks == kind_rollout_kda.check_state(rt, engine)
        (latent,) = kind_rollout_kda.check_latent_rows(engine)
        assert latent["ok"] == (rows == "as_written") and latent["nonzero"] > 0, latent
        assert (latent["beyond_f8_share"] > 0.9) if rows == "as_written" else (
            latent["beyond_f8_share"] == 0), latent
    finally:
        engine.destroy()


# -- the traffic and the cell ----------------------------------------------------


def test_traffic_is_qwen3nexts_cells_file_letter_for_letter():
    mine, theirs = REG.cell(CELL), REG.cell("rollout-qwen3next-mixedlen")
    assert mine["traffic"] == theirs["traffic"] == "agent-mixedlen-queued-rollout"
    t = mine["traffic_file"]
    assert t == theirs["traffic_file"]
    assert t["n_samples"] * t["inflight_groups"] == 256 and longest_sequence(t) == 8192


def test_cell_is_the_issues_parameter_for_parameter():
    cell = REG.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "kimi-linear-48b-a3b", "agent-mixedlen-queued-rollout", 1)
    d, r = cell["experiment"]["decode"], cell["experiment"]["rollout"]
    assert d == {"context_length": 8192, "max_running_requests": 128, "new_tokens_per_chunk": 128,
                 "page_size": 128, "dtype": "bfloat16", "kv_cache_dtype": "bfloat16",
                 "max_prefill_tokens": 32768}
    assert r["max_concurrent_rollouts"] == 256
    assert (cell["warmup_groups"], cell["warmup_scale"], cell["trace_after_seconds"],
            cell["trace_seconds"], cell["check_samples"]) == (16, 0.1, 15, 20, 6)
    # apart from the kind, the slots and the words it is qwen3next's cell
    other = REG.cell("rollout-qwen3next-mixedlen")
    same = set(cell) - {"kind", "who", "assumed", "experiment", "name", "config", "why",
                        "config_file"}
    assert {k: cell[k] for k in same} == {k: other[k] for k in same}
    # it reports the rollout metric and every per-layer metric all rollout cells share
    e2e = {m["name"] for m in REG.metrics("end_to_end", CELL)}
    assert e2e == {"rollout_tokens_per_s", "setup_s"}
    mine = {m["name"] for m in REG.metrics("per_layer", CELL)}
    shared = {m["name"] for m in REG.bench["per_layer"]
              if {"rollout-1.5b-gsm8k", "rollout-olmoe-gsm8k", "rollout-kexaone-mixedlen"}
              <= set(m.get("workloads", []))}
    assert mine == shared | set(NEW_METRICS) and len(shared) == 7
    # 9 cells of 24, still one on four chips; the new entries last in their lists
    cells = REG.bench["workloads"]
    assert len(cells) == 9 and sum(c["chips"] == 4 for c in cells) == 1
    assert cells[-1]["name"] == CELL and REG.bench["configs"][-1]["name"] == cell["config"]
    assert [m["name"] for m in REG.bench["per_layer"][-9:]] == list(NEW_METRICS)
    assert all(len(x["why"]) <= 200 for x in (cells[-1], REG.bench["configs"][-1]))


# -- the metrics ---------------------------------------------------------------


def _trace(chunks: int, steps_each: int = 128):
    """A device plane as the v5e writes it (nanoseconds): `chunks` executions
    of jit_chunk, a token step of which holds a state update in each of six
    KDA layers, a latent read in each of two latent layers and three grouped
    matmuls in each of seven sparse layers, named as the compiled program
    names them."""
    ops, t, modules = [], 1000.0, []
    for _ in range(chunks):
        start = t
        for _ in range(steps_each):
            for layer in range(8):
                name, dur = ((f"%paged_attention_latent.{layer}", 1200e3) if layer % 4 == 3
                             else (f"%kda_step.{layer}", 900e3))
                ops.append([f"{name} custom-call f32[128,32,128]", t, dur])
                t += dur
                if layer:
                    for rd in ("%ragged-dot-none.1", "%ragged-dot-none", "%ragged-dot-none.2"):
                        ops.append([f"{rd} custom-call bf16[128,1024]", t, 300e3])
                        t += 300e3
        modules.append(["jit_chunk(123)", start, t - start])
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
                             "1" if "max_over_mean" in name else "ms")
    cfg = _model_config()
    trace, end = _trace(chunks=2)
    running, depth, steps = 120.0, 3900, 256
    updates, rows = int(running) * 6 * steps, int(running) * 2 * depth * steps
    pairs, hot, touched = 118 * 7 * steps, 9 * 7 * steps, 31 * 7 * steps
    state_bytes, row_bytes = updates * 4_341_760, rows * 1280
    counters = {
        "chunks_dispatched_total": 3, "chunks_consumed_token_steps_total": steps,
        "moe_pairs_total": pairs,
        "moe_hot_expert_pairs_total": hot, "moe_absent_pairs_total": 7 * pairs,
        "moe_group_tokens_here_total": int(running) * 7 * steps,
        "moe_group_experts_touched_total": touched,
        "kv_latent_rows_read_total": rows, "kv_latent_bytes_read_total": row_bytes,
        "gdn_state_updates_total": updates, "gdn_state_bytes_total": state_bytes}
    work, fields = kind_rollout_kda.traced_work(
        trace, (0.0, end), 128, running, counters, cfg, "TPU v5e")
    assert work["steps"] == 256 and work["needed_step"]["bound"] == "memory"
    # the steps the counters cover are the engine's own count of them (a chunk more
    # has been dispatched than consumed)
    assert work["counted_steps"] == 256
    assert (work["state_updates_per_step"], work["latent_rows_per_step"]) == pytest.approx(
        (720.0, 936000.0))
    ctx = {"spans": Spans(), "window": (0, 1), "trace": trace, "trace_window": (0.0, end),
           "work": work, "fields": fields, "model_config": cfg, "device_kind": "TPU v5e",
           "chips": 1, "counters": counters}
    got = readers.read(spec, ctx)
    step_s = (6 * 900e3 + 2 * 1200e3 + 7 * 3 * 300e3) / 1e9  # the hand-made trace's token step
    want = {
        "kda_step_device_ms.rollout": 6 * 900e3 / 1e6,
        "nope_latent_attention_device_ms.rollout": 2 * 1200e3 / 1e6,
        "routed_expert_matmul_device_ms.rollout": 7 * 3 * 300e3 / 1e6,
        "moe_routed_expert_load_max_over_mean.rollout": 32 * 9 / 118,
        "kda_state_share_of_cache_bytes_pct.rollout": 100 * state_bytes / (state_bytes + row_bytes),
        "chunk_roofline_kda": 100 * work["needed_step"]["seconds"] / step_s,
        "kda_step_roofline": 100 * flops_kda.kda_step_needed_seconds(
            cfg, work["state_updates_per_step"], "TPU v5e")["seconds"] / (6 * 900e3 / 1e9),
        "nope_latent_attention_roofline": 100 * flops_kda.latent_attention_needed_seconds(
            cfg, work["latent_rows_per_step"], "TPU v5e")["seconds"] / (2 * 1200e3 / 1e9),
        "routed_expert_matmul_roofline": 100 * flops_kda.expert_matmuls_needed_seconds(
            cfg, work["held_pairs_per_step"] / 7, work["held_experts_touched_per_step"] / 7,
            "TPU v5e")["seconds"] / (3 * 300e3 / 1e9),
    }[name]
    assert got == pytest.approx(want, rel=1e-9)
    if "roofline" in name:
        assert 0 < got < 100
    # where the program has no such span, counter or kernel: nothing, no raise
    bare = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [["jit_chunk(1)", 0.0, 5.0]]},
        {"name": "XLA Ops", "events": [["%fusion.1 fusion f32[8]", 0.0, 5.0]]}]}]}
    empty = dict(ctx, trace=bare, trace_window=(0.0, 10.0), counters={}, fields={})
    if reader != "batch_field":
        assert readers.read(spec, empty) is None
    _, none = kind_rollout_kda.traced_work(bare, (0.0, 10.0), 128, running, counters, cfg,
                                           "TPU v5e")
    # a sub-window in which no chunk was consumed: nothing to feed the counts, no share
    idle = dict.fromkeys(counters, 0)
    assert kind_rollout_kda.traced_work(trace, (0.0, end), 128, running, idle, cfg,
                                        "TPU v5e")[1] == {}
    assert set(none) <= {"chunk_roofline_kda"}  # no kernel of its own to read: no share of it
