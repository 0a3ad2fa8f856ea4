"""The `qwen3-next-80b-a3b` configuration and its cell, on paper and on a
small hand-made trace: every catalog key carried, the configuration's
parameter and byte reckoning against the program's tree leaf by leaf;
`flops_linear` by hand at the cell's sizes; the `rollout_linear` kind found by
name, failing at once on a program that does not know the model, its
reference comparison failing on a reading one precision lower (matrices at
float8 by the log-probabilities' bounds, the state at bf16 by the state's own); the traffic's
lengths those of `agent-mixedlen-rollout` with twice the groups in flight;
each new metric's file naming a reader that was there and reading its number."""

import importlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_paths
from benchmark.lib import flops_linear, kind_rollout_linear, readers, weights
from benchmark.lib.harness import CONFIG_META_KEYS
from benchmark.lib.registry import Registry
from benchmark.lib.spans import Spans
from benchmark.lib.traffic import longest_sequence
from benchmark.reference import qwen3next_ref

from areal_tpu.models.qwen2 import ModelConfig, forward, init_params, param_shapes
from areal_tpu.ops.gdn_step import gdn_step

REG = Registry(bench_paths.REPO)
CELL = "rollout-qwen3next-mixedlen"
NEW_METRICS = {
    "gdn_step_device_ms.rollout": ("device_op_time", "kernels"),
    "gdn_step_roofline": ("batch_field", "kernels"),
    "chunk_roofline_linear": ("batch_field", "kernels"),
    "gated_attention_device_ms.rollout": ("device_op_time", "kernels"),
    "small_expert_matmul_device_ms.rollout": ("device_op_time", "kernels"),
    "small_expert_matmul_roofline": ("batch_field", "kernels"),
    "moe_small_expert_load_max_over_mean.rollout": ("counter_ratio", "decode engine"),
    "gdn_state_share_of_cache_bytes_pct.rollout": ("counter_ratio", "decode engine"),
}
# the readers benchmark/lib/readers.py had before this cell: none is added
READERS_THERE = {"counter_ratio", "host_span", "device_module_time", "device_op_time",
                 "device_idle", "roofline", "batch_field"}
# the model-configs guide's catalog entry, `config`, every key
CATALOG = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 5120, "linear_conv_kernel_dim": 4,
    "linear_key_head_dim": 128, "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144, "mlp_only_layers": [],
    "model_type": "qwen3_next", "moe_intermediate_size": 512, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts": 512, "num_experts_per_tok": 10,
    "num_hidden_layers": 48, "num_key_value_heads": 2, "partial_rotary_factor": 0.25,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936,
}


def _hf(**over):
    f = REG.cell(CELL)["config_file"]
    return dict({k: v for k, v in f.items() if k not in CONFIG_META_KEYS}, **over)


def _model_config(**over):
    return ModelConfig.from_hf_config(_hf(**over))


TINY = dict(vocab_size=256, hidden_size=64, moe_intermediate_size=32,
            shared_expert_intermediate_size=32, num_attention_heads=4, num_key_value_heads=2,
            head_dim=16, linear_key_head_dim=16, linear_value_head_dim=16,
            linear_num_key_heads=4, linear_num_value_heads=8)


# -- the configuration ---------------------------------------------------------


def test_configuration_carries_every_catalog_key_and_names_its_cut():
    entry = next(c for c in REG.bench["configs"] if c["name"] == "qwen3-next-80b-a3b")
    f = REG.cell(CELL)["config_file"]
    differs = sorted(k for k, v in CATALOG.items() if k not in f or f[k] != v)
    assert differs == sorted(f["reduced"]) == sorted(entry["reduced"]) == [
        "num_experts", "num_hidden_layers", "vocab_size"]
    assert (f["num_hidden_layers"], f["num_experts"], f["vocab_size"]) == (8, 64, 18992)
    assert (f["num_experts_published"], f["expert_first"], f["vocab_size_published"]) == (
        512, 0, 151936)
    assert 8 * f["vocab_size"] == 151936 and 8 * f["num_experts"] == 512
    assert f["source"] == entry["source"] and f["source"].endswith(
        "Qwen3-Next-80B-A3B-Instruct/blob/main/config.json")
    assert "8 chips share each layer" in f["deployment"] and "six pipeline stages" in f["deployment"]
    said = " ".join(f["assumed"])
    for what in ("float32", "A_log = log U(0, 16)", "dt_bias = 1", "effective scales",
                 "grouped by key head", "multi-token prediction"):
        assert what in said, what
    assert _model_config().layer_types == (
        ("linear_attention",) * 3 + ("full_attention",)) * 2  # two whole periods


def test_parameter_and_byte_reckoning_against_the_programs_tree():
    """ISSUE 32's reckoning, leaf by leaf of `param_shapes`."""
    cfg = _model_config()
    shapes = param_shapes(cfg)
    size = lambda t: sum(int(np.prod(s)) for s in jax.tree.leaves(  # noqa: E731
        t, is_leaf=lambda x: isinstance(x, tuple)))
    lin = shapes["layers_0"]["attn"]
    assert lin == {"qkvz_kernel": (2048, 12288), "ba_kernel": (2048, 64),
                   "conv_kernel": (8192, 4), "dt_bias": (32,), "A_log": (32,), "norm": (128,),
                   "out_kernel": (4096, 2048)}
    assert size(lin) == 33_718_464 == flops_linear.linear_mixer_params(cfg)
    full = shapes["layers_3"]["attn"]
    assert full["q_kernel"] == (2048, 16, 512) and full["k_kernel"] == (2048, 2, 256)
    assert size(full) == 27_263_488 == flops_linear.full_mixer_params(cfg)
    mlp = shapes["layers_0"]["mlp"]
    assert int(np.prod(mlp["router_kernel"])) == 1_048_576 and mlp["shared_router_kernel"] == (2048, 1)
    assert size({k: v for k, v in mlp.items() if k.startswith("shared_")}) == 3_145_728 + 2_048
    assert mlp["gate_kernel"] == (64, 2048, 512) and 3 * int(np.prod(mlp["gate_kernel"])) == 64 * 3_145_728
    outside_mixer = size(shapes["layers_0"]) - size(lin)
    assert outside_mixer == 205_527_040 == size(shapes["layers_3"]) - size(full)
    assert size(shapes["layers_0"]) == 239_245_504 and size(shapes["layers_3"]) == 232_790_528
    assert size(shapes["embed"]) == size(shapes["lm_head"]) == 38_895_616
    total = size(shapes)
    assert total == 6 * 239_245_504 + 2 * 232_790_528 + 2 * 38_895_616 + 2_048
    assert total == 1_978_847_360 == REG.cell(CELL)["config_file"]["parameters"]
    assert total == flops_linear.param_count(cfg)
    assert 3.95e9 < 2 * total < 3.96e9  # bf16 bytes
    # what the cell keeps resident: the two full layers' pool and the six linear layers' state
    d = REG.cell(CELL)["experiment"]["decode"]
    slots, ctx = d["max_running_requests"], d["context_length"]
    assert flops_linear.kv_row_bytes(cfg) == 2048  # a token of ONE full layer, K and V
    assert 2 * slots * ctx * 2048 == 2_147_483_648
    assert flops_linear.state_bytes(cfg) == 2 * 1024 * 1024
    assert flops_linear.conv_rows_bytes(cfg) == 3 * 8192 * 2
    state = 6 * (1 + slots) * (flops_linear.state_bytes(cfg) + flops_linear.conv_rows_bytes(cfg))
    assert 0.83e9 < state < 0.85e9
    resident = 2 * total + 2_147_483_648 + state
    assert 6.9e9 < resident < 7.0e9 and resident > 0.25 * 16e9  # the floor: a quarter of the chip


@pytest.mark.parametrize("width", ["tiny", "published", "published_full_depth"])
def test_param_count_is_the_trees_leaf_count(width):
    over = {"tiny": TINY, "published": {},
            "published_full_depth": dict(num_hidden_layers=48, num_experts=512, vocab_size=151936)}
    cfg = _model_config(**over[width])
    tree = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    assert flops_linear.param_count(cfg) == sum(int(x.size) for x in jax.tree.leaves(tree))
    if width == "published_full_depth":
        assert 79e9 < flops_linear.param_count(cfg) < 81e9  # "80B", without its MTP layer


def test_flops_linear_by_hand_at_the_cells_sizes():
    cfg = _model_config()
    assert flops_linear.layer_kinds(cfg) == {"linear": 6, "full": 2, "sparse": 8}
    running, live = 57.0, 57 * 4500.0
    pairs = 57 * 10 * 64 / 512
    assert flops_linear.held_pairs(cfg, running) == pytest.approx(pairs)  # 1.1 an expert
    touched = 64 * (1 - (63 / 64) ** pairs)
    assert flops_linear.experts_touched(cfg, running) == pytest.approx(touched)
    assert 42 < touched < 44  # a third of the held experts get no pair
    step = flops_linear.decode_step_needed_seconds(cfg, running, live, "TPU v5e")
    outside = (8 * (2 * 2048 + 2048 * 512 + 3 * 2048 * 512 + 2048) + 6 * 33_718_464
               + 2 * 27_263_488 + 2048 + 18992 * 2048)
    experts = 8 * touched * 3 * 2048 * 512
    state = 6 * running * 2 * (2 * 1024 * 1024 + 3 * 8192 * 2)
    rows = 2 * live * 2048
    written = running * (2 * 2048 + 2048 * 2)
    assert step["bytes"] == pytest.approx(2 * (outside + experts) + state + rows + written)
    assert step["state_bytes"] == pytest.approx(state) and 1.4e9 < state < 1.5e9
    assert step["full_rows_bytes"] == pytest.approx(rows)
    assert step["bound"] == "memory" and step["seconds"] == pytest.approx(step["bytes"] / 819e9)
    assert 0.004 < step["seconds"] < 0.007
    gdn = flops_linear.gdn_step_needed_seconds(cfg, running, "TPU v5e")
    assert gdn["bytes"] == running * 2 * 2 * 1024 * 1024
    assert gdn["seconds"] == pytest.approx(gdn["bytes"] / 819e9)
    mm = flops_linear.expert_matmuls_needed_seconds(cfg, running, "TPU v5e")
    assert mm["bytes"] == pytest.approx(
        2 * (touched * 3 * 2048 * 512 + pairs * (2 * 2048 + 4 * 512)))
    assert mm["bound"] == "memory"


# -- the kind ------------------------------------------------------------------


def test_kind_is_found_by_name_and_reads_this_models_config(tmp_path):
    assert REG.cell(CELL)["kind"] == "rollout_linear"
    kind = importlib.import_module(f"benchmark.lib.kind_{REG.cell(CELL)['kind']}")
    assert kind is kind_rollout_linear
    d = tmp_path / "model"
    d.mkdir()
    (d / "config.json").write_text(json.dumps(_hf()))
    mc = kind.require_linear_stack(str(d), REG.cell(CELL)["config_file"])
    assert mc.num_experts == 64 and mc.num_experts_published == 512
    # a program that reads the model as another one fails before anything is built
    (d / "config.json").write_text(json.dumps(_hf(num_experts_per_tok=8)))
    with pytest.raises(RuntimeError, match="the program read"):
        kind.require_linear_stack(str(d), REG.cell(CELL)["config_file"])


def test_a_program_that_does_not_know_the_model_fails_at_once(tmp_path, monkeypatch):
    """What the parent does on this cell: the registry refuses the model type."""
    from areal_tpu.models import qwen2

    d = tmp_path / "model"
    d.mkdir()
    (d / "config.json").write_text(json.dumps(_hf()))
    monkeypatch.setattr(qwen2, "MODEL_TYPES",
                        tuple(t for t in qwen2.MODEL_TYPES if t != "qwen3_next"))
    with pytest.raises(NotImplementedError, match="qwen3_next"):
        kind_rollout_linear.require_linear_stack(str(d), REG.cell(CELL)["config_file"])


def test_the_mixers_own_leaves_are_redrawn_from_the_seed():
    cfg = _model_config(**TINY)
    base = weights.seeded_params(cfg, 2**31 + 5)
    a = kind_rollout_linear.redraw_mixer_leaves(base, 2**31 + 5)
    b = kind_rollout_linear.redraw_mixer_leaves(base, 2**31 + 5)
    c = kind_rollout_linear.redraw_mixer_leaves(base, 2**31 + 6)
    lin = a["layers_0"]["attn"]
    assert float(jnp.abs(lin["dt_bias"].astype(jnp.float32) - 1).max()) == 0
    A = np.exp(np.asarray(lin["A_log"], np.float32))
    assert (A > 0).all() and (A <= 16.1).all()
    conv = np.asarray(lin["conv_kernel"], np.float32)
    assert np.abs(conv).max() <= 0.5 and conv.std() > 0.2
    jax.tree.map(lambda x, y: np.testing.assert_array_equal(np.asarray(x), np.asarray(y)), a, b)
    assert not np.array_equal(np.asarray(a["layers_0"]["attn"]["A_log"]),
                              np.asarray(c["layers_0"]["attn"]["A_log"]))
    # every other leaf is weights.py's
    np.testing.assert_array_equal(np.asarray(a["layers_0"]["attn"]["qkvz_kernel"]),
                                  np.asarray(base["layers_0"]["attn"]["qkvz_kernel"]))
    np.testing.assert_array_equal(np.asarray(a["layers_3"]["attn"]["q_kernel"]),
                                  np.asarray(base["layers_3"]["attn"]["q_kernel"]))


@pytest.mark.parametrize("what", ["bf16_compute", "float8_weights"])
def test_comparison_with_the_reference_at_a_tiny_width(what):
    """The program in bf16 agrees with the float32 reference under the
    reference's tolerances; the reference one precision lower (weights at
    float8's 3 mantissa bits) fails."""
    cfg = ModelConfig.from_hf_config(_hf(**dict(TINY, hidden_size=128, num_hidden_layers=4)),
                                     dtype="bfloat16", param_dtype="bfloat16")
    params = kind_rollout_linear.redraw_mixer_leaves(weights.seeded_params(cfg, 7), 7)
    ids = np.random.default_rng(3).integers(1, 256, 200).astype(np.int32)
    ref, margin = qwen3next_ref.token_logprobs(params, cfg, ids, with_margins=True)
    if what == "bf16_compute":
        T = len(ids)
        lg = forward(params, jnp.asarray(ids), jnp.arange(T), jnp.zeros(T, jnp.int32), cfg)
        lp = jax.nn.log_softmax(lg.astype(jnp.float32), axis=-1)
        got = np.asarray(lp[jnp.arange(T - 1), jnp.asarray(ids[1:])])
    else:
        low = jax.tree.map(
            lambda x: x.astype(jnp.float8_e4m3fn).astype(x.dtype) if x.ndim >= 2 else x, params)
        got = qwen3next_ref.token_logprobs(low, cfg, ids)
    c = kind_rollout_linear.compare_with_reference(what, got, ref, margin)
    assert c["ok"] == (what == "bf16_compute"), c


def _rounding_step(S, *a, **kw):
    """The program's state update, its state rounded to bf16 after the step."""
    o, S = gdn_step(S, *a, **kw)
    return o, jax.lax.reduce_precision(S, exponent_bits=8, mantissa_bits=7)


def _a_used_pool(step, dtype=jnp.float32):
    """A small pool [2 layers, 1 + 4 slots, 8 heads, 16, 16] after 12 steps
    of `step` in both layers, slot 2 never active (its rows stay zero)."""
    key = jax.random.PRNGKey(11)
    S = jnp.zeros((2, 5, 8, 16, 16), dtype)
    active = jnp.array([True, True, False, True])
    for t in range(12):
        q, k, v, g, b = (jax.random.normal(jax.random.fold_in(key, 5 * t + i), shape)
                         for i, shape in enumerate([(4, 8, 16)] * 3 + [(4, 8)] * 2))
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
    held = kind_rollout_linear.state_storage_check(S)
    replay = kind_rollout_linear.state_step_check(S, 2**31 + 9, step=step)
    np.testing.assert_array_equal(np.asarray(S, np.float32), before)  # the pool is left alone
    assert held["nonzero"] == 2 * 3 * 8 * 16 * 16  # the three active slots' rows
    if what == "float32":
        assert held["ok"] and held["beyond_bf16_share"] > 0.99, held
        assert replay["ok"] and max(replay["state_rel"], replay["out_rel"]) < 5e-6, replay
    else:
        assert not held["ok"] and held["beyond_bf16_share"] == 0, held
        assert not replay["ok"], replay
        assert 20 * qwen3next_ref.STATE_STEP_REL_TOL < replay["state_rel"] < 1e-2, replay


def test_check_state_reads_the_engines_pool_after_a_run():
    """`check_state` on the pool a tiny engine leaves after a group has
    decoded: float32 rows, both bounds met, the same answer twice."""
    from types import SimpleNamespace

    from areal_tpu.api.cli_args import JaxDecodeConfig
    from areal_tpu.engine.jax_decode import JaxDecodeEngine
    from benchmark.lib.kind_rollout import _request

    cfg = _model_config(**TINY)
    params = kind_rollout_linear.redraw_mixer_leaves(weights.seeded_params(cfg, 5), 5)
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
        checks = kind_rollout_linear.check_state(rt, engine)
        assert [c["ok"] for c in checks] == [True, True], checks
        assert checks == kind_rollout_linear.check_state(rt, engine)
    finally:
        engine.destroy()


# -- the traffic and the cell ----------------------------------------------------


def test_traffic_is_the_mixedlen_lengths_with_twice_the_groups_in_flight():
    t, base = REG.cell(CELL)["traffic_file"], REG.cell("rollout-kexaone-mixedlen")["traffic_file"]
    same = {k for k in base if k not in ("from", "inflight_groups")}
    assert {k: t[k] for k in same} == {k: base[k] for k in same}
    assert (t["inflight_groups"], base["inflight_groups"]) == (32, 16)
    assert t["n_samples"] * t["inflight_groups"] == 256
    assert longest_sequence(t) == 8192


def test_cell_is_the_issues_parameter_for_parameter():
    cell = REG.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "qwen3-next-80b-a3b", "agent-mixedlen-queued-rollout", 1)
    d, r = cell["experiment"]["decode"], cell["experiment"]["rollout"]
    assert d == {"context_length": 8192, "max_running_requests": 64, "new_tokens_per_chunk": 128,
                 "page_size": 128, "dtype": "bfloat16", "kv_cache_dtype": "bfloat16",
                 "max_prefill_tokens": 32768}
    assert r["max_concurrent_rollouts"] == 256
    assert (cell["warmup_groups"], cell["warmup_scale"], cell["trace_after_seconds"],
            cell["trace_seconds"], cell["check_samples"]) == (16, 0.1, 15, 20, 6)
    # it reports the rollout metric and every per-layer metric all rollout cells share
    e2e = {m["name"] for m in REG.metrics("end_to_end", CELL)}
    assert e2e == {"rollout_tokens_per_s", "setup_s"}
    mine = {m["name"] for m in REG.metrics("per_layer", CELL)}
    shared = {m["name"] for m in REG.bench["per_layer"]
              if {"rollout-1.5b-gsm8k", "rollout-olmoe-gsm8k", "rollout-kexaone-mixedlen"}
              <= set(m.get("workloads", []))}
    assert mine == shared | set(NEW_METRICS) and len(shared) == 7


# -- the metrics ---------------------------------------------------------------


def _trace(chunks: int, steps_each: int = 128):
    """A device plane as the v5e writes it (nanoseconds): `chunks` executions of
    jit_chunk, a token step of which holds a state update in each of six linear layers,
    a paged read in each of two full layers and three grouped matmuls a
    layer, named as the compiled program names them."""
    ops, t, modules = [], 1000.0, []
    for _ in range(chunks):
        start = t
        for _ in range(steps_each):
            for layer in range(8):
                name, dur = ((f"%paged_attention.{layer}", 900e3) if layer % 4 == 3
                             else (f"%gdn_step.{layer}", 450e3))
                ops.append([f"{name} custom-call f32[64,32,128]", t, dur])
                t += dur
                for rd in ("%ragged-dot-none.1", "%ragged-dot-none", "%ragged-dot-none.2"):
                    ops.append([f"{rd} custom-call bf16[640,512]", t, 300e3])
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
    running, live = 57.0, 57 * 4500.0
    work, fields = kind_rollout_linear.traced_work(
        trace, (0.0, end), 128, running, live, cfg, "TPU v5e")
    assert work["steps"] == 256 and work["needed_step"]["bound"] == "memory"
    updates = 57 * 6 * 256
    state_bytes = updates * 2 * (2 * 1024 * 1024 + 3 * 8192 * 2)
    kv_bytes = int(live) * 2 * 256 * 2048
    ctx = {"spans": Spans(), "window": (0, 1), "trace": trace, "trace_window": (0.0, end),
           "work": work, "fields": fields, "model_config": cfg, "device_kind": "TPU v5e",
           "chips": 1, "counters": {
               "moe_pairs_total": 71 * 8 * 256, "moe_hot_expert_pairs_total": 5 * 8 * 256,
               "moe_absent_pairs_total": 499 * 8 * 256,
               "kv_full_rows_read_total": int(live) * 2 * 256, "kv_full_bytes_read_total": kv_bytes,
               "gdn_state_updates_total": updates, "gdn_state_bytes_total": state_bytes}}
    got = readers.read(spec, ctx)
    step_s = (6 * 450e3 + 2 * 900e3 + 8 * 3 * 300e3) / 1e9  # the hand-made trace's token step
    want = {
        "gdn_step_device_ms.rollout": 6 * 450e3 / 1e6,
        "gated_attention_device_ms.rollout": 2 * 900e3 / 1e6,
        "small_expert_matmul_device_ms.rollout": 8 * 3 * 300e3 / 1e6,
        "moe_small_expert_load_max_over_mean.rollout": 64 * 5 / 71,
        "gdn_state_share_of_cache_bytes_pct.rollout": 100 * state_bytes / (state_bytes + kv_bytes),
        "chunk_roofline_linear": 100 * work["needed_step"]["seconds"] / step_s,
        "gdn_step_roofline": 100 * flops_linear.gdn_step_needed_seconds(
            cfg, running, "TPU v5e")["seconds"] / (450e3 / 1e9),
        "small_expert_matmul_roofline": 100 * flops_linear.expert_matmuls_needed_seconds(
            cfg, running, "TPU v5e")["seconds"] / (3 * 300e3 / 1e9),
    }[name]
    assert got == pytest.approx(want, rel=1e-9)
    if "roofline" in name:
        assert 0 < got < 100
    # where the program has no such span, counter or kernel (the parent): nothing, no raise
    bare = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [["jit_chunk(1)", 0.0, 5.0]]},
        {"name": "XLA Ops", "events": [["%fusion.1 fusion f32[8]", 0.0, 5.0]]}]}]}
    empty = dict(ctx, trace=bare, trace_window=(0.0, 10.0), counters={}, fields={})
    if reader != "batch_field":
        assert readers.read(spec, empty) is None
    _, none = kind_rollout_linear.traced_work(bare, (0.0, 10.0), 128, running, live, cfg, "TPU v5e")
    assert set(none) <= {"chunk_roofline_linear"}  # no kernel of its own to read: no share of it
