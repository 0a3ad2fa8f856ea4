"""The `ai21-jamba2-3b` configuration and its cell, on paper and on a small
hand-made trace: every catalog key carried and nothing reduced; `flops_ssm`'s
bytes and operations by hand at the published widths; the cell, its traffic
file and its five metrics ISSUE 50's parameter for parameter; the kind found
by name, its warm-up covering every bucket the traffic's prompts meet, its
reference comparison passing the program's reading and failing a reading one
precision lower; each new metric's file naming a reader that was there and
reading its number, and nothing where the program has no such kernel or
counter. Assertions about order are by `index(...)` against a neighbour."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_paths
from benchmark.lib import flops_ssm, kind_rollout_ssm, readers
from benchmark.lib.harness import CONFIG_META_KEYS
from benchmark.lib.registry import Registry
from benchmark.lib.spans import Spans
from benchmark.lib.traffic import Traffic, longest_sequence, output_lengths, prompt_lengths
from benchmark.reference import jamba_ref

from areal_tpu.models.qwen2 import ModelConfig

REG = Registry(bench_paths.REPO)
CELL, CONFIG, TRAFFIC = "rollout-jamba2-reasoning", "ai21-jamba2-3b", "reasoning-queued-rollout"
NEW_METRICS = {
    "ssm_step_device_ms.rollout": ("device_op_time", "kernels", "ms"),
    "ssm_step_roofline": ("batch_field", "kernels", "%"),
    "chunk_roofline_ssm": ("batch_field", "kernels", "%"),
    "ssm_state_share_of_cache_bytes_pct.rollout": ("counter_ratio", "decode engine", "%"),
    "mqa_attention_device_ms.rollout": ("device_op_time", "kernels", "ms"),
}
READERS_THERE = {"counter_ratio", "host_span", "device_module_time", "device_op_time",
                 "device_idle", "roofline", "batch_field"}
# the model-configs guide's catalog entry for AI21-Jamba2-3B, `config`, every key
CATALOG = {
    "attn_layer_offset": 7, "attn_layer_period": 14, "expert_layer_offset": 1,
    "expert_layer_period": 2, "hidden_act": "silu", "hidden_size": 2560,
    "intermediate_size": 8192, "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_state": 16,
    "mamba_dt_rank": 160, "mamba_expand": 2, "mamba_proj_bias": False,
    "max_position_embeddings": 262144, "model_type": "jamba", "num_attention_heads": 20,
    "num_experts": 1, "num_experts_per_tok": 1, "num_hidden_layers": 28,
    "num_key_value_heads": 1, "num_logits_to_keep": 1, "rms_norm_eps": 1e-06,
    "sliding_window": None, "tie_word_embeddings": True, "use_mamba_kernels": True,
    "vocab_size": 65536,
}
SHARED = ["decode_slot_occupancy_pct.rollout", "decode_queue_ms.rollout", "rollout_tpot_p95_ms",
          "chunk_device_ms.rollout", "prefill_device_ms.rollout", "device_idle_pct.rollout",
          "decode_discarded_pct.rollout"]


def _model_config():
    f = REG.cell(CELL)["config_file"]
    return ModelConfig.from_hf_config({k: v for k, v in f.items() if k not in CONFIG_META_KEYS})


# -- the configuration -------------------------------------------------------------


def test_configuration_carries_every_catalog_key_and_reduces_nothing():
    f = REG.cell(CELL)["config_file"]
    assert {k: f[k] for k in CATALOG} == CATALOG
    assert set(f) - set(CATALOG) == set(CONFIG_META_KEYS)
    entry = next(c for c in REG.bench["configs"] if c["name"] == CONFIG)
    assert f["reduced"] == entry["reduced"] == [] and REG.cell(CELL)["reduced"] == []
    assert f["source"] == entry["source"] == (
        "https://huggingface.co/ai21labs/AI21-Jamba2-3B/blob/main/config.json")
    assert entry["file"] == "benchmark/configs/ai21-jamba2-3b.json" and len(entry["why"]) <= 200
    family = [a for a in f["assumed"] if a.startswith("[family]")]
    assert len(family) == 5 and any("seeded weights" in a for a in f["assumed"])
    for word in ("attn_layer_period", "num_experts 1", "NO positional encoding", "dt_layernorm",
                 "feed_forward", "final_layernorm"):
        assert any(word in a for a in family), word
    assert "one chip holds the whole model" in f["deployment"] and "jax:dNt1+dM" in f["deployment"]
    # the configuration comes after the one the benchmark had last
    names = [c["name"] for c in REG.bench["configs"]]
    assert names.index(CONFIG) == names.index("kimi-linear-48b-a3b") + 1


def test_flops_ssm_against_a_hand_count_at_the_published_widths():
    cfg = _model_config()
    H, Di, N, Rk = 2560, 5120, 16, 160
    mixer = (H * 2 * Di + Di * 4 + Di + Di * (Rk + 2 * N) + Rk + 2 * N + Rk * Di + Di + Di * N
             + Di + Di * H)
    assert flops_ssm.ssm_mixer_params(cfg) == mixer == 41_241_792
    assert flops_ssm.attention_params(cfg) == 2 * H * 20 * 128 + 2 * H * 128 == 13_762_560
    assert flops_ssm.mlp_params(cfg) == 3 * H * 8192 == 62_914_560
    total = 65536 * H + 26 * mixer + 2 * 13_762_560 + 28 * (62_914_560 + 2 * H) + H
    assert flops_ssm.param_count(cfg) == total == 3_029_337_472
    assert REG.cell(CELL)["config_file"]["parameters"]["total"] == round(total, -7)
    # a live update: 327,680 B of float32 state and 30,720 B of three bf16 rows, in and out
    assert flops_ssm.state_bytes(cfg) == N * Di * 4 == 327_680
    assert flops_ssm.conv_rows_bytes(cfg) == 3 * Di * 2 == 30_720
    assert flops_ssm.state_update_bytes(cfg) == 716_800
    # 4.77 GB a step at 256 live slots; a slot's cache 9.32 MB whatever the context
    assert 26 * 256 * flops_ssm.state_update_bytes(cfg) == 4_771_020_800
    assert 26 * 358_400 == 9_318_400 and flops_ssm.attention_row_bytes(cfg) == 512
    # the kernel's own bytes: the live states in and out, a slot's rows of dt,
    # u, y (channels) and B, C (lanes) in float32, A and D once a call
    k = flops_ssm.ssm_step_needed_seconds(cfg, 26 * 256, "TPU v5e", calls=26)
    rows = (3 * Di + 2 * N) * 4
    assert k["bytes"] == 26 * 256 * (2 * 327_680 + rows) + 26 * (N * Di + Di) * 4
    assert k["flops"] == 7 * 26 * 256 * N * Di and k["exps"] == 26 * 256 * N * Di
    assert k["seconds"] == k["bytes"] / 819e9 and k["bound"] == "memory"
    # the issue's step: 11.1 GB, 13.6 ms of bytes beside 7.9 ms of matmuls,
    # 62% of the bytes in the mixers (their state and their weights)
    step = flops_ssm.decode_step_needed_seconds(cfg, 256, 26 * 256, 2 * 256 * 1200, "TPU v5e")
    assert step["bound"] == "memory" and step["weights_bytes"] == 2 * total
    assert step["state_bytes"] == 4_771_020_800
    assert step["bytes"] == pytest.approx(11.1e9, rel=0.01)
    assert step["seconds"] == pytest.approx(13.6e-3, rel=0.01)
    assert step["flops"] / 197e12 == pytest.approx(7.9e-3, rel=0.01)
    mixers = step["state_bytes"] + 26 * 2 * mixer
    assert mixers / step["bytes"] == pytest.approx(0.62, abs=0.005)


# -- the traffic and the cell ----------------------------------------------------------


def test_traffic_file_is_the_issues_parameter_for_parameter():
    t = REG.cell(CELL)["traffic_file"]
    assert {k: v for k, v in t.items() if k != "from"} == {
        "n_samples": 8, "temperature": 1.0, "prompt_len": {"lo": 128, "hi": 1024},
        "prompt_strata": 8,
        "output_len": {"dist": "lognormal", "median": 768, "sigma": 0.7, "lo": 64, "hi": 2048},
        "inflight_groups": 64, "first_cohort_min_scale": 0.1, "epoch_groups": 16}
    assert "ASSUMED" in t["from"] and "ISSUE 50" in t["from"]
    assert t["n_samples"] * t["inflight_groups"] == 512 and longest_sequence(t) == 3072
    plens = prompt_lengths(t["prompt_len"], t["prompt_strata"])
    assert plens == [184, 296, 408, 520, 632, 744, 856, 968]
    outs = output_lengths(t["output_len"], 128)
    assert min(outs) >= 64 and max(outs) == 2048 and 700 < float(np.median(outs)) < 840
    # every epoch of 16 groups holds exactly the 128 mid-quantiles, whatever the seed
    for seed in (1, 2**31 + 7):
        tr = Traffic(t, 65536, seed)
        assert sorted(n for g in range(16) for n in tr.group(g).output_lens) == sorted(outs)
        assert all(len(tr.group(g).prompt) in plens for g in range(16))


def test_cell_is_the_issues_parameter_for_parameter():
    cell = REG.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"], cell["kind"]) == (
        CONFIG, TRAFFIC, 1, "rollout_ssm")
    d, r = cell["experiment"]["decode"], cell["experiment"]["rollout"]
    assert d == {"context_length": 3072, "max_running_requests": 256, "new_tokens_per_chunk": 128,
                 "page_size": 128, "dtype": "bfloat16", "kv_cache_dtype": "bfloat16"}
    assert r["max_concurrent_rollouts"] == 512
    assert (cell["warmup_groups"], cell["warmup_scale"], cell["trace_after_seconds"],
            cell["trace_seconds"], cell["check_samples"]) == (16, 0.1, 15, 20, 6)
    assert len(cell["why"]) <= 200
    for word in ("512 requests over 256 slots", "whole model", "4.8 GB of state", "62%", "MQA"):
        assert word in cell["why"], word
    # it reports the rollout metric, every per-layer metric all rollout cells
    # share, and its own five
    assert {m["name"] for m in REG.metrics("end_to_end", CELL)} == {
        "rollout_tokens_per_s", "setup_s"}
    assert {m["name"] for m in REG.metrics("per_layer", CELL)} == set(SHARED) | set(NEW_METRICS)
    # appended after the cell the benchmark had last, in every list it joins
    cells = [c["name"] for c in REG.bench["workloads"]]
    assert cells.index(CELL) == cells.index("rollout-kimilinear-mixedlen") + 1
    assert sum(c["chips"] == 4 for c in REG.bench["workloads"]) == 1
    lists = [m["workloads"] for m in REG.bench["end_to_end"] + REG.bench["per_layer"]
             if m["name"] in SHARED + ["rollout_tokens_per_s"]]
    assert len(lists) == 8
    for names in lists:
        assert names.index(CELL) == names.index("rollout-kimilinear-mixedlen") + 1
    metrics = [m["name"] for m in REG.bench["per_layer"]]
    first = metrics.index("ssm_step_device_ms.rollout")
    assert metrics[first:first + 5] == list(NEW_METRICS)
    assert first > metrics.index("moe_routed_expert_load_max_over_mean.rollout")


def test_the_kind_is_found_by_name_and_its_warm_up_covers_every_bucket():
    kind = importlib.import_module(f"benchmark.lib.kind_{REG.cell(CELL)['kind']}")
    assert kind is kind_rollout_ssm and callable(kind.run)
    t = REG.cell(CELL)["traffic_file"]
    buckets = kind.warm_buckets(t)
    # a prompt of p tokens prefills p - 1 in buckets of 64: one bucket a
    # stratum, every one of them at or under the dense prefill's 1,024
    assert buckets == {192: 184, 320: 296, 448: 408, 576: 520, 640: 632, 768: 744, 896: 856,
                       1024: 968}
    for p in prompt_lengths(t["prompt_len"], t["prompt_strata"]):
        assert any(b - 64 < p - 1 <= b for b in buckets), p
    # a wave holds exactly B distinct prompts of a bucket (B = 1, 2, 4, 8: what
    # the engine batches), never more than a pass's budget admits, and every
    # (bucket, B) is made
    made = set()
    bucket_of = {p: b for b, p in buckets.items()}
    for wave in kind.prefill_waves(buckets, 8192, 256):
        counts = {p: wave.count(p) for p in set(wave)}
        assert len(set(counts.values())) == 1 and next(iter(counts.values())) in (1, 2, 4, 8)
        assert sum(bucket_of[p] for p in wave) <= 8192 and len(wave) <= 256
        made |= {(bucket_of[p], n) for p, n in counts.items()}
    assert made == {(b, w) for b in buckets for w in (1, 2, 4, 8)}
    assert kind.prefill_waves(buckets, 8192, 256)[0] == sorted(buckets.values(), reverse=True)
    # the chunk's depths reach the whole context: 24 pages of 128
    assert longest_sequence(t) // 128 == 24


def test_the_kind_fails_at_once_on_a_program_that_reads_another_model(tmp_path):
    import json

    f = REG.cell(CELL)["config_file"]
    hf = {k: v for k, v in f.items() if k not in CONFIG_META_KEYS}
    (tmp_path / "config.json").write_text(json.dumps(hf))
    mc = kind_rollout_ssm.require_ssm(str(tmp_path), f)
    assert mc.layer_runs == ((0, 7), (8, 21), (22, 28))
    (tmp_path / "config.json").write_text(json.dumps({**hf, "attn_layer_offset": 3}))
    with pytest.raises(RuntimeError, match="the configuration says"):
        kind_rollout_ssm.require_ssm(str(tmp_path), f)
    (tmp_path / "config.json").write_text(json.dumps({**hf, "model_type": "jamba_next"}))
    with pytest.raises(NotImplementedError, match="not in the registry"):
        kind_rollout_ssm.require_ssm(str(tmp_path), f)


def test_the_seeded_draw_of_the_special_leaves():
    cfg = ModelConfig.from_hf_config(dict(
        CATALOG, hidden_size=64, intermediate_size=96, num_hidden_layers=14, vocab_size=128,
        num_attention_heads=4, mamba_dt_rank=8), dtype="float32", param_dtype="float32")
    from benchmark.lib import weights

    a = kind_rollout_ssm.redraw_mixer_leaves(weights.seeded_params(cfg, 2**31 + 5), 2**31 + 5)
    b = kind_rollout_ssm.redraw_mixer_leaves(weights.seeded_params(cfg, 2**31 + 5), 2**31 + 5)
    c = kind_rollout_ssm.redraw_mixer_leaves(weights.seeded_params(cfg, 6), 6)
    m = a["run_0_7"]["attn"]
    np.testing.assert_allclose(np.asarray(m["ssm_A_log"][3, :, 5]), np.log(np.arange(1, 17)),
                               rtol=1e-6)
    assert (np.asarray(m["D"]) == 1).all()
    dt0 = np.asarray(jax.nn.softplus(m["dt_bias"]))
    assert 0.999e-3 <= dt0.min() and dt0.max() <= 0.1001
    assert abs(np.asarray(m["dt_kernel"])).max() <= 8 ** -0.5
    for leaf in ("conv_kernel", "conv_bias"):
        assert abs(np.asarray(m[leaf])).max() <= 0.5 and np.asarray(m[leaf]).std() > 0.2
    same = jax.tree.map(lambda x, y: bool((x == y).all()), a, b)
    assert all(jax.tree.leaves(same))
    assert not bool((a["run_0_7"]["attn"]["dt_bias"] == c["run_0_7"]["attn"]["dt_bias"]).all())


# -- the comparison --------------------------------------------------------------------


def test_the_comparison_passes_the_programs_reading_and_fails_one_precision_lower():
    rng = np.random.default_rng(0)
    ref = rng.normal(-2.0, 1.0, 1200)
    # the bf16 program on the chip: mean 0.074-0.084, largest 0.26-0.42 (PERF.md)
    ok = kind_rollout_ssm.compare_with_reference("x", ref + rng.normal(0, 0.1, 1200), ref)
    assert ok["ok"] and 0.07 < ok["mean_abs"] < 0.09 and ok["max_abs"] < 0.6
    assert (jamba_ref.MEAN_ABS_TOL, jamba_ref.MAX_ABS_TOL) == (0.16, 0.9)
    # twice the program's level on the mean, or one token a nat off, fails
    assert not kind_rollout_ssm.compare_with_reference(
        "x", ref + rng.normal(0, 0.25, 1200), ref)["ok"]
    far = ref.copy()
    far[7] += 1.0
    assert not kind_rollout_ssm.compare_with_reference("x", far, ref)["ok"]
    assert not kind_rollout_ssm.compare_with_reference("x", ref * np.nan, ref)["ok"]


def test_the_states_checks_fail_on_a_state_held_in_bf16():
    """`check_state`'s two bounds on a pool as a window leaves it: float32
    arithmetic passes both; a pool rounded to bf16 fails the storage's, an
    update that rounds what it writes the replay's (`lower_precision.py
    state` on the chip)."""
    from areal_tpu.ops.ssm_step import ssm_step

    S = jax.random.normal(jax.random.PRNGKey(0), (2, 1 + 4, 16, 128), jnp.float32)
    S = S.at[:, 0].set(0.0)
    assert kind_rollout_ssm.state_storage_check(S)["ok"]
    step = kind_rollout_ssm.state_step_check(S, 11, steps=16)
    assert step["ok"] and step["state_rel"] < 5e-6
    low = S.astype(jnp.bfloat16).astype(jnp.float32)
    assert not kind_rollout_ssm.state_storage_check(low)["ok"]

    def rounded(S, *a, **kw):
        y, S = ssm_step(S, *a, **kw)
        return y, jax.lax.reduce_precision(S, exponent_bits=8, mantissa_bits=7)

    bad = kind_rollout_ssm.state_step_check(S, 11, step=rounded, steps=16)
    assert not bad["ok"] and bad["state_rel"] > 10 * jamba_ref.STATE_STEP_REL_TOL


# -- the metrics -------------------------------------------------------------------------


def _trace(chunks: int, steps_each: int = 128):
    """A device plane as the v5e writes it (nanoseconds): `chunks` executions
    of jit_chunk, a token step of which holds a state update in each of 26
    state-space layers and a paged read in each of two attention layers,
    named as the compiled program names them."""
    ops, t, modules = [], 1000.0, []
    for _ in range(chunks):
        start = t
        for _ in range(steps_each):
            for layer in range(28):
                name, dur = ((f"%paged_attention.{layer}", 150e3) if layer % 14 == 7
                             else (f"%ssm_step.{layer}", 300e3))
                ops.append([f"{name} custom-call f32[256,1,5120]", t, dur])
                t += dur + 200e3  # the projections between
        modules.append(["jit_chunk(123)", start, t - start])
    return {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": modules}, {"name": "XLA Ops", "events": ops}]}]}, t


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_new_metric_names_a_reader_that_was_there_and_reads_the_context(name):
    reader, layer, unit = NEW_METRICS[name]
    spec = REG.layer_metric(name)
    entry = next(m for m in REG.bench["per_layer"] if m["name"] == name)
    assert spec["reader"] == reader and reader in READERS_THERE
    assert set(readers.READERS) == READERS_THERE
    assert set(entry) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    assert (entry["layer"], entry["unit"], entry["workloads"], entry["moves"]) == (
        layer, unit, [CELL], "rollout_tokens_per_s")
    assert entry["source"] == ("program_counter" if reader == "counter_ratio" else "device_trace")
    cfg = _model_config()
    trace, end = _trace(chunks=2)
    live, depth, steps = 230, 1200, 256
    updates, rows = live * 26 * steps, live * 2 * depth * steps
    state_bytes, row_bytes = updates * 716_800, rows * 512
    counters = {"chunks_dispatched_total": 3, "chunks_consumed_token_steps_total": steps,
                "kv_full_rows_read_total": rows, "kv_full_bytes_read_total": row_bytes,
                "gdn_state_updates_total": updates, "gdn_state_bytes_total": state_bytes}
    work, fields = kind_rollout_ssm.traced_work(trace, (0.0, end), 128, 240.0, counters, cfg,
                                                "TPU v5e")
    assert work["steps"] == 256 == work["counted_steps"]
    assert work["needed_step"]["bound"] == "memory"
    assert (work["state_updates_per_step"], work["live_slots_per_step"],
            work["attention_rows_per_step"]) == pytest.approx((230 * 26, 230, 230 * 2 * 1200))
    ctx = {"spans": Spans(), "window": (0, 1), "trace": trace, "trace_window": (0.0, end),
           "work": work, "fields": fields, "model_config": cfg, "device_kind": "TPU v5e",
           "chips": 1, "counters": counters}
    got = readers.read(spec, ctx)
    step_s = (26 * 300e3 + 2 * 150e3 + 28 * 200e3) / 1e9  # the hand-made trace's token step
    want = {
        "ssm_step_device_ms.rollout": 26 * 300e3 / 1e6,
        "mqa_attention_device_ms.rollout": 2 * 150e3 / 1e6,
        "ssm_state_share_of_cache_bytes_pct.rollout":
            100 * state_bytes / (state_bytes + row_bytes),
        "chunk_roofline_ssm": 100 * flops_ssm.decode_step_needed_seconds(
            cfg, 230, 230 * 26, 230 * 2 * 1200, "TPU v5e")["seconds"] / step_s,
        "ssm_step_roofline": 100 * flops_ssm.ssm_step_needed_seconds(
            cfg, 230 * 26, "TPU v5e", calls=26)["seconds"] / (26 * 300e3 / 1e9),
    }[name]
    assert got == pytest.approx(want, rel=1e-9)
    if "roofline" in name:
        assert 0 < got < 100
    if name == "ssm_state_share_of_cache_bytes_pct.rollout":
        assert got > 90
    # where the program has no such span, counter or kernel: nothing, no raise
    bare = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [["jit_chunk(1)", 0.0, 5.0]]},
        {"name": "XLA Ops", "events": [["%fusion.1 fusion f32[8]", 0.0, 5.0]]}]}]}
    empty = dict(ctx, trace=bare, trace_window=(0.0, 10.0), counters={}, fields={})
    assert readers.read(spec, empty) is None
    _, none = kind_rollout_ssm.traced_work(bare, (0.0, 10.0), 128, 240.0, counters, cfg,
                                           "TPU v5e")
    assert set(none) <= {"chunk_roofline_ssm"}  # no kernel of its own to read: no share of it
    # a sub-window in which no chunk was consumed: nothing to feed the counts, no share
    idle = dict.fromkeys(counters, 0)
    assert kind_rollout_ssm.traced_work(trace, (0.0, end), 128, 240.0, idle, cfg,
                                        "TPU v5e")[1] == {}
