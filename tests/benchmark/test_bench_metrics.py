"""Metric arithmetic against hand-worked values."""

from types import SimpleNamespace

import pytest

import bench_paths  # noqa: F401
from benchmark.lib import flops, metrics, readers
from benchmark.lib.spans import Spans

Q05 = SimpleNamespace(hidden_size=896, intermediate_size=4864, num_hidden_layers=24,
                      num_attention_heads=14, num_key_value_heads=2, vocab_size=151936,
                      tie_word_embeddings=True, qkv_bias=True, head_dim=None)
Q15 = SimpleNamespace(hidden_size=1536, intermediate_size=8960, num_hidden_layers=28,
                      num_attention_heads=12, num_key_value_heads=2, vocab_size=151936,
                      tie_word_embeddings=True, qkv_bias=True, head_dim=None)


def test_whole_step_rate_counts_only_whole_steps():
    # warm-up ended at 9.0; window [10, 20]; steps end at 12, 15, 19, 22
    r = metrics.whole_step_rate([9.0, 12.0, 15.0, 19.0, 22.0], [0, 100, 100, 100, 100], 10.0, 20.0)
    assert r == {"rate": 300 / 10.0, "steps": 3, "seconds": 10.0, "work": 300}
    with pytest.raises(ValueError):
        metrics.whole_step_rate([9.0, 25.0], [0, 100], 10.0, 20.0)
    with pytest.raises(ValueError):
        metrics.whole_step_rate([12.0], [100], 10.0, 20.0)


@pytest.mark.parametrize("q,want,beyond", [(95, 95, 5), (50, 50, 50), (100, 100, 0)])
def test_percentile_is_nearest_rank_with_its_count(q, want, beyond):
    p = metrics.percentile(list(range(100, 0, -1)), q)
    assert p == {"value": want, "n": 100, "beyond": beyond}


@pytest.mark.parametrize("cfg,params,kv,fwd0", [
    # per layer 0.5B: qkv 896*(14+4)*64 + bias 1152 + o 896*896 + mlp 3*896*4864 + 2 norms
    (Q05, 494032768, 12288, 24 * (2 * 896 * 18 * 64 + 2 * 896 * 896 + 6 * 896 * 4864) + 2 * 896 * 151936),
    (Q15, 1543714304, 28672, 28 * (2 * 1536 * 16 * 128 + 2 * 1536 * 1536 + 6 * 1536 * 8960) + 2 * 1536 * 151936),
])
def test_parameter_count_kv_bytes_and_flops(cfg, params, kv, fwd0):
    assert flops.param_count(cfg) == params
    assert flops.kv_bytes_per_token(cfg) == kv
    assert flops.forward_flops_per_token(cfg, 0) == fwd0
    per_ctx = 4 * cfg.num_attention_heads * flops.head_dim(cfg) * cfg.num_hidden_layers
    assert flops.forward_flops_per_token(cfg, 100) == fwd0 + 100 * per_ctx
    assert flops.train_flops_per_token(cfg, 100) == 3 * (fwd0 + 100 * per_ctx)


def test_the_copy_agrees_with_the_program_today():
    from areal_tpu.utils import flops as program

    for cfg in (Q05, Q15):
        assert flops.forward_flops_per_token(cfg, 321.5) == program.forward_flops_per_token(cfg, 321.5)


def test_causal_context_and_needed_times():
    assert flops.causal_avg_context([4]) == 2.5  # (1+2+3+4)/4
    assert flops.causal_avg_context([2, 4]) == (3 + 10) / 6
    d = flops.decode_step_needed_seconds(Q15, running=128, live_tokens=128 * 400, device_kind="TPU v5 lite")
    nbytes = 1543714304 * 2 + 51200 * 28672 + 128 * (28672 + 1536 * 2)
    assert d["bytes"] == nbytes and d["bound"] == "memory"
    assert d["seconds"] == pytest.approx(nbytes / 819e9)
    t = flops.train_needed_seconds(Q05, [100, 300], "TPU v5 lite", chips=4)
    want = 400 * 3 * flops.forward_flops_per_token(Q05, (5050 + 45150) / 400)
    assert t["flops"] == pytest.approx(want) and t["seconds"] == pytest.approx(want / (4 * 197e12))
    with pytest.raises(KeyError):
        flops.peaks("cpu")


def test_host_span_and_counter_readers():
    s = Spans()
    s.events = [("compute_logp", 0.0, 1.0), ("compute_logp", 10.0, 10.5), ("compute_logp", 12.0, 13.5),
                ("prepare_batch", 10.5, 12.0)]
    ctx = {"spans": s, "window": (9.0, 20.0),
           "counters": {"generated_tokens_total": 4096, "chunks_dispatched_total": 2,
                        "new_tokens_per_chunk": 128, "max_running_requests": 64,
                        "queue_secs_total": 3.0, "prefills_total": 10, "prefix_forks_total": 20}}
    assert readers.host_span(ctx, "compute_logp") == 1000.0  # the span before the window is out
    assert readers.host_span(ctx, "prepare_batch") == 1500.0
    assert readers.host_span(ctx, "nothing") is None
    occ = {"num": "generated_tokens_total", "den": ["chunks_dispatched_total", "new_tokens_per_chunk", "max_running_requests"], "scale": 100.0}
    assert readers.counter_ratio(ctx, **occ) == 25.0
    q = {"num": "queue_secs_total", "den": [["prefills_total", "prefix_forks_total"]], "scale": 1000.0}
    assert readers.counter_ratio(ctx, **q) == 100.0
    assert readers.counter_ratio(ctx, "missing", ["prefills_total"]) is None
    assert readers.device_idle({"trace": None}) is None  # nothing to read: left out
