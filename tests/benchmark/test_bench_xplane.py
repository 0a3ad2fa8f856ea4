"""The trace reduction against a trace recorded on the v5e in PR 23: the
first second of the traced window of a `train-0.5b-gsm8k` run (one
`compute_logp`, one `compute_advantages`, the start of a `ppo_update`), kept
as the plain structure `xplane.load` gives. The expected numbers were read
from the events by hand (a listing of the `XLA Modules` line and a plain loop
over the `XLA Ops` intervals), not with the functions under test."""

import gzip
import json
import os

import pytest

import bench_paths
from benchmark.lib import readers, xplane

LO = 48084148.0  # start of the bench/traced_window span, ns on the trace's clock


@pytest.fixture(scope="module")
def trace():
    path = os.path.join(bench_paths.FIXTURES, "train_0.5b_v5e_first_second.json.gz")
    assert os.path.getsize(path) < 1_000_000
    return json.loads(gzip.open(path).read())


def test_planes_lines_and_the_window(trace):
    assert [p["name"] for p in xplane.device_planes(trace)] == ["/device:TPU:0"]
    assert xplane.window(trace) == (LO, LO + 1e9)
    spans = [s[0] for s in xplane.host_spans(trace)]
    assert spans == ["bench/traced_window", "bench/compute_logp",
                     "bench/compute_advantages", "bench/ppo_update"]


def test_busy_is_the_union_of_the_op_intervals(trace):
    b = xplane.busy(trace, LO, LO + 1e9)
    # 10,815 op events, many nested in one another: their union inside the
    # window is 954,371,221 ns
    assert b == {"busy_s": 0.954371221, "per_device_s": [0.954371221], "window_s": 1.0}
    assert readers.device_idle({"trace": trace, "trace_window": (LO, LO + 1e9)}) == pytest.approx(4.5628779)
    # half the window: clipping, not dropping, the op that straddles the cut
    half = xplane.busy(trace, LO, LO + 0.5e9)["busy_s"]
    assert 0.45 < half < 0.5


@pytest.mark.parametrize("pattern,calls,total_ns", [
    # four forward programs of compute_logp: 152,031,340 + 152,034,347 +
    # 152,035,466 + 96,927,039 ns
    ("^jit_fwd_step", 4, 553028192),
    # two train steps START inside the second (308,520,810 and 665,859,638 ns;
    # the second runs on past the cut and counts whole)
    ("^jit_grad_step", 2, 974380448),
    ("^jit_apply_update", 1, 17180647),
    ("^jit_nothing", 0, 0),
])
def test_module_time_by_name(trace, pattern, calls, total_ns):
    m = xplane.module_time(trace, pattern, LO, LO + 1e9)
    assert m["calls"] == calls and m["seconds"] == pytest.approx(total_ns / 1e9, abs=1e-12)
    per_call = readers.device_module_time({"trace": trace, "trace_window": (LO, LO + 1e9)}, pattern)
    assert per_call == (pytest.approx(total_ns / 1e6 / calls) if calls else None)


def test_idle_gaps_carry_the_open_host_span(trace):
    gaps = xplane.idle_gaps(trace, LO, LO + 1e9, 5)
    assert len(gaps) == 5 and gaps == sorted(gaps, key=lambda g: -g[1])
    by_name = {}
    for name, seconds in gaps:
        by_name.setdefault(name, []).append(seconds)
    # between the end of jit_gae_padded (574,271,228 ns after the window's
    # start) and the next program (579,999,642) the host was in compute_advantages
    assert by_name["compute_advantages"] == [pytest.approx(0.0057284, abs=2e-6)]
    # from the window's start to the first operation (4,368,765 ns) it was
    # entering compute_logp; between two forward programs likewise
    assert any(s == pytest.approx(0.0043688, abs=2e-6) or s == pytest.approx(0.0045397, abs=2e-6)
               for s in by_name["compute_logp"])
    assert "ppo_update" in by_name and all(s < 0.01 for _, s in gaps)


def test_top_ops_have_short_names(trace):
    ops = xplane.top_ops(trace, LO, LO + 1e9, 10)
    assert len(ops) == 10 and ops == sorted(ops, key=lambda o: -o[1])
    assert ops[0][0].startswith("%while.") and ops[0][1] == pytest.approx(0.479111885)
    assert all(len(name) < 100 for name, _ in ops)
    long = ("%copy.73 = bf16[28,1281,128,2,128]{4,3,2,1,0:T(2,128)(2,1)} "
            "copy(bf16[28,1281,128,2,128]{4,3,2,1,0:T(2,128)(2,1)} %x)")
    assert xplane.short_name(long) == "%copy.73 copy bf16[28,1281,128,2,128]"
    assert xplane.short_name("not hlo") == "not hlo"


def test_op_time_by_name_and_per_step(trace):
    # the forward loop over layers of compute_logp is the top operation
    assert xplane.op_time(trace, r"^%while\.6 ", LO, LO + 1e9) == pytest.approx(0.479111885)
    assert xplane.op_time(trace, " all-gather ", LO, LO + 1e9) == 0.0  # one chip: no collective
    ctx = {"trace": trace, "trace_window": (LO, LO + 1e9), "work": {"steps": 2}}
    assert readers.device_op_time(ctx, r"^%while\.6 ") == pytest.approx(479.111885 / 2)
    assert readers.device_op_time(ctx, " all-gather ") is None
    assert readers.device_op_time(dict(ctx, work={}), r"^%while") is None
    names = ["%all-gather.3 all-gather bf16[4,8]", "%all-reduce-start.1 all-reduce-start f32[8]",
             "%fusion.9 fusion bf16[8]", "%collective-permute-done.2 collective-permute-done f32[2]"]
    rx = __import__("re").compile(json.load(open(os.path.join(
        bench_paths.REPO, "benchmark/layer_metrics/collective_device_ms.train.json")))["args"]["pattern"])
    assert [bool(rx.search(n)) for n in names] == [True, True, False, True]


def test_roofline_of_the_traced_train_steps(trace):
    from types import SimpleNamespace

    cfg = SimpleNamespace(hidden_size=896, intermediate_size=4864, num_hidden_layers=24,
                          num_attention_heads=14, num_key_value_heads=2, vocab_size=151936,
                          tie_word_embeddings=True, head_dim=None)
    ctx = {"trace": trace, "trace_window": (LO, LO + 1e9), "model_config": cfg,
           "device_kind": "TPU v5 lite", "chips": 1, "work": {"lengths": [512] * 24}}
    # 12,288 tokens at a mean causal context of 256.5: needed FLOPs over
    # 197e12 is 0.1934 s; the two train steps took 0.974380448 s
    flops_per_token = 3 * (24 * (2 * 896 * 18 * 64 + 2 * 896 * 896 + 6 * 896 * 4864 + 4 * 256.5 * 896)
                           + 2 * 896 * 151936)
    want = 100 * (12288 * flops_per_token / 197e12) / 0.974380448
    got = readers.roofline(ctx, "train_step", "^jit_grad_step")
    assert got == pytest.approx(want) and 19 < got < 21
    assert readers.roofline(dict(ctx, work=None), "train_step", "^jit_grad_step") is None
