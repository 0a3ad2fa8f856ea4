"""The six per-layer metrics of PR 24 read the names the program now gives
its kernels and programs. Each is a data file for a reader that was there;
here each resolves through the registry and reads, from a trace recorded on
the v5e with the new names (the first 1.45 s of the traced window of a
`train-0.5b-gsm8k` run: `compute_logp`, `compute_advantages`, one whole
minibatch and the start of the next), the value computed by hand: a listing
of the `XLA Modules` line and a plain loop over the `XLA Ops` events
(`_scratch/make_fixture.py` of the PR), not the functions under test. On the
parent's trace, which has no such names, each returns nothing and does not
raise."""

import gzip
import json
import os
import sys

import pytest

import bench_paths
from benchmark.lib import readers
from benchmark.lib.registry import Registry

sys.path.insert(0, bench_paths.REPO)
from tools import trace_report  # noqa: E402

REG = Registry(bench_paths.REPO)
NEW = {
    "flash_fwd_device_ms.train": ("device_op_time", "kernels", "train_tokens_per_s"),
    "flash_bwd_device_ms.train": ("device_op_time", "kernels", "train_tokens_per_s"),
    "fwd_step_device_ms.train": ("device_module_time", "model step", "train_tokens_per_s"),
    "apply_update_device_ms.train": ("device_module_time", "model step", "train_tokens_per_s"),
    "prefill_device_ms.rollout": ("device_module_time", "model step", "rollout_tokens_per_s"),
    "decode_discarded_pct.rollout": ("counter_ratio", "decode engine", "rollout_tokens_per_s"),
}
LO = 48613395.0  # start of the bench/traced_window span, ns on the trace's clock
CUT = LO + 1.45e9


def _load(name):
    return json.loads(gzip.open(os.path.join(bench_paths.FIXTURES, name)).read())


@pytest.fixture(scope="module")
def trace():
    path = os.path.join(bench_paths.FIXTURES, "train_0.5b_v5e_named_kernels.json.gz")
    assert os.path.getsize(path) < 1_000_000
    return _load("train_0.5b_v5e_named_kernels.json.gz")


@pytest.fixture(scope="module")
def parent_trace():
    return _load("train_0.5b_v5e_first_second.json.gz")


@pytest.mark.parametrize("name", sorted(NEW))
def test_metric_resolves_with_a_reader_that_was_there(name):
    reader, layer, moves = NEW[name]
    spec = REG.layer_metric(name)
    assert spec["reader"] == reader and reader in readers.READERS
    entry = next(m for m in REG.bench["per_layer"] if m["name"] == name)
    assert entry["layer"] == layer and entry["moves"] == moves
    kind = "train" if name.endswith(".train") else "rollout"
    cells = [w["name"] for w in REG.bench["workloads"] if w["name"].startswith(kind)]
    assert entry["workloads"] == cells
    for cell in cells:
        assert name in [m["name"] for m in REG.metrics("per_layer", cell)]


@pytest.mark.parametrize("name,by_hand_ms", [
    # 219 %flash_fwd.N events in the cut (four forward programs, two grad steps
    # with their rematerialised second forward, the start of a third):
    # 567,608,008 ns; the test's ctx calls the cut one step
    ("flash_fwd_device_ms.train", 567.608008),
    # 51 %flash_dq.N events, 102,579,226 ns, and 51 %flash_dkv.N, 141,706,597 ns
    ("flash_bwd_device_ms.train", 244.285823),
    # jit_fwd_step: 152,032,825 + 96,928,113 + 152,027,813 + 152,031,142 ns in 4 runs
    ("fwd_step_device_ms.train", 138.25497325),
    # jit_apply_update: one run of 17,175,181 ns
    ("apply_update_device_ms.train", 17.175181),
])
def test_metric_reads_the_value_computed_by_hand(trace, name, by_hand_ms):
    ctx = {"trace": trace, "trace_window": (LO, CUT), "work": {"steps": 1}}
    assert readers.read(REG.layer_metric(name), ctx) == pytest.approx(by_hand_ms, rel=1e-9)


@pytest.mark.parametrize("name", ["flash_fwd_device_ms.train", "flash_bwd_device_ms.train",
                                  "prefill_device_ms.rollout"])
def test_metric_is_left_out_on_a_trace_without_the_names(parent_trace, name):
    """PR 23's trace: the kernels are `%checkpoint.23`, `%closed_call.12`, and
    no program is called `jit_prefill...`."""
    ctx = {"trace": parent_trace, "trace_window": (48084148.0, 48084148.0 + 1e9),
           "work": {"steps": 1}}
    assert readers.read(REG.layer_metric(name), ctx) is None


def test_prefill_and_discarded_from_a_hand_made_context():
    mods = [["jit_prefill_batched(11)", 100.0, 20e6], ["jit_chunk(7)", 30e6, 900e6],
            ["jit_prefill_batched(12)", 940e6, 30e6], ["jit_prefill_suffix(3)", 980e6, 10e6],
            ["jit_batched(5)", 995e6, 1e6]]  # the parent's name for it: not counted
    ctx = {"trace": {"planes": [{"name": "/device:TPU:0", "lines": [
               {"name": "XLA Modules", "events": mods}]}]},
           "trace_window": (0.0, 1e9),
           "counters": {"runahead_discarded_tokens_total": 128.0,
                        "generated_tokens_total": 3968.0}}
    assert readers.read(REG.layer_metric("prefill_device_ms.rollout"), ctx) == pytest.approx(20.0)
    # 100 x 128 / (3968 + 128)
    assert readers.read(REG.layer_metric("decode_discarded_pct.rollout"), ctx) == 3.125
    ctx["counters"] = {"generated_tokens_total": 10.0}  # a program without the counter
    assert readers.read(REG.layer_metric("decode_discarded_pct.rollout"), ctx) is None


# -- the operator's reader on the same trace (tools/trace_report.py) ----------


def test_trace_report_names_the_kernels(trace):
    rows = {r[0]: r for r in trace_report.kernel_table(trace, LO, CUT)}
    assert rows["%flash_fwd custom-call"][1] == pytest.approx(0.567608008, rel=1e-9)
    assert rows["%flash_fwd custom-call"][3] == 219
    assert rows["%flash_dkv custom-call"][1] == pytest.approx(0.141706597, rel=1e-9)
    # a kernel has nothing nested in it: self time is all of it
    assert rows["%flash_dq custom-call"][2] == pytest.approx(0.102579226, rel=1e-9)
    assert not [k for k in rows if k.startswith(("%checkpoint", "%closed_call",
                                                 "%rematted_computation", "%shard_map"))]


def test_trace_report_splits_the_trainer_spans_into_self_time(trace):
    rows = {r[0]: r for r in trace_report.span_table(trace)}
    # compute_logp: 575,363,361 ns, of which upload_mb, fwd_step and
    # wait_device (four each) take 13,647,390 + 1,086,390 + 558,269,491
    assert rows["areal/train/compute_logp"][2] == pytest.approx(0.575363361, rel=1e-9)
    assert rows["areal/train/compute_logp"][3] == pytest.approx(0.002360090, rel=1e-6)
    assert rows["areal/train/wait_device"][1] == 5 and rows["areal/train/step_stats"][1] == 1
    # train_batch is all children: split_mbs, upload_mb, grad_step, read_stats,
    # apply_update, wait_device, step_stats
    first = rows["areal/train/train_batch"]
    assert first[1] == 2 and first[3] < 0.05 * first[2]  # (the cut ends inside the second)


def test_trace_report_attributes_every_idle_gap_to_a_program_span(trace):
    """By a plain sweep over the op intervals the cut has ten gaps of 1 ms or
    more: before a program starts the upload of its micro-batch (3), between
    two forward programs the read of the first one's result (4,
    `wait_device`), one while the benchmark computes advantages (no program
    span: reported under the benchmark's, in brackets), one in `read_stats`
    before `apply_update` is dispatched, and the longest (9.84 ms) after the
    step in `step_stats`, where `train_batch` reads the learning rate and the
    losses."""
    gaps = trace_report.idle_gaps(trace, LO, CUT, 1e6)
    by_span = {r[0]: r[2] for r in trace_report.gaps_by_span(gaps, LO)}
    assert by_span == {
        "areal/train/upload_mb": 3, "areal/train/wait_device": 4,
        "[bench/compute_advantages]": 1, "areal/train/read_stats": 1,
        "areal/train/step_stats": 1}
    longest = max(gaps, key=lambda g: g["seconds"])
    assert longest["span"] == "areal/train/step_stats"
    assert longest["seconds"] == pytest.approx(0.009840611, rel=1e-6)
