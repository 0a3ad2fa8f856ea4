"""The loop cell `grpo-0.5b-gsm8k` on paper: `lib/program_spans.py`'s
arithmetic on a small record kept as JSON, the kind's loop against
`examples/gsm8k_grpo.py:main`'s call order, its workflow's order of return,
every metric file against a context built by hand, and the traffic file
against ISSUE 34's table."""

import asyncio
import json
import os
import types

import numpy as np
import pytest

import bench_paths
from benchmark.lib import kind_grpo, program_spans, readers
from benchmark.lib.registry import Registry
from tools import trace_report

REG = Registry()
CELL = "grpo-0.5b-gsm8k"
S = 1e9


@pytest.fixture(scope="module")
def fixture():
    with open(os.path.join(bench_paths.FIXTURES, "grpo_record_small.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def placed(fixture):
    """The record on the trace's clock, which stands 500 s behind the host's."""
    lo, hi = 1.0 * S, 14.0 * S
    host = fixture["host_window_s"]
    clock = program_spans.clock_offset((host[0] * S, host[1] * S), (lo, hi))
    assert clock == {"offset_ns": -500.0 * S, "skew_ns": 0.0}
    return program_spans.shifted(fixture["spans"], clock["offset_ns"])


# -- kind_grpo.py -----------------------------------------------------------


def test_traced_fields_from_the_record_and_the_trace(fixture):
    ctx = {"trace": fixture["trace"], "trace_window": (1.0 * S, 14.0 * S)}
    fields, note, gaps = kind_grpo.traced_fields(
        ctx, fixture["spans"], tuple(fixture["host_window_s"]), step_ms=6500.0)
    # jit_chunk 2.4 + 0.35 and jit_prefill_batched 0.5 start inside the 13 s; the
    # chunk that began before the window does not count
    assert fields["device_decode_share_pct"] == pytest.approx(100 * 3.25 / 13)
    assert fields["device_train_share_pct"] == pytest.approx(100 * 5.5 / 13)
    # 2.7 of the window's 13 s in the two spans, of a step of 6.5 s
    assert fields["train_wait_device_ms"] == pytest.approx(1350.0)
    assert note["clock_skew_ms"] == 0.0 and note["record_spans"] == len(fixture["spans"])
    assert note["record_open_at_stop"] == ["decode/idle"]
    assert gaps[0] == ["trainer:rollout/gate_closed|decode:decode/wait_device", pytest.approx(0.8)]
    assert all(name.startswith("trainer:") and "|decode:" in name for name, _ in gaps)
    assert not any(name.endswith("decode:no_span") for name, _ in gaps)


def test_a_program_without_the_record_or_the_counters_is_read_as_far_as_it_goes(fixture):
    """The parent commit under this cell: no `recording()`, no
    `get_loop_metrics()`. The trace's own numbers stand, the rest is left out."""
    ctx = {"trace": fixture["trace"], "trace_window": (1.0 * S, 14.0 * S),
           "breakdown": {"idle_gaps": [["no span", 0.8]]}}
    fields, note, gaps = kind_grpo.traced_fields(
        ctx, [], tuple(fixture["host_window_s"]), step_ms=6500.0)
    assert set(fields) == {"device_decode_share_pct", "device_train_share_pct"}
    assert gaps == [["no span", 0.8]] and note == {}
    engine_only = types.SimpleNamespace(get_metrics=lambda: {"generated_tokens_total": 7})
    assert kind_grpo.read_metrics(engine_only) == {"generated_tokens_total": 7}
    both = types.SimpleNamespace(get_metrics=lambda: {"a_total": 1},
                                 get_loop_metrics=lambda: {"pauses_total": 2})
    assert kind_grpo.read_metrics(both) == {"a_total": 1, "pauses_total": 2}


CALLS = ["rollout.prepare_batch", "actor.compute_logp", "actor.compute_advantages",
         "actor.ppo_update", "rollout.pause", "actor.set_version", "actor.update_weights",
         "rollout.set_version", "rollout.resume"]


class _Logged:
    def __init__(self, name, log, returns):
        self._name, self._log, self._returns = name, log, returns

    def __getattr__(self, attr):
        def call(*a, **kw):
            self._log.append(f"{self._name}.{attr}")
            return self._returns.get(attr)
        return call


def test_the_kinds_loop_holds_mains_call_order():
    """`grpo_step` calls what `examples/gsm8k_grpo.py:main` calls on the two
    engines between two steps, in main's order, under main's timing keys."""
    from areal_tpu.utils import perf_tracer

    log: list[str] = []
    actor = _Logged("actor", log, {"compute_logp": np.zeros((2, 4)), "ppo_update": [{}]})
    rollout = _Logged("rollout", log, {"prepare_batch": {"input_ids": np.zeros((2, 4))}})
    with perf_tracer.recording() as rec:
        batch, stats = kind_grpo.grpo_step(actor, rollout, None, None, "meta", global_step=6)
    assert log == CALLS
    assert "prox_logp" in batch and isinstance(stats, list)
    keys = [s["name"] for s in sorted(rec.snapshot(), key=lambda s: s["start_ns"])]
    assert keys == ["step/rollout", "step/recompute_logp", "step/compute_advantage",
                    "step/train_step", "step/update_weights"]
    src = open(os.path.join(bench_paths.REPO, "examples", "gsm8k_grpo.py")).read()
    loop = src[src.index("for global_step in range(start_step, max_steps):"):]
    at = [loop.index(c + "(") for c in CALLS]
    assert at == sorted(at), "main's own order changed: the cell's loop follows it"
    for key in ("rollout", "recompute_logp", "compute_advantage", "train_step", "update_weights"):
        assert f'record_timing("{key}")' in loop


class _FakeEngine:
    """Answers each request after a delay its length sets, so groups finish
    out of order."""

    def __init__(self, delay):
        self.delay = delay

    async def agenerate(self, req):
        n = req.gconfig.max_new_tokens
        await asyncio.sleep(self.delay(req.rid))
        return types.SimpleNamespace(
            input_tokens=list(req.input_ids), output_tokens=[1] * n, input_len=len(req.input_ids),
            output_len=n, output_logprobs=[-0.5] * n, output_versions=[2] * n, stop_reason="length")


def test_groups_return_in_the_order_of_their_batches():
    from benchmark.lib.traffic import Traffic

    traffic = Traffic(REG.cell(CELL)["traffic_file"], 1000, 2**31 + 11)
    wf = kind_grpo.PinnedGroups(traffic, groups_per_batch=2, temperature=1.0)
    # batch 0 is groups 0, 1; batch 1 groups 2, 3. Group 1 is the slowest.
    slow = {"g0": 0.02, "g1": 0.08, "g2": 0.0, "g3": 0.04}
    engine = _FakeEngine(lambda rid: slow[rid.split("s")[0]])
    order: list[int] = []

    async def episode(i):
        traj = await wf.arun_episode(engine, {"group": i})
        order.append(i)
        return traj

    async def drive():
        return await asyncio.gather(*[episode(i) for i in (3, 2, 1, 0)])

    trajs = asyncio.run(drive())
    assert order[:2] == [0, 1] and sorted(order[2:]) == [2, 3]
    traj = trajs[-1]  # group 0, as RLVRWorkflow returns a group
    lens = traffic.group(0).output_lens
    n_in = len(traffic.group(0).prompt)
    assert traj["input_ids"].shape == (8, n_in + max(lens))
    assert (traj["attention_mask"].sum(1) == n_in + np.asarray(lens)).all()
    assert (traj["loss_mask"].sum(1) == np.asarray(lens)).all()
    assert set(np.unique(traj["versions"])) <= {-1, 0, 2} and traj["versions"].max() == 2
    assert set(np.unique(traj["rewards"])) <= {0.0, 1.0}
    assert len(wf.done) == 32 and all(r["resp"].output_len == r["want"] for r in wf.done)
    # the same seed deals the same rewards
    again = kind_grpo.PinnedGroups(traffic, 2, 1.0)
    assert (asyncio.run(again.arun_episode(engine, {"group": 0}))["rewards"] == traj["rewards"]).all()


def test_a_warm_up_step_ends_with_the_engine_idle():
    seen = iter([{"running_requests": 3, "queued_requests": 0},
                 {"running_requests": 0, "queued_requests": 2},
                 {"running_requests": 0, "queued_requests": 0}])
    polls = []
    engine = types.SimpleNamespace(get_metrics=lambda: polls.append(1) or next(seen))
    kind_grpo.settle(engine)
    assert len(polls) == 3
    busy = types.SimpleNamespace(get_metrics=lambda: {"running_requests": 1, "queued_requests": 0})
    with pytest.raises(TimeoutError):
        kind_grpo.settle(busy, timeout=0.05)


def test_group_loader_deals_whole_cycles():
    it = iter(kind_grpo.GroupLoader(8))
    first, second = next(it), next(it)
    assert [d["group"] for d in first] == list(range(8))
    assert [d["group"] for d in second] == list(range(8, 16))


def test_window_counters_are_deltas_of_totals_and_gauges_at_the_close():
    m0 = {"generated_tokens_total": 100, "sched_idle_secs_total": 1.5, "running_requests": 9,
          "consumed_staleness_max": 1, "role": "unified", "kv_fabric_digest": [1, 2]}
    m1 = {"generated_tokens_total": 350, "sched_idle_secs_total": 4.0, "running_requests": 3,
          "consumed_staleness_max": 2, "role": "unified", "kv_fabric_digest": [3],
          "new_since_total": 5, "flag": True}
    cfg = types.SimpleNamespace(new_tokens_per_chunk=128, max_running_requests=64)
    assert kind_grpo.window_counters(m0, m1, 51.0, cfg) == {
        "generated_tokens_total": 250, "sched_idle_secs_total": 2.5, "running_requests": 3,
        "consumed_staleness_max": 2, "new_since_total": 5, "window_secs": 51.0,
        "new_tokens_per_chunk": 128, "max_running_requests": 64}


# -- the files ----------------------------------------------------------------

SCHED = {"admit": 0.5, "prefill": 0.25, "dispatch": 1.0, "consume": 1.5, "wait_device": 40.0,
         "paused": 1.25, "idle": 5.0, "other": 0.5}  # 50 s of a thread's life
COUNTERS = {
    "window_secs": 50.0, "gate_closed_staleness_secs_total": 1.0,
    "gate_closed_concurrency_secs_total": 44.0, "paused_secs_total": 2.0, "pauses_total": 6,
    "prepare_batch_secs_total": 18.0, "batches_prepared_total": 6, "pending_secs_total": 336.0,
    "episodes_finished_total": 48, "consumed_samples_total": 384,
    "consumed_staleness_versions_total": 96, "weight_swap_secs_total": 1.5,
    "weight_updates_total": 6, **{f"sched_{k}_secs_total": v for k, v in SCHED.items()}}
FIELDS = {"train_wait_device_ms": 2700.0, "loop_step_ms": 8000.0,
          "loop_trained_tokens_per_s": 3787.0, "device_decode_share_pct": 25.0,
          "device_train_share_pct": 42.5}
NEW = {  # metric: (reader, layer, source, the value by hand from the context above)
    "loop_gate_closed_pct.grpo": ("counter_ratio", "loop", "program_counter", 90.0),
    "loop_paused_pct.grpo": ("counter_ratio", "loop", "program_counter", 4.0),
    "loop_batch_wait_ms.grpo": ("counter_ratio", "loop", "program_counter", 3000.0),
    "loop_episode_pending_ms.grpo": ("counter_ratio", "loop", "program_counter", 7000.0),
    "loop_sample_staleness_versions.grpo": ("counter_ratio", "loop", "program_counter", 0.25),
    "weights_swap_ms.grpo": ("counter_ratio", "decode engine", "program_counter", 250.0),
    "sched_wait_device_pct.grpo": ("counter_ratio", "decode engine", "program_counter", 80.0),
    "sched_idle_pct.grpo": ("counter_ratio", "decode engine", "program_counter", 10.0),
    "sched_host_work_pct.grpo": ("counter_ratio", "decode engine", "program_counter", 7.5),
    "train_wait_device_ms.grpo": ("batch_field", "trainer", "program_span", 2700.0),
    "loop_step_ms.grpo": ("batch_field", "loop", "host_clock", 8000.0),
    "loop_trained_tokens_per_s.grpo": ("batch_field", "loop", "host_clock", 3787.0),
    "device_decode_share_pct.grpo": ("batch_field", "device", "device_trace", 25.0),
    "device_train_share_pct.grpo": ("batch_field", "device", "device_trace", 42.5),
}


@pytest.mark.parametrize("name", sorted(NEW))
def test_metric_names_a_reader_that_was_there_and_reads_a_number(name):
    reader, layer, source, by_hand = NEW[name]
    spec = REG.layer_metric(name)
    assert spec["reader"] == reader and reader in readers.READERS
    entry = next(m for m in REG.bench["per_layer"] if m["name"] == name)
    assert (entry["layer"], entry["source"], entry["moves"], entry["workloads"]) == (
        layer, source, "rollout_tokens_per_s", [CELL])
    ctx = {"counters": COUNTERS, "fields": FIELDS}
    assert readers.read(spec, ctx) == pytest.approx(by_hand)
    # a program without the counter or the span: left out, not raised
    assert readers.read(spec, {"counters": {"window_secs": 50.0}, "fields": {}}) is None


def test_the_cell_reports_what_issue_34_lists():
    names = {m["name"] for m in REG.metrics("per_layer", CELL)}
    assert names == set(NEW) | {"chunk_device_ms.rollout", "device_idle_pct.rollout",
                                "decode_slot_occupancy_pct.rollout", "decode_queue_ms.rollout"}
    assert [m["name"] for m in REG.metrics("end_to_end", CELL)] == ["rollout_tokens_per_s", "setup_s"]
    # the last of every list it joined: nothing before it moved
    for m in REG.bench["end_to_end"] + REG.bench["per_layer"]:
        if CELL in m.get("workloads", []):
            assert m["workloads"][-1] == CELL
    assert REG.bench["workloads"][-1]["name"] == CELL
    # the scheduler's states of the three shares are the program's eight
    from areal_tpu.engine.jax_decode import SCHED_STATES

    den = REG.layer_metric("sched_idle_pct.grpo")["args"]["den"][0]
    assert den == [f"sched_{s}_secs_total" for s in SCHED_STATES]


def test_traffic_and_cell_files_equal_issue_34s_table():
    cell = REG.cell(CELL)
    t = cell["traffic_file"]
    assert {k: t[k] for k in t if k != "from"} == {
        "n_samples": 8, "temperature": 1.0, "prompt_len": {"lo": 64, "hi": 256},
        "prompt_strata": 8, "groups_per_batch": 8,
        "output_len": {"dist": "lognormal", "median": 256, "sigma": 0.7, "lo": 16, "hi": 1024}}
    # the same lengths as the trainer-only cell's traffic: a step packs the same shapes
    train = REG.cell("train-0.5b-gsm8k")["traffic_file"]
    assert all(t[k] == train[k] for k in ("n_samples", "prompt_len", "prompt_strata",
                                          "output_len", "groups_per_batch"))
    e = cell["experiment"]
    assert (cell["kind"], cell["config"], cell["chips"]) == ("grpo", "qwen2.5-0.5b", 1)
    assert e["rollout"] == {"max_concurrent_rollouts": 16, "max_head_offpolicyness": 4}
    assert e["decode"] == {"context_length": 1280, "max_running_requests": 64,
                           "new_tokens_per_chunk": 128, "page_size": 128,
                           "dtype": "bfloat16", "kv_cache_dtype": "bfloat16"}
    a = e["actor"]
    assert (a["ppo_n_minibatches"], a["mb_spec"], a["gradient_checkpointing"]) == (
        4, {"max_tokens_per_mb": 8192}, True)
    assert (cell["warmup_steps"], cell["min_whole_steps"]) == (3, 4)
    # 8 s traced, not the table's 20: the profiler's stop (PERF.md section 7)
    assert (cell["trace_after_seconds"], cell["trace_seconds"]) == (15, 8)
    assert REG.bench["run_seconds"] == 51
    # every group holds the same eight lengths, 30,296 tokens a step
    from benchmark.lib.traffic import Traffic, batch_lengths

    tr = Traffic(t, 151936, 7)
    assert sorted(tr.group(0).output_lens) == sorted(tr.group(13).output_lens)
    assert sum(batch_lengths(tr.train_batch(2, 8))) == 30296
