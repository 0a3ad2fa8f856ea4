"""The loop's time, counted where the work happens: the staleness gate's
closed periods by the rule that closed them, the executor's pauses, the wait
for a batch, an episode's time and its wait, how stale the consumed samples
were (`WorkflowExecutor.get_metrics()`); the scheduler thread's time by state
and what a weight push costs (`JaxDecodeEngine.get_metrics()`)."""

import asyncio
import time

import numpy as np
import pytest

from areal_tpu.api.cli_args import (
    GenerationHyperparameters,
    InferenceEngineConfig,
    JaxDecodeConfig,
)
from areal_tpu.api.io_struct import ModelRequest
from areal_tpu.api.workflow_api import RolloutWorkflow
from areal_tpu.core.staleness_manager import StalenessManager
from areal_tpu.core.workflow_executor import WorkflowExecutor
from areal_tpu.utils import perf_tracer


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self) -> float:
        return self.t


class FakeEngine:
    def get_version(self):
        return 0


class VersionedWorkflow(RolloutWorkflow):
    """One sample of 2 prompt + 3 generated tokens whose `versions` the item
    gives (the newest is what the gate's promise is about)."""

    async def arun_episode(self, engine, data):
        await asyncio.sleep(0.001)
        return dict(
            input_ids=np.ones((1, 5), np.int32),
            attention_mask=np.ones((1, 5), bool),
            versions=np.array([[-1, -1, *data["versions"]]], np.int32),
        )


@pytest.fixture
def clock(monkeypatch):
    c = FakeClock()
    monkeypatch.setattr(WorkflowExecutor, "_clock", staticmethod(c))
    return c


def _executor(**kw):
    ex = WorkflowExecutor(InferenceEngineConfig(**kw), FakeEngine())
    ex.initialize()
    return ex


@pytest.mark.parametrize("version, running, accepted, want", [
    (0, 0, 0, (4, "concurrency")),    # min(4 - 0, (1 + 0 + 1) * 4 - 0 = 8)
    (0, 4, 0, (0, "concurrency")),    # min(0, 4): the slots are full
    (0, 2, 6, (0, "staleness")),      # min(2, 8 - 8 = 0): two versions' worth is out
    (1, 2, 6, (2, "concurrency")),    # a version later: min(2, 12 - 8 = 4)
    (0, 1, 9, (-2, "staleness")),     # over capacity is negative, and named
])
def test_the_gate_names_the_term_that_binds(version, running, accepted, want):
    sm = StalenessManager(max_concurrent_rollouts=4, consumer_batch_size=4, max_staleness=1)
    sm.rollout_stat.running, sm.rollout_stat.accepted = running, accepted
    assert sm.gate(version) == want
    assert sm.get_capacity(version) == want[0]


def test_gate_closed_seconds_by_the_rule_that_closed_it(clock):
    """A scripted sequence against a fake clock: open, closed by concurrency
    for 3 s, open, closed by staleness for 5 s (2 s of it still running when
    the counters are read), with one span a closed period."""
    ex = _executor(max_concurrent_rollouts=2, consumer_batch_size=2, max_head_offpolicyness=1)
    sm, wf = ex.staleness_manager, VersionedWorkflow()
    try:
        with perf_tracer.recording() as rec:
            for _ in range(5):
                ex.submit(dict(versions=[0, 0, 0]), workflow=wf)
            ex._admit_pending()  # two start, three are held: 2 of 2 slots
            assert sm.get_stats().running == 2 and sm.gate(0) == (0, "concurrency")
            assert ex.get_metrics()["gate_closed_concurrency_secs_total"] == 0.0
            clock.t += 3.0
            assert ex.get_metrics()["gate_closed_concurrency_secs_total"] == 3.0
            # both finish and are accepted; two more start, and with four
            # samples out at version 0 (two versions' worth) staleness binds
            sm.on_rollout_accepted()
            sm.on_rollout_accepted()
            ex._admit_pending()
            assert sm.get_stats().running == 2 and sm.gate(0) == (0, "staleness")
            clock.t += 3.0
            sm.on_rollout_accepted()
            sm.on_rollout_accepted()
            ex._admit_pending()  # slots free, still closed: the same period
            assert sm.gate(0) == (0, "staleness")
            clock.t += 2.0
            m = ex.get_metrics()
            assert m["gate_closed_concurrency_secs_total"] == 3.0
            assert m["gate_closed_staleness_secs_total"] == 5.0
            # the next version opens it
            ex.set_version(1)
            ex._admit_pending()
            clock.t += 7.0
            m = ex.get_metrics()
            assert (m["gate_closed_staleness_secs_total"],
                    m["gate_closed_concurrency_secs_total"]) == (5.0, 3.0)
            assert sm.get_stats().running == 1
        closed = [s for s in rec.snapshot() if s["name"] == "rollout/gate_closed"]
        assert [s["ids"]["by"] for s in closed] == ["concurrency", "staleness"]
        assert all(s["parent"] is None and not s["open"] for s in closed)
    finally:
        ex.destroy()


def test_nothing_pending_is_not_a_closed_gate(clock):
    ex = _executor(max_concurrent_rollouts=1, consumer_batch_size=1, max_head_offpolicyness=0)
    try:
        ex.submit(dict(versions=[0, 0, 0]), workflow=VersionedWorkflow())
        ex._admit_pending()  # capacity is 0 now, but nothing waits
        clock.t += 10.0
        ex._admit_pending()
        m = ex.get_metrics()
        assert m["gate_closed_concurrency_secs_total"] == 0.0
        assert m["gate_closed_staleness_secs_total"] == 0.0
    finally:
        ex.destroy()


def test_paused_seconds_from_pause_to_resume(clock):
    ex = _executor(max_concurrent_rollouts=2, consumer_batch_size=2)
    try:
        with perf_tracer.recording() as rec:
            ex.pause()
            clock.t += 4.0
            assert ex.get_metrics()["paused_secs_total"] == 4.0  # running period
            # the loop holds it through its push, as main does
            with perf_tracer.span("step/update_weights"):
                clock.t += 1.5
            ex.resume()
            clock.t += 9.0
            ex.pause()
            clock.t += 0.5
            ex.resume()
        m = ex.get_metrics()
        assert m["paused_secs_total"] == 6.0 and m["pauses_total"] == 2
        spans = {s["name"]: s for s in rec.snapshot()}
        # detached: the span of the push is not its child, it is not the
        # child of whatever called pause()
        assert spans["rollout/paused"]["parent"] is None
        assert spans["step/update_weights"]["parent"] is None
    finally:
        ex.destroy()


def test_consumed_staleness_in_versions():
    """Trainer version at consumption less the newest version of a sample's
    tokens: 3 - 3, 3 - 2 (its newest token, not its first) and 3 - 0."""
    ex = _executor(max_concurrent_rollouts=8, consumer_batch_size=4, max_head_offpolicyness=8)
    try:
        ex.set_version(3)
        for versions in ([3, 3, 3], [1, 2, 2], [0, 0, 0]):
            ex.submit(dict(versions=versions), workflow=VersionedWorkflow())
        batch = ex.wait(3, timeout=30)
        assert batch["versions"].shape == (3, 5)
        m = ex.get_metrics()
        assert m["consumed_samples_total"] == 3
        assert m["consumed_staleness_versions_total"] == 0 + 1 + 3
        assert m["consumed_staleness_max"] == 3
        assert m["episodes_finished_total"] == 3
        assert m["episode_secs_total"] > 0.0 and m["pending_secs_total"] >= 0.0
    finally:
        ex.destroy()


class FakeLoader:
    def __init__(self, items, batch_size):
        self.items, self.batch_size = items, batch_size

    def __iter__(self):
        for i in range(0, len(self.items), self.batch_size):
            yield self.items[i:i + self.batch_size]


def test_prepare_batch_counts_its_wait_and_the_record_has_the_episodes():
    ex = _executor(max_concurrent_rollouts=8, consumer_batch_size=2, max_head_offpolicyness=4)
    loader = FakeLoader([dict(versions=[0, 0, 0])] * 8, 2)
    try:
        with perf_tracer.recording() as rec:
            t0 = time.monotonic()
            ex.prepare_batch(loader, workflow=VersionedWorkflow())
            ex.prepare_batch(loader, workflow=VersionedWorkflow())
            wall = time.monotonic() - t0
        m = ex.get_metrics()
        assert m["batches_prepared_total"] == 2
        assert 0.0 < m["prepare_batch_secs_total"] <= wall
        names = [s["name"] for s in rec.snapshot()]
        # after the fact, with the rid: one an episode launched, one an
        # episode done (two batches stay in the pipeline, so some still run)
        assert names.count("rollout/pending") >= names.count("rollout/episode") >= 4
        assert names.count("rollout/prepare_batch") == 2
        assert all("rid" in s["ids"] for s in rec.snapshot()
                   if s["name"] in ("rollout/pending", "rollout/episode"))
    finally:
        ex.destroy()


def test_every_metric_is_a_number():
    ex = _executor()
    try:
        m = ex.get_metrics()
        assert set(m) == {
            "gate_closed_staleness_secs_total", "gate_closed_concurrency_secs_total",
            "paused_secs_total", "pauses_total", "prepare_batch_secs_total",
            "batches_prepared_total", "episode_secs_total", "pending_secs_total",
            "episodes_finished_total", "consumed_samples_total",
            "consumed_staleness_versions_total", "consumed_staleness_max"}
        for k, v in m.items():
            assert isinstance(v, float if k.endswith("_secs_total") else int), k
    finally:
        ex.destroy()


# -- the decode engine ---------------------------------------------------

SCHED_STATES = ("admit", "prefill", "dispatch", "consume", "wait_device",
                "hold", "paused", "idle", "other")


@pytest.fixture(scope="module")
def engine(cpu_devices):
    import jax

    from areal_tpu.engine.jax_decode import JaxDecodeEngine
    from areal_tpu.models.qwen2 import ModelConfig, init_params

    tiny = ModelConfig(vocab_size=64, hidden_size=32, intermediate_size=64,
                       num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
                       dtype="float32", param_dtype="float32")
    eng = JaxDecodeEngine(
        JaxDecodeConfig(context_length=256, max_running_requests=4, new_tokens_per_chunk=4,
                        page_size=128, dtype="float32", kv_cache_dtype="float32"),
        InferenceEngineConfig())
    params = init_params(tiny, jax.random.PRNGKey(0))
    eng.set_model(params, tiny)
    eng.initialize()
    yield eng, params, tiny
    eng.destroy()


def _generate(eng, n_new=8, prompt=(1, 5, 9, 13, 2)):
    return eng.generate(ModelRequest(
        input_ids=list(prompt),
        gconfig=GenerationHyperparameters(greedy=True, max_new_tokens=n_new)), timeout=300)


def _sched(m):
    return {k: m[f"sched_{k}_secs_total"] for k in SCHED_STATES}


def test_scheduler_states_sum_to_the_threads_life(engine):
    """Work, then a wait for traffic, then a pause: the nine exclusive
    states account for the whole stretch within 1%, and each of the three
    phases shows where it should."""
    eng = engine[0]
    _generate(eng)  # compiles: not in the stretch
    t0, m0 = time.monotonic(), eng.get_metrics()
    _generate(eng, 16, prompt=(3, 7, 11, 4, 8, 2))  # a prompt no slot holds: a prefill
    time.sleep(0.3)  # nothing queued, nothing active
    eng.pause_generation()
    time.sleep(0.2)
    eng.continue_generation()
    time.sleep(0.05)
    m1, t1 = eng.get_metrics(), time.monotonic()
    delta = {k: _sched(m1)[k] - _sched(m0)[k] for k in SCHED_STATES}
    assert all(v >= 0.0 for v in delta.values()), delta
    assert sum(delta.values()) == pytest.approx(t1 - t0, rel=0.01)
    assert delta["idle"] >= 0.25 and delta["paused"] >= 0.19
    assert delta["dispatch"] > 0 and delta["wait_device"] > 0 and delta["consume"] > 0
    assert delta["admit"] > 0 and delta["prefill"] > 0


def test_decode_idle_is_open_while_nothing_is_queued(engine):
    eng = engine[0]
    with perf_tracer.recording() as rec:
        _generate(eng)
        # the response returns before the scheduler's pass ends: wait for the
        # thread to have gone idle (on busy cores that takes more than a
        # moment), then let the idle period run
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:
            open_now = [s for s in rec.snapshot() if s["open"]]
            if [s["name"] for s in open_now] == ["decode/idle"]:
                break
            time.sleep(0.01)
        time.sleep(0.1)
        snap = rec.snapshot()
        open_now = [s for s in snap if s["open"]]
        assert [s["name"] for s in open_now] == ["decode/idle"]
        # the scheduler's spans leave no instant of its thread unmarked
        # but the loop's own bookkeeping between them
        thread = open_now[0]["thread"]
        names = {s["name"] for s in snap if s["thread"] == thread}
        assert {"decode/pass", "decode/admit", "decode/prefill", "decode/dispatch_chunk",
                "decode/consume_chunk", "decode/wait_device", "decode/idle"} <= names
        _generate(eng)  # traffic ends the idle period
        # (the response returns before the pass ends: on busy cores the thread's
        # next idle span, the second, may not have begun yet)
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline and sum(
                s["name"] == "decode/idle" for s in rec.snapshot()) < 2:
            time.sleep(0.01)
    idle = [s for s in rec.snapshot() if s["name"] == "decode/idle"]
    assert len(idle) >= 2 and not idle[0]["open"]
    assert idle[0]["end_ns"] - idle[0]["start_ns"] >= 0.09e9


def test_weight_swap_counts_the_push_and_its_drain(engine):
    """An in-memory push: pause requested to generation resumed, and the part
    of it that waited for the chunk boundary."""
    eng, params, tiny = engine
    from areal_tpu.api.io_struct import WeightUpdateMeta

    m0 = eng.get_metrics()
    for version in (1, 2):
        eng.update_weights_from_distributed(WeightUpdateMeta(type="memory"), params, tiny)
        eng.set_version(version)
    m1 = eng.get_metrics()
    assert m1["weight_updates_total"] - m0["weight_updates_total"] == 2
    swap = m1["weight_swap_secs_total"] - m0["weight_swap_secs_total"]
    drain = m1["weight_drain_secs_total"] - m0["weight_drain_secs_total"]
    assert swap >= drain > 0.0
    assert len(_generate(eng).output_tokens) == 8  # and it generates again


def test_metrics_lost_their_unread_keys_and_kept_the_routers(engine):
    from areal_tpu.launcher.router import _PRESSURE_KEYS

    m = engine[0].get_metrics()
    for gone in ("ttft_queue_p50_ms", "ttft_queue_p99_ms", "ttft_prefill_p50_ms",
                 "ttft_transfer_p50_ms", "transfer_secs_total"):
        assert gone not in m, gone
    # every key of the router's list that the engine itself provides is
    # still there (the fabric and host-tier keys among them)
    missing = [k for k in _PRESSURE_KEYS if k not in m]
    assert not missing, missing
    assert "queue_secs_total" in m and "itl_wall_p99_ms" in m
