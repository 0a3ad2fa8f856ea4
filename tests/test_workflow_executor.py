import asyncio
import time

import numpy as np
import pytest

from areal_tpu.api.cli_args import InferenceEngineConfig
from areal_tpu.api.workflow_api import RolloutWorkflow
from areal_tpu.core.workflow_executor import (
    WorkflowExecutor,
    check_trajectory_format,
)


class FakeEngine:
    def get_version(self):
        return 0


class EchoWorkflow(RolloutWorkflow):
    """Returns a 1-sample trajectory built from the item, or None if
    data['reject'] is set."""

    async def arun_episode(self, engine, data):
        await asyncio.sleep(0.01)
        if data.get("reject"):
            return None
        L = int(data.get("len", 4))
        return dict(
            input_ids=np.full((1, L), data["value"], dtype=np.int32),
            attention_mask=np.ones((1, L), dtype=bool),
            rewards=np.array([float(data["value"])], dtype=np.float32),
        )


class FakeLoader:
    """Iterable of lists of items with a batch_size attr."""

    def __init__(self, items, batch_size):
        self.items = items
        self.batch_size = batch_size

    def __iter__(self):
        for i in range(0, len(self.items), self.batch_size):
            yield self.items[i : i + self.batch_size]


@pytest.fixture()
def executor():
    cfg = InferenceEngineConfig(
        max_concurrent_rollouts=16,
        consumer_batch_size=4,
        max_head_offpolicyness=2,
        check_trajectory_format=True,
    )
    ex = WorkflowExecutor(cfg, FakeEngine())
    ex.initialize()
    yield ex
    ex.destroy()


def test_rollout_batch_collects_all(executor):
    data = [dict(value=i, len=3 + i % 2) for i in range(6)]
    batch = executor.rollout_batch(data, workflow=EchoWorkflow())
    assert batch["input_ids"].shape[0] == 6
    assert sorted(batch["rewards"].tolist()) == [0.0, 1.0, 2.0, 3.0, 4.0, 5.0]


def test_rejected_episodes_not_counted(executor):
    for i in range(4):
        executor.submit(dict(value=i), workflow=EchoWorkflow())
    executor.submit(dict(value=99, reject=True), workflow=EchoWorkflow())
    batch = executor.wait(4, timeout=10)
    assert batch["input_ids"].shape[0] == 4
    stats = executor.get_stats()
    assert stats.accepted == 4


def test_should_accept_filter(executor):
    for i in range(6):
        executor.submit(
            dict(value=i),
            workflow=EchoWorkflow(),
            should_accept=lambda t: float(t["rewards"][0]) % 2 == 0,
        )
    batch = executor.wait(3, timeout=10)
    assert sorted(batch["rewards"].tolist()) == [0.0, 2.0, 4.0]


def test_staleness_gates_admission(executor):
    # max_staleness=2, bs=4, version=0 -> at most 12 admitted
    for i in range(20):
        executor.submit(dict(value=i), workflow=EchoWorkflow())
    batch = executor.wait(12, timeout=10)
    assert batch["input_ids"].shape[0] == 12
    stats = executor.get_stats()
    assert stats.submitted == 12  # the rest are gated in pending
    # bumping the version admits more
    executor.set_version(1)
    batch = executor.wait(4, timeout=10)
    assert batch["input_ids"].shape[0] == 4


def test_prepare_batch_returns_batches(executor):
    loader = FakeLoader([dict(value=i) for i in range(32)], batch_size=4)
    b1 = executor.prepare_batch(loader, workflow=EchoWorkflow())
    assert b1["input_ids"].shape[0] == 4
    executor.set_version(1)
    b2 = executor.prepare_batch(loader, workflow=EchoWorkflow())
    assert b2["input_ids"].shape[0] == 4


def test_format_check():
    with pytest.raises(ValueError):
        check_trajectory_format({})
    with pytest.raises(ValueError):
        check_trajectory_format(dict(input_ids=np.zeros((2, 3))))
    with pytest.raises(ValueError):
        check_trajectory_format(
            dict(
                input_ids=np.zeros((2, 3)),
                attention_mask=np.zeros((2, 4)),
            )
        )
    with pytest.raises(ValueError):
        check_trajectory_format(
            dict(
                input_ids=np.zeros((2, 3)),
                attention_mask=np.zeros((2, 3)),
                rewards=np.zeros(5),
            )
        )
    check_trajectory_format(
        dict(
            input_ids=np.zeros((2, 3)),
            attention_mask=np.zeros((2, 3)),
            rewards=np.zeros(2),
        )
    )


# -- failure accounting (ISSUE 9 satellite) ---------------------------------


class BoomWorkflow(RolloutWorkflow):
    async def arun_episode(self, engine, data):
        await asyncio.sleep(0.005)
        raise RuntimeError("rollout died")


def test_failed_episode_releases_running_slot_exactly_once(executor):
    """A rollout task that raises must decrement rollout_stat.running
    exactly once — no leak (wedged capacity), no double-release
    (negative running)."""
    n = 6
    for i in range(n):
        executor.submit({"value": i}, workflow=BoomWorkflow())
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        executor._admit_pending()
        executor._collect()
        stats = executor.staleness_manager.get_stats()
        if stats.submitted == n and stats.running == 0:
            break
        time.sleep(0.02)
    stats = executor.staleness_manager.get_stats()
    assert stats.submitted == n
    assert stats.running == 0, "failed episodes leaked running slots"
    assert stats.accepted == 0


def test_failure_streak_escalates_but_releases_slots(executor):
    """16 consecutive failures must surface a RuntimeError (a systematic
    failure, e.g. a crashed decode engine) — with every slot released
    first, so recovery after the operator intervenes starts from clean
    accounting. The message must embed the root cause (ISSUE 14
    satellite), not just point at __cause__."""
    for i in range(20):
        executor.submit({"value": i}, workflow=BoomWorkflow())
    with pytest.raises(RuntimeError, match="rollout died"):
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            executor._admit_pending()
            executor._collect()
            time.sleep(0.02)
    # nothing leaked: every still-"running" slot is accounted for by a
    # result the executor had not yet processed when it escalated (plus
    # any task still in flight) — processed failures all released
    unprocessed = len(executor.runner.poll_results())
    stats = executor.staleness_manager.get_stats()
    assert stats.running == unprocessed + executor.runner.inflight


def test_dead_engine_fails_wait_at_once(executor):
    """One episode failing with EngineDeadError (the decode scheduler died)
    ends wait() with that error — not a 16-failure streak that a small run
    never reaches, and not the 3600 s default timeout."""
    from areal_tpu.api.engine_api import EngineDeadError

    class DeadWorkflow(RolloutWorkflow):
        async def arun_episode(self, engine, data):
            raise EngineDeadError("decode scheduler died (lowering error)")

    executor.submit({"value": 0}, workflow=DeadWorkflow())
    t0 = time.monotonic()
    with pytest.raises(EngineDeadError, match="lowering error"):
        executor.wait(1)
    assert time.monotonic() - t0 < 10


def test_cancelled_episode_not_counted_as_failure():
    """A drained (cancelled) episode releases its slot but must not feed
    the consecutive-failure escalation."""
    from areal_tpu.core.async_task_runner import TaskResult

    cfg = InferenceEngineConfig(
        max_concurrent_rollouts=4, consumer_batch_size=2,
        max_head_offpolicyness=2,
    )
    ex = WorkflowExecutor(cfg, FakeEngine())
    ex.staleness_manager.on_rollout_submitted()
    streak_before = ex._consecutive_failures
    try:
        ex._on_result(
            TaskResult(task_id=0, exception=asyncio.CancelledError())
        )
        assert ex.staleness_manager.get_stats().running == 0
        assert ex._consecutive_failures == streak_before
    finally:
        pass


# -- sample ledger (ISSUE 14) ------------------------------------------------


def test_batches_are_stamped_and_journaled(executor):
    """Accepted trajectories carry (rollout_id, rollout_version); wait()
    journals exactly the consumed identities."""
    executor.set_version(0)
    data = [dict(value=i) for i in range(4)]
    batch = executor.rollout_batch(data, workflow=EchoWorkflow())
    assert sorted(batch["rollout_id"].tolist()) == [0, 1, 2, 3]
    assert batch["rollout_version"].tolist() == [0, 0, 0, 0]
    assert executor.ledger.consumed_count() == 4
    assert executor.ledger.pending_count() == 0


def test_already_consumed_rid_is_deduped(executor):
    """A duplicate arriving for a consumed rollout id (a still-running
    replica delivering after a trainer restart) must be rejected, not
    trained twice."""
    executor.submit(dict(value=1), workflow=EchoWorkflow(), rollout_id=7)
    batch = executor.wait(1, timeout=10)
    assert batch["rollout_id"].tolist() == [7]
    assert executor.ledger.consumed_count() == 1
    # the duplicate: same rid, fresh submission
    executor.submit(dict(value=1), workflow=EchoWorkflow(), rollout_id=7)
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        executor._admit_pending()
        executor._collect()
        st = executor.get_stats()
        if st.running == 0 and executor.ledger.deduped_total() >= 1:
            break
        time.sleep(0.02)
    assert executor.ledger.deduped_total() == 1
    assert executor.ledger.consumed_count() == 1
    assert len(executor._result_cache) == 0
    assert executor.get_stats().running == 0


def test_executor_state_roundtrip_restores_capacity(tmp_path):
    """load_state_dict: accepted := consumed count, running := 0 — a
    restarted executor's staleness cap continues from the committed
    consumption, not from counters inflated by died-in-flight work."""
    cfg = InferenceEngineConfig(
        max_concurrent_rollouts=16,
        consumer_batch_size=4,
        max_head_offpolicyness=2,
        check_trajectory_format=True,
    )
    ex = WorkflowExecutor(cfg, FakeEngine())
    ex.initialize()
    try:
        ex.attach_ledger_wal(str(tmp_path / "ledger.wal"))
        ex.rollout_batch(
            [dict(value=i) for i in range(4)], workflow=EchoWorkflow()
        )
        # two more accepted but never consumed: they die with the process
        ex.submit(dict(value=9), workflow=EchoWorkflow())
        ex.submit(dict(value=10), workflow=EchoWorkflow())
        deadline = time.monotonic() + 10
        while len(ex._result_cache) < 2 and time.monotonic() < deadline:
            ex._admit_pending()
            ex._collect()
            time.sleep(0.02)
        assert len(ex._result_cache) == 2
        state = ex.state_dict()
    finally:
        ex.destroy()

    ex2 = WorkflowExecutor(cfg, FakeEngine())
    ex2.initialize()
    try:
        ex2.attach_ledger_wal(str(tmp_path / "ledger.wal"))
        ex2.load_state_dict(state)
        st = ex2.get_stats()
        assert st.accepted == 4  # consumed count, not the raw 6
        assert st.running == 0
        assert ex2._result_cache == []
        # fresh rids continue after every previously issued id
        assert ex2.ledger.new_rid() == 6
        # capacity at version 0: min(16 - 0, (2+0+1)*4 - 4) = 8
        assert ex2.staleness_manager.get_capacity(0) == 8
    finally:
        ex2.destroy()
