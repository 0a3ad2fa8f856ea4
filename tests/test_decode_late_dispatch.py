"""The next decode chunk is dispatched when the device is about to need it, not
when the last one is read back (`_hold_dispatch`, `_wait_held`): a request
that arrives meanwhile is admitted at once and joins the very next chunk.

Held here, on the CPU at tiny widths, with the scheduler's passes made by hand
and the clock, the device-time estimate and the chunk's readiness injected (no
sleep decides anything): an arrival during a hold is in the next chunk, one
sooner than on the schedule without the hold; every stream is the synchronous
(`decode_runahead_chunks=0`) engine's to the bit; the dispatch is never held
with a request left queued, with every slot live, with nothing in flight, at
run-ahead 0 or for a program with no device-time reading; the admissions of
one hold spend one `max_prefill_tokens` between them; an estimate that
overshot is counted; and, with the scheduler's own thread in a hold that only
an event can end, a pause, a weight swap, `abort_all`, a scheduler exception,
shutdown and an arrival each end it at once.
"""

import os
import sys
import time

import jax
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from test_decode_handover import (  # noqa: E402
    MODELS,
    TIMEOUT,
    _engine,
    _queue,
    _request,
    _uniform,
)

from areal_tpu.api.engine_api import EngineDeadError  # noqa: E402
from areal_tpu.engine import jax_decode  # noqa: E402

CHUNK = 4
EST = 1.0  # the injected device seconds of every chunk program
LEAD = jax_decode._HOLD_MARGIN * EST  # no dispatch time on a clock that stands still


class _Clock:
    """The engine's host clock, moved by hand."""

    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


class _Wake:
    """In the place of `engine._wake`: a wait does the next thing the script
    says (an arrival: it returns as a set event would) or, with no script, lets
    the clock run for the time asked, as a wait that times out."""

    def __init__(self, clock):
        self.clock, self.script, self.waits = clock, [], 0

    def set(self):
        pass

    def clear(self):
        pass

    def wait(self, timeout):
        self.waits += 1
        if self.script:
            self.script.pop(0)()
        else:
            self.clock.t += timeout


def _by_hand(engine):
    """End the scheduler thread (its passes are made here, with no pause flag
    in their way) and put the clock, the estimate, the chunk's readiness and
    the wake under the test's hand. `engine.ready[0]` is what `_chunk_ready`
    answers (a chunk is still running until the test says so)."""
    engine._shutdown.set()
    engine._thread.join(TIMEOUT)
    assert not engine._thread.is_alive()
    engine._shutdown.clear()
    engine._clock = clock = _Clock()
    engine._wake = wake = _Wake(clock)
    engine.ready, engine.est = [False], [None]  # (no estimate until `_primed` gives one)
    engine._chunk_ready = lambda rec: engine.ready[0]
    engine._chunk_estimate = lambda rec: engine.est[0]
    engine.log = []  # (chunk number, the requests live in it)
    dispatch = engine._dispatch_chunk

    def logged(active):
        rec = dispatch(active)
        if rec is not None:
            engine.log.append((rec.chunk, [s.rid for s, a in zip(rec.items, rec.active) if a]))
        return rec

    engine._dispatch_chunk = logged
    return clock, wake


def _drive(engine, hold=None, runahead=1):
    """One pass and, if it ends in a hold, the hold's wait; returns the hold."""
    with engine._sched_lock:
        paused, _, hold = engine._pass_locked(runahead, hold)
    assert not paused
    if hold is not None:
        engine._wait_held(hold)
    return hold


def _first_chunk(engine, rid):
    return next(n for n, rids in engine.log if rid in rids)


def _primed(engine, cfg, n=16, stale=False):
    """A alone through two passes: chunk 2 in flight behind chunk 1, which was
    read back as it ended (the ready stamp is the device's: chunk 2 started
    then), and an estimate for every program from here on: the third pass can
    hold. `stale`: chunk 1 was running when chunk 2 went out and had ended
    when the host came to read it, so its stamp is the host's arrival."""
    base = engine.log[-1][0] if engine.log else 0
    a = _queue(engine, _request(cfg.vocab_size, 1, n), "a")
    assert _drive(engine) is None
    if stale:
        answers = iter([False, True])  # at chunk 2's dispatch; at chunk 1's read-back
        engine._chunk_ready = lambda rec: next(answers)
    assert _drive(engine) is None
    engine._chunk_ready = lambda rec: engine.ready[0]
    assert [n for n, _ in engine.log[-2:]] == [base + 1, base + 2] and len(engine._inflight) == 1
    assert (engine._inflight[0].t_start is None) == stale  # (known where chunk 1 was seen to end)
    engine.est[0] = EST
    return a


@pytest.fixture
def held(cpu_devices):
    """(engine, cfg, clock, wake): three slots, chunks of 4, driven by hand."""
    cfg, params = _uniform()
    engine = _engine(cfg, params, max_running_requests=3, new_tokens_per_chunk=CHUNK)
    clock, wake = _by_hand(engine)
    yield engine, cfg, clock, wake
    engine.destroy()


def _counters(engine):
    m = engine.get_metrics()
    return (m["chunks_dispatched_total"], m["chunks_held_total"], m["held_admissions_total"],
            m["chunks_dispatched_late_total"])


def test_an_arrival_during_a_hold_is_in_the_very_next_chunk(held):
    engine, cfg, clock, wake = held
    a = _primed(engine, cfg)
    t0 = clock.t
    wake.script.append(lambda: _queue(engine, _request(cfg.vocab_size, 2, 8), "b"))
    hold = _drive(engine)  # chunk 3 is held; B arrives during the wait
    assert hold is not None and hold.deadline == pytest.approx(t0 + EST - LEAD)
    assert clock.t == t0 and wake.waits == 1 and _counters(engine) == (2, 0, 0, 0)
    assert _drive(engine, hold) is hold  # B admitted at once; a slot is still empty: held on
    assert engine._slots[1] is not None and engine._slots[1].rid == "b"
    assert hold.deadline <= clock.t < hold.deadline + jax_decode._HOLD_POLL_S
    assert _counters(engine) == (2, 0, 1, 0)
    assert _drive(engine, hold) is None  # the deadline: chunk 3 goes out, chunk 2 is read
    assert engine.log[-1] == (3, ["a", "b"]) and _counters(engine) == (3, 1, 1, 0)
    assert len(a.tokens) == 2 * CHUNK and len(engine._inflight) == 1
    assert engine.get_metrics()["device_idle_s"] == 0.0


def test_without_the_hold_the_same_arrival_waits_a_chunk_more(held):
    """The schedule of the engine as it was: the dispatch at once, so the
    arrival (after it, as a closed loop's successor comes) is in chunk 4."""
    engine, cfg, clock, wake = held
    engine._dispatch_deadline = lambda: None
    _primed(engine, cfg)
    assert _drive(engine) is None and engine.log[-1] == (3, ["a"])
    _queue(engine, _request(cfg.vocab_size, 2, 8), "b")
    assert _drive(engine) is None
    assert _first_chunk(engine, "b") == 4 and _counters(engine) == (4, 0, 0, 0)


def test_every_slot_filled_ends_the_hold_before_its_deadline(held):
    """Two arrivals inside one hold fill the table: nothing more can gain."""
    engine, cfg, clock, wake = held
    _primed(engine, cfg)
    wake.script += [lambda: _queue(engine, _request(cfg.vocab_size, 2, 8), "b"),
                    lambda: _queue(engine, _request(cfg.vocab_size, 3, 8), "c")]
    hold = _drive(engine)
    hold = _drive(engine, hold)  # B in, C arrives
    t = clock.t
    assert hold is not None and _drive(engine, hold) is None  # C in: dispatched at once
    assert clock.t == t < hold.deadline
    assert engine.log[-1] == (3, ["a", "b", "c"]) and _counters(engine) == (3, 1, 2, 0)


def test_one_prefill_budget_a_dispatched_chunk(cpu_devices):
    """`max_prefill_tokens` of one bucket: B's prefill (the first on the
    budget) goes through, C's in the same hold finds it spent, stays queued,
    and a request left queued ends the hold; the next chunk's budget takes C."""
    cfg, params = _uniform()
    engine = _engine(cfg, params, max_running_requests=3, new_tokens_per_chunk=CHUNK,
                     max_prefill_tokens=64)
    try:
        clock, wake = _by_hand(engine)
        _primed(engine, cfg)
        wake.script += [lambda: _queue(engine, _request(cfg.vocab_size, 2, 8), "b"),
                        lambda: _queue(engine, _request(cfg.vocab_size, 3, 8), "c")]
        hold = _drive(engine)
        hold = _drive(engine, hold)
        assert hold is not None and engine._n_prefills == 2 and hold.budget.spent
        t = clock.t
        assert _drive(engine, hold) is None and clock.t == t
        assert [i.rid for i in engine._overflow] == ["c"] and engine._n_prefills == 2
        assert engine.log[-1] == (3, ["a", "b"]) and _counters(engine) == (3, 1, 1, 0)
        hold = _drive(engine)  # a fresh budget: C is admitted, then every slot is live
        assert hold is None and engine._n_prefills == 3
        assert engine.log[-1] == (4, ["a", "b", "c"]) and _counters(engine) == (4, 1, 1, 0)
    finally:
        engine.destroy()


def test_an_overshot_estimate_is_counted_with_its_seconds(held):
    engine, cfg, clock, wake = held
    _primed(engine, cfg, n=32)
    hold = _drive(engine)
    assert hold is not None and clock.t >= hold.deadline  # (it ran to its deadline)
    # a second hold, and this time the chunk in flight ends well before the deadline
    assert _drive(engine, hold) is None and _counters(engine) == (3, 1, 0, 0)
    with engine._sched_lock:
        _, _, hold = engine._pass_locked(1, None)
    assert hold is not None
    clock.t += 0.25
    engine.ready[0] = True
    waits = wake.waits
    engine._wait_held(hold)
    ended = clock.t
    assert wake.waits == waits and hold.rec.t_ended == ended  # found ended: no wait
    clock.t += 0.05
    assert _drive(engine, hold) is None
    late = engine._inflight[-1]
    assert _counters(engine) == (4, 2, 0, 1) and late.t_start == late.t_dispatch == ended + 0.05
    m = engine.get_metrics()
    assert m["chunks_late_in_admit_total"] == 0 and m["device_idle_s"] == 0.0
    # the chunk that ended early is stamped where the host saw it end, not where
    # it was read back: the late one's read-back finds the device's idle between
    # the two, and counts no second of it as busy besides
    busy = m["device_busy_s"]
    clock.t += 1.0
    assert _drive(engine) is None
    m = engine.get_metrics()
    assert m["device_idle_s"] == pytest.approx(0.05)
    assert m["device_busy_s"] - busy == pytest.approx(1.0)


def test_an_admission_that_outlasts_the_deadline_is_counted_apart(held):
    """B arrives in a hold and its admission waits on the device (an admission
    of some dozens of programs does: the device takes only so many ahead) until
    the chunk in flight has ended, past the deadline: late, and not by the
    estimate's fault."""
    engine, cfg, clock, wake = held
    _primed(engine, cfg)
    wake.script.append(lambda: _queue(engine, _request(cfg.vocab_size, 2, 8), "b"))
    hold = _drive(engine)
    admit = engine._admit

    def blocks_until_the_chunk_ends(budget=None):
        out = admit(budget)
        clock.t = hold.deadline + 0.1
        engine.ready[0] = True
        return out

    engine._admit = blocks_until_the_chunk_ends
    assert _drive(engine, hold) is None
    assert engine.log[-1] == (3, ["a", "b"]) and _counters(engine) == (3, 1, 1, 0)
    assert engine.get_metrics()["chunks_late_in_admit_total"] == 1
    # (nobody saw chunk 2 end: no reading of it, and chunk 3 started at its dispatch)
    assert hold.rec.t_ended is None and engine._inflight[-1].t_start == hold.deadline + 0.1


def test_a_reading_is_a_programs_start_to_its_end_where_the_host_saw_both(held):
    """Chunk 1 goes out to an idle device and the host is waiting when it ends:
    start to end is a reading of its program, and chunk 2, dispatched
    meanwhile, started at that end. The engine's own estimate from there on."""
    engine, cfg, clock, wake = held
    del engine._chunk_estimate
    _queue(engine, _request(cfg.vocab_size, 1, 16), "a")
    assert _drive(engine) is None
    t0 = clock.t
    assert engine._inflight[0].t_start == t0 and not engine._chunk_dev_s
    asked = []

    def ends_while_the_host_waits(rec):
        asked.append(rec.chunk)
        if len(asked) == 2:
            clock.t += 0.7
        return False

    engine._chunk_ready = ends_while_the_host_waits
    clock.t += 0.3
    assert _drive(engine) is None and asked == [1, 1]
    engine._chunk_ready = lambda rec: engine.ready[0]
    (program, seen), = engine._chunk_dev_s.items()
    assert list(seen) == [pytest.approx(1.0)] and engine._inflight[0].t_start == t0 + 1.0
    assert engine._inflight[0].program is program  # (the same bucket: the same program)
    hold = _drive(engine)
    assert hold is not None
    assert hold.deadline == pytest.approx(t0 + 1.0 + (1.0 - jax_decode._HOLD_MARGIN) * 1.0)


# -- where the dispatch is never held -------------------------------------------


def _left_queued(engine, cfg):
    # B fills the second slot; C finds none and stays queued
    _queue(engine, _request(cfg.vocab_size, 2, 16), "b")
    _primed(engine, cfg)
    _queue(engine, _request(cfg.vocab_size, 3, 8), "c")


def _every_slot_live(engine, cfg):
    _queue(engine, _request(cfg.vocab_size, 2, 16), "b")
    _primed(engine, cfg)


def _no_reading(engine, cfg):
    _primed(engine, cfg)
    del engine._chunk_estimate  # the engine's own: from its readings
    engine._chunk_dev_s.clear()


def _nothing_live(engine, cfg):
    # A's two chunks are dispatched: it is spent, and nothing else is live
    _primed(engine, cfg, n=2 * CHUNK)


NEVER = {"a request left queued": (_left_queued, 2), "every slot live": (_every_slot_live, 2),
         "no reading for the program": (_no_reading, 3),
         "nothing to dispatch": (_nothing_live, 3)}


@pytest.mark.parametrize("case", sorted(NEVER))
def test_never_held(cpu_devices, case):
    prepare, slots = NEVER[case]
    cfg, params = _uniform()
    engine = _engine(cfg, params, max_running_requests=slots, new_tokens_per_chunk=CHUNK)
    try:
        clock, wake = _by_hand(engine)
        prepare(engine, cfg)
        n = len(engine.log)
        assert _drive(engine) is None and wake.waits == 0
        assert len(engine.log) == n + (case != "nothing to dispatch")
        assert _counters(engine)[1:] == (0, 0, 0)
    finally:
        engine.destroy()


def test_a_ready_stamp_that_is_the_hosts_is_not_trusted(held):
    """Chunk 1 was running when chunk 2 went out and had ended when the host
    came to read it: chunk 2 started somewhere between, and the deadline is
    reckoned from the earlier of the two, its dispatch (too early is safe)."""
    engine, cfg, clock, wake = held
    a = _queue(engine, _request(cfg.vocab_size, 1, 16), "a")
    assert _drive(engine) is None
    asked = []

    def running_at_the_dispatch_ended_at_the_read_back(rec):
        asked.append(rec.chunk)
        if len(asked) == 2:
            clock.t += 0.2  # (the host comes to read it that much later)
        return len(asked) == 2

    engine._chunk_ready = running_at_the_dispatch_ended_at_the_read_back
    clock.t += 0.3
    dispatched = clock.t
    assert _drive(engine) is None and asked == [1, 1]
    engine._chunk_ready = lambda rec: engine.ready[0]
    assert engine._inflight[0].t_start is None and engine._last_ready_t == dispatched + 0.2
    engine.est[0] = EST
    hold = _drive(engine)
    assert hold is not None and hold.deadline == pytest.approx(dispatched + EST - LEAD)
    assert not engine._chunk_dev_s  # (and such a chunk gives no reading)


def test_the_same_table_is_held_once_the_condition_is_met(held):
    """The control for the cases above: three slots, one live, nothing queued,
    a chunk in flight, a reading, a ready stamp that is the device's."""
    engine, cfg, clock, wake = held
    _primed(engine, cfg)
    assert _drive(engine) is not None and wake.waits > 0


@pytest.mark.parametrize("runahead", [0, 1], ids=["run-ahead 0", "nothing in flight"])
def test_never_held_with_nothing_in_flight(cpu_devices, runahead):
    cfg, params = _uniform()
    engine = _engine(cfg, params, runahead, max_running_requests=3, new_tokens_per_chunk=CHUNK)
    try:
        clock, wake = _by_hand(engine)
        engine.est[0] = EST
        a = _queue(engine, _request(cfg.vocab_size, 1, 16), "a")
        passes = 4 if runahead == 0 else 1  # (at run-ahead 0 every pass reads its chunk back)
        for _ in range(passes):
            assert _drive(engine, runahead=runahead) is None
        assert len(engine.log) == passes and wake.waits == 0
        assert a.future.done() == (runahead == 0)
        assert _counters(engine)[1:] == (0, 0, 0)
    finally:
        engine.destroy()


# -- streams ----------------------------------------------------------------------

# (prompt's seed, pinned length, greedy), in order of arrival: the first two at
# once, each of the others during a hold; 0 and 1 share a prompt (the second
# forks the first, which has not decoded yet or has), 5 repeats 2's
WORK = [(0, 20, True), (0, 9, False), (1, 13, False), (2, 16, True), (3, 24, True),
        (1, 5, False), (4, 10, False), (5, 1, True), (6, 17, False)]


def _serve_by_hand(engine, reqs, runahead):
    """The first two requests at once, each of the others alone: during a
    hold's wait (run-ahead 1) or, at run-ahead 0, which never holds, before a
    pass that finds a slot empty. Either way a request is admitted the moment
    it arrives, in a wave of its own, so the two engines run the same prefill
    programs (a wave of two distinct prompts is another program, whose last
    bits differ), and their admission orders are the arrival order."""
    clock, wake = _by_hand(engine)
    engine.est[0] = EST
    items = [_queue(engine, r, f"r{i}") for i, r in enumerate(reqs[:2])]
    later = list(enumerate(reqs))[2:]

    def arrive():
        if later:
            i, r = later.pop(0)
            items.append(_queue(engine, r, f"r{i}"))

    hold = None
    for n in range(400):
        if all(i.future.done() for i in items) and not later:
            return [i.future.result(timeout=0) for i in items]
        if n and None in engine._slots and (
                runahead == 0 or (hold is None and not engine._inflight)):
            arrive()  # (no hold to arrive in: run-ahead 0, or the engine has gone idle)
        elif not wake.script:
            wake.script.append(arrive)
        hold = _drive(engine, hold, runahead)
    raise AssertionError("not served")


@pytest.fixture(scope="module", params=["uniform", "state", "latent"])
def streams(request, cpu_devices):
    """{"sync", "held"}: WORK through the synchronous engine and through the
    run-ahead engine, both driven by hand, a request arriving in every hold."""
    cfg, params = MODELS[request.param]()
    reqs = [_request(cfg.vocab_size, s, n, g) for s, n, g in WORK]
    out = {}
    for name, runahead in (("sync", 0), ("held", 1)):
        engine = _engine(cfg, params, runahead, max_running_requests=3,
                         new_tokens_per_chunk=CHUNK)
        try:
            out[name] = _serve_by_hand(engine, reqs, runahead)
            out[name + " metrics"] = engine.get_metrics()
        finally:
            engine.destroy()
    return out


@pytest.mark.parametrize("greedy", [True, False], ids=["greedy", "sampled"])
def test_streams_are_the_synchronous_engines_to_the_bit(streams, greedy):
    picked = [i for i, w in enumerate(WORK) if w[2] == greedy]
    for i in picked:
        a, b = streams["sync"][i], streams["held"][i]
        assert len(b.output_tokens) == WORK[i][1] and b.stop_reason == "length", i
        assert a.output_tokens == b.output_tokens, i
        assert a.output_logprobs == b.output_logprobs, i
        assert a.output_versions == b.output_versions, i


def test_the_arrivals_were_admitted_in_holds(streams):
    assert streams["sync metrics"]["chunks_held_total"] == 0
    m = streams["held metrics"]
    assert m["chunks_held_total"] >= 3 and m["held_admissions_total"] >= 3
    assert m["chunks_dispatched_late_total"] == 0 and m["runahead_discarded_tokens_total"] == 0


# -- the scheduler's own thread in a hold that only an event ends -------------------


@pytest.fixture
def in_a_hold(cpu_devices, monkeypatch):
    """(engine, cfg, a): the scheduler thread, alive, inside `decode/hold` with
    A live in one of two slots; the clock stands still and the hold looks at
    the device once a minute, so nothing but `_wake` ends its wait."""
    monkeypatch.setattr(jax_decode, "_HOLD_POLL_S", 60.0)
    cfg, params = _uniform()
    engine = _engine(cfg, params, max_running_requests=2, new_tokens_per_chunk=CHUNK)
    engine._clock = _Clock()
    engine.ready = [False]
    engine._chunk_ready = lambda rec: engine.ready[0]
    engine._chunk_estimate = lambda rec: EST
    a = _queue(engine, _request(cfg.vocab_size, 1, 64), "a")
    engine._wake.set()
    _until_held(engine)
    yield engine, cfg, a
    engine.ready[0] = True  # (never held again: what is left runs out)
    engine.destroy()


def _until_held(engine, more_than=0.0):
    deadline = time.monotonic() + TIMEOUT
    while engine._sched_clock.read().get("hold", 0.0) <= more_than:
        assert time.monotonic() < deadline, "the scheduler thread never held a dispatch"
        time.sleep(0.002)


def _prompt(since, what):
    # far under the minute a hold that missed the event would sleep
    assert time.monotonic() - since < 20.0, f"{what} waited for the hold's own timeout"


def test_a_pause_ends_a_hold_and_drains(in_a_hold):
    engine, cfg, a = in_a_hold
    t0 = time.monotonic()
    engine.pause_generation()
    _prompt(t0, "the pause")
    assert not engine._inflight and not a.future.done()
    held = engine.get_metrics()["sched_hold_secs_total"]
    assert held > 0.0
    engine.continue_generation()
    _until_held(engine, more_than=held)  # it goes on, and holds again


def test_a_weight_swap_ends_a_hold_and_commits_on_a_drained_engine(in_a_hold):
    engine, cfg, a = in_a_hold
    t0 = time.monotonic()
    with engine._weight_swap():
        assert not engine._inflight
        engine.params = jax.tree.map(lambda x: x * 1.05, engine.params)
        engine._version += 1
    _prompt(t0, "the swap")
    n = len(a.tokens)
    assert n % CHUNK == 0 and set(a.versions) == {0}
    engine.ready[0] = True  # (never held again: the rest runs out)
    engine._wake.set()
    resp = a.future.result(timeout=TIMEOUT)
    assert resp.output_versions == [0] * n + [1] * (64 - n)


def test_abort_all_ends_a_hold_and_returns_what_there_is(in_a_hold):
    engine, cfg, a = in_a_hold
    t0 = time.monotonic()
    engine.pause_generation()
    assert engine.abort_all() == 1
    _prompt(t0, "the abort")
    resp = a.future.result(timeout=0)
    assert resp.stop_reason == "interrupt" and len(resp.output_tokens) % CHUNK == 0
    assert not engine._inflight and engine.get_metrics()["running_requests"] == 0
    engine.continue_generation()


def test_a_scheduler_exception_in_a_held_admission_fails_every_request(in_a_hold):
    engine, cfg, a = in_a_hold

    def dies(budget=None):
        raise RuntimeError("made to fail")

    engine._admit = dies
    t0 = time.monotonic()
    b = _queue(engine, _request(cfg.vocab_size, 2, 8), "b")
    engine._wake.set()
    for item in (a, b):
        with pytest.raises(EngineDeadError):
            item.future.result(timeout=TIMEOUT)
    _prompt(t0, "the failure")
    assert not engine._inflight and engine._thread_exc is not None


def test_shutdown_ends_a_hold(in_a_hold):
    engine, cfg, a = in_a_hold
    thread = engine._thread
    t0 = time.monotonic()
    engine.destroy()
    assert time.monotonic() - t0 < 4.0 and not thread.is_alive()  # (the join's limit is 5 s)


def test_an_arrival_ends_the_wait_and_is_admitted_in_the_hold(in_a_hold):
    engine, cfg, a = in_a_hold
    t0 = time.monotonic()
    b = _queue(engine, _request(cfg.vocab_size, 2, 8), "b")
    engine._wake.set()
    deadline = time.monotonic() + TIMEOUT
    while engine._slots[1] is not b:
        assert time.monotonic() < deadline, "the arrival was not admitted"
        time.sleep(0.002)
    _prompt(t0, "the arrival")
    # every slot is live now: the held chunk went out with both, before any deadline
    deadline = time.monotonic() + TIMEOUT
    while engine.get_metrics()["chunks_held_total"] < 1:
        assert time.monotonic() < deadline
        time.sleep(0.002)
    m = engine.get_metrics()
    assert m["held_admissions_total"] == 1 and m["chunks_dispatched_late_total"] == 0
