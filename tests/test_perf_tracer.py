"""`perf_tracer.span`, the one primitive the program marks time with: always
a `TraceAnnotation`, and with AREAL_TPU_PERF_TRACE=1 also a record in memory
that is written as Chrome JSON. `maybe_xprof_step` is the operator's way to a
device trace of a real run."""

import glob
import json
import os
import sys
import threading
import time

import pytest

from areal_tpu.utils import perf_tracer, stats_tracker


@pytest.fixture
def recording(tmp_path, monkeypatch):
    """Recording on, into tmp_path; off again (and nothing left registered
    in the module) afterwards."""
    monkeypatch.setenv("AREAL_TPU_PERF_TRACE", "1")
    monkeypatch.setenv("AREAL_TPU_PERF_TRACE_DIR", str(tmp_path))
    rec = perf_tracer.init_from_env(rank=0)
    yield rec
    monkeypatch.setenv("AREAL_TPU_PERF_TRACE", "0")
    perf_tracer.init_from_env()


@pytest.fixture
def not_recording(monkeypatch):
    monkeypatch.setenv("AREAL_TPU_PERF_TRACE", "0")
    assert perf_tracer.init_from_env() is None


def _by_name(rec):
    return {row[1]: row for row in rec.spans}


def test_nesting_gives_each_span_its_parent(recording):
    with perf_tracer.span("outer"):
        with perf_tracer.span("inner"):
            with perf_tracer.span("leaf"):
                pass
        with perf_tracer.span("second"):
            pass
    rows = _by_name(recording)
    outer_id = rows["outer"][0]
    assert rows["outer"][4] is None
    assert rows["inner"][4] == outer_id and rows["second"][4] == outer_id
    assert rows["leaf"][4] == rows["inner"][0]
    # a child lies inside its parent on the clock
    assert rows["outer"][2] <= rows["inner"][2] <= rows["inner"][3] <= rows["outer"][3]


def test_ids_are_kept_with_the_span(recording):
    with perf_tracer.span("decode/dispatch_chunk", chunk=7, active=128, version=3):
        pass
    assert _by_name(recording)["decode/dispatch_chunk"][6] == {
        "chunk": 7, "active": 128, "version": 3}


def test_record_after_the_fact_has_no_parent_and_the_given_times(recording):
    t1 = time.monotonic()
    with perf_tracer.span("around"):
        perf_tracer.record("request/queue", t1 - 2.5, t1, rid="r1", slot=4)
    row = _by_name(recording)["request/queue"]
    assert row[4] is None and row[6] == {"rid": "r1", "slot": 4}
    assert row[3] - row[2] == pytest.approx(2.5e9, abs=1e3)
    assert row[3] == pytest.approx(t1 * 1e9, abs=1e3)


def test_threads_keep_their_own_stacks(recording):
    started, release = threading.Event(), threading.Event()

    def worker():
        with perf_tracer.span("worker/outer"):
            started.set()
            assert release.wait(10)
            with perf_tracer.span("worker/inner"):
                pass

    t = threading.Thread(target=worker)
    with perf_tracer.span("main/outer"):
        t.start()
        assert started.wait(10)
        with perf_tracer.span("main/inner"):  # opened while worker/outer is open
            pass
        release.set()
        t.join(10)
        assert not t.is_alive()
    rows = _by_name(recording)
    assert rows["main/inner"][4] == rows["main/outer"][0]
    assert rows["worker/inner"][4] == rows["worker/outer"][0]
    assert rows["worker/outer"][4] is None
    assert rows["worker/inner"][5] != rows["main/inner"][5]  # thread ids


def test_an_exception_closes_the_span_and_restores_the_stack(recording):
    with pytest.raises(ValueError):
        with perf_tracer.span("fails"):
            raise ValueError("x")
    with perf_tracer.span("after"):
        pass
    rows = _by_name(recording)
    assert "fails" in rows and rows["after"][4] is None


def test_off_keeps_nothing_and_writes_nothing(tmp_path, monkeypatch, not_recording):
    monkeypatch.setenv("AREAL_TPU_PERF_TRACE_DIR", str(tmp_path))
    with perf_tracer.span("a", step=1):
        with perf_tracer.step_span("b", 2):
            pass
    perf_tracer.record("c", 0.0, 1.0)
    assert perf_tracer.recorder() is None
    assert os.listdir(tmp_path) == []


def test_save_writes_chrome_json_with_ids_and_parents(recording, tmp_path):
    with perf_tracer.span("train/ppo_update", step=5):
        with perf_tracer.span("train/minibatch", step=5, minibatch=0):
            time.sleep(0.002)
    path = recording.save()
    assert path == recording.save_path and path.startswith(str(tmp_path))
    assert os.path.basename(path) == f"trace-rank0-{os.getpid()}.json"
    events = {e["name"]: e for e in json.load(open(path))["traceEvents"]}
    up, mb = events["train/ppo_update"], events["train/minibatch"]
    assert up["ph"] == "X" and up["pid"] == 0 and up["args"]["step"] == 5
    assert mb["args"]["parent"] == up["args"]["span"] and mb["args"]["minibatch"] == 0
    assert mb["dur"] >= 2000 and up["ts"] <= mb["ts"]  # microseconds


def test_record_timing_emits_the_step_span_and_the_series(recording):
    with stats_tracker.record_timing("train_step"):
        with perf_tracer.span("train/ppo_update"):
            pass
    rows = _by_name(recording)
    assert rows["train/ppo_update"][4] == rows["step/train_step"][0]
    assert stats_tracker.export_all()["timeperf/train_step"] >= 0.0


def test_step_span_carries_the_step(recording):
    with perf_tracer.step_span("train/train_batch", 12, tokens=8192):
        pass
    assert _by_name(recording)["train/train_batch"][6] == {"step": 12, "tokens": 8192}


def test_init_from_env(tmp_path, monkeypatch):
    monkeypatch.setenv("AREAL_TPU_PERF_TRACE", "1")
    monkeypatch.setenv("AREAL_TPU_PERF_TRACE_DIR", str(tmp_path))
    rec = perf_tracer.init_from_env(rank=5)
    assert rec is perf_tracer.recorder() and rec.rank == 5
    assert rec.save_path == str(tmp_path / f"trace-rank5-{os.getpid()}.json")
    monkeypatch.setenv("AREAL_TPU_PERF_TRACE", "0")
    assert perf_tracer.init_from_env(rank=5) is None
    assert perf_tracer.recorder() is None


def test_the_environment_is_read_on_the_first_span(tmp_path, monkeypatch):
    monkeypatch.setenv("AREAL_TPU_PERF_TRACE", "1")
    monkeypatch.setenv("AREAL_TPU_PERF_TRACE_DIR", str(tmp_path))
    monkeypatch.setattr(perf_tracer, "_recorder", perf_tracer._UNREAD)
    with perf_tracer.span("first"):
        pass
    try:
        assert [row[1] for row in perf_tracer.recorder().spans] == ["first"]
    finally:
        monkeypatch.setenv("AREAL_TPU_PERF_TRACE", "0")
        perf_tracer.init_from_env()


def test_spans_are_on_a_running_profile(tmp_path, not_recording):
    """With a profile running a span is in the trace under `areal/<name>`,
    whether or not recording is on: that is what puts the program's phases
    on the device trace's clock."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        with perf_tracer.span("decode/consume_chunk", chunk=3):
            jax.block_until_ready(jax.jit(lambda x: x * 2)(jnp.ones(16)))
        with perf_tracer.step_span("train/train_batch", 4):
            pass
    finally:
        jax.profiler.stop_trace()
    (pb,) = glob.glob(str(tmp_path) + "/**/*.xplane.pb", recursive=True)
    names = {e.name for p in ProfileData.from_file(pb).planes
             for line in p.lines for e in line.events}
    assert "areal/decode/consume_chunk" in names
    assert any(n.startswith("areal/train/train_batch") for n in names)


def test_maybe_xprof_step_window(tmp_path, monkeypatch):
    """The env-gated window starts at the first configured step and stops
    exactly once after the last — captured via the real jax profiler."""
    monkeypatch.setenv("AREAL_TPU_XPROF_DIR", str(tmp_path))
    monkeypatch.setenv("AREAL_TPU_XPROF_STEPS", "1-2")
    monkeypatch.setitem(perf_tracer._xprof_state, "active", False)
    monkeypatch.setitem(perf_tracer._xprof_state, "done", False)
    import jax
    import jax.numpy as jnp

    for step in range(5):
        perf_tracer.maybe_xprof_step(step)
        jax.block_until_ready(jax.jit(lambda x: x + 1)(jnp.ones(8)))
    assert perf_tracer._xprof_state["done"]
    assert not perf_tracer._xprof_state["active"]
    assert glob.glob(str(tmp_path) + "/**/*.xplane.pb", recursive=True)


def test_a_real_runs_trace_and_record_lie_on_one_clock(tmp_path, monkeypatch, not_recording):
    """The operator's pair: a device trace (`AREAL_TPU_XPROF_DIR`) and the span
    record of the same run. Both hold `xprof_window`; `trace_report --spans`
    places the record by it, with a span still open at the profiler's stop."""
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from tools import trace_report

    monkeypatch.setenv("AREAL_TPU_XPROF_DIR", str(tmp_path / "xprof"))
    monkeypatch.setenv("AREAL_TPU_XPROF_STEPS", "1-2")
    monkeypatch.setitem(perf_tracer._xprof_state, "active", False)
    monkeypatch.setitem(perf_tracer._xprof_state, "done", False)
    with perf_tracer.recording() as rec:
        with perf_tracer.span("decode/paused"):  # open before the start and after the stop
            for step in range(4):
                perf_tracer.maybe_xprof_step(step)
                with perf_tracer.step_span("train/train_batch", step):
                    time.sleep(0.01)
            path = rec.save(str(tmp_path / "record.json"))
    r = trace_report.report(str(tmp_path / "xprof"), spans=path)
    assert abs(r["record_clock"]["skew_ns"]) < 5e6  # the two ends agree to milliseconds
    rows = {row[0]: row for row in r["spans"]}
    assert rows["areal/train/train_batch"][1] == 4  # the record's, the steps before the window too
    assert "areal/decode/paused" in rows and "areal/xprof_window" not in rows


def test_trace_report_reads_a_span_record(recording, tmp_path):
    """tools/trace_report.py on the Chrome JSON: count, total and self time."""
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from tools import trace_report

    t = time.monotonic()
    perf_tracer.record("train/ppo_update", t, t + 2.0)
    perf_tracer.record("train/minibatch", t + 0.5, t + 1.0)
    perf_tracer.record("train/minibatch", t + 1.0, t + 1.25)
    rows = {r[0]: r for r in trace_report.report(recording.save())["spans"]}
    assert rows["areal/train/minibatch"][1:3] == (2, pytest.approx(0.75, abs=1e-5))
    assert rows["areal/train/ppo_update"][1] == 1
    assert rows["areal/train/ppo_update"][3] == pytest.approx(1.25, abs=1e-5)  # self


# -- one complete record, placed on the trace's clock (ISSUE 34) -------------


def test_recording_on_and_off_in_one_process(not_recording):
    with perf_tracer.span("before"):
        pass
    with perf_tracer.recording() as rec:
        assert perf_tracer.recorder() is rec
        with perf_tracer.span("inside", step=1):
            pass
        perf_tracer.record("request/queue", 1.0, 2.0, rid="r")
        late = perf_tracer.span("closes_after")
        late.__enter__()
    assert perf_tracer.recorder() is None
    late.__exit__(None, None, None)  # began inside: still kept, where it began
    with perf_tracer.span("after"):
        pass
    perf_tracer.record("request/queue", 3.0, 4.0, rid="s")
    assert [s["name"] for s in rec.snapshot()] == ["inside", "request/queue", "closes_after"]
    # a second stretch is a second record
    with perf_tracer.recording() as again:
        with perf_tracer.span("second"):
            pass
    assert again is not rec and [s["name"] for s in again.snapshot()] == ["second"]


def test_recording_inside_the_environments_record_is_that_record(recording):
    with perf_tracer.recording() as rec:
        assert rec is recording
        with perf_tracer.span("inside"):
            pass
    assert perf_tracer.recorder() is recording  # and it stays on after
    assert [row[1] for row in recording.spans] == ["inside"]


def test_snapshot_returns_a_span_another_thread_holds_open(not_recording):
    started, release = threading.Event(), threading.Event()

    def worker():
        with perf_tracer.span("decode/paused", version=3):
            started.set()
            assert release.wait(10)

    with perf_tracer.recording() as rec:
        t = threading.Thread(target=worker)
        t.start()
        assert started.wait(10)
        with perf_tracer.span("step/update_weights"):
            now = time.monotonic_ns()
            snap = {s["name"]: s for s in rec.snapshot(now)}
        # both are open at `now`, on two threads, each cut there and marked
        assert set(snap) == {"decode/paused", "step/update_weights"}
        assert all(s["open"] and s["end_ns"] == now for s in snap.values())
        assert snap["decode/paused"]["thread"] == t.ident != snap["step/update_weights"]["thread"]
        assert snap["decode/paused"]["ids"] == {"version": 3}
        assert snap["decode/paused"]["start_ns"] <= now
        release.set()
        t.join(10)
        assert not t.is_alive()
        done = {s["name"]: s for s in rec.snapshot()}
        assert not done["decode/paused"]["open"] and not done["step/update_weights"]["open"]
        assert done["decode/paused"]["end_ns"] >= now


def test_a_detached_span_outlives_the_span_it_began_in(not_recording):
    with perf_tracer.recording() as rec:
        with perf_tracer.span("rollout/prepare_batch"):
            gate = perf_tracer.span("rollout/gate_closed", by="staleness").open()
            with perf_tracer.span("inner"):
                pass
        with perf_tracer.span("step/train_step"):
            assert {s["name"] for s in rec.snapshot() if s["open"]} == {
                "rollout/gate_closed", "step/train_step"}
        gate.close()
    rows = {s["name"]: s for s in rec.snapshot()}
    assert rows["rollout/gate_closed"]["parent"] is None
    # nobody's parent either: what opens meanwhile nests as if it were not there
    assert rows["inner"]["parent"] == rows["rollout/prepare_batch"]["id"]
    assert rows["step/train_step"]["parent"] is None
    assert rows["rollout/gate_closed"]["end_ns"] >= rows["step/train_step"]["end_ns"]


def test_save_marks_the_spans_still_open(not_recording, tmp_path):
    with perf_tracer.recording() as rec:
        with perf_tracer.span("decode/idle"):
            path = rec.save(str(tmp_path / "r.json"))
    events = {e["name"]: e for e in json.load(open(path))["traceEvents"]}
    assert events["decode/idle"]["args"]["open"] is True


@pytest.mark.parametrize("first, last", [(5_000_000_000, 5_000_000_000),  # one clock, shifted
                                         (-7_000_000, -6_400_000)])       # and 0.6 ms of drift
def test_the_record_is_placed_on_the_traces_clock_by_two_instants(first, last):
    """The traced window's start and stop, on the host's clock and on the
    trace's: the record moves by the mean of the two differences, and how
    far they are apart is reported."""
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from benchmark.lib import program_spans

    host = (1_000_000_000.0, 21_000_000_000.0)
    clock = program_spans.clock_offset(host, (host[0] + first, host[1] + last))
    assert clock == {"offset_ns": (first + last) / 2, "skew_ns": last - first}
    (moved,) = program_spans.shifted(
        [dict(name="decode/paused", start_ns=2e9, end_ns=3e9, open=True)], clock["offset_ns"])
    assert moved["start_ns"] == 2e9 + (first + last) / 2 and moved["end_ns"] - moved["start_ns"] == 1e9
    assert moved["open"] and moved["name"] == "decode/paused"
