"""`lib/program_spans.py`: the program's own record of its spans
(`perf_tracer.Recorder.snapshot()`) beside a device trace. Its arithmetic on a
small record of one step of the colocated loop kept as JSON (self time, a
thread's innermost span, a gap named by both threads, the clock), and
`tools/trace_report.py --spans` on the same pair."""

import json
import os

import pytest

import bench_paths
from benchmark.lib import program_spans
from tools import trace_report

S = 1e9
WAIT_SPANS = ("train/wait_device", "train/read_stats")  # where the trainer waits for the device


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


# -- lib/program_spans.py --------------------------------------------------


def test_self_time_is_the_duration_less_the_childrens_cover(placed):
    own = program_spans.self_times(placed)
    assert own[8] == pytest.approx((5.7 - 1.5 - 0.4) * S)  # ppo_update less read_stats, wait_device
    assert own[21] == pytest.approx((4.1 - 3.95) * S)  # consume_chunk without the wait
    assert own[12] == pytest.approx((1.0 - 0.15 - 0.7) * S)  # update_weights less pause, commit
    # detached: thirteen seconds of its own, and nothing of prepare_batch's
    assert own[3] == pytest.approx(13.0 * S)
    assert own[2] == pytest.approx(3.8 * S)


@pytest.mark.parametrize("thread, at, want", [
    (11, 3.4, "rollout/gate_closed"),   # began last of the three open there
    (11, 12.55, "weights/commit"),      # inside update_weights, inside the pause, behind the gate
    (11, 13.6, None),                   # between steps
    (22, 3.4, "decode/wait_device"),    # never the after-the-fact request/decode
    (22, 12.55, "decode/paused"),
    (22, 13.6, "decode/idle"),          # open at the snapshot, cut there
])
def test_innermost_span_of_a_thread(placed, thread, at, want):
    assert program_spans.innermost(placed, thread, at * S) == want


def test_threads_are_known_by_what_they_open(placed):
    assert program_spans.thread_of(placed, "step/") == 11
    assert program_spans.thread_of(placed, "decode/") == 22
    assert program_spans.thread_of(placed, "nothing/") is None


def test_seconds_of_named_spans_inside_a_window(placed):
    assert program_spans.seconds_inside(placed, WAIT_SPANS, 1.0 * S, 14.0 * S) == \
        pytest.approx(0.8 + 0.4 + 1.5)
    # cut at the window's edges
    assert program_spans.seconds_inside(placed, "decode/idle", 1.0 * S, 14.0 * S) == pytest.approx(1.0)
    assert program_spans.seconds_inside(placed, "train/read_stats", 8.0 * S, 9.0 * S) == pytest.approx(0.5)


def test_a_gap_is_named_by_both_threads(fixture, placed):
    gaps = program_spans.device_gaps(fixture["trace"], 1.0 * S, 14.0 * S)
    assert [(a / S, b / S) for a, b in gaps] == [
        pytest.approx(g) for g in [(3.0, 3.8), (7.2, 7.25), (12.25, 12.85), (13.2, 14.0)]]
    assert program_spans.device_gaps(fixture["trace"], 1.0 * S, 14.0 * S, 0.1 * S) == [
        g for g in gaps if g[1] - g[0] >= 0.1 * S]
    named = program_spans.name_gaps(gaps, placed, {"trainer": 11, "decode": 22}, k=3)
    assert [n for n, _ in named] == [
        "trainer:rollout/gate_closed|decode:decode/wait_device",
        "trainer:no_span|decode:decode/idle",
        "trainer:weights/commit|decode:decode/paused"]
    assert [s for _, s in named] == [pytest.approx(x) for x in (0.8, 0.8, 0.6)]
    # a thread whose unmarked time has a name of its own
    (last,) = program_spans.name_gaps(gaps[3:], placed, {"trainer": 11}, unmarked={"trainer": "between"})
    assert last[0] == "trainer:between"


def test_chrome_json_round_trip(fixture, tmp_path):
    """What `Recorder.save` writes comes back as the same spans."""
    from areal_tpu.utils import perf_tracer

    with perf_tracer.recording() as rec:
        with perf_tracer.span("step/rollout"):
            with perf_tracer.span("rollout/prepare_batch", version=3):
                pass
        perf_tracer.record("request/queue", 1.0, 2.0, rid="r")
        with perf_tracer.span("decode/idle"):
            back = program_spans.from_chrome(json.load(open(rec.save(str(tmp_path / "r.json")))))
            snap = rec.snapshot()
    by = {s["name"]: s for s in back}
    assert by["rollout/prepare_batch"]["parent"] == by["step/rollout"]["id"]
    assert by["rollout/prepare_batch"]["ids"] == {"version": 3}
    assert by["decode/idle"]["open"] and not by["step/rollout"]["open"]
    assert by["request/queue"]["end_ns"] - by["request/queue"]["start_ns"] == pytest.approx(1e9)
    assert len(back) == len(snap) == 4


# -- the operator's reader on the same pair (tools/trace_report.py --spans) --


def test_trace_report_lays_the_record_over_the_trace(fixture, tmp_path):
    host = fixture["host_window_s"]
    events = [dict(name=s["name"], ph="X", ts=s["start_ns"] / 1e3,
                   dur=(s["end_ns"] - s["start_ns"]) / 1e3, pid=0, tid=s["thread"],
                   args={**s["ids"], "span": s["id"], "parent": s["parent"]})
              for s in fixture["spans"]]
    events.append(dict(name="traced_window", ph="X", ts=host[0] * 1e6,
                       dur=(host[1] - host[0]) * 1e6, pid=0, tid=44, args={}))
    record, trace = tmp_path / "record.json", tmp_path / "trace.json"
    record.write_text(json.dumps({"traceEvents": events}))
    trace.write_text(json.dumps(fixture["trace"]))
    r = trace_report.report(str(trace), gap_ms=100.0, spans=str(record))
    assert r["record_clock"] == {"offset_ns": -500.0 * S, "skew_ns": 0.0}
    by_thread = r["gaps_by_thread"]
    assert {row[0]: row[1:] for row in by_thread["thread 11"]} == {
        "areal/rollout/gate_closed": (1, pytest.approx(0.8), pytest.approx(0.8)),
        "areal/weights/commit": (1, pytest.approx(0.6), pytest.approx(0.6)),
        None: (1, pytest.approx(0.8), pytest.approx(0.8))}
    assert {row[0]: row[1] for row in by_thread["thread 22"]} == {
        "areal/decode/wait_device": 1, "areal/decode/paused": 1, "areal/decode/idle": 1}
    # no gap is under "no span" for the scheduler thread, the open span included
    assert None not in {row[0] for row in by_thread["thread 22"]}
    assert "thread 33" not in by_thread  # after-the-fact spans place no thread
    assert r["longest_gaps"][0] == ["trainer:rollout/gate_closed|decode:decode/wait_device",
                                    pytest.approx(0.8)]
    assert [n for n, _ in r["longest_gaps"]][2] == "trainer:weights/commit|decode:decode/paused"
    # without the anchor nothing says where the record lies
    record.write_text(json.dumps({"traceEvents": events[:-1]}))
    with pytest.raises(ValueError, match="traced_window"):
        trace_report.report(str(trace), spans=str(record))
