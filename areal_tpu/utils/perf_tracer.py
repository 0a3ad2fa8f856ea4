"""The one way the program marks time: `span(name, **ids)`.

A span always enters a `jax.profiler.TraceAnnotation("areal/<name>", **ids)`,
so while a device trace is running (`maybe_xprof_step`, or the benchmark's
`--trace 1`) it lies on the trace's clock beside the device's operations; with
no trace running that costs a flag test. While recording is on it is also kept
in memory, on `time.monotonic_ns` and with the span that was open around it on
its thread. Recording is on inside `with recording():` (a stretch of a live
process: the benchmark's loop cell) or for a whole process under
`AREAL_TPU_PERF_TRACE=1`, which also writes the record as Chrome-trace JSON
(chrome://tracing, Perfetto, `tools/trace_report.py`) when the process exits,
under `AREAL_TPU_PERF_TRACE_DIR`. `Recorder.snapshot()` is the whole record at
an instant: the finished spans and the ones still open on any thread.

`ids` tie spans together: `rid` a request, `step` a trainer step, `chunk` a
decode chunk, `version` a weight version. `PERF.md` lists every span.
"""

from __future__ import annotations

import atexit
import contextlib
import itertools
import json
import os
import threading
import time
from pathlib import Path

from jax.profiler import StepTraceAnnotation, TraceAnnotation

PREFIX = "areal/"


class Recorder:
    """The spans of one process, in memory: finished ones, and the ones that
    are open now on any thread."""

    def __init__(self, rank: int = 0, save_path: str | None = None):
        self.rank = rank
        self.save_path = save_path
        # (id, name, start_ns, end_ns, parent id or None, thread, ids)
        self.spans: list[tuple] = []
        # id -> (name, start_ns, parent id or None, thread, ids)
        self._live: dict[int, tuple] = {}
        self._ids = itertools.count(1)
        self._lock = threading.Lock()
        self._open = threading.local()  # .stack: ids of the thread's open spans

    def _stack(self) -> list[int]:
        try:
            return self._open.stack
        except AttributeError:
            self._open.stack = []
            return self._open.stack

    def begin(self, name, ids, detached: bool = False) -> int:
        """A span opens now on this thread; returns its id. A detached span
        is no parent and no child: it may close after the span around it."""
        sid = next(self._ids)
        parent = None
        if not detached:
            stack = self._stack()
            parent = stack[-1] if stack else None
            stack.append(sid)
        row = (name, time.monotonic_ns(), parent, threading.get_ident(), ids)
        with self._lock:
            self._live[sid] = row
        return sid

    def end(self, sid: int, detached: bool = False) -> None:
        t1 = time.monotonic_ns()
        if not detached:
            self._stack().pop()
        with self._lock:
            name, t0, parent, thread, ids = self._live.pop(sid)
            self.spans.append((sid, name, t0, t1, parent, thread, ids))

    def add(self, name, start_ns, end_ns, parent, ids, sid=None) -> None:
        row = (sid or next(self._ids), name, int(start_ns), int(end_ns), parent,
               threading.get_ident(), ids)
        with self._lock:
            self.spans.append(row)

    def snapshot(self, now_ns: int | None = None) -> list[dict]:
        """Every span as {"id", "name", "start_ns", "end_ns", "parent",
        "thread", "ids", "open"}: the finished ones, and each span still open
        on any thread cut at `now_ns` and marked open."""
        now_ns = time.monotonic_ns() if now_ns is None else int(now_ns)
        with self._lock:
            done, live = list(self.spans), dict(self._live)
        keys = ("id", "name", "start_ns", "end_ns", "parent", "thread", "ids")
        out = [dict(zip(keys, row), open=False) for row in done]
        out += [dict(zip(keys, (sid, name, t0, max(now_ns, t0), parent, thread, ids)), open=True)
                for sid, (name, t0, parent, thread, ids) in live.items()]
        return out

    def save(self, path: str | None = None) -> str | None:
        path = path or self.save_path
        if not path:
            return None
        events = [
            dict(name=s["name"], ph="X", ts=s["start_ns"] / 1e3,
                 dur=(s["end_ns"] - s["start_ns"]) / 1e3,
                 pid=self.rank, tid=s["thread"] % 100000,
                 args={**s["ids"], "span": s["id"], "parent": s["parent"],
                       **({"open": True} if s["open"] else {})})
            for s in self.snapshot()
        ]
        p = Path(path)
        p.parent.mkdir(parents=True, exist_ok=True)
        with open(p, "w") as f:
            json.dump({"traceEvents": events, "displayTimeUnit": "ms"}, f)
        return str(p)


# None: recording is off. _UNREAD: the environment has not been looked at yet.
_UNREAD = object()
_recorder: Recorder | None | object = _UNREAD


def init_from_env(rank: int = 0) -> Recorder | None:
    """(Re)read AREAL_TPU_PERF_TRACE / AREAL_TPU_PERF_TRACE_DIR. Called on
    the first span if nobody called it before; a process that knows its rank
    calls it itself."""
    global _recorder
    if os.environ.get("AREAL_TPU_PERF_TRACE", "0") in ("1", "true"):
        trace_dir = os.environ.get("AREAL_TPU_PERF_TRACE_DIR", "/tmp/areal_tpu/traces")
        # the pid keeps the processes of one host (trainer, decode servers)
        # from writing over each other
        path = os.path.join(trace_dir, f"trace-rank{rank}-{os.getpid()}.json")
        _recorder = Recorder(rank, path)
        atexit.register(_recorder.save)
    else:
        _recorder = None
    return _recorder


def recorder() -> Recorder | None:
    """The process's recorder, or None when recording is off."""
    return init_from_env() if _recorder is _UNREAD else _recorder


@contextlib.contextmanager
def recording(rank: int = 0):
    """Recording on for the `with` block, whatever the environment says;
    yields the recorder (the environment's own, if that is on). A span that
    opens inside and closes after the block is still kept."""
    global _recorder
    before = recorder()
    _recorder = rec = before if before is not None else Recorder(rank)
    try:
        yield rec
    finally:
        _recorder = before


class span:
    """`with span("decode/dispatch_chunk", chunk=n): ...`

    `open()` ... `close()` in place of the `with` is for a span that lasts
    across calls and may end after the span it began in (the staleness gate's
    closed period begins inside a `prepare_batch` and ends in a later one):
    in the record it is detached, with no parent and no children. Both ends
    are on one thread."""

    __slots__ = ("name", "ids", "_step", "_ann", "_rec", "_sid", "_detached")

    def __init__(self, name: str, **ids):
        self.name = name
        self.ids = ids
        self._step = None
        self._detached = False

    def __enter__(self):
        # (an annotation starts when it is made, so it is made here)
        if self._step is None:
            self._ann = TraceAnnotation(PREFIX + self.name, **self.ids)
        else:
            self._ann = StepTraceAnnotation(PREFIX + self.name,
                                            step_num=self._step, **self.ids)
        self._ann.__enter__()
        rec = self._rec = recorder()
        if rec is not None:
            self._sid = rec.begin(self.name, self.ids, self._detached)
        return self

    def __exit__(self, *exc):
        if self._rec is not None:
            self._rec.end(self._sid, self._detached)
        self._ann.__exit__(*exc)
        return False

    def open(self) -> "span":
        self._detached = True
        return self.__enter__()

    def close(self) -> None:
        self.__exit__(None, None, None)


class StateClock:
    """Time by state, for counters: `switch` charges the time since the last
    switch to the state that is left, so the states are exclusive and sum to
    the time in any of them (None is none: a thread not running, no period
    open); `read` (any thread) counts the running state up to now. A lock of
    its own, a leaf: held for a few assignments."""

    def __init__(self, states=(), clock=time.monotonic):
        self._clock = clock
        self._lock = threading.Lock()
        self._secs = dict.fromkeys(states, 0.0)
        self._state: str | None = None
        self._t = 0.0

    def switch(self, state: str | None) -> str | None:
        """Returns the state that was left."""
        now = self._clock()
        with self._lock:
            left = self._state
            if left is not None:
                self._secs[left] = self._secs.get(left, 0.0) + now - self._t
            self._state, self._t = state, now
        return left

    def read(self) -> dict[str, float]:
        now = self._clock()
        with self._lock:
            out = dict(self._secs)
            if self._state is not None:
                out[self._state] = out.get(self._state, 0.0) + now - self._t
        return out


def step_span(name: str, step: int, **ids) -> span:
    """A span the profiler's tools group by step (`StepTraceAnnotation`)."""
    s = span(name, step=step, **ids)
    s._step = step
    return s


def record(name: str, start: float, end: float, **ids) -> None:
    """A span known only after the fact (a request's wait in the queue is
    known at admission): `start` and `end` are `time.monotonic()` seconds. It
    goes into the in-memory record only, with no parent."""
    rec = recorder()
    if rec is not None:
        rec.add(name, start * 1e9, end * 1e9, None, ids)


# ---------------------------------------------------------------------------
# Device traces of a real run. AREAL_TPU_XPROF_DIR=/path makes the train
# engine capture a jax.profiler trace of the steps AREAL_TPU_XPROF_STEPS
# (default "2-4", inclusive, after the warm-up compiles); the spans above are
# in it. Read it with `python tools/trace_report.py /path`, xprof or Perfetto.
# ---------------------------------------------------------------------------

# jax.profiler supports ONE process-global trace; "owner" records which
# engine claimed the window so co-resident engines (PPO actor + critic
# both call maybe_xprof_step from train_batch) cannot flush or skew each
# other's capture: the first engine to reach the start step owns it.
_xprof_state = {"active": False, "done": False, "owner": None, "window": None}
# the span around the captured window: in the trace and, with recording on,
# in the record, so a reader can lay the one over the other
# (`tools/trace_report.py <trace> --spans <record>`)
XPROF_WINDOW = "xprof_window"


def _xprof_flush() -> None:
    if _xprof_state["active"]:
        import jax

        _xprof_state["window"].close()
        jax.profiler.stop_trace()
        _xprof_state["active"] = False
        _xprof_state["owner"] = None
        _xprof_state["done"] = True


def maybe_xprof_step(step: int, owner: object = None) -> None:
    """Called by the train engine at the top of every train_batch; free when
    AREAL_TPU_XPROF_DIR is unset.

    `owner` identifies the calling engine; the window is claimed by the
    first owner to reach the start step and only that owner's step counter
    advances/ends it."""
    target = os.environ.get("AREAL_TPU_XPROF_DIR")
    if not target or _xprof_state["done"]:
        return
    import jax

    lo, _, hi = os.environ.get("AREAL_TPU_XPROF_STEPS", "2-4").partition("-")
    lo, hi = int(lo), int(hi or lo)
    if not _xprof_state["active"] and lo <= step <= hi:
        os.makedirs(target, exist_ok=True)
        jax.profiler.start_trace(target)
        _xprof_state["window"] = span(XPROF_WINDOW).open()
        _xprof_state["active"] = True
        _xprof_state["owner"] = owner
        # short runs (or a crash mid-window) never see a step > hi call;
        # flush at exit so the capture is not silently lost
        atexit.register(_xprof_flush)
    elif (
        _xprof_state["active"]
        and step > hi
        and _xprof_state["owner"] == owner
    ):
        _xprof_flush()
