"""The one way the program marks time: `span(name, **ids)`.

A span always enters a `jax.profiler.TraceAnnotation("areal/<name>", **ids)`,
so while a device trace is running (`maybe_xprof_step`, or the benchmark's
`--trace 1`) it lies on the trace's clock beside the device's operations; with
no trace running that costs a flag test. With `AREAL_TPU_PERF_TRACE=1` it is
also kept in memory, on `time.monotonic_ns` and with the span that was open
around it on its thread, and written as Chrome-trace JSON
(chrome://tracing, Perfetto, `tools/trace_report.py`) when the process exits,
under `AREAL_TPU_PERF_TRACE_DIR`.

`ids` tie spans together: `rid` a request, `step` a trainer step, `chunk` a
decode chunk, `version` a weight version. `PERF.md` lists every span.
"""

from __future__ import annotations

import atexit
import itertools
import json
import os
import threading
import time
from pathlib import Path

from jax.profiler import StepTraceAnnotation, TraceAnnotation

PREFIX = "areal/"


class Recorder:
    """Finished spans of one process, in memory."""

    def __init__(self, rank: int = 0, save_path: str | None = None):
        self.rank = rank
        self.save_path = save_path
        # (id, name, start_ns, end_ns, parent id or None, thread, ids)
        self.spans: list[tuple] = []
        self._ids = itertools.count(1)
        self._lock = threading.Lock()
        self._open = threading.local()  # .stack: ids of the thread's open spans

    def _stack(self) -> list[int]:
        try:
            return self._open.stack
        except AttributeError:
            self._open.stack = []
            return self._open.stack

    def add(self, name, start_ns, end_ns, parent, ids, sid=None) -> None:
        row = (sid or next(self._ids), name, int(start_ns), int(end_ns), parent,
               threading.get_ident(), ids)
        with self._lock:
            self.spans.append(row)

    def save(self, path: str | None = None) -> str | None:
        path = path or self.save_path
        if not path:
            return None
        with self._lock:
            spans = list(self.spans)
        events = [
            dict(name=name, ph="X", ts=start / 1e3, dur=(end - start) / 1e3,
                 pid=self.rank, tid=thread % 100000,
                 args={**ids, "span": sid, "parent": parent})
            for sid, name, start, end, parent, thread, ids in spans
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


class span:
    """`with span("decode/dispatch_chunk", chunk=n): ...`"""

    __slots__ = ("name", "ids", "_step", "_ann", "_rec", "_sid", "_parent", "_t0")

    def __init__(self, name: str, **ids):
        self.name = name
        self.ids = ids
        self._step = None

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
            stack = rec._stack()
            self._parent = stack[-1] if stack else None
            self._sid = next(rec._ids)
            stack.append(self._sid)
            self._t0 = time.monotonic_ns()
        return self

    def __exit__(self, *exc):
        rec = self._rec
        if rec is not None:
            t1 = time.monotonic_ns()
            rec._stack().pop()
            rec.add(self.name, self._t0, t1, self._parent, self.ids, self._sid)
        self._ann.__exit__(*exc)
        return False


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
_xprof_state = {"active": False, "done": False, "owner": None}


def _xprof_flush() -> None:
    if _xprof_state["active"]:
        import jax

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
