"""The program's own record of its spans (`areal_tpu/utils/perf_tracer.py`:
`Recorder.snapshot()`, or the Chrome JSON it saves) beside a device trace.

A span here is a dict `{"id", "name", "start_ns", "end_ns", "parent",
"thread", "ids", "open"}`; the record is on the host's `time.monotonic_ns`.
It holds what a trace cannot: spans known only after the fact (a request's
wait, an episode) and spans open when the profiler starts or stops. Everything
below is arithmetic on plain lists, used by `tools/trace_report.py --spans`
(and by the loop cell's kind, built in PR 34 and kept out of the benchmark:
`bench_artifacts/pr34/cell/`), and checked on a small record kept with the
tests.
"""

from __future__ import annotations

from . import xplane

WINDOW_SPAN = "traced_window"  # the benchmark's anchor in a saved record


# -- one clock -----------------------------------------------------------

def clock_offset(host_ns: tuple[float, float], trace_ns: tuple[float, float]) -> dict:
    """Where the record lies on the trace's clock, by measurement: the same
    two instants (the start and the stop of the traced window) on the host's
    monotonic clock and on the trace's. `offset_ns` (their mean difference)
    is added to a record's times; `skew_ns` is how far the two differences
    are apart (what the mapping cannot be trusted beyond)."""
    first = trace_ns[0] - host_ns[0]
    last = trace_ns[1] - host_ns[1]
    return {"offset_ns": (first + last) / 2.0, "skew_ns": last - first}


def shifted(spans: list[dict], offset_ns: float) -> list[dict]:
    return [{**s, "start_ns": s["start_ns"] + offset_ns, "end_ns": s["end_ns"] + offset_ns}
            for s in spans]


def from_chrome(chrome: dict) -> list[dict]:
    """The Chrome JSON `Recorder.save` writes, back as spans (ns)."""
    out = []
    for e in chrome.get("traceEvents", []):
        if e.get("ph") != "X":
            continue
        args = dict(e.get("args", {}))
        sid, parent, open_ = args.pop("span", None), args.pop("parent", None), args.pop("open", False)
        out.append({"id": sid, "name": e["name"], "start_ns": e["ts"] * 1e3,
                    "end_ns": (e["ts"] + e["dur"]) * 1e3, "parent": parent,
                    "thread": e["tid"], "ids": args, "open": bool(open_)})
    return out


def as_host_plane(spans: list[dict], prefix: str) -> dict:
    """The spans as one host plane of `xplane.load`'s structure, a line a
    thread, each name under `prefix`: what reads a trace's host lines reads
    the record."""
    lines: dict = {}
    for s in spans:
        lines.setdefault(s["thread"], []).append(
            [prefix + s["name"], s["start_ns"], s["end_ns"] - s["start_ns"]])
    return {"name": "/host:record", "lines": [
        {"name": f"thread {t}", "events": sorted(evs, key=lambda e: e[1])}
        for t, evs in sorted(lines.items(), key=lambda kv: str(kv[0]))]}


# -- intervals -----------------------------------------------------------

def _cover(intervals: list[tuple[float, float]], lo: float, hi: float) -> float:
    clipped = [(max(a, lo), min(b, hi)) for a, b in intervals if min(b, hi) > max(a, lo)]
    return sum(b - a for a, b in xplane.union(clipped))


def self_times(spans: list[dict]) -> dict:
    """{span id: its duration less what its children cover of it}, ns. A
    child is a span that names it as `parent` (after-the-fact and detached
    spans have none, and are nobody's)."""
    children: dict = {}
    for s in spans:
        if s["parent"] is not None:
            children.setdefault(s["parent"], []).append((s["start_ns"], s["end_ns"]))
    return {s["id"]: (s["end_ns"] - s["start_ns"])
            - _cover(children.get(s["id"], []), s["start_ns"], s["end_ns"])
            for s in spans}


def seconds_inside(spans: list[dict], names, lo: float, hi: float) -> float:
    """Seconds of the spans so named that lie inside [lo, hi] (each cut to
    it; spans of one name do not overlap on a thread, so this is a sum)."""
    names = {names} if isinstance(names, str) else set(names)
    return sum(max(0.0, min(s["end_ns"], hi) - max(s["start_ns"], lo))
               for s in spans if s["name"] in names) / 1e9


def thread_of(spans: list[dict], prefix: str):
    """The thread that opened most of the spans whose name starts so, or
    None: the scheduler's is where `decode/` is, the trainer's `step/`."""
    count: dict = {}
    for s in spans:
        if s["name"].startswith(prefix):
            count[s["thread"]] = count.get(s["thread"], 0) + 1
    return max(count, key=count.get) if count else None


def innermost(spans: list[dict], thread, t: float) -> str | None:
    """The name of the span of `thread` open at `t` that began last (of two
    that began together, the shorter), or None. After-the-fact spans are
    written by whichever thread knew them: they are not where a thread is."""
    best = None
    for s in spans:
        if s["thread"] == thread and s["start_ns"] <= t <= s["end_ns"] and not _after_the_fact(s):
            key = (s["start_ns"], -(s["end_ns"] - s["start_ns"]))
            if best is None or key > best[0]:
                best = (key, s["name"])
    return best[1] if best else None


AFTER_THE_FACT = ("request/", "rollout/episode", "rollout/pending", WINDOW_SPAN)


def _after_the_fact(span: dict) -> bool:
    return span["name"].startswith(AFTER_THE_FACT)


# -- the device's gaps, named by what each thread was in ------------------

def device_gaps(trace: dict, lo: float, hi: float, min_ns: float = 0.0) -> list[tuple[float, float]]:
    """Every stretch of [lo, hi] of at least `min_ns` in which no operation
    ran on chip 0, in order."""
    planes = xplane.device_planes(trace)
    if not planes:
        return []
    busy = xplane.union(xplane._clip(xplane._line(planes[0], xplane.OPS_LINE), lo, hi))
    gaps, t = [], lo
    for a, b in busy:
        if a - t >= min_ns and a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi - t >= min_ns and hi > t:
        gaps.append((t, hi))
    return gaps


def name_gaps(gaps: list[tuple[float, float]], spans: list[dict], threads: dict,
              k: int = 5, unmarked: dict | None = None) -> list[list]:
    """The k longest gaps as [name, seconds], the name
    `<label>:<innermost span>|...` over `threads` ({label: thread}) at the
    gap's middle. A thread in no span there reads `unmarked[label]` if that
    is given (a thread whose unmarked time has a name), else `no_span`."""
    unmarked = unmarked or {}
    out = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:k]:
        mid = (a + b) / 2.0
        name = "|".join(
            f"{label}:{innermost(spans, thread, mid) or unmarked.get(label, 'no_span')}"
            for label, thread in threads.items())
        out.append([name, (b - a) / 1e9])
    return out
