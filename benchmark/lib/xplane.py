"""Reduction of a JAX profiler trace to numbers.

`load(path)` turns an `.xplane.pb` into a plain structure
`{"planes": [{"name", "lines": [{"name", "events": [[name, start_ns, dur_ns]]}]}]}`
(`jax.profiler.ProfileData`, nothing else); every other function works on
that structure, so that the arithmetic is checked against a recorded trace
kept as JSON (tests/benchmark/fixtures/).

What the v5e's trace looks like (read by hand, PR 23): one plane
`/device:TPU:<n>` per chip with the lines `XLA Modules` (one event per
execution of a jitted program, named `<module>(<fingerprint>)`), `XLA Ops`
(one event per HLO operation, the device's busy time) and `Steps`; host
threads are lines of the plane `/host:CPU`, where a `TraceAnnotation` appears
under its own name. Device and host events share one clock.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_LINES = (OPS_LINE, MODULES_LINE, "Steps")
SPAN_PREFIX = "bench/"
WINDOW_SPAN = SPAN_PREFIX + "traced_window"


_HLO = re.compile(r"^(%[\w.\-]+) = (.*?)\s([a-z][\w\-]*)\(")


def short_name(hlo: str, shape_chars: int = 48) -> str:
    """`%copy.73 copy bf16[28,1281,128,2,128]` from the whole HLO line the
    trace names an operation by (hundreds of characters)."""
    m = _HLO.match(hlo)
    if not m:
        return hlo[: shape_chars + 32]
    name, shape, opcode = m.groups()
    return f"{name} {opcode} {shape.split('{')[0][:shape_chars]}"


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path: str, keep_host=lambda name: name.startswith(SPAN_PREFIX)) -> dict:
    """Device planes in full; of the host planes only the events `keep_host`
    accepts (the benchmark's own spans), which keeps the structure small."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        is_dev = bool(DEVICE_PLANE.match(plane.name))
        lines = []
        for line in plane.lines:
            ops = is_dev and line.name == OPS_LINE
            evs = [[short_name(e.name) if ops else e.name,
                    float(e.start_ns), float(e.duration_ns)]
                   for e in line.events if (is_dev and line.name in DEVICE_LINES)
                   or (not is_dev and keep_host(e.name))]
            if evs:
                lines.append({"name": line.name, "events": evs})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def device_planes(trace: dict) -> list[dict]:
    return sorted((p for p in trace["planes"] if DEVICE_PLANE.match(p["name"])),
                  key=lambda p: int(DEVICE_PLANE.match(p["name"]).group(1)))


def _line(plane: dict, name: str) -> list:
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def host_spans(trace: dict) -> list[list]:
    """[name, start_ns, dur_ns] of the benchmark's spans, any host thread."""
    out = []
    for p in trace["planes"]:
        if DEVICE_PLANE.match(p["name"]):
            continue
        for line in p["lines"]:
            out += [e for e in line["events"] if e[0].startswith(SPAN_PREFIX)]
    return sorted(out, key=lambda e: e[1])


def window(trace: dict) -> tuple[float, float]:
    """The traced window on the trace's clock: the `bench/traced_window` span
    if the host wrote one, else from the first to the last device event."""
    for name, start, dur in host_spans(trace):
        if name == WINDOW_SPAN:
            return start, start + dur
    starts, ends = [], []
    for p in device_planes(trace):
        for e in _line(p, OPS_LINE):
            starts.append(e[1])
            ends.append(e[1] + e[2])
    if not starts:
        raise ValueError("the trace holds no device operation")
    return min(starts), max(ends)


def _clip(events: list, lo: float, hi: float) -> list[tuple[float, float]]:
    out = []
    for _, start, dur in events:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            out.append((a, b))
    return out


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def busy(trace: dict, lo: float, hi: float) -> dict:
    """Seconds in which an operation ran on the device inside [lo, hi]: the
    union of the `XLA Ops` intervals, per device plane and their mean."""
    per = []
    for p in device_planes(trace):
        per.append(sum(b - a for a, b in union(_clip(_line(p, OPS_LINE), lo, hi))) / 1e9)
    if not per:
        raise ValueError("the trace holds no device plane")
    return {"busy_s": sum(per) / len(per), "per_device_s": per,
            "window_s": (hi - lo) / 1e9}


def module_time(trace: dict, pattern: str, lo: float, hi: float,
                line: str = MODULES_LINE) -> dict:
    """Device seconds and executions of the events on `line` whose name
    matches `pattern` and which START inside [lo, hi]; means over the device
    planes (a sharded program runs once on every chip)."""
    rx = re.compile(pattern)
    secs, calls = [], []
    for p in device_planes(trace):
        evs = [e for e in _line(p, line) if rx.search(e[0]) and lo <= e[1] < hi]
        secs.append(sum(e[2] for e in evs) / 1e9)
        calls.append(len(evs))
    n = max(len(secs), 1)
    return {"seconds": sum(secs) / n, "calls": sum(calls) / n}


def op_time(trace: dict, pattern: str, lo: float, hi: float) -> float:
    """Device seconds of the `XLA Ops` events whose (short) name matches
    `pattern` and which start inside [lo, hi]; mean over the device planes."""
    rx = re.compile(pattern)
    planes = device_planes(trace)
    total = sum(e[2] for p in planes for e in _line(p, OPS_LINE)
                if lo <= e[1] < hi and rx.search(e[0]))
    return total / 1e9 / max(len(planes), 1)


def top_ops(trace: dict, lo: float, hi: float, k: int = 10) -> list[list]:
    """The k device operations with most time, [name, seconds] (mean over the
    device planes), under the names the trace gives them."""
    planes = device_planes(trace)
    total: dict[str, float] = {}
    for p in planes:
        for name, start, dur in _line(p, OPS_LINE):
            if lo <= start < hi:
                total[name] = total.get(name, 0.0) + dur
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns / 1e9 / max(len(planes), 1)] for name, ns in ranked]


def idle_gaps(trace: dict, lo: float, hi: float, k: int = 5) -> list[list]:
    """The k longest gaps of device 0 inside [lo, hi] in which no operation
    ran, each named by the innermost benchmark span open at its middle (or
    `no span`), [name, seconds]."""
    planes = device_planes(trace)
    if not planes:
        return []
    busy_iv = union(_clip(_line(planes[0], OPS_LINE), lo, hi))
    gaps, t = [], lo
    for a, b in busy_iv:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    spans = [s for s in host_spans(trace) if s[0] != WINDOW_SPAN]
    out = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:k]:
        mid = (a + b) / 2
        open_ = [s for s in spans if s[1] <= mid <= s[1] + s[2]]
        name = min(open_, key=lambda s: s[2])[0][len(SPAN_PREFIX):] if open_ else "no span"
        out.append([name, (b - a) / 1e9])
    return out
