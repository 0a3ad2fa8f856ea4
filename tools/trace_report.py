"""What a device trace, or a span record, says about one run.

    python tools/trace_report.py <path> [--spans <record.json>] [--gap-ms 1.0] [--top 20]

`<path>` is a directory the JAX profiler wrote into (`AREAL_TPU_XPROF_DIR` of
a real run, `.bench_work/<cell>/trace` of a benchmark run with `--trace 1`),
an `.xplane.pb`, or the Chrome JSON that `AREAL_TPU_PERF_TRACE=1` leaves in
`AREAL_TPU_PERF_TRACE_DIR`. From a device trace it prints

  device     busy and idle share of the window, per chip
  kernels    device time by operation name (`%flash_fwd`, `%paged_attention`,
             `%fusion`, ...): inclusive, and what is not spent in nested operations
  scopes     device self time by the program's `jax.named_scope` path, from
             the `op_name` the trace keeps in each operation's metadata (a
             linear layer's mixer shows as `layer/attn/gdn_chunk_scan` in a
             prefill, `layer/attn/gdn_step` and `layer/attn/conv_state` in a
             decode step, beside `attention_full` of the gated layers; a Kimi
             Delta Attention layer as `layer/attn/` + `qkv`, `conv`,
             `kda_gate`, `kda_chunk_scan` or `conv_state`, `kda_step`, then
             `out_gate`, `out_proj`, beside the latent layers' `q_proj`,
             `kv_latent`, `absorb_q`, `latent_attention`, `absorb_out`; a
             state-space layer as `layer/attn/` + `in_proj`, `conv`,
             `ssm_params`, `ssm_scan` in a prefill or `conv_state`,
             `ssm_params`, `ssm_step` in a decode step, then `out_gate`,
             `out_proj`: the `while/body` of a scanned run of layers is
             folded away like a chunk's)
  spans      the program's `areal/` spans (and the benchmark's `bench/`): count,
             total and self time (total minus the spans nested in it)
  idle gaps  every gap of chip 0 over `--gap-ms`, summed by the innermost
             `areal/` span open at its middle on any host thread

  by thread  the same gaps for each host thread by that thread's own innermost
             span: what the trainer and what the scheduler was in; with
             `--spans` also the five longest, each named by both

and from a span record the spans table alone, and under it the decode
scheduler's admissions: how many requests it admitted and the share of them
that took a slot handed over before its request's last chunk was read back
(`decode/admit`'s `handed_over`). Device and host events of one
trace share a clock. `--spans` lays `perf_tracer`'s record (the Chrome JSON)
over the trace's own `areal/` events: it also holds the spans known after the
fact and those open when the profiler started or stopped. The record is on
the host's monotonic clock and is placed by measurement, from a span both hold:
`xprof_window` of a run under `AREAL_TPU_XPROF_DIR` with `AREAL_TPU_PERF_TRACE=1`,
or a benchmark kind's `traced_window` beside `bench/traced_window`.
The arithmetic on intervals is the benchmark's (`benchmark/lib/xplane.py`:
`load`, `union`, `busy`; `benchmark/lib/program_spans.py`: the clock, the gaps,
a thread's innermost span); what this file adds is what a later benchmark PR
lifts into readers.
"""

from __future__ import annotations

import argparse
import gzip
import json
import os
import re
import sys
from collections import defaultdict

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.lib import program_spans, xplane  # noqa: E402

PROGRAM_PREFIX = "areal/"
PREFIXES = (PROGRAM_PREFIX, xplane.SPAN_PREFIX)
# name-stack components that are transformations, not the program's scopes
_NOISE = re.compile(r"^(main|while|body|cond|closed_call|checkpoint|rematted_computation|"
                    r"branch_\d+_fun|pjit|core_call|custom_jvp_call|custom_vjp_call(_jaxpr)?|"
                    r"shard_map|remat\d*|scan)$")


def _keep(name: str) -> bool:
    return name.startswith(PREFIXES)


def load(path: str) -> tuple[dict, str | None]:
    """(trace in `xplane.load`'s structure, the .xplane.pb it came from)."""
    if path.endswith((".json", ".json.gz")):
        opener = gzip.open if path.endswith(".gz") else open
        with opener(path, "rt") as f:
            data = json.load(f)
        if "planes" in data:  # a recorded trace kept as JSON (tests)
            return data, None
        return spans_record(data), None
    pb = path if path.endswith(".pb") else xplane.find_xplane(path)
    return xplane.load(pb, keep_host=_keep), pb


def spans_record(chrome: dict) -> dict:
    """`perf_tracer`'s Chrome JSON as one host plane, a line per thread, and
    the spans themselves (`record`: their ids are not in a plane's events)."""
    spans = program_spans.from_chrome(chrome)
    return {"planes": [program_spans.as_host_plane(spans, PROGRAM_PREFIX)], "record": spans}


def with_record(trace: dict, record_path: str) -> tuple[dict, dict]:
    """(the trace with the record's spans in place of its own `areal/`
    events, {"offset_ns", "skew_ns"}). Both hold the traced window: the
    trace as `bench/traced_window`, the record as `traced_window` on the
    host's clock."""
    opener = gzip.open if record_path.endswith(".gz") else open
    with opener(record_path, "rt") as f:
        spans = program_spans.from_chrome(json.load(f))
    # the span both hold: the benchmark's `traced_window` (`bench/` in the
    # trace) or `maybe_xprof_step`'s `xprof_window` (`areal/` in the trace)
    in_trace = {e[0]: (e[1], e[1] + e[2]) for _, events in host_lines(trace) for e in events}
    pairs = ((program_spans.WINDOW_SPAN, xplane.WINDOW_SPAN),
             ("xprof_window", PROGRAM_PREFIX + "xprof_window"))
    anchor, on_trace = next(
        ((s, in_trace[there]) for here, there in pairs for s in spans
         if s["name"] == here and there in in_trace), (None, None))
    if anchor is None:
        raise ValueError(f"{record_path} and the trace share no `traced_window` or "
                         "`xprof_window` span: nothing says where the record lies on the "
                         "trace's clock")
    clock = program_spans.clock_offset((anchor["start_ns"], anchor["end_ns"]), on_trace)
    spans = program_spans.shifted([s for s in spans if s is not anchor], clock["offset_ns"])
    planes = []
    for p in trace["planes"]:
        if xplane.DEVICE_PLANE.match(p["name"]):
            planes.append(p)
            continue
        lines = [{"name": line["name"],
                  "events": [e for e in line["events"] if not e[0].startswith(PROGRAM_PREFIX)]}
                 for line in p["lines"]]
        planes.append({"name": p["name"], "lines": [line for line in lines if line["events"]]})
    planes.append(program_spans.as_host_plane(spans, PROGRAM_PREFIX))
    return {"planes": planes, "record": spans}, clock


def scope_of(op_name: str) -> str:
    """`jit(chunk)/while/body/closed_call/decode_step/layer/attn/...` ->
    `chunk/decode_step/layer/attn/...`; the backward of a scope (its
    `transpose(jvp(...))`) gets a `bwd:` in front."""
    out = []
    for part in op_name.rstrip(":").split("/"):
        bwd = part.startswith("transpose(")
        part = re.sub(r"^(?:transpose\(|jvp\(|jit\(|vmap\()+", "", part).rstrip(")")
        if not part or _NOISE.match(part):
            continue
        out.append(("bwd:" if bwd else "") + part)
    return "/".join(out)


def _varint(buf: memoryview, i: int) -> tuple[int, int]:
    val = shift = 0
    while True:
        byte = buf[i]
        i += 1
        val |= (byte & 0x7F) << shift
        shift += 7
        if byte < 0x80:
            return val, i


def _fields(buf: memoryview):
    """(field number, wire type, value) of one protobuf message: a varint's
    value, or the bytes of a length-delimited field. Enough of the wire format
    for the profiler's XSpace, whose Python reader (`ProfileData`) gives an
    event's own stats but not those of its metadata, where `op_name` is."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
            yield field, wire, val
        else:
            if wire == 2:
                size, i = _varint(buf, i)
            else:
                size = {1: 8, 5: 4}[wire]
            yield field, wire, buf[i:i + size]
            i += size


def _map_value(entry: memoryview) -> memoryview | None:
    return next((v for f, w, v in _fields(entry) if f == 2 and w == 2), None)


def op_names(pb: str) -> dict[str, dict[str, str]]:
    """{device plane: {an operation's name in the trace (its HLO line): its
    `op_name`, the JAX name stack}}, from the planes' event metadata
    (XPlane.event_metadata[..].stats, the stat called `tf_op`)."""
    with open(pb, "rb") as f:
        space = memoryview(f.read())
    out: dict[str, dict[str, str]] = {}
    for f_no, wire, plane in _fields(space):
        if f_no != 1 or wire != 2:
            continue
        name, events, stat_names = "", [], {}
        for f2, w2, v in _fields(plane):
            if f2 == 2 and w2 == 2:
                name = bytes(v).decode()
            elif f2 == 4 and w2 == 2:  # map<int64, XEventMetadata>
                events.append(_map_value(v))
            elif f2 == 5 and w2 == 2:  # map<int64, XStatMetadata>
                meta = dict((f3, v3) for f3, _, v3 in _fields(_map_value(v)))
                stat_names[meta.get(1)] = bytes(meta.get(2, b"")).decode()
        if not xplane.DEVICE_PLANE.match(name):
            continue
        wanted = {i for i, n in stat_names.items() if n in ("tf_op", "op_name")}
        table = out.setdefault(name, {})
        for meta in events:
            ev_name, op = None, None
            for f3, w3, v3 in _fields(meta):
                if f3 == 2 and w3 == 2:
                    ev_name = bytes(v3).decode(errors="replace")
                elif f3 == 5 and w3 == 2:  # XStat
                    stat = dict((f4, v4) for f4, _, v4 in _fields(v3))
                    if stat.get(1) in wanted and 5 in stat:
                        op = bytes(stat[5]).decode(errors="replace")
            if ev_name and op:
                table[ev_name] = op
    return out


def op_scopes(pb: str) -> dict[str, list[str | None]]:
    """Per device plane, the scope path of each `XLA Ops` event, in the
    events' order (which is `xplane.load`'s); None where an event has none."""
    from jax.profiler import ProfileData

    names = op_names(pb)
    out = {}
    for plane in ProfileData.from_file(pb).planes:
        table = names.get(plane.name)
        if not table:
            continue
        cache: dict[str, str | None] = {}
        for line in plane.lines:
            if line.name != xplane.OPS_LINE:
                continue
            scopes = []
            for e in line.events:
                if e.name not in cache:
                    raw = table.get(e.name)
                    cache[e.name] = scope_of(raw) if raw else None
                scopes.append(cache[e.name])
            out[plane.name] = scopes
    return out


def self_times(events: list) -> list[float]:
    """Each event's duration minus the events nested in it (one line's
    events nest properly or follow each other)."""
    order = sorted(range(len(events)), key=lambda i: (events[i][1], -events[i][2]))
    own = [e[2] for e in events]
    stack: list[int] = []
    for i in order:
        _, start, dur = events[i]
        while stack and events[stack[-1]][1] + events[stack[-1]][2] <= start:
            stack.pop()
        if stack:
            own[stack[-1]] -= dur
        stack.append(i)
    return [max(x, 0.0) for x in own]


def _in(events, lo, hi):
    return [i for i, e in enumerate(events) if lo <= e[1] < hi]


def kernel_table(trace: dict, lo: float, hi: float) -> list[tuple[str, float, float, int]]:
    """[(name, inclusive s, self s, events)], mean over chips, by operation
    name without its number: `%flash_fwd.3 custom-call ...` -> `%flash_fwd custom-call`."""
    planes = xplane.device_planes(trace)
    acc: dict[str, list[float]] = defaultdict(lambda: [0.0, 0.0, 0])
    for p in planes:
        events = xplane._line(p, xplane.OPS_LINE)
        own = self_times(events)
        for i in _in(events, lo, hi):
            parts = events[i][0].split(" ")
            key = re.sub(r"\.\d+$", "", parts[0]) + (" " + parts[1] if len(parts) > 1 else "")
            a = acc[key]
            a[0] += events[i][2]
            a[1] += own[i]
            a[2] += 1
    n = max(len(planes), 1)
    return sorted(((k, v[0] / 1e9 / n, v[1] / 1e9 / n, v[2] // n) for k, v in acc.items()),
                  key=lambda r: -r[2])


def scope_table(trace: dict, scopes: dict, lo: float, hi: float, depth: int) -> list[tuple[str, float]]:
    """[(scope path cut to `depth` components, self seconds)], mean over chips."""
    planes = xplane.device_planes(trace)
    acc: dict[str, float] = defaultdict(float)
    for p in planes:
        events = xplane._line(p, xplane.OPS_LINE)
        names = scopes.get(p["name"])
        if not names or len(names) != len(events):
            continue
        own = self_times(events)
        for i in _in(events, lo, hi):
            path = names[i] or "(no op_name)"
            acc["/".join(path.split("/")[:depth])] += own[i]
    n = max(len(planes), 1)
    return sorted(((k, v / 1e9 / n) for k, v in acc.items()), key=lambda r: -r[1])


def host_lines(trace: dict) -> list[tuple[str, list]]:
    return [(line["name"], line["events"]) for p in trace["planes"]
            if not xplane.DEVICE_PLANE.match(p["name"]) for line in p["lines"]]


def span_table(trace: dict) -> list[tuple[str, int, float, float]]:
    """[(span, count, total s, self s)] over every host thread."""
    acc: dict[str, list[float]] = defaultdict(lambda: [0, 0.0, 0.0])
    for _, events in host_lines(trace):
        events = [e for e in events if _keep(e[0])]
        for e, own in zip(events, self_times(events)):
            a = acc[e[0]]
            a[0] += 1
            a[1] += e[2]
            a[2] += own
    return sorted(((k, int(v[0]), v[1] / 1e9, v[2] / 1e9) for k, v in acc.items()),
                  key=lambda r: -r[2])


def idle_gaps(trace: dict, lo: float, hi: float, min_ns: float) -> list[dict]:
    """Every gap of chip 0 in [lo, hi] of at least `min_ns`:
    {"start", "seconds", "span", "thread"}; `span` is the innermost `areal/`
    span open at the gap's middle on any host thread (the shortest, if
    several threads have one), else the innermost `bench/` span in brackets,
    else None."""
    threads = host_lines(trace)
    out = []
    for a, b in program_spans.device_gaps(trace, lo, hi, min_ns):
        mid = (a + b) / 2
        best = {}
        for thread, events in threads:
            for name, start, dur in events:
                if start <= mid <= start + dur and name != xplane.WINDOW_SPAN:
                    prefix = PROGRAM_PREFIX if name.startswith(PROGRAM_PREFIX) else "other"
                    if prefix not in best or dur < best[prefix][2]:
                        best[prefix] = (name, thread, dur)
        if PROGRAM_PREFIX in best:
            span, thread = best[PROGRAM_PREFIX][:2]
        elif "other" in best:
            span, thread = f"[{best['other'][0]}]", best["other"][1]
        else:
            span, thread = None, None
        out.append({"start": a, "seconds": (b - a) / 1e9, "span": span, "thread": thread})
    return out


def gaps_by_span(gaps: list[dict], lo: float) -> list[tuple]:
    """[(span, thread, count, total s, longest s, first at s, last at s)],
    the last two from the window's start. A span still open when the profiler
    stops is not in the trace, so gaps late in the window may have none."""
    acc: dict[tuple, list[float]] = defaultdict(lambda: [0, 0.0, 0.0, float("inf"), 0.0])
    for g in gaps:
        a = acc[(g["span"] or "no span on any thread", g["thread"] or "-")]
        a[0] += 1
        a[1] += g["seconds"]
        a[2] = max(a[2], g["seconds"])
        a[3] = min(a[3], (g["start"] - lo) / 1e9)
        a[4] = max(a[4], (g["start"] - lo) / 1e9)
    return sorted(((k[0], k[1], int(v[0]), v[1], v[2], v[3], v[4]) for k, v in acc.items()),
                  key=lambda r: -r[3])


def gaps_by_thread(trace: dict, gaps: list[dict]) -> dict[str, list[tuple]]:
    """{host thread that holds `areal/` spans: [(its innermost `areal/` span
    at the gap's middle or None, gaps, total s, longest s)]}: every gap once
    for every such thread, so each thread's rows sum to the idle time."""
    out = {}
    for thread, events in host_lines(trace):
        spans = [{"name": e[0], "thread": thread, "start_ns": e[1], "end_ns": e[1] + e[2]}
                 for e in events if e[0].startswith(PROGRAM_PREFIX)
                 and not e[0][len(PROGRAM_PREFIX):].startswith(program_spans.AFTER_THE_FACT)]
        if not spans:
            continue
        acc: dict = defaultdict(lambda: [0, 0.0, 0.0])
        for g in gaps:
            a = acc[program_spans.innermost(spans, thread, g["start"] + g["seconds"] * 1e9 / 2)]
            a[0] += 1
            a[1] += g["seconds"]
            a[2] = max(a[2], g["seconds"])
        out[thread] = sorted(((k, int(v[0]), v[1], v[2]) for k, v in acc.items()),
                             key=lambda r: -r[2])
    return out


def admissions(spans: list[dict]) -> dict | None:
    """{"requests", "handed_over"}: the requests the decode scheduler admitted
    (a `request/queue` span each) and those of them that took a SPENT slot,
    one whose request's last chunk was dispatched and not yet read back (the
    `handed_over` of the `decode/admit` spans; ISSUE 48). From a span record
    alone: a device trace's annotation is made when a span opens. None where
    the record holds no admission."""
    requests = sum(s["name"] == "request/queue" for s in spans)
    if not requests:
        return None
    return {"requests": requests,
            "handed_over": sum(int(s["ids"].get("handed_over", 0)) for s in spans
                               if s["name"] == "decode/admit")}


def report(path: str, gap_ms: float = 1.0, top: int = 20, depth: int = 4,
           spans: str | None = None) -> dict:
    """Everything `main` prints, as data."""
    trace, pb = load(path)
    out: dict = {}
    if spans:
        trace, out["record_clock"] = with_record(trace, spans)
    out["spans"] = span_table(trace)
    if "record" in trace:
        out["admissions"] = admissions(trace["record"])
    if not xplane.device_planes(trace):
        return out
    lo, hi = xplane.window(trace)
    out["window_s"] = (hi - lo) / 1e9
    out["busy"] = xplane.busy(trace, lo, hi)
    out["kernels"] = kernel_table(trace, lo, hi)[:top]
    out["scopes"] = scope_table(trace, op_scopes(pb), lo, hi, depth)[:top] if pb else []
    gaps = idle_gaps(trace, lo, hi, gap_ms * 1e6)
    out["gaps"] = gaps
    out["gaps_by_span"] = gaps_by_span(gaps, lo)
    out["gaps_by_thread"] = gaps_by_thread(trace, gaps)
    if "record" in trace:
        # the five longest, each by what the loop's two threads were in: the
        # trainer's is where `step/` spans are, the scheduler's `decode/`
        threads = {label: program_spans.thread_of(trace["record"], prefix)
                   for label, prefix in (("trainer", "step/"), ("decode", "decode/"))}
        out["longest_gaps"] = program_spans.name_gaps(
            [(g["start"], g["start"] + g["seconds"] * 1e9) for g in gaps], trace["record"],
            {k: v for k, v in threads.items() if v is not None})
    return out


def main(argv) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("path")
    ap.add_argument("--spans", help="perf_tracer's Chrome JSON of the same run, laid over the trace")
    ap.add_argument("--gap-ms", type=float, default=1.0)
    ap.add_argument("--top", type=int, default=20)
    ap.add_argument("--depth", type=int, default=4, help="components of a scope path kept")
    ap.add_argument("--json", action="store_true", help="print the report as one JSON object")
    args = ap.parse_args(argv)
    r = report(args.path, args.gap_ms, args.top, args.depth, args.spans)
    if args.json:
        print(json.dumps(r))
        return 0
    if "record_clock" in r:
        c = r["record_clock"]
        print(f"record: placed on the trace's clock at {c['offset_ns'] / 1e9:+.6f} s; the window's "
              f"two ends differ by {c['skew_ns'] / 1e6:.3f} ms")
    if "busy" in r:
        b = r["busy"]
        print(f"device: window {r['window_s']:.3f} s, busy {b['busy_s']:.3f} s, idle "
              f"{100 * (1 - b['busy_s'] / b['window_s']):.2f}% (per chip busy: "
              + ", ".join(f"{s:.3f}" for s in b["per_device_s"]) + ")")
        print(f"\nkernels: device seconds by operation name (mean over chips), top {args.top} by self time")
        print(f"  {'inclusive':>10} {'self':>10} {'events':>7}  name")
        for name, incl, own, n in r["kernels"]:
            print(f"  {incl:10.4f} {own:10.4f} {n:7d}  {name}")
        print(f"\nscopes: device self seconds by named_scope path (depth {args.depth})")
        if not r["scopes"]:
            print("  (the trace carries no op_name for its operations)")
        for name, secs in r["scopes"]:
            print(f"  {secs:10.4f}  {name}")
    print("\nspans: host spans, every thread")
    print(f"  {'count':>6} {'total s':>10} {'self s':>10}  name")
    for name, n, total, own in r["spans"]:
        print(f"  {n:6d} {total:10.4f} {own:10.4f}  {name}")
    if r.get("admissions"):
        a = r["admissions"]
        print(f"\nadmissions: {a['requests']} requests admitted, {a['handed_over']} "
              f"({100.0 * a['handed_over'] / a['requests']:.1f}%) into a slot whose request's last "
              "chunk was dispatched and not yet read back")
    if "gaps" in r:
        gaps = r["gaps"]
        print(f"\nidle gaps of chip 0 over {args.gap_ms} ms: {len(gaps)}, "
              f"{sum(g['seconds'] for g in gaps):.4f} s in all")
        print(f"  {'count':>6} {'total ms':>10} {'longest ms':>11} {'from s':>8} {'to s':>8}  "
              "innermost areal/ span at the gap's middle (thread)")
        for span, thread, n, total, longest, first, last in r["gaps_by_span"]:
            print(f"  {n:6d} {1e3 * total:10.2f} {1e3 * longest:11.2f} {first:8.3f} {last:8.3f}  "
                  f"{span} ({thread})")
        for name, seconds in r.get("longest_gaps", []):
            print(f"  longest: {1e3 * seconds:10.2f} ms  {name}")
        for thread, rows in r["gaps_by_thread"].items():
            print(f"\nthe same gaps by what thread `{thread}` was in")
            print(f"  {'count':>6} {'total ms':>10} {'longest ms':>11}  its innermost areal/ span")
            for span, n, total, longest in rows:
                print(f"  {n:6d} {1e3 * total:10.2f} {1e3 * longest:11.2f}  {span or 'no span'}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
