"""Generic readers of per-layer metrics. A metric is a file
`benchmark/layer_metrics/<name>.json` that names one of these with its
parameters; the code below is written once. A reader takes the run's context
and returns a number, or None when there is nothing to read (the harness then
leaves the metric out of the line).

The context (`ctx`) a kind of cell fills:
  spans        lib/spans.py:Spans of the whole run (host clock)
  window       (t_open, t_close) of the measured window, host clock
  counters     {name: delta over the window} of the engine's counters, plus
               the engine settings a ratio needs (constants)
  trace        lib/xplane.py structure of the traced sub-window, or None
  trace_window (lo_ns, hi_ns) on the trace's clock
  fields       {name: number} computed by the kind from the window's batches
  work         what the roofline functions need (see `roofline`), and
               "steps", the number of whole steps traced
  model_config, device_kind, chips
"""

from __future__ import annotations

from . import flops, xplane


def _sum(counters: dict, names) -> float | None:
    if isinstance(names, (int, float)):
        return float(names)
    if isinstance(names, str):
        names = [names]
    if any(n not in counters for n in names):
        return None
    return float(sum(counters[n] for n in names))


def counter_ratio(ctx, num, den, scale: float = 1.0):
    """scale * sum(num) / product(each factor of den); a factor is a counter
    name, a list of names (summed) or a constant."""
    counters = ctx.get("counters") or {}
    top = _sum(counters, num)
    bottom = 1.0
    for factor in den:
        f = _sum(counters, factor)
        if f is None:
            return None
        bottom *= f
    if top is None or bottom <= 0:
        return None
    return scale * top / bottom


def host_span(ctx, span: str):
    """Mean milliseconds of the spans of that name that ended inside the
    window."""
    lo, hi = ctx["window"]
    durs = ctx["spans"].within(span, lo, hi)
    return 1e3 * sum(durs) / len(durs) if durs else None


def device_module_time(ctx, pattern: str):
    """Milliseconds of device time per execution of the XLA module whose name
    matches `pattern`, in the traced sub-window."""
    if ctx.get("trace") is None:
        return None
    lo, hi = ctx["trace_window"]
    m = xplane.module_time(ctx["trace"], pattern, lo, hi)
    return 1e3 * m["seconds"] / m["calls"] if m["calls"] else None


def device_op_time(ctx, pattern: str):
    """Milliseconds of device time per traced step in the operations whose
    name matches `pattern` (mean over chips); nothing if none matches."""
    steps = (ctx.get("work") or {}).get("steps")
    if ctx.get("trace") is None or not steps:
        return None
    lo, hi = ctx["trace_window"]
    seconds = xplane.op_time(ctx["trace"], pattern, lo, hi)
    return 1e3 * seconds / steps if seconds > 0 else None


def device_idle(ctx):
    """100 * (1 - busy / traced window), mean over the device planes."""
    if ctx.get("trace") is None:
        return None
    lo, hi = ctx["trace_window"]
    b = xplane.busy(ctx["trace"], lo, hi)
    return 100.0 * (1.0 - b["busy_s"] / b["window_s"])


def roofline(ctx, function: str, pattern: str):
    """100 * least possible seconds / measured device seconds of the modules
    matching `pattern` in the traced sub-window. The least possible time comes
    from lib/flops.py, fed what the traced window really did (`ctx["work"]`):

    decode_chunk  {"token_steps", "running", "live_tokens"}: token steps the
                  traced chunks computed, and the time-mean number of running
                  requests and of cached tokens they attend over
    train_step    {"lengths"}: the sequence lengths of the traced steps
    """
    if ctx.get("trace") is None or not ctx.get("work"):
        return None
    lo, hi = ctx["trace_window"]
    m = xplane.module_time(ctx["trace"], pattern, lo, hi)
    if not m["calls"] or m["seconds"] <= 0:
        return None
    cfg, kind, work = ctx["model_config"], ctx["device_kind"], ctx["work"]
    if function == "decode_chunk":
        step = flops.decode_step_needed_seconds(
            cfg, work["running"], work["live_tokens"], kind)
        needed = m["calls"] * work["tokens_per_chunk"] * step["seconds"]
    elif function == "train_step":
        needed = flops.train_needed_seconds(
            cfg, work["lengths"], kind, ctx["chips"])["seconds"]
    else:
        raise ValueError(f"unknown roofline function {function!r}")
    return 100.0 * needed / m["seconds"]


def batch_field(ctx, field: str):
    return (ctx.get("fields") or {}).get(field)


READERS = {
    "counter_ratio": counter_ratio,
    "host_span": host_span,
    "device_module_time": device_module_time,
    "device_op_time": device_op_time,
    "device_idle": device_idle,
    "roofline": roofline,
    "batch_field": batch_field,
}


def read(spec: dict, ctx: dict):
    return READERS[spec["reader"]](ctx, **spec.get("args", {}))
