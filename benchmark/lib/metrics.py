"""Metric arithmetic, kept with the benchmark so that no later PR can change
how a number is made."""

from __future__ import annotations

import math


def whole_step_rate(step_ends: list[float], step_work: list[float],
                    t_open: float, t_close: float) -> dict:
    """Work per second over WHOLE steps.

    `step_ends[i]` is the host time step i ended (synchronised), `step_work[i]`
    its work (tokens). The clock starts at the end of the last step that ended
    at or before `t_open` (the end of the last warm-up step) and stops at the
    end of the last step that ended inside the window; only the steps in
    between count. A window of ten-odd steps then does not jitter by the part
    of a step the window's edges cut off."""
    before = [t for t in step_ends if t <= t_open]
    if not before:
        raise ValueError("no step ended before the window opened")
    start = before[-1]
    inside = [(t, w) for t, w in zip(step_ends, step_work) if t_open < t <= t_close]
    if not inside:
        raise ValueError("no whole step ended inside the window")
    seconds = inside[-1][0] - start
    work = sum(w for _, w in inside)
    return {"rate": work / seconds, "steps": len(inside), "seconds": seconds,
            "work": work}


def percentile(values: list[float], q: float) -> dict:
    """Nearest-rank percentile with its sample count, and how many samples
    lie beyond it (a tail wants at least ten)."""
    if not values:
        raise ValueError("percentile of nothing")
    xs = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(xs)))
    return {"value": xs[rank - 1], "n": len(xs), "beyond": len(xs) - rank}
