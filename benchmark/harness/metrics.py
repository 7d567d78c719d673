"""End-to-end arithmetic: from the window's task records to the numbers
a user of the system would see. Kept here so that no later PR computes
them another way.

A task record is {"plan", "group", "rows" (fact rows of its splits),
"t_submit", "t_done" (client clock, seconds from the window's start),
"ok", "done" (the DONE frame's metrics), ...}.
"""

from __future__ import annotations

import statistics


def percentile(values: list, q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between
    closest ranks (numpy's default), of at least one value."""
    xs = sorted(values)
    if len(xs) == 1:
        return float(xs[0])
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (pos - lo))


def latencies_ms(tasks: list) -> list:
    return [(t["t_done"] - t["t_submit"]) * 1e3 for t in tasks if t["ok"]]


def fact_rows_per_s(tasks: list) -> float:
    """Fact rows of the tasks completed in the window over the time from
    the window's start to the last completion inside it, so that a task
    cut by the window's end does not quantise the rate."""
    done = [t for t in tasks if t["ok"]]
    if not done:
        return 0.0
    return sum(t["rows"] for t in done) / max(t["t_done"] for t in done)


def end_to_end(tasks: list, setup_s: float) -> dict:
    lat = latencies_ms(tasks)
    out = {"setup_s": setup_s, "fact_rows_per_s": fact_rows_per_s(tasks)}
    if lat:
        out["task_p50_ms"] = statistics.median(lat)
        out["task_p90_ms"] = percentile(lat, 90)
    return out
