"""Shared by the readers of the version-2 cost ledger (the DONE frame's
``cost_ledger`` since the tracing PR: ``queue_s``, ``layers_s``,
``scan_worker_s``, ``cpu_s``, ``counts``, ``compile.task_*``). A frame
that lacks the field — a version-1 ledger, a program from before those
spans and counters — gives ``None``, and the metric is left out of the
line. Beside the readers like ``planlib`` beside the plans; not a metric."""

import statistics


def field(task, *path):
    """The number at ``cost_ledger[path...]`` of one task record, or
    None where the frame has no such number."""
    node = (task.get("done") or {}).get("cost_ledger")
    for key in path:
        if not isinstance(node, dict) or key not in node:
            return None
        node = node[key]
    if isinstance(node, bool) or not isinstance(node, (int, float)):
        return None
    return node


def over_tasks(ctx, *path, scale=1.0, reduce=statistics.median):
    """``reduce`` (the median, unless said otherwise) over the window's
    tasks of ``cost_ledger[path...] * scale``."""
    vals = [v * scale for v in (field(t, *path) for t in ctx["tasks"])
            if v is not None]
    return reduce(vals) if vals else None
