"""Shared by the readers of the ledger's CPU self times (``layers_cpu_s``,
``scan_worker_cpu_s``: what of a span's self time its thread spent on a
CPU). Off-CPU is wall less CPU, not below 0: a compile that fires inside
a span leaves its wall and not its CPU. These readers give the MEAN over
the window's tasks, not the median the other ledger readers give: on the
chip's host the thread CPU clock advances in ticks of 10 ms, so a task's
CPU is a count of ticks and a median of such counts moves in steps of
10 ms, where the mean over some hundred tasks converges. A frame without
the CPU field — a program from before it — gives ``None``. Beside the
readers like ``ledgerlib``; not a metric."""

import statistics

import ledgerlib


def off_cpu(task, wall_path, cpu_path, less=0.0):
    """Seconds of ``cost_ledger[wall_path]`` (less ``less``, a wait the
    span declares) that ``cost_ledger[cpu_path]`` does not cover, or
    None where the frame lacks either."""
    wall = ledgerlib.field(task, *wall_path)
    cpu = ledgerlib.field(task, *cpu_path)
    if wall is None or cpu is None:
        return None
    return max(wall - less - cpu, 0.0)


def mean_ms(values):
    values = [v * 1e3 for v in values if v is not None]
    return statistics.mean(values) if values else None
