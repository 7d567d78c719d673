"""Entry layer: median per task of cpu_s / wall_s — the CPU seconds of
the task's thread and its scan workers over the task's wall time: how
much of a task is computing rather than waiting (GIL, device, queue)."""

import statistics

import ledgerlib


def read(ctx):
    shares = []
    for task in ctx["tasks"]:
        cpu = ledgerlib.field(task, "cpu_s")
        wall = ledgerlib.field(task, "wall_s")
        if cpu is not None and wall:
            shares.append(cpu / wall)
    return statistics.median(shares) if shares else None
