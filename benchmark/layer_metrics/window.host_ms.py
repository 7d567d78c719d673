"""Operator layer: median per task of the window operator's time —
ops_s.window.host_s + ops_s.window.device_wait_s: the exclusive host
time of the auron:op/window spans (the collected batches' concatenation,
the launch of the one window program) and the wait of its one read (the
output's row count, with the rows it saw and the partitions it found).
Over the tasks whose plan holds a window; a frame without one, or a
program from before the operator's spans, gives None."""

import statistics

import ledgerlib


def read(ctx):
    vals = []
    for task in ctx["tasks"]:
        host = ledgerlib.field(task, "ops_s", "window", "host_s")
        if host is not None:
            wait = ledgerlib.field(task, "ops_s", "window",
                                   "device_wait_s") or 0.0
            vals.append((host + wait) * 1e3)
    return statistics.median(vals) if vals else None
