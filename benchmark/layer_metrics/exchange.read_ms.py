"""Exchange layer: median per task of ops_s.shuffle_exchange_read, host
and device wait together — the reducers' side of the task's exchanges:
on the mesh route one gather a non-empty (partition, source, round)
slice out of the mesh buffer and one device_put home, then the consumer
pulls it. None where no task's frame has that operator."""

import statistics

import ledgerlib


def read(ctx):
    vals = []
    for task in ctx["tasks"]:
        host = ledgerlib.field(task, "ops_s", "shuffle_exchange_read",
                               "host_s")
        wait = ledgerlib.field(task, "ops_s", "shuffle_exchange_read",
                               "device_wait_s")
        if host is not None:
            vals.append((host + (wait or 0.0)) * 1e3)
    return statistics.median(vals) if vals else None
