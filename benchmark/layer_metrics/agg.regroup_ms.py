"""Operator layer: median per task of the aggregation operators' time in
a plan that regroups for a DISTINCT — ops_s.agg.host_s +
ops_s.agg.device_wait_s: the exclusive host time of the auron:op/agg
spans and the waits of their reads, every AggOp of the plan summed (q28:
24, four a band). Over the tasks whose frame counts distinct groups; a
plan without a DISTINCT, or a program from before that counter, gives
None."""

import statistics

import ledgerlib


def read(ctx):
    vals = []
    for task in ctx["tasks"]:
        host = ledgerlib.field(task, "ops_s", "agg", "host_s")
        if host is not None and ledgerlib.field(
                task, "counts", "agg_distinct_groups"):
            wait = ledgerlib.field(task, "ops_s", "agg",
                                   "device_wait_s") or 0.0
            vals.append((host + wait) * 1e3)
    return statistics.median(vals) if vals else None
