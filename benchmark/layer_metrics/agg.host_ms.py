"""Operator layer: median per task of ops_s.agg.host_s — the exclusive
host time of the task's aggregation operators (the auron:op/agg spans
less their readbacks and compile seconds), every AggOp of the plan summed:
the part of ops.host_ms the aggregation holds."""

import ledgerlib


def read(ctx):
    return ledgerlib.over_tasks(ctx, "ops_s", "agg", "host_s", scale=1e3)
