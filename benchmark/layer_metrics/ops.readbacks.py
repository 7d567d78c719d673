"""Operator layer: median per task of counts.readbacks — explicit
device -> host sync points (timed_get, device_fence, the timer's sync,
the batched result fetch)."""

import ledgerlib


def read(ctx):
    return ledgerlib.over_tasks(ctx, "counts", "readbacks")
