"""Operator layer: median per task of what the ledger calls device_s —
the host waiting in block_until_ready, inclusive and summed over
operators. It is a wait on the host clock, not device time, and is named
for what it is."""

import statistics


def read(ctx):
    vals = [t["done"]["cost_ledger"]["device_s"] * 1e3 for t in ctx["tasks"]]
    return statistics.median(vals) if vals else None
