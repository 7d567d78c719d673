"""Scan + convert layer: median per task of host seconds spent turning
decoded Arrow into device batches (cost_ledger.host_s.convert)."""

import statistics


def read(ctx):
    vals = [t["done"]["cost_ledger"]["host_s"]["convert"] * 1e3
            for t in ctx["tasks"]]
    return statistics.median(vals) if vals else None
