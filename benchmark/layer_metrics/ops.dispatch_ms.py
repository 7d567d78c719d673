"""Operator layer: median per task of host seconds spent dispatching
device programs (cost_ledger.host_s.dispatch)."""

import statistics


def read(ctx):
    vals = [t["done"]["cost_ledger"]["host_s"]["dispatch"] * 1e3
            for t in ctx["tasks"]]
    return statistics.median(vals) if vals else None
