"""Exchange layer: median per task of the bytes the mesh collectives
moved (cost_ledger.mesh_bytes)."""

import statistics


def read(ctx):
    vals = [t["done"]["cost_ledger"]["mesh_bytes"] for t in ctx["tasks"]]
    return statistics.median(vals) if vals else None
