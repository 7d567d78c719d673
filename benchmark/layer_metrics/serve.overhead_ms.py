"""Entry layer: median over the window's tasks of the client's latency
less the server's own wall time for the task (cost_ledger.wall_s): the
socket, the frames, admission and the Arrow batches back."""

import statistics


def read(ctx):
    gaps = [(t["t_done"] - t["t_submit"] - t["done"]["cost_ledger"]["wall_s"])
            * 1e3 for t in ctx["tasks"]]
    return statistics.median(gaps) if gaps else None
