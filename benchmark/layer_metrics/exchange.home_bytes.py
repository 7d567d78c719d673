"""Exchange layer: MEAN a stage of counts.mesh_home_bytes — the padded
bytes of the received shards that a device_put moved from another chip to
the home chip before the reducers cut them: the second crossing of every
mesh exchange (three quarters of its slot buffers), which running the
reduce side where the rows landed would remove. A mean: the plans of a
mix differ. A program from before that counter gives None."""

import statistics

import ledgerlib


def read(ctx):
    return ledgerlib.over_tasks(ctx, "counts", "mesh_home_bytes",
                                reduce=statistics.fmean)
