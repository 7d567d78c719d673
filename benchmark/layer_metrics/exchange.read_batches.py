"""Exchange layer: MEAN per task of counts.mesh_read_batches — the
non-empty (partition, source, round) slices the task's reducers read out
of its mesh buffers, each one gather and one device_put to the home
chip. A mean, not a median: the plans of a mix differ (a stage with
three mesh exchanges beside one with one). A program from before that
counter gives None."""

import statistics

import ledgerlib


def read(ctx):
    return ledgerlib.over_tasks(ctx, "counts", "mesh_read_batches",
                                reduce=statistics.fmean)
