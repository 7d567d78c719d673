"""Operator layer: MEAN per task of counts.window_rows — rows into a
window program (every partition of it sorted and scanned in ONE program
a capacity). A mean: the plans of a mix differ (one has no window). A
size to hold equal, parent against change. A program from before that
counter gives None."""

import statistics

import ledgerlib


def read(ctx):
    return ledgerlib.over_tasks(ctx, "counts", "window_rows",
                                reduce=statistics.fmean)
