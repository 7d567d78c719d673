"""Operator layer: MEAN per task of counts.expand_rows_out — rows that
left a program an expand operator ran in (a ROLLUP's copies of every
joined row, on their way into the aggregation). A mean: one plan of the
mix expands. A size to hold equal, parent against change. A program from
before that counter gives None."""

import statistics

import ledgerlib


def read(ctx):
    return ledgerlib.over_tasks(ctx, "counts", "expand_rows_out",
                                reduce=statistics.fmean)
