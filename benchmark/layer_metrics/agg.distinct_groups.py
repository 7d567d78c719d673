"""Operator layer: MEAN per task of counts.agg_distinct_groups — the
groups that left a keyed ``partial_merge`` aggregation whose parent counts
one of its group columns: the distinct (keys, argument) pairs of a
single-DISTINCT aggregate as Spark plans it, q28's six price sets summed.
At one scan partition it equals the sum of the answer's six ``B*_CNTD``.
The configuration requires it to be at least 1 in every task: a distinct
count taken any other way counts none. A size to hold equal, parent
against change. A program from before that counter gives None."""

import statistics

import ledgerlib


def read(ctx):
    return ledgerlib.over_tasks(ctx, "counts", "agg_distinct_groups",
                                reduce=statistics.fmean)
