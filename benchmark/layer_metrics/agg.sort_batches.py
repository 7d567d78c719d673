"""Operator layer: MEAN per task of counts.agg_sort_batches — batches
through the sort path of the general aggregation (a batch sort-and-reduce,
then a merge into the hash-sorted state). A mean, not a median: the plans
of a mix differ (a decimal sum takes the hash table). A size to hold
equal, parent against change. A program from before that counter gives
None."""

import statistics

import ledgerlib


def read(ctx):
    return ledgerlib.over_tasks(ctx, "counts", "agg_sort_batches",
                                reduce=statistics.fmean)
