"""Operator layer: MEAN per task of counts.agg_hash_batches — batches
folded through the hash-table aggregation state (one agg_step program and
one readback each; a retry after growth counts once). A mean, not a
median: the plans of a mix differ (a double sum takes the sort path), and
a median would read one of them. A size to hold equal, parent against
change. A program from before that counter gives None."""

import statistics

import ledgerlib


def read(ctx):
    return ledgerlib.over_tasks(ctx, "counts", "agg_hash_batches",
                                reduce=statistics.fmean)
