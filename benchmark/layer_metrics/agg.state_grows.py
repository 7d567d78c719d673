"""Operator layer: mean per task of counts.agg_state_grows — capacity
doublings of an aggregation state while the task's batches fold into it
(the hash table's re-bucketing program, the sorted state's wider merge):
each is a program keyed on the old and the new capacity. A program from
before that counter gives None."""

import statistics

import ledgerlib


def read(ctx):
    return ledgerlib.over_tasks(ctx, "counts", "agg_state_grows",
                                reduce=statistics.fmean)
