"""Operator layer: MEAN per task of counts.batch_shrinks — batches that
left a stage holding a filter at a capacity cut to their live rows
(ops/base.count_output(shrink=True): one columnar.batch.shrink launch
each, from the row count read anyway; counts.batch_shrink_lanes sums the
lanes that went). 12 a q28 task (its twelve filtered scan batches,
65,536 -> 1,024), 0 where no filtered batch of 32,768 slots or more
keeps an eighth of them or fewer. A mean: the plans of a mix differ. It
says how often the mechanism engages — a size to read beside
ops.device_wait_ms, whatever direction the entry carries. A program from
before that counter gives None."""

import statistics

import ledgerlib


def read(ctx):
    return ledgerlib.over_tasks(ctx, "counts", "batch_shrinks",
                                reduce=statistics.fmean)
