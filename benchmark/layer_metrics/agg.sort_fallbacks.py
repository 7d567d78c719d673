"""Operator layer: the SUM over the window's tasks of
counts.agg_sort_fallbacks — hash-table aggregations that overflowed their
probe budget and latched the sort path for the rest of their stream. A
sum, so that one shows; the configurations' guarantees say 0. A program
from before that counter gives None."""

import ledgerlib


def read(ctx):
    return ledgerlib.over_tasks(ctx, "counts", "agg_sort_fallbacks",
                                reduce=sum)
