"""Operator layer: MEAN per task of counts.decimal_div_rows — rows that
left a program that divided decimal by decimal, once a division in it
(q59's seven ratios count a row seven times). The configuration requires
it to be at least 1 in every task: a quotient computed in double counts
none. A size to hold equal, parent against change. A program from before
that counter gives None."""

import statistics

import ledgerlib


def read(ctx):
    return ledgerlib.over_tasks(ctx, "counts", "decimal_div_rows",
                                reduce=statistics.fmean)
