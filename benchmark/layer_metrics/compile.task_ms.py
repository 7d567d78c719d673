"""Planner and compile layer: MEAN per task of compile.task_seconds — the
XLA compiles and persistent-cache fetches that fired on the task's own
threads. A mean, because a median would hide the few stalled tasks that
carry all of it."""

import statistics

import ledgerlib


def read(ctx):
    return ledgerlib.over_tasks(ctx, "compile", "task_seconds", scale=1e3,
                                reduce=statistics.fmean)
