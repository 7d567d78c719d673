"""Scan and convert layer: median per task of the prefetch worker's
host -> device transfer time (scan_worker_s.h2d, one auron:scan/h2d span
a batch)."""

import ledgerlib


def read(ctx):
    return ledgerlib.over_tasks(ctx, "scan_worker_s", "h2d", scale=1e3)
