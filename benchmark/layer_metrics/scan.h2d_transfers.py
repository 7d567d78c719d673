"""Scan and convert layer: median per task of the host -> device
transfers the scans made (counts.h2d_transfers: one a buffer today)."""

import ledgerlib


def read(ctx):
    return ledgerlib.over_tasks(ctx, "counts", "h2d_transfers")
