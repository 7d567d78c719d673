"""Operator layer: median per task of counts.row_syncs — reads of a
batch's row count (or a state's group count) that found it still on the
device (obs/profile.row_count): syncs counted apart from ops.readbacks,
whose wait is in ops.device_wait_ms. A frame without the count gives
None."""

import ledgerlib


def read(ctx):
    return ledgerlib.over_tasks(ctx, "counts", "row_syncs")
