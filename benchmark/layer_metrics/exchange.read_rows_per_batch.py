"""Exchange layer: live rows a received slice — the sum over the window's
stages of counts.mesh_read_rows (the live rows of every non-empty
(partition, source, round) slice the reducers are handed) over the sum of
counts.mesh_read_batches (those slices). It is the size of the batch a
merge aggregate steps on behind a mesh exchange: thousands of rows in the
q65 stages, about 200 in a q28 stage, where each of the 96 slices is a
hash-table step of its own. A program from before the row count (the
parent of PR 48) gives None."""

import ledgerlib


def read(ctx):
    rows = batches = 0
    for task in ctx["tasks"]:
        a = ledgerlib.field(task, "counts", "mesh_read_rows")
        b = ledgerlib.field(task, "counts", "mesh_read_batches")
        if a is not None and b is not None:
            rows += a
            batches += b
    return rows / batches if batches else None
