"""Exchange layer: median per task of counts.mesh_rounds — completed
all-to-all rounds of the task's mesh-routed exchanges. A program from
before that counter gives None."""

import ledgerlib


def read(ctx):
    return ledgerlib.over_tasks(ctx, "counts", "mesh_rounds")
