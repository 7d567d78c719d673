"""Entry layer: median per task of the wait for a scheduler slot
(cost_ledger.queue_s, the auron:serve/queue span) — before wall_s
starts, and until now inside serve.overhead_ms."""

import ledgerlib


def read(ctx):
    return ledgerlib.over_tasks(ctx, "queue_s", scale=1e3)
