"""Entry layer: median per task of layers_s.send — Arrow IPC
serialisation of the result batches, the socket writes and the wait for
the client's window (the auron:serve/send spans)."""

import ledgerlib


def read(ctx):
    return ledgerlib.over_tasks(ctx, "layers_s", "send", scale=1e3)
