"""Exchange layer: the gang door's share of the window's stages — the
sum over the window's tasks of exchange_s.gang_wait over the sum of
their wall_s. Where four short stages meet at the door it is most of a
stage; with one client it reads near 0. A share and not the median wait
(exchange.gang_wait_ms): it stays comparable when a change shortens the
stage round the door. None where no frame has the exchange_s split."""

import ledgerlib


def read(ctx):
    wait = wall = 0.0
    for task in ctx["tasks"]:
        a = ledgerlib.field(task, "exchange_s", "gang_wait")
        b = ledgerlib.field(task, "wall_s")
        if a is not None and b is not None:
            wait += a
            wall += b
    return wait / wall if wall else None
