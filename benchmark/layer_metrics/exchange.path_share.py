"""Exchange layer: the exchange path's share of the window's stages, both
sides — the sum over the window's stages of layers_s.exchange (the map
side: materialize, map_write, the gang door, the stack and the sharded
round with its fence) plus ops_s.shuffle_exchange_read host and device
wait (the reduce side: the shard's crossing home, the cut, the pull),
over the sum of their wall_s. It says whether a cell is one in which the
exchange mechanism does the work. None where no frame has the layer."""

import ledgerlib


def read(ctx):
    path = wall = 0.0
    for task in ctx["tasks"]:
        layer = ledgerlib.field(task, "layers_s", "exchange")
        total = ledgerlib.field(task, "wall_s")
        if layer is None or total is None:
            continue
        host = ledgerlib.field(task, "ops_s", "shuffle_exchange_read",
                               "host_s")
        wait = ledgerlib.field(task, "ops_s", "shuffle_exchange_read",
                               "device_wait_s")
        path += layer + (host or 0.0) + (wait or 0.0)
        wall += total
    return path / wall if wall else None
