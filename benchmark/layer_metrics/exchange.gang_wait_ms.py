"""Exchange layer: median per task of exchange_s.gang_wait — the stage's
wait at the mesh's gang door (MeshPlane.gang: the scheduler's turn and
the FIFO behind another task's sharded stage), which every exchange of
the task passes once unless its thread already holds the door. With one
client it reads near 0; with as many clients as task slots it is what a
stage pays for the map side running inside the door. A frame without
the exchange_s split (a program from before it) gives None."""

import ledgerlib


def read(ctx):
    return ledgerlib.over_tasks(ctx, "exchange_s", "gang_wait", scale=1e3)
