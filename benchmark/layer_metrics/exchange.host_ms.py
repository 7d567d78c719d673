"""Exchange layer: median per task of layers_s.exchange — the exchange's
exclusive host time on the task's thread: auron:exchange/materialize
less the operators it drives, with (where the program has them) the gang
door, the stacking of the partitions' batches into one sharded batch,
and the stage program's launch and fence."""

import ledgerlib


def read(ctx):
    return ledgerlib.over_tasks(ctx, "layers_s", "exchange", scale=1e3)
