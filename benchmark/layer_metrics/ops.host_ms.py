"""Operator layer: median per task of layers_s.op_host — the operators'
exclusive host time on the task's thread (the auron:op/<name> spans less
their children, their readbacks and compile seconds); ops_s splits it by
operator."""

import ledgerlib


def read(ctx):
    return ledgerlib.over_tasks(ctx, "layers_s", "op_host", scale=1e3)
