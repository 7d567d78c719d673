"""Operator layer: median per task of layers_s.op_device_wait — the
operators' time inside device_fence / timed_get / the timer's sync (the
auron:op/readback spans): the host waiting for the device, exclusive."""

import ledgerlib


def read(ctx):
    return ledgerlib.over_tasks(ctx, "layers_s", "op_device_wait", scale=1e3)
