"""Entry layer: median per task of layers_s.other — wall_s less every
layer span's exclusive time, that is what the spans do not cover yet."""

import ledgerlib


def read(ctx):
    return ledgerlib.over_tasks(ctx, "layers_s", "other", scale=1e3)
