"""Scan and convert layer: median per task of layers_s.to_arrow — the
fence on the result batch and device -> Arrow (the
auron:convert/to_arrow spans)."""

import ledgerlib


def read(ctx):
    return ledgerlib.over_tasks(ctx, "layers_s", "to_arrow", scale=1e3)
