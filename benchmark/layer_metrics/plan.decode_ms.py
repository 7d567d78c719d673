"""Planner and compile layer: median per task of layers_s.plan —
TaskDefinition parse, plan_from_bytes and program-cache builds (the
auron:plan/decode and auron:plan/build spans), compile seconds taken out."""

import ledgerlib


def read(ctx):
    return ledgerlib.over_tasks(ctx, "layers_s", "plan", scale=1e3)
