"""Operator layer: median per task of counts.program_calls — calls of
programs handed out by runtime/programs.py (the engine's own jitted
programs; eager one-op programs are not among them)."""

import ledgerlib


def read(ctx):
    return ledgerlib.over_tasks(ctx, "counts", "program_calls")
