"""Planner + compile layer: seconds the serving process spent compiling
XLA programs, or fetching them from the persistent cache, before the
window opened (the process-wide sum of ``utils/compile_stats.py``)."""


def read(ctx):
    return ctx["warm_stats"]["xla_compile_s"]
