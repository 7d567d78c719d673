"""Planner + compile layer: XLA programs compiled, or fetched from the
persistent cache, between the end of warm-up and the end of the window
(the process-wide count of ``utils/compile_stats.py``). Warm-up covers
every input shape of the window, so this is expected to be 0."""


def read(ctx):
    return ctx["stats"]["xla_programs"] - ctx["warm_stats"]["xla_programs"]
