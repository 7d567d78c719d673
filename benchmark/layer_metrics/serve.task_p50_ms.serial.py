"""Entry layer: median of the client's task latency in a cell that ONE
closed-loop client drives, where it is the service time of a stage and
rows over the rate: the same quantity as ``fact_rows_per_s``, which is
what judges such a cell end to end. It is not an end-to-end metric
there: a window holds 30 to 37 stages, and six seeds read 1,415 to
1,655 ms, a spread of 0.10 (my chip runs, PR 31) against the 0.08 that
bounds ``task_p50_ms`` in the cells with hundreds of samples. The seed
makes the tables, and the tables decide how many batches a stage's
exchanges read (27 to 39, ~48 ms of host time each): one seed run twice
repeats its median to 0.4 % (PERF.md, "run to run")."""

import statistics

from harness import metrics


def read(ctx):
    lat = metrics.latencies_ms(ctx["tasks"])
    return statistics.median(lat) if lat else None
