"""Operator layer: MEAN per task of the aggregation's program calls — the
sum of counts.program_calls_by_site over the sites that start with
``hashtable.agg_`` or ``ops.agg.`` (the hash table's set-up, step, growth
and export; the batch reduce, the state merge, the emit and the partial
skip's pass-through). 42 a q28 task before the aggregation's glue became
programs (18 steps, 12 exports, 12 reduces beside 480 eager launches),
at most 66 since (18 steps, 12 reduces, 24 emits, 12 set-ups and no eager
launch): it says how often the new programs engage — a size to read
beside ops.host_cpu_ms, whatever direction the entry carries. A mean: the
plans of a mix differ. A frame without the per-site counts gives None."""

import statistics

SITES = ("hashtable.agg_", "ops.agg.")


def read(ctx):
    vals = []
    for task in ctx["tasks"]:
        ledger = (task.get("done") or {}).get("cost_ledger") or {}
        sites = (ledger.get("counts") or {}).get("program_calls_by_site")
        if isinstance(sites, dict):
            vals.append(sum(n for site, n in sites.items()
                            if site.startswith(SITES)))
    return statistics.fmean(vals) if vals else None
