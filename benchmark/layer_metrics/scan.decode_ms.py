"""Scan and convert layer: median per task of the prefetch worker's
parquet -> Arrow time (scan_worker_s.decode, the auron:scan/decode
spans); overlapped with the task's thread, so beside layers_s."""

import ledgerlib


def read(ctx):
    return ledgerlib.over_tasks(ctx, "scan_worker_s", "decode", scale=1e3)
