"""Scan and convert layer: median per task of the prefetch worker's
Arrow -> padded numpy time (scan_worker_s.encode, the auron:scan/encode
spans)."""

import ledgerlib


def read(ctx):
    return ledgerlib.over_tasks(ctx, "scan_worker_s", "encode", scale=1e3)
