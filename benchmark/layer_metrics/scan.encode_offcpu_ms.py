"""Scan and convert layer: mean per task of scan_worker_s.encode less
scan_worker_cpu_s.encode, not below 0 — the prefetch worker's time in
auron:scan/encode without a CPU. Encode does no I/O and no device call,
so this is the worker's wait for the interpreter lock and little else:
the cleanest lock-wait reading the program gives. A frame without
scan_worker_cpu_s gives None."""

import offcpulib


def read(ctx):
    return offcpulib.mean_ms(
        offcpulib.off_cpu(task, ("scan_worker_s", "encode"),
                          ("scan_worker_cpu_s", "encode"))
        for task in ctx["tasks"])
