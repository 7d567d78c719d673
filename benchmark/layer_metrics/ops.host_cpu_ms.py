"""Operator layer: mean per task of layers_cpu_s.op_host — the CPU the
task's thread spent inside the operators' exclusive host time (the
auron:op/<name> spans' CPU self time, thread CPU clock): the operators'
real host work. ops.host_ms less this is waiting (ops.host_offcpu_ms).
A mean, as offcpulib says why. A frame without the field (a program
from before it) gives None."""

import statistics

import ledgerlib


def read(ctx):
    return ledgerlib.over_tasks(ctx, "layers_cpu_s", "op_host", scale=1e3,
                                reduce=statistics.mean)
