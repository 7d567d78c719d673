"""Operator layer: mean per task of layers_s.op_host less
layers_cpu_s.op_host, not below 0 — the part of the operators' exclusive
host time in which the task's thread was not on a CPU: waiting for the
interpreter lock, blocked in a C call, or descheduled. An upper bound on
the lock wait inside operators; since every row-count read passes
auron:op/readback no wait for the chip is in it. A frame without
layers_cpu_s gives None."""

import offcpulib


def read(ctx):
    return offcpulib.mean_ms(
        offcpulib.off_cpu(task, ("layers_s", "op_host"),
                          ("layers_cpu_s", "op_host"))
        for task in ctx["tasks"])
