"""Entry layer: mean per task of the task thread's undeclared
waiting — the sum over the layers that declare no wait (plan, op_host,
exchange less its gang_wait, to_arrow, send) of wall less CPU, each not
below 0. The declared waits (scan_wait, op_device_wait, gang_wait, the
queue) are left out: what remains is the interpreter lock, a blocking C
call or the scheduler. A frame without layers_cpu_s gives None."""

import ledgerlib
import offcpulib

LAYERS = ("plan", "op_host", "exchange", "to_arrow", "send")


def task_off_cpu(task):
    gang = ledgerlib.field(task, "exchange_s", "gang_wait") or 0.0
    parts = [offcpulib.off_cpu(task, ("layers_s", layer),
                               ("layers_cpu_s", layer),
                               less=gang if layer == "exchange" else 0.0)
             for layer in LAYERS]
    return None if None in parts else sum(parts)


def read(ctx):
    return offcpulib.mean_ms(task_off_cpu(t) for t in ctx["tasks"])
