"""Exchange layer: device time of the all-to-all operations per task,
from the device trace: the seconds of every XLA operation whose name
says all-to-all inside the traced slice (averaged over the chips), over
the tasks that completed inside the slice."""


def read(ctx):
    trace, cut = ctx["trace"], ctx["trace_slice"]
    if not trace or "stop" not in cut:
        return None
    secs = sum(s for name, s in trace["op_s"].items()
               if "all-to-all" in name.lower()
               or "alltoall" in name.lower())
    n = sum(1 for t in ctx["tasks"]
            if cut["start"] <= t["t_done"] <= cut["stop"])
    if not secs or not n:
        return None
    return secs * 1e3 / n
