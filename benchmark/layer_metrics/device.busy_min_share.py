"""Device: the least busy chip's share of the traced slice — the
smallest busy_s_by_device over window_s, where a chip the cell uses and
the trace has no plane for counts as 0. Above 0 says that every chip of
the cell ran device operations."""


def read(ctx):
    trace = ctx["trace"]
    if not trace or not trace.get("window_s"):
        return None
    busy = list(trace["busy_s_by_device"].values())
    if not busy:
        return None
    busy += [0.0] * (int(ctx["device"]["count"]) - len(busy))
    return min(busy) / trace["window_s"]
