"""Exchange layer: the all-to-all's share of its roofline in the traced
slice — the bytes a chip sent over the ICI, over what the ICI's peak
(harness/peaks.json) would have carried in the all-to-all's device
seconds. None where the slice holds no all-to-all time, no bytes, or no
peak for the device."""

import ledgerlib
from harness import peaks


def sent_bytes_a_chip(live_bytes: float, chips: int) -> float:
    """What one chip puts on the wire of ``live_bytes`` moved by the
    whole mesh (counts.mesh_bytes: rows received x row width, summed over
    the chips): its 1/chips of the rows, less the 1/chips of those that
    stay with it (destinations taken as even, as a hash gives them)."""
    if chips < 2:
        return 0.0
    return live_bytes / chips * (chips - 1) / chips


def read(ctx):
    trace, cut = ctx["trace"], ctx["trace_slice"]
    if not trace or "stop" not in cut:
        return None
    # op_s is averaged over the chips: one chip's seconds in the slice
    secs = sum(s for name, s in trace["op_s"].items()
               if "all-to-all" in name.lower()
               or "alltoall" in name.lower())
    live = sum(ledgerlib.field(t, "counts", "mesh_bytes") or 0
               for t in ctx["tasks"]
               if cut["start"] <= t["t_done"] <= cut["stop"])
    sent = sent_bytes_a_chip(live, int(ctx["device"]["count"]))
    try:
        peak = peaks.peaks_for(ctx["device"]["kind"])["ici_bits_per_s"] / 8
    except KeyError:
        return None
    if not secs or not sent or not peak:
        return None
    return sent / (secs * peak)
