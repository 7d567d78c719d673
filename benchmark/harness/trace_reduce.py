"""From a profiler trace (``.xplane.pb``) to device busy time, the top
device operations and the longest idle gaps.

One reducer for every cell, kept with the benchmark so that every PR
computes these numbers the same way. It reads the file with
``jax.profiler.ProfileData`` and nothing else.

What the planes of a v5e trace look like (looked at by hand, PR 24): a
plane ``/device:TPU:<n>`` for each chip, whose line ``XLA Ops`` holds one
event for each executed HLO operation (start and duration in ns) and
whose line ``XLA Modules`` holds one event for each executed program;
``/host:CPU`` holds a line for each host thread with XLA's own TraceMe
events. Busy time is the union of the ``XLA Ops`` intervals of a chip,
averaged over the chips; an idle gap is a stretch between two such
intervals, named by the host event that covers most of it.

    python benchmark/harness/trace_reduce.py <trace dir or file> [--dump]
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys

import numpy as np

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE_PREFIX = "/host:"
TOP_N = 10


def find_xplane(trace_dir: str) -> str:
    if os.path.isfile(trace_dir):
        return trace_dir
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb"))) \
        or sorted(glob.glob(os.path.join(trace_dir, "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _intervals(line) -> list:
    """[(start_ns, end_ns, name)] of a line's events, by start."""
    out = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
           for ev in line.events]
    out.sort()
    return out


def merge(intervals: list) -> list:
    """Union of [(start, end, ...)] (sorted by start) as [(start, end)]."""
    merged: list = []
    for item in intervals:
        start, end = item[0], item[1]
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1][1] = end
        else:
            merged.append([start, end])
    return [(a, b) for a, b in merged]


_HLO = re.compile(r"^(%[\w.\-]+) = (.*?)\b([a-z][\w\-]*)\(")


def short_name(name: str) -> str:
    """An ``XLA Ops`` event is named by its whole HLO line; the breakdown
    keeps ``%name opcode result-type``: '%fusion.15 fusion u32[65536]'."""
    m = _HLO.match(name)
    if not m:
        return name[:80]
    op, rtype, opcode = m.groups()
    rtype = "(tuple)" if rtype.startswith("(") else rtype.split("{")[0]
    return f"{op} {opcode} {rtype.strip()}"[:80]


def _cover_name(gap, starts, ends, names) -> str:
    """What the host was doing in ``gap``. Host events nest, so among
    those that cover at least half of the gap the shortest (innermost)
    names it; failing that, the one that covers most. ``starts`` and
    ``ends`` are numpy arrays over the host events, ``names`` a list."""
    if not len(starts):
        return "(no host event)"
    cover = np.minimum(ends, gap[1]) - np.maximum(starts, gap[0])
    if cover.max() <= 0:
        return "(no host event)"
    inner = np.flatnonzero(cover >= (gap[1] - gap[0]) / 2)
    if len(inner):
        return names[inner[np.argmin((ends - starts)[inner])]]
    return names[int(np.argmax(cover))]


def reduce_planes(planes: list, chips: int = 1) -> dict:
    """``planes``: [(plane name, [(line name, [(start, end, name)])])] —
    the shape ``_read`` gives and a test can write by hand. ``chips`` is
    how many chips the cell uses: a chip that ran nothing in the slice
    has no plane in the trace and still counts in the averages."""
    device = [(n, lines) for n, lines in planes
              if n.startswith(DEVICE_PLANE_PREFIX)]
    host_events = [
        ev for n, lines in planes if n.startswith(HOST_PLANE_PREFIX)
        for _line, events in lines for ev in events]
    h_starts = np.array([ev[0] for ev in host_events], np.int64)
    h_ends = np.array([ev[1] for ev in host_events], np.int64)
    h_names = [ev[2] for ev in host_events]
    spans = [(min(ev[0] for ev in events), max(ev[1] for ev in events))
             for _n, lines in planes for _l, events in lines if events]
    if not spans:
        return {"window_s": 0.0, "busy_s": 0.0, "devices": 0,
                "busy_s_by_device": {}, "op_s": {}, "op_count": {},
                "modules": 0, "device_ops": [], "idle_gaps": []}
    t_lo = min(a for a, _b in spans)
    t_hi = max(b for _a, b in spans)
    busy_by_dev, op_s, op_count, gaps = {}, {}, {}, []
    modules = 0
    for name, lines in device:
        ops = [ev for line, events in lines if line == OPS_LINE
               for ev in events]
        ops.sort()
        modules += sum(len(events) for line, events in lines
                       if line == MODULES_LINE)
        merged = merge(ops)
        busy_by_dev[name] = sum(b - a for a, b in merged) * 1e-9
        for start, end, op in ops:
            op_s[op] = op_s.get(op, 0.0) + (end - start) * 1e-9
            op_count[op] = op_count.get(op, 0) + 1
        edges = [t_lo] + [t for a, b in merged for t in (a, b)] + [t_hi]
        for i in range(0, len(edges), 2):
            if edges[i + 1] > edges[i]:
                gaps.append((edges[i + 1] - edges[i],
                             (edges[i], edges[i + 1]), name))
    n_dev = max(len(device), chips, 1)
    gaps.sort(reverse=True)
    idle = {}
    for length, gap, _dev in gaps[:200]:
        what = _cover_name(gap, h_starts, h_ends, h_names)
        idle[what] = idle.get(what, 0.0) + length * 1e-9 / n_dev
    top_ops = sorted(op_s.items(), key=lambda kv: -kv[1])[:TOP_N]
    return {
        "window_s": (t_hi - t_lo) * 1e-9,
        "busy_s": sum(busy_by_dev.values()) / n_dev,
        "devices": len(device),
        "busy_s_by_device": busy_by_dev,
        "op_s": {k: v / n_dev for k, v in op_s.items()},
        "op_count": op_count,
        "modules": modules,
        "device_ops": [[short_name(k), v / n_dev] for k, v in top_ops],
        "idle_gaps": [[k, v] for k, v in
                      sorted(idle.items(), key=lambda kv: -kv[1])[:TOP_N]],
    }


def _read(path: str) -> list:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    return [(plane.name, [(line.name, _intervals(line))
                          for line in plane.lines])
            for plane in data.planes]


def reduce_file(path: str, chips: int = 1) -> dict:
    return reduce_planes(_read(path), chips)


def reduce_dir(trace_dir: str, chips: int = 1) -> dict:
    path = find_xplane(trace_dir)
    out = reduce_file(path, chips)
    out["xplane_bytes"] = os.path.getsize(path)
    return out


def dump(path: str) -> None:
    """Planes, lines and their commonest event names: what to look at by
    hand before trusting the reduction on a new device or jax."""
    for plane, lines in _read(path):
        print(f"plane {plane!r}: {len(lines)} lines")
        for line, events in lines:
            names: dict = {}
            for start, end, name in events:
                cnt, tot = names.get(name, (0, 0))
                names[name] = (cnt + 1, tot + end - start)
            top = sorted(names.items(), key=lambda kv: -kv[1][1])[:8]
            print(f"  line {line!r}: {len(events)} events; " + "; ".join(
                f"{n[:60]} x{c} {t * 1e-6:.2f}ms" for n, (c, t) in top))


if __name__ == "__main__":
    target = find_xplane(sys.argv[1])
    if "--dump" in sys.argv[2:]:
        dump(target)
    else:
        print(json.dumps(reduce_file(target), indent=1))
