"""Performance forensics: host/device time attribution.

The [speed] ROADMAP item is a *measurement* problem before it is an
optimization problem: q01 CPU throughput decayed 276k → 108k rows/s
across three bench rounds with nothing pointing at WHERE the time went.
``elapsed_compute`` (ops/base.timer) honestly measures each operator's
wall — but Flare (PAPERS.md, 1703.08219) attributes exactly this class
of loss to host-side glue *around* the engine, and a single wall number
cannot separate the XLA execution from the python that feeds it.

This module splits every operator's wall into:

- ``elapsed_device`` — time spent waiting on the accelerator. The
  central program registry (runtime/programs.py) wraps every jitted
  program it hands out; each invocation times the async dispatch
  (call → return) and leaves the arrays in flight. The wait is timed
  where execution synchronizes: ``device_fence`` at the boundaries
  that need materialized results (to_arrow, sort collect, shuffle
  materialize) and ``timed_get`` at the control-scalar readbacks.
  Kernels that bypass the registry (the dense grouped-agg module jits)
  still get a split through the ``timer.track`` fallback: the
  tracked-value registration marks the dispatch/device boundary.
- ``elapsed_host_*`` — named host buckets for the remainder:
  ``dispatch`` (python glue until the async call returns: arg prep,
  cache lookups, jax dispatch), ``convert`` (arrow↔device transfers:
  scan decode waits, the executor's to_arrow materialization),
  ``serde`` (shuffle/spill frame pack/unpack + host slicing),
  ``iter`` (executor drive-loop bookkeeping between batches), and
  ``other`` (the unclassified residue, so per-timer attribution sums
  to the measured wall by construction).

Recording contract (same shape as obs/trace.py):

- disabled path: one cached config-epoch compare per timer / per
  program call — no frame allocation, no clock reads beyond what
  ``elapsed_compute`` already pays;
- enabled recording is thread-local (a frame STACK per thread, pushed
  by ops/base.timer) — kernel calls credit the innermost open frame,
  so nested/inclusive timers keep today's inclusive semantics and the
  residue lands in the inner operator's ``other``.

Beyond the per-op counters, when the ``program`` trace category
records, each wrapped call drops a ``program.call`` span carrying the
split so tools/trace_report.py can print host/device columns.

The frames above are INCLUSIVE (a parent's timer runs across its
child's ``next()``); the exclusive split by layer and operator is the
layer spans' (obs/trace.layer_span), which the sync points here open as
``auron:op/readback`` and count as the task's ``readbacks`` (``timed_get``,
``device_fence``) or ``row_syncs`` (``row_count``).
"""

from __future__ import annotations

import threading
from typing import Optional

from auron_tpu.obs import trace as _trace

#: host-bucket vocabulary (counter names are "elapsed_host_" + bucket)
HOST_BUCKETS = ("dispatch", "convert", "serde", "iter", "other")

#: (config epoch, enabled) verdict cache — the disabled hot path is one
#: int compare (the trace/faults pattern)
_CACHED: tuple[int, Optional[bool]] = (-1, None)

_TLS = threading.local()


def enabled() -> bool:
    global _CACHED
    from auron_tpu import config as cfg
    epoch, val = _CACHED
    if epoch == cfg.config_epoch() and val is not None:
        return val
    epoch = cfg.config_epoch()
    val = bool(cfg.get_config().get(cfg.PROFILE_ENABLED))
    _CACHED = (epoch, val)
    return val


# ---------------------------------------------------------------------------
# frames: per-timer attribution scopes (thread-local stack)
# ---------------------------------------------------------------------------

class Frame:
    """One open timer scope's accumulators (nanoseconds)."""

    __slots__ = ("device", "dispatch", "convert", "serde", "iter",
                 "calls")

    def __init__(self):
        self.device = 0
        self.dispatch = 0
        self.convert = 0
        self.serde = 0
        self.iter = 0
        self.calls = 0


def _stack() -> list:
    st = getattr(_TLS, "stack", None)
    if st is None:
        st = []
        _TLS.stack = st
    return st


def push_frame() -> Optional[Frame]:
    """Open an attribution frame for a timer scope; None when profiling
    is off (the caller skips the pop entirely)."""
    if not enabled():
        return None
    f = Frame()
    _stack().append(f)
    return f


def pop_frame(frame: Frame, sink, wall_ns: int,
              track_offset_ns: Optional[int] = None,
              bucket: Optional[str] = None) -> None:
    """Close ``frame`` and flush its attribution into ``sink`` (the
    owning ops.base.MetricsSet).

    - wrapped program calls recorded their own dispatch/device split;
    - with NO wrapped call but a ``timer.track`` registration,
      ``track_offset_ns`` marks the dispatch→device boundary (the dense
      grouped-agg path, whose module-level jits bypass the registry);
    - with neither, a ``bucket`` hint classifies the whole wall (host
      sections: scan decode waits → convert, shuffle serde → serde);
    - the residue is ``other`` so the buckets sum to the wall.

    Only nonzero buckets materialize counters (metric snapshots stay
    small; EXPLAIN ANALYZE shows what actually happened, not the whole
    vocabulary)."""
    st = _stack()
    if st and st[-1] is frame:
        st.pop()
    else:   # pragma: no cover - unwound out of order (exception paths)
        try:
            st.remove(frame)
        except ValueError:
            pass
    device = frame.device
    dispatch = frame.dispatch
    convert = frame.convert
    serde = frame.serde
    iter_ns = frame.iter
    if frame.calls == 0:
        if track_offset_ns is not None:
            dispatch += max(track_offset_ns, 0)
            device += max(wall_ns - max(track_offset_ns, 0), 0)
        elif bucket is not None:
            if bucket == "convert":
                convert += wall_ns
            elif bucket == "serde":
                serde += wall_ns
            elif bucket == "iter":
                iter_ns += wall_ns
            else:
                dispatch += wall_ns
    other = wall_ns - (device + dispatch + convert + serde + iter_ns)
    if device:
        sink.counter("elapsed_device").add(device)
    if dispatch:
        sink.counter("elapsed_host_dispatch").add(dispatch)
    if convert:
        sink.counter("elapsed_host_convert").add(convert)
    if serde:
        sink.counter("elapsed_host_serde").add(serde)
    if iter_ns:
        sink.counter("elapsed_host_iter").add(iter_ns)
    if other > 0:
        sink.counter("elapsed_host_other").add(other)


# ---------------------------------------------------------------------------
# program-call instrumentation (runtime/programs.py wraps through here)
# ---------------------------------------------------------------------------

def _block(out) -> None:
    """Wait for every array leaf of a program result; a device error
    that surfaces at the wait propagates."""
    import jax
    jax.block_until_ready(out)


def on_call(dispatch_ns: int, device_ns: int, site: str) -> None:
    """One wrapped program invocation's split: credit the innermost
    frame and drop a ``program.call`` span when that trace category
    records."""
    st = getattr(_TLS, "stack", None)
    if st:
        f = st[-1]
        f.dispatch += dispatch_ns
        f.device += device_ns
        f.calls += 1
    _trace.count_program_call(site)
    if _trace.category_enabled("program"):
        total = dispatch_ns + device_ns
        # start reconstructed from the durations: no clock reads beyond
        # the two the wrapper already took
        _trace.complete_span(
            "program", "program.call",
            _trace.tracer().now_ns() - total, total, site=site,
            dispatch_ms=round(dispatch_ns / 1e6, 4),
            device_ms=round(device_ns / 1e6, 4))


class ProfiledProgram:
    """Transparent callable proxy timing the dispatch of each
    invocation. Attribute access (``cache_info``-style introspection)
    passes through to the wrapped program."""

    __slots__ = ("_fn", "_site")

    def __init__(self, fn, site: str):
        object.__setattr__(self, "_fn", fn)
        object.__setattr__(self, "_site", site)

    def __call__(self, *args, **kwargs):
        import time
        t0 = time.perf_counter_ns()
        out = self._fn(*args, **kwargs)
        # the arrays stay in flight — batch N+1 dispatches while N
        # computes. The device wait is measured where execution
        # synchronizes (device_fence / timed_get), so attribution still
        # sums to wall; per call we record dispatch only.
        on_call(time.perf_counter_ns() - t0, 0, self._site)
        return out

    def __getattr__(self, name):
        return getattr(object.__getattribute__(self, "_fn"), name)


def wrap_program(value, site: str):
    """The registry's return hook: wrap a callable program in the
    per-invocation timer when profiling is on; everything else (and the
    disabled path) passes through untouched."""
    if not callable(value) or not enabled():
        return value
    return ProfiledProgram(value, site)


# ---------------------------------------------------------------------------
# sync points: where execution waits for the device
# ---------------------------------------------------------------------------

def device_fence(value, sink=None) -> int:
    """The materialization point: block until every array
    leaf of ``value`` is ready and attribute the wait as device time —
    to the innermost open frame when one is recording, else to ``sink``
    (a MetricsSet) when given. Returns the wait in nanoseconds.

    Call this ONLY where execution semantically requires materialized
    results (the to_arrow export, sort collect, shuffle materialize):
    nothing else waits, so batch N+1 dispatches while N computes."""
    import time
    t0 = time.perf_counter_ns()
    with _trace.readback_span():
        _block(value)
    ns = time.perf_counter_ns() - t0
    if not enabled():
        return ns
    st = getattr(_TLS, "stack", None)
    if st:
        st[-1].device += ns
    elif sink is not None:
        sink.counter("elapsed_device").add(ns)
    return ns


def _get(values, counted_as: str):
    """``jax.device_get`` inside ``auron:op/readback``, the wait
    credited to the innermost open frame's device bucket."""
    import time

    import jax
    t0 = time.perf_counter_ns()
    with _trace.readback_span(counted_as):
        out = jax.device_get(values)
    st = getattr(_TLS, "stack", None)
    if st:
        st[-1].device += time.perf_counter_ns() - t0
    return out


def timed_get(values):
    """``jax.device_get`` with the wait credited to the innermost open
    frame's device bucket — for the per-batch control-scalar readbacks
    (agg group counts, hashtable overflow flags, fused limit budgets)
    that ARE real sync points: they carry the device wait of the
    programs dispatched before them, and attributing them as device
    keeps the host buckets honest."""
    import jax
    out = _get(values, "readbacks")
    _trace.count("d2h_bytes", sum(
        getattr(leaf, "nbytes", 0)
        for leaf in jax.tree_util.tree_leaves(out)))
    return out


def row_count(batch) -> int:
    """``int(batch.num_rows)`` of a batch, or of a count that is handed
    in itself (a state's group count). A count that is still a device
    scalar is a sync point like any other — the read waits for the
    program that makes the batch, and for the chip's queue before it —
    so it is read as ``timed_get`` reads (``jax.device_get`` inside
    ``auron:op/readback``: device wait of the operator whose span it is
    in), and counted among the task's ``row_syncs``, not its
    ``readbacks``. A count already on the host returns at once with no
    span."""
    n = getattr(batch, "num_rows", batch)
    if type(n) is int:
        return n
    import jax
    if not isinstance(n, jax.Array):
        return int(n)
    return int(_get(n, "row_syncs"))


def row_count_and(batch, vector):
    """``row_count(batch)`` and the host values of ``vector`` (a small
    integer array the program that made the batch also returned) in the
    ONE read the row count costs: (rows, [values])."""
    n, values = _get((batch.num_rows, vector), "row_syncs")
    return int(n), values.tolist()


# ---------------------------------------------------------------------------
# aggregate views
# ---------------------------------------------------------------------------

def summarize_tree(node) -> dict:
    """Host/device rollup over a metric tree (obs/metric_tree.MetricNode)
    — the machine-readable profile section bench.py records and the
    EXPLAIN ANALYZE footer's source. Millisecond floats."""
    device = 0
    buckets = {b: 0 for b in HOST_BUCKETS}
    compute = 0
    for n in node.walk():
        device += n.metrics.get("elapsed_device", 0)
        compute += n.metrics.get("elapsed_compute", 0)
        for b in HOST_BUCKETS:
            buckets[b] += n.metrics.get("elapsed_host_" + b, 0)
    host = {b: round(v / 1e6, 3) for b, v in buckets.items() if v}
    return {
        "device_ms": round(device / 1e6, 3),
        "host_ms": round(sum(buckets.values()) / 1e6, 3),
        "host_buckets_ms": host,
        "elapsed_compute_ms": round(compute / 1e6, 3),
    }
