"""Span plane: the engine's query→stage→task→operator→event timeline.

Dapper-shaped tracing for the runtime (PAPERS.md distributed-tracing
line): every recovery- or latency-relevant boundary opens a *span*
(named, categorized, attributed, nested via a per-thread stack) or drops
a zero-duration *event*. What the reference gets from pprof HTTP
endpoints plus log archaeology — "what happened when" across retries,
shuffle fetches, spills, compiles and watchdog decisions — is here one
timeline, exportable two ways:

- Chrome-trace JSON (``export_chrome``): the ``{"traceEvents": [...]}``
  format Perfetto / chrome://tracing load directly;
- JSONL (``export_jsonl``): one span per line for programmatic
  consumption (``tools/trace_report.py``).

Recording contract (the <2% overhead budget, PERF.md):

- **disabled hot path**: one cached config-epoch compare (the
  fault-plane pattern, runtime/faults.py) — no lock, no dict lookup;
- **enabled recording is lock-free**: each thread appends to its own
  buffer (registered once under the tracer lock); merge happens only at
  export/snapshot time. The ``auron.trace.max_spans`` cap is enforced
  with the same lock-freedom, so it is approximate by design.

Span identity is stable and deterministic per process: monotonic
counters assign trace ids (one per top-level query scope) and span ids
(global), never wall-clock or randomness, so two runs of the same
single-threaded pipeline number their spans identically.

*Layer spans* (:func:`layer_span`) are the spans at the boundaries of
PERF.md's layers — entry, planner and compile, scan and convert,
operators, exchange. They are the same context manager with three
duties, two of them independent of ``auron.trace.enabled``: every entry
opens a ``jax.profiler.TraceAnnotation("auron:<layer>/<name>")``, so any
profiler session shows the program's layers on ``/host:CPU`` under the
clock of the device planes; every exit charges the span's SELF time
(its duration less what its child layer spans and the compile events
inside it cover, per thread) to the running task's
:class:`TaskAccumulator`, which ``obs/ledger.build`` folds into the
version-2 ledger; and with tracing on the ``Span`` is recorded as any
other. They live on the one per-thread stack this module already has.
Beside the wall clock a layer span reads the thread's CPU clock
(``time.thread_time_ns``), and its CPU self time is charged where its
wall self time is: what is left of a span's wall after its CPU is a
wait the span did not declare — for the interpreter lock, in a C call
that blocks, or descheduled. That clock is a system call — 6 µs on the
chip's host where the wall clock is 0.07, in ticks of 10 ms, the
interpreter held while it runs: read round every span of every task it
cost the host-bound cells 5 % of their rate. So one task in
:data:`CPU_TIMED_EVERY` is *CPU-timed* and only its spans read it (a
window's mean is the reading; the other tasks' frames lack the CPU
fields), and the spans that ARE a declared wait
(:data:`DECLARED_WAITS`) leave it alone in every task.

Config surface: ``auron.trace.{enabled,dir,events,max_spans}``
(config.py). The knobs are deliberately NOT trace-semantic in the
program-cache sense (config.TRACE_SEMANTIC_KEYS): flipping tracing must
never retrace a kernel.
"""

from __future__ import annotations

import itertools
import json
import os
import threading
import time
from typing import NamedTuple, Optional

from auron_tpu.obs import flight_recorder as _flight
from auron_tpu.obs.flight_recorder import get_role, set_role  # noqa: F401

#: span categories (the auron.trace.events allowlist vocabulary).
#: The ``mesh`` category carries the SPMD plane's routing AND fault
#: domain: ``exchange.route`` (per-exchange routing decision),
#: ``mesh.gang`` (gang-door occupancy), ``exchange.demote`` (mid-query
#: route demotion with reason/recompute cost), ``mesh.straggler``
#: (round slower than straggler_factor × rolling p50) and
#: ``mesh.quarantine`` (device retired from future submeshes) —
#: tools/mesh_report.py prints all of them.
#: The ``cache`` category is the warm-path serving plane
#: (auron_tpu/cache): ``cache.hit`` / ``cache.miss`` / ``cache.store``
#: / ``cache.evict`` on the result/subplan cache and ``aot.warm``
#: spans around each ahead-of-time plan warming at Session init.
#: The ``fleet`` category is the cross-process serving plane:
#: ``fleet.submit`` (client-side conversation span), ``fleet.adopt``
#: (a process adopting an inbound wire trace context — carries
#: remote_parent/remote_role/remote_pid, the stitch tool's cross-
#: process link), ``fleet.route`` (router routing decision) and
#: ``fleet.forward`` (router hop span around one replica
#: conversation; failover shows as a second hop to the survivor).
#: The ``layer`` category holds the layer spans that have no older
#: category of their own (serve/scan/op/convert: up to ~2,000 a task).
CATEGORIES = ("query", "task", "program", "shuffle", "spill", "fault",
              "watchdog", "memory", "sched", "mesh", "journal", "cache",
              "fleet", "layer")

_SPAN_IDS = itertools.count(1)     # next() is GIL-atomic
_TRACE_IDS = itertools.count(1)


class _Settings(NamedTuple):
    enabled: bool
    dir: str
    events: Optional[frozenset]    # None = every category
    max_spans: int
    propagate: bool


#: (config epoch, settings) — the disabled check must cost one int
#: compare (same verdict-cache shape as runtime/faults._CACHED)
_CACHED: tuple[int, Optional[_Settings]] = (-1, None)


def _settings() -> _Settings:
    global _CACHED
    from auron_tpu import config as cfg
    epoch, st = _CACHED
    if epoch == cfg.config_epoch() and st is not None:
        return st
    # read the epoch BEFORE the values: a concurrent set() bumps it
    # after we read, so a stale cache entry misses on the next call
    epoch = cfg.config_epoch()
    conf = cfg.get_config()
    ev = conf.get(cfg.TRACE_EVENTS)
    cats = frozenset(c.strip() for c in ev.split(",") if c.strip())
    st = _Settings(
        enabled=conf.get(cfg.TRACE_ENABLED),
        dir=conf.get(cfg.TRACE_DIR),
        events=cats or None,
        max_spans=conf.get(cfg.TRACE_MAX_SPANS),
        propagate=conf.get(cfg.TRACE_PROPAGATE),
    )
    _CACHED = (epoch, st)
    return st


class Span:
    """One finished span (events are zero-duration spans)."""

    __slots__ = ("trace_id", "span_id", "parent_id", "cat", "name",
                 "ts_ns", "dur_ns", "tid", "attrs")

    def __init__(self, trace_id, span_id, parent_id, cat, name, ts_ns,
                 dur_ns, tid, attrs):
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.cat = cat
        self.name = name
        self.ts_ns = ts_ns
        self.dur_ns = dur_ns
        self.tid = tid
        self.attrs = attrs

    def to_dict(self) -> dict:
        return {"trace": self.trace_id, "span": self.span_id,
                "parent": self.parent_id, "cat": self.cat,
                "name": self.name, "ts_us": self.ts_ns / 1000.0,
                "dur_us": self.dur_ns / 1000.0, "tid": self.tid,
                "attrs": self.attrs}

    @classmethod
    def from_dict(cls, d: dict) -> "Span":
        return cls(d["trace"], d["span"], d["parent"], d["cat"],
                   d["name"], round(d["ts_us"] * 1000.0),
                   round(d["dur_us"] * 1000.0), d["tid"],
                   d.get("attrs") or {})


class Tracer:
    """Process tracer: per-thread lock-free buffers, merged on demand."""

    def __init__(self):
        self._lock = threading.Lock()
        self._buffers: list[list[Span]] = []
        self._tls = threading.local()
        #: approximate buffered-span count (lock-free increments)
        self._count = 0
        self.dropped = 0
        #: wall-clock epoch of the monotonic ts origin (JSONL metadata)
        self.epoch_wall = time.time()
        self._t0 = time.perf_counter_ns()

    # -- recording (per-thread, lock-free) ----------------------------------

    def _buf(self) -> list:
        buf = getattr(self._tls, "buf", None)
        if buf is None:
            buf = []
            with self._lock:
                self._buffers.append(buf)
            self._tls.buf = buf
        return buf

    def _stack(self) -> list:
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = []
            self._tls.stack = st
        return st

    @property
    def current_trace(self) -> int:
        return getattr(self._tls, "trace", 0)

    def set_trace(self, trace_id: int) -> None:
        self._tls.trace = trace_id

    def now_ns(self) -> int:
        return time.perf_counter_ns() - self._t0

    def record(self, span: Span, max_spans: int) -> None:
        sink = getattr(self._tls, "sink", None)
        if sink is not None:
            # adopted wire trace (wire_scope): stream the span straight
            # to its per-role file instead of buffering — the dead
            # replica's partial spans survive a SIGKILL, replica memory
            # stays flat without drop(), and a router thread sharing
            # the client's process never double-exports into the
            # client's buffered trace
            sink.write(span)
            return
        if self._count >= max_spans:
            self.dropped += 1
            return
        self._buf().append(span)
        self._count += 1

    # -- merge / export ------------------------------------------------------

    def spans(self, trace_id: Optional[int] = None) -> list[Span]:
        """Merged snapshot of every thread's buffer, timeline-ordered."""
        with self._lock:
            buffers = list(self._buffers)
        out: list[Span] = []
        for buf in buffers:
            out.extend(buf[:len(buf)])   # len() pins a consistent prefix
        if trace_id is not None:
            out = [s for s in out if s.trace_id == trace_id]
        out.sort(key=lambda s: (s.ts_ns, s.span_id))
        return out

    def drop(self, trace_id: int) -> None:
        """Forget one trace's spans (post-export memory bound)."""
        with self._lock:
            buffers = list(self._buffers)
        for buf in buffers:
            n = len(buf)   # pin: the owning thread may append concurrently
            kept = [s for s in buf[:n] if s.trace_id != trace_id]
            if len(kept) != n:
                buf[:n] = kept
                self._count -= n - len(kept)

    def reset(self) -> None:
        with self._lock:
            for buf in self._buffers:
                del buf[:]
            self._count = 0
            self.dropped = 0


_TRACER = Tracer()


def tracer() -> Tracer:
    return _TRACER


def enabled() -> bool:
    return _settings().enabled


def category_enabled(cat: str) -> bool:
    """True when spans of ``cat`` would actually record — tracing on
    AND the category not excluded by auron.trace.events. Hot paths that
    pay per-item clock reads purely to feed a span should gate on this,
    not on :func:`enabled` alone."""
    st = _settings()
    return st.enabled and (st.events is None or cat in st.events)


def reset() -> None:
    """Drop every buffered span (tests, chaos-run isolation)."""
    _TRACER.reset()


# ---------------------------------------------------------------------------
# recording API
# ---------------------------------------------------------------------------

class _Noop:
    """Disabled-path span: a shared, attribute-tolerant no-op."""

    __slots__ = ()
    span_id = 0

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs):
        return self


_NOOP = _Noop()


class _SpanCM:
    __slots__ = ("cat", "name", "attrs", "span_id", "_parent", "_t0",
                 "_max")

    #: None on plain spans; a :class:`_LayerSpan` names its layer
    layer = None

    def __init__(self, cat, name, attrs, max_spans):
        self.cat = cat
        self.name = name
        self.attrs = attrs
        self._max = max_spans

    def set(self, **attrs):
        """Attach attributes discovered mid-span (bytes read, rows...)."""
        self.attrs.update(attrs)
        return self

    def __enter__(self):
        tr = _TRACER
        stack = tr._stack()
        self._parent = stack[-1].span_id if stack else 0
        self.span_id = next(_SPAN_IDS)
        stack.append(self)
        self._t0 = tr.now_ns()
        return self

    def __exit__(self, exc_type, exc, tb):
        tr = _TRACER
        self._pop(tr._stack())
        if exc_type is not None:
            self.attrs.setdefault("error", exc_type.__name__)
        t0 = self._t0
        dur = tr.now_ns() - t0
        # flight-recorder tee (obs/flight_recorder): completed spans
        # join the always-on ring — attrs are final here (error set)
        _flight.tee(self.cat, self.name, self.attrs, dur_ns=dur)
        tr.record(Span(tr.current_trace, self.span_id, self._parent,
                       self.cat, self.name, t0, dur,
                       threading.get_ident(), self.attrs), self._max)
        return False


    def _pop(self, stack: list) -> None:
        # pop by identity, not position: spans held open across
        # generator yields (shuffle.fetch, spill.read wrap streams) can
        # exit out of LIFO order when a consumer interleaves two
        # streams — a positional pop would strand the dead span on the
        # stack forever, misparenting every later span on the thread
        if stack and stack[-1] is self:
            stack.pop()
        else:
            try:
                stack.remove(self)
            except ValueError:
                pass


def span(cat: str, name: str, **attrs):
    """Open a span (context manager). Disabled / filtered categories
    return a shared no-op whose cost is the settings check."""
    st = _settings()
    if not st.enabled or (st.events is not None and cat not in st.events):
        return _NOOP
    return _SpanCM(cat, name, attrs, st.max_spans)


def event(cat: str, name: str, **attrs) -> None:
    """Record a zero-duration span at the current stack position.

    Tees into the always-on flight recorder BEFORE the enabled check:
    structured events (fault injections, retries, sheds, admission
    decisions) stay reconstructable even with tracing off — the
    black-box contract (obs/flight_recorder.py)."""
    _flight.tee(cat, name, attrs)
    st = _settings()
    if not st.enabled or (st.events is not None and cat not in st.events):
        return
    tr = _TRACER
    stack = tr._stack()
    tr.record(Span(tr.current_trace, next(_SPAN_IDS),
                   stack[-1].span_id if stack else 0, cat, name,
                   tr.now_ns(), 0, threading.get_ident(), attrs),
              st.max_spans)


def complete_span(cat: str, name: str, start_ns: int, dur_ns: int,
                  **attrs) -> None:
    """Record an already-finished span with explicit timing — for work
    accumulated across a GENERATOR's production segments (shuffle reads,
    spill reads). Holding a ``span()`` context open across yields would
    (a) time the consumer's compute while the generator is suspended and
    (b) keep the span on the per-thread stack so every consumer-side
    span misparents under it; measuring each ``next()`` segment and
    recording once at exhaustion reports only the producer's own cost.
    Parent is the CURRENT stack top (the consumer driving the
    generator), never the span itself."""
    _flight.tee(cat, name, attrs, dur_ns=dur_ns)
    st = _settings()
    if not st.enabled or (st.events is not None and cat not in st.events):
        return
    tr = _TRACER
    stack = tr._stack()
    tr.record(Span(tr.current_trace, next(_SPAN_IDS),
                   stack[-1].span_id if stack else 0, cat, name, start_ns,
                   dur_ns, threading.get_ident(), attrs), st.max_spans)


def stream_spanned(cat: str, name: str, it, time_counter=None, **attrs):
    """Yield ``it``'s items, timing ONLY the production segments (each
    ``next()``), and record ONE completed span at exhaustion or
    abandonment (:func:`complete_span` explains why a span must never
    stay open across yields). ``time_counter`` — an ops.base Metric —
    additionally accrues the produced nanoseconds even when tracing is
    off, for host metrics (``shuffle_read_total_time``) that ride the
    same clock. With the category off/filtered and no counter, this
    degrades to plain iteration: zero per-item overhead."""
    record = category_enabled(cat)
    if not record and time_counter is None:
        yield from it
        return
    tr = _TRACER
    it = iter(it)
    start = tr.now_ns()
    produced_ns = 0
    n = 0
    try:
        while True:
            t0 = tr.now_ns()
            try:
                item = next(it)
            except StopIteration:
                produced_ns += tr.now_ns() - t0
                break
            produced_ns += tr.now_ns() - t0
            n += 1
            yield item
    finally:
        if time_counter is not None:
            time_counter.add(produced_ns)
        if record:
            complete_span(cat, name, start, produced_ns, items=n,
                          **attrs)


# ---------------------------------------------------------------------------
# layer spans and the per-task accumulator (module docstring)
# ---------------------------------------------------------------------------

#: ``layers_s`` keys a layer span charges (``compile`` comes from
#: :func:`on_compile`; the ledger adds ``other`` = wall − the rest)
_LAYER_KEY = {"plan": "plan", "scan": "scan_wait", "convert": "to_arrow",
              "exchange": "exchange"}
LAYER_KEYS = ("plan", "compile", "scan_wait", "op_host", "op_device_wait",
              "exchange", "to_arrow", "send")
#: ``layers_cpu_s`` keys: the layers a span charges (a compile's CPU is
#: not known apart from the span it fired in)
LAYER_CPU_KEYS = tuple(k for k in LAYER_KEYS if k != "compile")
#: one task in this many reads the CPU clock round its spans (a prime, so
#: that the timed tasks of a round-robin mix of 4 plans from 4 clients are
#: not all of one plan)
CPU_TIMED_EVERY = 7
_TASK_SEQ = itertools.count()
#: (layer, key) of the spans that wait by declaration — for the device,
#: the scan worker, the gang door, a scheduler slot: their CPU is ~0 by
#: construction, so they book none and do not read the CPU clock
DECLARED_WAITS = frozenset({("op", "readback"), ("scan", "wait"),
                            ("exchange", "gang_wait"),
                            ("serve", "queue")})
SCAN_WORKER_KEYS = ("decode", "encode", "h2d")
#: the exchange layer's spans: ``exchange_s`` splits ``layers_s.exchange``
#: by them (the last three only on the mesh route)
EXCHANGE_KEYS = ("materialize", "map_write", "broadcast_collect",
                 "gang_wait", "mesh_stack", "mesh_round")
COUNT_KEYS = ("program_calls", "readbacks",
              # row-count reads of a device batch (``obs/profile.
              # row_count``): syncs too, counted apart from the control
              # readbacks above
              "row_syncs", "d2h_bytes", "h2d_transfers",
              "h2d_bytes", "encode_pyloop_values",
              # a file scan's width: the columns it reads, and those of
              # its files it leaves unread (ir/pruning.py)
              "scan_columns_read", "scan_columns_pruned",
              # the mesh route of an exchange (0 on every other path):
              # completed all-to-all rounds, quota re-runs, live bytes
              # received, and the padded slot buffers that held them
              "mesh_rounds", "mesh_escalations", "mesh_bytes",
              "mesh_slot_bytes",
              # times the stage took the gang door (``MeshPlane.gang``,
              # not a re-entrant pass): one a round, quota re-runs inside
              "mesh_gang_acquires",
              # its read side: the batches the reducers were handed (one
              # a partition where its rows fit one), their live rows (a
              # host count the buffer holds: rows ÷ batches is the size
              # of what a reducer steps on), the bytes that a device_put
              # moved from another chip to the home chip, and the
              # non-empty (partition, source, round) slices merged into
              # those batches (slices ÷ batches: how often the merge
              # engages, 1.0 where a partition hears from one source)
              "mesh_read_batches", "mesh_read_rows", "mesh_home_bytes",
              "mesh_read_slices",
              # the general (unbounded-key) aggregation: batches folded
              # into the hash table's state and into the sort path's,
              # groups the keyed aggregations emitted, capacity
              # growths of either state mid-stream, and hash-table
              # overflows that latched the sort path; the latter under
              # a second name too, the one a client's fault rule reads
              # (a DONE frame with a nonzero "demot..." leaf is a failed
              # task to benchmark/run.py and the served smoke, as a
              # demoted exchange route is): the answer stays exact, the
              # task was not computed on the path its plan was given
              "agg_hash_batches", "agg_sort_batches", "agg_groups",
              "agg_state_grows", "agg_sort_fallbacks",
              "agg_demoted_to_sort",
              # reads of a shared subplan's result that found it held,
              # the producer having run for another parent (ops/reuse.py)
              "subplan_reuse_hits",
              # the ratio reports' operators (PR 42), each counted from a
              # row count the host reads anyway: rows into a window
              # program and the partitions it found there, rows that
              # left a program an expand ran in, and rows that left one
              # that divided decimal by decimal, once a division
              "window_rows", "window_partitions", "expand_rows_out",
              "decimal_div_rows",
              # a single-DISTINCT aggregate as Spark plans it (PR 44),
              # from row counts read anyway: the groups that left the
              # keyed partial_merge aggregation whose parent counts one
              # of its group columns (the distinct (keys, argument)
              # pairs), and the rows that left a join without keys
              "agg_distinct_groups", "cross_join_rows",
              # batches a filter (or the fused stage holding one) handed
              # on at a capacity cut to their live rows (PR 45:
              # ``ops/base.count_output(shrink=True)``, one
              # ``columnar.batch.shrink`` launch each), and the lanes
              # that went (capacity - target, summed)
              "batch_shrinks", "batch_shrink_lanes")

_ANNOTATION = None


def _annotation(name: str, attrs: dict):
    """A ``jax.profiler.TraceAnnotation`` (imported at first use: this
    module loads in processes that must stay off jax until they have
    chosen a platform). Outside a profiler session it costs under a
    microsecond."""
    global _ANNOTATION
    if _ANNOTATION is None:
        from jax.profiler import TraceAnnotation
        _ANNOTATION = TraceAnnotation
    return _ANNOTATION(name, **attrs) if attrs else _ANNOTATION(name)


class TaskAccumulator:
    """One served task's exclusive nanoseconds by layer, and its counts.

    Bound to the handler thread by :func:`task_scope` and to the scan
    prefetch workers the task starts by :func:`worker_scope`. The task
    thread writes its fields without a lock; a worker writes only the
    ``worker_*`` fields, under ``_lock`` (several workers of one task
    can be alive at once). :meth:`sealed` is what ``obs/ledger.build``
    folds into the ledger."""

    __slots__ = ("query_id", "cpu_timed", "queue_ns", "layers", "layers_cpu",
                 "exchange", "ops",
                 "counts", "calls_by_site", "layer_spans", "compiles",
                 "compile_ns",
                 "worker_ns", "worker_span_cpu_ns", "worker_counts",
                 "worker_spans",
                 "worker_cpu_ns", "worker_compiles", "worker_compile_ns",
                 "_cpu0", "_lock")

    def __init__(self, query_id: str = ""):
        self.query_id = query_id
        #: do this task's spans read the CPU clock (module docstring)
        self.cpu_timed = next(_TASK_SEQ) % CPU_TIMED_EVERY == 0
        self.queue_ns = 0
        self.layers = dict.fromkeys(LAYER_KEYS, 0)
        #: the same spans' CPU self time (thread CPU clock): what of a
        #: layer's wall the task's thread spent computing
        self.layers_cpu = dict.fromkeys(LAYER_CPU_KEYS, 0)
        self.exchange = dict.fromkeys(EXCHANGE_KEYS, 0)
        #: op name -> [host ns, device-wait ns, op spans, host CPU ns]
        self.ops: dict[str, list] = {}
        self.counts = dict.fromkeys(COUNT_KEYS, 0)
        self.calls_by_site: dict[str, int] = {}
        self.layer_spans = 0
        self.compiles = 0
        self.compile_ns = 0
        self.worker_ns = dict.fromkeys(SCAN_WORKER_KEYS, 0)
        self.worker_span_cpu_ns = dict.fromkeys(SCAN_WORKER_KEYS, 0)
        self.worker_counts = dict.fromkeys(COUNT_KEYS, 0)
        self.worker_spans = 0
        self.worker_cpu_ns = 0
        self.worker_compiles = 0
        self.worker_compile_ns = 0
        self._cpu0 = time.thread_time_ns()
        self._lock = threading.Lock()

    def start(self) -> None:
        """The task holds its slot: its CPU clock starts here, with the
        interval the ledger calls ``wall_s``."""
        self._cpu0 = time.thread_time_ns()

    def _charge(self, span: "_LayerSpan", self_ns: int,
                cpu_ns: int) -> None:
        """Book one closed layer span's self time, wall and CPU (task
        thread)."""
        self.layer_spans += 1
        layer = span.layer
        if layer == "op":
            up = span._up
            if span.key == "readback":
                self._op(up.key)[1] += self_ns
                key = "op_device_wait"
            else:
                ent = self._op(span.key)
                ent[0] += self_ns
                ent[2] += 1
                ent[3] += cpu_ns
                key = "op_host"
        elif layer == "serve":
            if span.key == "queue":
                self.queue_ns += self_ns
                return
            if span.key != "send":
                # serve/task is the root: what it alone covers is `other`
                return
            key = "send"
        else:
            key = _LAYER_KEY.get(layer)
            if key is None:
                return
            if layer == "exchange":
                self.exchange[span.key] = \
                    self.exchange.get(span.key, 0) + self_ns
        self.layers[key] += self_ns
        self.layers_cpu[key] += cpu_ns

    def _op(self, name: str) -> list:
        ent = self.ops.get(name)
        if ent is None:
            ent = self.ops[name] = [0, 0, 0, 0]
        return ent

    def _charge_worker(self, span: "_LayerSpan", self_ns: int,
                       cpu_ns: int) -> None:
        with self._lock:
            self.worker_spans += 1
            if span.layer == "scan" and span.key in self.worker_ns:
                self.worker_ns[span.key] += self_ns
                self.worker_span_cpu_ns[span.key] += cpu_ns

    def sealed(self, wall_s: float) -> dict:
        """The ledger's version-2 fields. ``layers_s.other`` is
        ``wall_s`` less every other key, so the keys sum to ``wall_s`` by
        construction and ``other`` is what no span covers yet."""
        with self._lock:
            worker_ns = dict(self.worker_ns)
            worker_span_cpu = dict(self.worker_span_cpu_ns)
            worker_counts = dict(self.worker_counts)
            worker_spans = self.worker_spans
            worker_cpu = self.worker_cpu_ns
            compiles = self.compiles + self.worker_compiles
            compile_ns = self.compile_ns + self.worker_compile_ns
        # (a key's CPU is cut at its wall here, key by key and not span
        # by span: a compile leaves its span's wall and not its CPU, and
        # where the CPU clock is coarse — 10 ms ticks on the chip's host
        # — a tick lands whole in a span a hundredth its size)
        timed = self.cpu_timed
        layers = {k: round(v * 1e-9, 6) for k, v in self.layers.items()}
        layers["compile"] = round(self.compile_ns * 1e-9, 6)
        layers["other"] = round(float(wall_s) - sum(layers.values()), 6)
        counts = {k: self.counts[k] + worker_counts[k] for k in COUNT_KEYS}
        counts["program_calls_by_site"] = dict(
            sorted(self.calls_by_site.items()))
        counts["layer_spans"] = self.layer_spans + worker_spans
        ops = {}
        for name, (h, d, n, c) in sorted(self.ops.items()):
            ops[name] = {"host_s": round(h * 1e-9, 6),
                         "device_wait_s": round(d * 1e-9, 6), "batches": n}
            if timed:
                ops[name]["cpu_s"] = round(min(c, h) * 1e-9, 6)
        return {
            "queue_s": round(self.queue_ns * 1e-9, 6),
            "layers_s": layers,
            # None in a task that was not CPU-timed
            "layers_cpu_s": {k: round(min(v, self.layers[k]) * 1e-9, 6)
                             for k, v in self.layers_cpu.items()}
            if timed else None,
            "exchange_s": {k: round(v * 1e-9, 6)
                           for k, v in self.exchange.items()},
            "ops_s": ops,
            "scan_worker_s": {k: round(v * 1e-9, 6)
                              for k, v in worker_ns.items()},
            "scan_worker_cpu_s": {k: round(min(v, worker_ns[k]) * 1e-9, 6)
                                  for k, v in worker_span_cpu.items()}
            if timed else None,
            "cpu_s": round((time.thread_time_ns() - self._cpu0
                            + worker_cpu) * 1e-9, 6),
            "counts": counts,
            "compile": {"task_xla_compiles": compiles,
                        "task_seconds": round(compile_ns * 1e-9, 4)},
        }


class _LayerSpan(_SpanCM):
    """A span at a layer boundary: annotation always, self time to the
    task's accumulator always, a recorded ``Span`` with tracing on."""

    __slots__ = ("layer", "key", "_record", "_ann", "_acc", "_worker",
                 "_up", "_child_ns", "_cpu", "_c0", "_child_cpu_ns")

    def __init__(self, layer, key, cat, name, attrs, record, max_spans):
        _SpanCM.__init__(self, cat, name, attrs, max_spans)
        self.layer = layer
        self.key = key
        self._record = record
        self._child_ns = 0
        self._child_cpu_ns = 0

    def __enter__(self):
        tr = _TRACER
        tls = tr._tls
        stack = tr._stack()
        up = None
        for s in reversed(stack):
            if s.layer is not None:
                up = s
                break
        self._up = up
        self._parent = stack[-1].span_id if stack else 0
        # an unrecorded layer span passes its parent's id through, so a
        # recorded child still links to the nearest recorded ancestor
        self.span_id = next(_SPAN_IDS) if self._record else self._parent
        stack.append(self)
        self._acc = acc = getattr(tls, "task", None)
        self._worker = getattr(tls, "worker", False)
        self._cpu = acc is not None and acc.cpu_timed \
            and (self.layer, self.key) not in DECLARED_WAITS
        self._ann = ann = _annotation(
            "auron:" + self.layer + "/" + self.key, self.attrs)
        ann.__enter__()
        # the CPU clock is read inside the wall clock's interval, so a
        # span's CPU never exceeds its duration
        self._t0 = tr.now_ns()
        self._c0 = time.thread_time_ns() if self._cpu else 0
        return self

    def __exit__(self, exc_type, exc, tb):
        tr = _TRACER
        cpu = time.thread_time_ns() - self._c0 if self._cpu else 0
        t0 = self._t0
        dur = tr.now_ns() - t0
        self._ann.__exit__(exc_type, exc, tb)
        self._pop(tr._stack())
        up = self._up
        acc = self._acc
        # a readback outside an operator (the to_arrow fence) stays in
        # the layer that made it: ops_s sums to op_host + op_device_wait
        own = not (self.key == "readback" and self.layer == "op"
                   and (up is None or up.layer != "op"))
        if acc is not None:
            self_ns = dur - self._child_ns
            cpu_ns = max(cpu - self._child_cpu_ns, 0)
            if self._worker:
                acc._charge_worker(self, self_ns, cpu_ns)
            elif own:
                acc._charge(self, self_ns, cpu_ns)
            else:
                acc.layer_spans += 1
        if own and up is not None:
            up._child_ns += dur
            up._child_cpu_ns += cpu
        if self._record:
            if exc_type is not None:
                self.attrs.setdefault("error", exc_type.__name__)
            _flight.tee(self.cat, self.name, self.attrs, dur_ns=dur)
            tr.record(Span(tr.current_trace, self.span_id, self._parent,
                           self.cat, self.name, t0, dur,
                           threading.get_ident(), self.attrs), self._max)
        return False


def layer_span(layer: str, key: str, *, cat: str = "layer",
               name: Optional[str] = None, **attrs):
    """Open the span of one layer boundary (module docstring). ``layer``
    and ``key`` are fixed strings of low cardinality — the annotation
    reads ``auron:<layer>/<key>``; ids and sizes go in ``attrs``.
    ``cat`` / ``name`` are what the recorded ``Span`` carries with
    tracing on (default: the ``layer`` category, ``<layer>.<key>``), so
    a boundary that already had a span keeps its name in the exports.

    Close it before a generator ``yield``: left open it would keep
    charging its layer while the consumer runs."""
    st = _settings()
    record = st.enabled and (st.events is None or cat in st.events)
    return _LayerSpan(layer, key, cat, name or layer + "." + key, attrs,
                      record, st.max_spans)


def current_task() -> Optional[TaskAccumulator]:
    return getattr(_TRACER._tls, "task", None)


class _TaskBinding:
    """Binds a :class:`TaskAccumulator` to the current thread (and, for
    a scan worker, adds the thread's CPU time when it leaves)."""

    __slots__ = ("acc", "_worker", "_saved")

    def __init__(self, acc, worker):
        self.acc = acc
        self._worker = worker

    def __enter__(self):
        tls = _TRACER._tls
        self._saved = (getattr(tls, "task", None),
                       getattr(tls, "worker", False))
        tls.task = self.acc
        tls.worker = self._worker
        return self.acc

    def __exit__(self, *exc):
        tls = _TRACER._tls
        tls.task, tls.worker = self._saved
        acc = self.acc
        if self._worker and acc is not None:
            # the worker thread exists for this scan alone: its CPU
            # clock since birth is what it spent on the task
            with acc._lock:
                acc.worker_cpu_ns += time.thread_time_ns()
        return False


def task_scope(query_id: str = "") -> _TaskBinding:
    """A fresh accumulator bound to this (handler) thread."""
    return _TaskBinding(TaskAccumulator(query_id), False)


def worker_scope(acc: Optional[TaskAccumulator]) -> _TaskBinding:
    """Bind a scan worker thread to the task that started it."""
    return _TaskBinding(acc, True)


def count(key: str, n: int = 1) -> None:
    """Add to one of the task's ``counts`` (no-op outside a task)."""
    tls = _TRACER._tls
    acc = getattr(tls, "task", None)
    if acc is None:
        return
    if getattr(tls, "worker", False):
        with acc._lock:
            acc.worker_counts[key] += n
    else:
        acc.counts[key] += n


def readback_span(counted_as: str = "readbacks") -> _LayerSpan:
    """The span of one explicit device -> host sync point
    (``auron:op/readback``), counted among the task's ``readbacks`` (a
    row-count read among its ``row_syncs``)."""
    count(counted_as)
    return layer_span("op", "readback")


def count_program_call(site: str) -> None:
    acc = getattr(_TRACER._tls, "task", None)
    if acc is not None:
        acc.counts["program_calls"] += 1
        by = acc.calls_by_site
        by[site] = by.get(site, 0) + 1


def on_compile(seconds: float) -> None:
    """One XLA compile or persistent-cache fetch finished on this
    thread (``utils/compile_stats`` listener): it is the task's, and on
    the task's own thread its seconds come out of whichever layer span
    they fell in (``layers_s.compile``)."""
    tls = _TRACER._tls
    acc = getattr(tls, "task", None)
    if acc is None:
        return
    ns = int(seconds * 1e9)
    if getattr(tls, "worker", False):
        with acc._lock:
            acc.worker_compiles += 1
            acc.worker_compile_ns += ns
        return
    acc.compiles += 1
    acc.compile_ns += ns
    stack = getattr(tls, "stack", None)
    if stack:
        for s in reversed(stack):
            if s.layer is not None:
                s._child_ns += ns
                break


class _QueryScope:
    """Top-level query scope: assigns the trace id, opens the root
    ``query.execute`` span, and exports/drops the trace when the
    OUTERMOST scope exits (nested Session.execute calls — host-fn
    children, scalar subqueries — join the enclosing trace)."""

    __slots__ = ("trace_id", "_span", "_outermost", "_entered",
                 "_label")

    def __init__(self, label: str):
        self._label = label
        self.trace_id = 0
        self._span = _NOOP
        self._outermost = False
        self._entered = False

    def __enter__(self):
        st = _settings()
        if not st.enabled:
            return self
        self._entered = True
        tr = _TRACER
        depth = getattr(tr._tls, "query_depth", 0)
        tr._tls.query_depth = depth + 1
        if depth == 0:
            self.trace_id = next(_TRACE_IDS)
            tr.set_trace(self.trace_id)
            self._outermost = True
        else:
            self.trace_id = tr.current_trace
        # the span itself may be a no-op (the 'query' category can be
        # filtered by auron.trace.events) — scope bookkeeping must not
        # depend on it, or depth would leak and the trace never export
        self._span = span("query", "query.execute", label=self._label)
        self._span.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb):
        self._span.__exit__(exc_type, exc, tb)
        if not self._entered:
            return False
        tr = _TRACER
        tr._tls.query_depth = max(getattr(tr._tls, "query_depth", 1) - 1,
                                  0)
        if self._outermost:
            # leave no stale trace id on the thread: spans recorded
            # BETWEEN queries (session init, watchdog probes) must not
            # tag themselves onto an already-exported trace
            tr.set_trace(0)
            st = _settings()
            if st.dir:
                # best-effort like every observability sink: an
                # unwritable trace dir must never discard the query
                # result computed inside the scope (or shadow the
                # query's own exception)
                try:
                    export_trace_dir(st.dir, self.trace_id)
                except Exception:
                    import logging
                    logging.getLogger(__name__).exception(
                        "trace export to %r failed", st.dir)
                finally:
                    tr.drop(self.trace_id)
        return False


def query_scope(label: str = "") -> _QueryScope:
    return _QueryScope(label)


# ---------------------------------------------------------------------------
# cross-process propagation (the serving wire protocol's TRACE frame)
# ---------------------------------------------------------------------------

def _span_line(s: Span, role: Optional[str] = None,
               pid: Optional[int] = None) -> dict:
    """One exported JSONL record: the span dict plus the cross-process
    alignment keys (role, pid, epoch wall-clock) the stitch tool needs
    — monotonic-only timestamps cannot be ordered across processes."""
    d = s.to_dict()
    d["role"] = role if role is not None else get_role()
    d["pid"] = pid if pid is not None else os.getpid()
    d["wall"] = round(_TRACER.epoch_wall + s.ts_ns * 1e-9, 6)
    return d


class _SpanSink:
    """Streaming per-role JSONL sink for one adopted wire trace
    (thread-local, installed by :class:`_WireScope`): every span the
    thread records is appended and flushed immediately, best-effort —
    a SIGKILLed replica leaves its partial spans on disk."""

    __slots__ = ("role", "pid", "_f")

    def __init__(self, path: str, role: str):
        self.role = role
        self.pid = os.getpid()
        self._f = open(path, "a")

    def write(self, s: Span) -> None:
        try:
            self._f.write(
                json.dumps(_span_line(s, self.role, self.pid),
                           default=str) + "\n")
            self._f.flush()
        except Exception:   # pragma: no cover - best-effort sink
            pass

    def close(self) -> None:
        try:
            self._f.close()
        except Exception:   # pragma: no cover
            pass


def wire_context() -> Optional[dict]:
    """The current thread's trace context for the wire (the TRACE
    frame payload): trace id, parent span id (current stack top) and
    the sender's role/pid. ``None`` when propagation or tracing is off
    or no trace is active — callers send no frame in that case, so the
    disabled wire is byte-identical to before."""
    st = _settings()
    if not st.enabled or not st.propagate:
        return None
    tr = _TRACER
    t = tr.current_trace
    if not t:
        return None
    stack = tr._stack()
    # inside an adopted wire scope the thread speaks AS that role (an
    # in-process router forwarding from a client process must stamp
    # role=router, or the stitcher resolves the parent span against
    # the wrong process group)
    role = getattr(tr._tls, "wire_role", None) or get_role()
    return {"trace": t, "parent": stack[-1].span_id if stack else 0,
            "role": role, "pid": os.getpid()}


class _WireScope:
    """Adopt an inbound wire trace context on this thread: take the
    remote trace id, pretend an outer query scope is open (so a nested
    ``query_scope`` JOINS the trace instead of minting a new id and
    exporting it), open a ``fleet.adopt`` span carrying the remote
    parent/role/pid (span ids are per-process counters, so the
    cross-process parent link must travel as attributes — the stitch
    tool resolves it), and, when ``auron.trace.dir`` is set, stream
    this thread's spans straight to ``trace_<id>_<role><pid>.jsonl``."""

    __slots__ = ("trace_id", "_ctx", "_role", "_span", "_saved",
                 "_sink", "_entered")

    def __init__(self, ctx: Optional[dict], role: Optional[str]):
        self._ctx = ctx if isinstance(ctx, dict) else None
        self._role = role
        self.trace_id = 0
        self._span = _NOOP
        self._sink = None
        self._entered = False

    def __enter__(self):
        st = _settings()
        try:
            trace_id = int((self._ctx or {}).get("trace") or 0)
        except (TypeError, ValueError):
            trace_id = 0
        if not st.enabled or not st.propagate or trace_id <= 0:
            return self
        tr = _TRACER
        tls = tr._tls
        self._entered = True
        self.trace_id = trace_id
        self._saved = (tr.current_trace,
                       getattr(tls, "query_depth", 0),
                       getattr(tls, "sink", None),
                       getattr(tls, "wire_role", None))
        tr.set_trace(trace_id)
        tls.query_depth = self._saved[1] + 1
        role = self._role or get_role()
        tls.wire_role = role
        if st.dir:
            try:
                os.makedirs(st.dir, exist_ok=True)
                path = os.path.join(
                    st.dir,
                    f"trace_{trace_id:08d}_{role}{os.getpid()}.jsonl")
                tls.sink = _SpanSink(path, role)
                self._sink = tls.sink
            except Exception:   # unwritable dir: record to the buffer
                tls.sink = self._saved[2]
        ctx = self._ctx or {}
        self._span = span("fleet", "fleet.adopt", role=role,
                          remote_parent=ctx.get("parent") or 0,
                          remote_role=ctx.get("role") or "",
                          remote_pid=ctx.get("pid") or 0)
        self._span.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb):
        if not self._entered:
            return False
        # close the adopt span BEFORE restoring the sink: its record
        # must land in the adopted trace's file, not the local buffer
        self._span.__exit__(exc_type, exc, tb)
        tr = _TRACER
        tls = tr._tls
        tr.set_trace(self._saved[0])
        tls.query_depth = self._saved[1]
        tls.sink = self._saved[2]
        tls.wire_role = self._saved[3]
        if self._sink is not None:
            self._sink.close()
        return False


def wire_scope(ctx: Optional[dict], role: Optional[str] = None) -> _WireScope:
    """Adopt ``ctx`` (a :func:`wire_context` dict off the wire) for the
    duration of the scope. A ``None``/invalid context, tracing off, or
    propagation off all degrade to a no-op scope."""
    return _WireScope(ctx, role)


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------

def chrome_trace(spans: list[Span]) -> dict:
    """Chrome-trace JSON object (Perfetto / chrome://tracing loadable):
    complete ('ph': 'X') events with microsecond ts/dur."""
    pid = os.getpid()
    events = []
    for s in spans:
        events.append({
            "name": s.name, "cat": s.cat, "ph": "X",
            "ts": s.ts_ns / 1000.0, "dur": s.dur_ns / 1000.0,
            "pid": pid, "tid": s.tid,
            "args": dict(s.attrs, trace=s.trace_id, span=s.span_id,
                         parent=s.parent_id),
        })
    return {"traceEvents": events, "displayTimeUnit": "ms",
            "otherData": {"dropped_spans": _TRACER.dropped,
                          "epoch_wall": _TRACER.epoch_wall}}


def export_chrome(path: str, trace_id: Optional[int] = None,
                  spans: Optional[list] = None) -> int:
    """Write a Chrome-trace JSON file; returns the span count.
    ``spans`` skips the merge for callers that already snapshotted."""
    if spans is None:
        spans = _TRACER.spans(trace_id)
    tmp = path + ".part"
    with open(tmp, "w") as f:
        json.dump(chrome_trace(spans), f)
    os.replace(tmp, path)
    return len(spans)


def export_jsonl(path: str, trace_id: Optional[int] = None,
                 spans: Optional[list] = None) -> int:
    """Write the JSONL event log (one span per line, timeline order);
    returns the span count. ``spans`` as in :func:`export_chrome`."""
    if spans is None:
        spans = _TRACER.spans(trace_id)
    tmp = path + ".part"
    with open(tmp, "w") as f:
        for s in spans:
            f.write(json.dumps(_span_line(s), default=str) + "\n")
    os.replace(tmp, path)
    return len(spans)


def read_jsonl(path: str) -> list[Span]:
    """Load a JSONL event log back into Span records (trace_report).
    Malformed lines are skipped — a SIGKILLed process's streamed sink
    file may end mid-write, and the intact prefix is the evidence."""
    return [Span.from_dict(d) for d in read_jsonl_raw(path)]


def read_jsonl_raw(path: str) -> list[dict]:
    """The JSONL event log as raw dicts, keeping the cross-process keys
    (role/pid/wall) that :class:`Span` does not model — the stitch
    renderer's loader. Skips malformed/truncated lines like
    :func:`read_jsonl`."""
    out = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                d = json.loads(line)
            except ValueError:
                continue
            if isinstance(d, dict) and "span" in d:
                out.append(d)
    return out


def export_trace_dir(trace_dir: str, trace_id: int) -> tuple[str, str]:
    """Per-query export into ``auron.trace.dir``: Chrome trace + JSONL,
    named by trace id. Returns the two paths."""
    os.makedirs(trace_dir, exist_ok=True)
    chrome = os.path.join(trace_dir, f"trace_{trace_id:08d}.json")
    jsonl = os.path.join(trace_dir, f"trace_{trace_id:08d}.jsonl")
    spans = _TRACER.spans(trace_id)   # one merge+sort for both files
    export_chrome(chrome, spans=spans)
    export_jsonl(jsonl, spans=spans)
    return chrome, jsonl
