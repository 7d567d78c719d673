"""Process-wide metrics registry: counters, gauges, fixed-bucket
histograms, Prometheus text exposition.

Per-task metrics (ops/base.MetricsSet) die with their ExecContext; this
registry is the process-lifetime aggregate the scrape surface reads —
the role the reference's pprof/metrics HTTP endpoints play
(auron/src/http/mod.rs:25-108). The executor feeds it one observation
per finished task (gated by ``auron.metrics.registry``): task seconds,
retries, recovery counters, spill volume. ``render_prometheus`` emits
the standard text format and additionally collects live totals from the
runtime singletons (program-cache builds/hits per site, backend
compiles, injected faults, watchdog fallbacks) so a scrape needs no
separate wiring per subsystem.

Histograms are fixed-bucket (Prometheus-shaped: cumulative ``le``
buckets + ``_sum``/``_count``) with p50/p95/p99 estimation by linear
interpolation inside the bucket — exact enough for dashboards, O(1)
memory, no reservoir.

The exposition is trace_salt-aware: ``auron_info`` carries the current
``config.trace_salt()`` so a scraper can correlate metric shifts with
trace-semantic config flips (the same salt that partitions every
program-cache key, runtime/programs.py).
"""

from __future__ import annotations

import re
import threading
from typing import Optional

#: default latency buckets (seconds): 1ms .. 2min, roughly log-spaced
DEFAULT_BUCKETS = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
                   0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0)


def _label_key(labels: dict) -> tuple:
    return tuple(sorted(labels.items()))


def escape_label(v) -> str:
    """Prometheus text-format label-value escaping (exposition format
    spec): backslash, double-quote and newline — in THAT order, or the
    escapes themselves get re-escaped."""
    return (str(v).replace("\\", "\\\\").replace('"', '\\"')
            .replace("\n", "\\n"))


def _fmt_labels(labels: tuple) -> str:
    if not labels:
        return ""
    inner = ",".join(f'{k}="{escape_label(v)}"' for k, v in labels)
    return "{" + inner + "}"


class Counter:
    """Monotonic counter."""

    def __init__(self, name: str, labels: tuple):
        self.name = name
        self.labels = labels
        self._lock = threading.Lock()
        self.value = 0.0

    def inc(self, v: float = 1.0) -> None:
        with self._lock:
            self.value += v

    def expose(self) -> list[str]:
        return [f"{self.name}{_fmt_labels(self.labels)} {self.value:g}"]


class Gauge:
    """Last-write-wins value."""

    def __init__(self, name: str, labels: tuple):
        self.name = name
        self.labels = labels
        self._lock = threading.Lock()
        self.value = 0.0

    def set(self, v: float) -> None:
        with self._lock:
            self.value = float(v)

    def inc(self, v: float = 1.0) -> None:
        with self._lock:
            self.value += v

    def expose(self) -> list[str]:
        return [f"{self.name}{_fmt_labels(self.labels)} {self.value:g}"]


class Histogram:
    """Fixed cumulative-bucket histogram with percentile estimation."""

    def __init__(self, name: str, labels: tuple,
                 buckets: Optional[tuple] = None):
        self.name = name
        self.labels = labels
        self.buckets = tuple(sorted(buckets or DEFAULT_BUCKETS))
        self._lock = threading.Lock()
        #: per-bucket NON-cumulative counts; [-1] is the +Inf overflow
        self.counts = [0] * (len(self.buckets) + 1)
        self.sum = 0.0
        self.count = 0

    def observe(self, v: float) -> None:
        with self._lock:
            self.sum += v
            self.count += 1
            for i, b in enumerate(self.buckets):
                if v <= b:
                    self.counts[i] += 1
                    return
            self.counts[-1] += 1

    def percentile(self, p: float) -> float:
        """Estimate the p-quantile (p in [0, 1]) by linear interpolation
        inside the bucket holding the target rank; the overflow bucket
        answers with the largest finite bound (a floor, honestly)."""
        with self._lock:
            total = self.count
            if total == 0:
                return 0.0
            rank = p * total
            cum = 0.0
            lo = 0.0
            for i, b in enumerate(self.buckets):
                c = self.counts[i]
                if cum + c >= rank and c > 0:
                    frac = (rank - cum) / c
                    return lo + (b - lo) * min(max(frac, 0.0), 1.0)
                cum += c
                lo = b
            return self.buckets[-1]

    def expose(self) -> list[str]:
        base = dict(self.labels)
        out = []
        cum = 0
        with self._lock:
            for i, b in enumerate(self.buckets):
                cum += self.counts[i]
                lab = _label_key(dict(base, le=f"{b:g}"))
                out.append(f"{self.name}_bucket{_fmt_labels(lab)} {cum}")
            cum += self.counts[-1]
            lab = _label_key(dict(base, le="+Inf"))
            out.append(f"{self.name}_bucket{_fmt_labels(lab)} {cum}")
            out.append(f"{self.name}_sum{_fmt_labels(self.labels)} "
                       f"{self.sum:g}")
            out.append(f"{self.name}_count{_fmt_labels(self.labels)} "
                       f"{self.count}")
        return out


class MetricsRegistry:
    """Name+labels → instrument store; one per process (get_registry)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._instruments: dict[tuple, object] = {}
        self._types: dict[str, str] = {}

    def _get(self, cls, typ: str, name: str, labels: dict, **kw):
        key = (name, _label_key(labels))
        with self._lock:
            prev = self._types.setdefault(name, typ)
            if prev != typ:
                raise TypeError(
                    f"metric {name!r} already registered as {prev}")
            inst = self._instruments.get(key)
            if inst is None:
                inst = cls(name, _label_key(labels), **kw)
                self._instruments[key] = inst
            return inst

    def counter(self, name: str, **labels) -> Counter:
        return self._get(Counter, "counter", name, labels)

    def gauge(self, name: str, **labels) -> Gauge:
        return self._get(Gauge, "gauge", name, labels)

    def histogram(self, name: str, buckets: Optional[tuple] = None,
                  **labels) -> Histogram:
        return self._get(Histogram, "histogram", name, labels,
                         buckets=buckets)

    def snapshot(self) -> dict:
        """{name{labels}: value | {sum, count, p50, p95, p99}}."""
        with self._lock:
            items = list(self._instruments.items())
        out = {}
        for (name, labels), inst in items:
            key = f"{name}{_fmt_labels(labels)}"
            if isinstance(inst, Histogram):
                out[key] = {"sum": inst.sum, "count": inst.count,
                            "p50": inst.percentile(0.50),
                            "p95": inst.percentile(0.95),
                            "p99": inst.percentile(0.99)}
            else:
                out[key] = inst.value
        return out

    def render_prometheus(self) -> str:
        """Prometheus text exposition: registered instruments plus live
        totals collected from the runtime singletons. Conformance
        contract (pinned by tests/test_metrics_registry.py): exactly one
        ``# HELP`` and one ``# TYPE`` line per metric family, emitted
        before the family's first sample; label values escaped; a
        histogram's ``+Inf`` bucket equals its ``_count``."""
        with self._lock:
            items = sorted(self._instruments.items(),
                           key=lambda kv: kv[0])
            types = dict(self._types)
        lines = []
        seen = set()
        for (name, _labels), inst in items:
            if name not in seen:
                lines.append(f"# HELP {name} {_help_text(name)}")
                lines.append(f"# TYPE {name} {types[name]}")
                seen.add(name)
            lines.extend(inst.expose())
        for name, typ, samples in _collect_runtime():
            if name in seen:   # registered instruments own the family
                continue
            seen.add(name)
            lines.append(f"# HELP {name} {_help_text(name)}")
            lines.append(f"# TYPE {name} {typ}")
            lines.extend(samples)
        return "\n".join(lines) + "\n"

    def reset(self) -> None:
        with self._lock:
            self._instruments.clear()
            self._types.clear()


#: HELP text per metric family — the exposition's one-HELP-per-family
#: conformance line; unknown families fall back to a generic string so
#: a new metric can never break a scrape by missing an entry here.
_HELP = {
    "auron_info": "Build/config identity (trace_salt label).",
    "auron_program_builds_total": "Program-cache builds per compile site.",
    "auron_program_hits_total": "Program-cache hits per compile site.",
    "auron_program_live": "Live compiled programs per compile site.",
    "auron_backend_compiles_total": "Raw XLA backend compiles.",
    "auron_backend_compile_seconds_total": "Seconds spent in XLA compiles.",
    "auron_faults_injected_total": "Chaos-plane fault injections.",
    "auron_watchdog_stalls_total": "Task stalls flagged by the watchdog.",
    "auron_trace_dropped_spans": "Spans dropped past auron.trace.max_spans.",
    "auron_sched_running": "Queries running, per scheduler.",
    "auron_sched_queued": "Queries queued, per scheduler.",
    "auron_tasks_total": "Finished tasks observed by the registry.",
    "auron_task_seconds": "Per-task wall seconds.",
    "auron_task_retries_total": "Transient task retries.",
    "auron_corruption_recomputes_total":
        "Map recomputes after checksum mismatches.",
    "auron_spill_runs_total": "Spill runs written.",
    "auron_spill_bytes_total": "Bytes spilled.",
    "auron_output_rows_total": "Rows produced by finished tasks.",
    "auron_query_duration_seconds":
        "End-to-end per-query latency by outcome "
        "(ok|shed|cancelled|failed) — the SLO-burn source.",
    "auron_bundles_written_total": "Post-mortem bundles written.",
    "auron_flight_events": "Events currently buffered by the recorder.",
    "auron_ops_scrapes_total": "Ops-endpoint requests served, per path.",
    "auron_cache_hits_total": "Warm-path cache hits, per plane.",
    "auron_cache_misses_total": "Warm-path cache misses, per plane.",
    "auron_cache_evictions_total":
        "Warm-path cache evictions (capacity LRU + memmgr pressure).",
    "auron_cache_inserts_total": "Warm-path cache inserts.",
    "auron_cache_bytes": "Bytes held by the warm-path cache.",
    "auron_cache_entries": "Entries held by the warm-path cache.",
    "auron_aot_warmed": "Plans warmed by the last AOT startup pass.",
    "auron_aot_errors": "Errors in the last AOT startup pass.",
    "auron_fleet_routed_total":
        "Fleet router submissions routed, per replica and pick reason.",
    "auron_fleet_spillover_total":
        "Fleet router spill-over retries after a replica shed.",
    "auron_fleet_shed_total":
        "Fleet-wide sheds surfaced to the client (every replica shed).",
    "auron_fleet_failover_total":
        "Fleet failovers per replica and action (resume|reexecute).",
    "auron_fleet_failover_seconds":
        "Fleet failover latency: replica-death detect to recovery done.",
    "auron_fleet_replica_deaths_total":
        "Liveness-confirmed replica deaths recorded by the router.",
    "auron_fleet_guard_shared_total":
        "Failover re-executions answered from the single-flight guard.",
    "auron_fleet_errors_forwarded_total":
        "Replica ERROR frames the router forwarded to clients.",
    "auron_fleet_replica_up":
        "Replica reachability as seen by the router (1 up, 0 down).",
}


def _help_text(name: str) -> str:
    return _HELP.get(name, "auron runtime metric.")


def _collect_runtime() -> list[tuple]:
    """Live totals from the runtime singletons — collected at scrape
    time so subsystems need no push wiring. Best-effort: a missing
    module never fails the exposition. Returns ``(family name, type,
    [sample lines])`` so the renderer can keep the one-HELP/TYPE-per-
    family conformance contract."""
    fams: list[tuple] = []

    def lab(**labels) -> str:
        return _fmt_labels(_label_key(labels))

    try:
        from auron_tpu import config as cfg
        salt = ",".join(str(v) for v in cfg.trace_salt())
        fams.append(("auron_info", "gauge",
                     [f"auron_info{lab(trace_salt=salt)} 1"]))
    except Exception:
        pass
    try:
        from auron_tpu.runtime import programs
        builds, hits, live = [], [], []
        for site, st in sorted(programs.snapshot().items()):
            builds.append(f"auron_program_builds_total{lab(site=site)} "
                          f"{st['builds']}")
            hits.append(f"auron_program_hits_total{lab(site=site)} "
                        f"{st['hits']}")
            live.append(f"auron_program_live{lab(site=site)} "
                        f"{st['live']}")
        fams.append(("auron_program_builds_total", "counter", builds))
        fams.append(("auron_program_hits_total", "counter", hits))
        fams.append(("auron_program_live", "gauge", live))
    except Exception:
        pass
    try:
        from auron_tpu.utils import compile_stats
        snap = compile_stats.snapshot()
        fams.append(("auron_backend_compiles_total", "counter",
                     [f"auron_backend_compiles_total {snap.count}"]))
        fams.append(("auron_backend_compile_seconds_total", "counter",
                     [f"auron_backend_compile_seconds_total "
                      f"{snap.seconds:g}"]))
    except Exception:
        pass
    try:
        from auron_tpu.runtime import faults
        fams.append(("auron_faults_injected_total", "counter",
                     [f"auron_faults_injected_total {faults.totals()}"]))
    except Exception:
        pass
    try:
        from auron_tpu.runtime import watchdog
        fams.append(("auron_watchdog_stalls_total", "counter",
                     [f"auron_watchdog_stalls_total "
                      f"{watchdog.stall_totals()}"]))
    except Exception:
        pass
    try:
        from auron_tpu.obs import trace
        fams.append(("auron_trace_dropped_spans", "counter",
                     [f"auron_trace_dropped_spans "
                      f"{trace.tracer().dropped}"]))
    except Exception:
        pass
    try:
        # scheduler occupancy collected LIVE and summed by name across
        # every scheduler in the process: several Sessions share the
        # "session" name, and per-change gauge sets from each would
        # overwrite one another last-writer-wins
        from auron_tpu.runtime import scheduler
        states = scheduler.aggregate_states()
        if states:
            running, queued = [], []
            for name, st in sorted(states.items()):
                running.append(f"auron_sched_running"
                               f"{lab(scheduler=name)} {st['running']}")
                queued.append(f"auron_sched_queued"
                              f"{lab(scheduler=name)} {st['queued']}")
            fams.append(("auron_sched_running", "gauge", running))
            fams.append(("auron_sched_queued", "gauge", queued))
    except Exception:
        pass
    try:
        from auron_tpu.cache import result_cache as _rcache
        rc = _rcache.get_cache().stats()
        fams.append(("auron_cache_hits_total", "counter", [
            f"auron_cache_hits_total{lab(plane='result')} {rc['hits']}",
            f"auron_cache_hits_total{lab(plane='subplan')} "
            f"{rc['subplan_hits']}"]))
        fams.append(("auron_cache_misses_total", "counter", [
            f"auron_cache_misses_total{lab(plane='result')} "
            f"{rc['misses']}",
            f"auron_cache_misses_total{lab(plane='subplan')} "
            f"{rc['subplan_misses']}"]))
        fams.append(("auron_cache_evictions_total", "counter",
                     [f"auron_cache_evictions_total {rc['evictions']}"]))
        fams.append(("auron_cache_inserts_total", "counter",
                     [f"auron_cache_inserts_total {rc['inserts']}"]))
        fams.append(("auron_cache_bytes", "gauge",
                     [f"auron_cache_bytes {rc['bytes']}"]))
        fams.append(("auron_cache_entries", "gauge",
                     [f"auron_cache_entries {rc['entries']}"]))
    except Exception:
        pass
    try:
        from auron_tpu.cache import aot as _aot
        a = _aot.last_stats()
        fams.append(("auron_aot_warmed", "gauge",
                     [f"auron_aot_warmed {a['warmed']}"]))
        fams.append(("auron_aot_errors", "gauge",
                     [f"auron_aot_errors {len(a['errors'])}"]))
    except Exception:
        pass
    return fams


_REGISTRY = MetricsRegistry()


def get_registry() -> MetricsRegistry:
    return _REGISTRY


#: (config epoch, enabled) verdict cache — per-task feeding checks this
_CACHED: tuple[int, Optional[bool]] = (-1, None)


def enabled() -> bool:
    global _CACHED
    from auron_tpu import config as cfg
    epoch, val = _CACHED
    if epoch == cfg.config_epoch() and val is not None:
        return val
    epoch = cfg.config_epoch()
    val = cfg.get_config().get(cfg.METRICS_REGISTRY)
    _CACHED = (epoch, val)
    return val


def observe_memmgr(status: dict) -> None:
    """Mirror one MemManager.status() snapshot onto registry gauges —
    called by the manager on every ``update_mem_used`` / spill decision
    (gated by auron.metrics.registry), so the HBM/DRAM tier pressure the
    paper's memory manager arbitrates is scrapeable live:

    - ``auron_memmgr_budget_bytes`` / ``auron_memmgr_used_bytes`` /
      ``auron_memmgr_consumers`` / ``auron_memmgr_fair_share_bytes``
    - ``auron_memmgr_spills_total`` / ``auron_memmgr_spilled_bytes_total``
      (monotonic manager totals, exposed last-write-wins so a scrape
      between managers never double-counts)
    - ``auron_memmgr_consumer_bytes{consumer=...}`` per registered
      consumer. A consumer absent from a later snapshot keeps its last
      value (gauges are last-write-wins, not reaped); cardinality is
      bounded by the set of consumer NAMES, which are stable per
      operator class, not per instance.
    """
    if not enabled():
        return
    r = _REGISTRY
    r.gauge("auron_memmgr_budget_bytes").set(status["total"])
    r.gauge("auron_memmgr_used_bytes").set(status["used"])
    r.gauge("auron_memmgr_consumers").set(status["num_consumers"])
    r.gauge("auron_memmgr_fair_share_bytes").set(
        status.get("fair_share", 0))
    r.gauge("auron_memmgr_spills_total").set(status["num_spills"])
    r.gauge("auron_memmgr_spilled_bytes_total").set(
        status["spilled_bytes"])
    for name, used in status.get("consumers", {}).items():
        r.gauge("auron_memmgr_consumer_bytes", consumer=name).set(used)


def observe_task(wall_s: float, snap: dict, output_rows: int = 0) -> None:
    """One finished task's observation: called by the retry driver with
    the task's metrics snapshot (gated by auron.metrics.registry)."""
    if not enabled():
        return
    r = _REGISTRY
    r.counter("auron_tasks_total").inc()
    r.histogram("auron_task_seconds").observe(wall_s)
    rec = snap.get("recovery") or {}
    r.counter("auron_task_retries_total").inc(
        rec.get("transient_retries", 0))
    r.counter("auron_corruption_recomputes_total").inc(
        rec.get("corruption_recomputes", 0))
    spill_count = spill_bytes = 0
    for vals in snap.values():
        if isinstance(vals, dict):
            spill_count += vals.get("mem_spill_count", 0)
            spill_bytes += vals.get("mem_spill_size", 0)
    r.counter("auron_spill_runs_total").inc(spill_count)
    r.counter("auron_spill_bytes_total").inc(spill_bytes)
    r.counter("auron_output_rows_total").inc(output_rows)


# ---------------------------------------------------------------------------
# per-query SLO surface (the ops plane's /metrics acceptance metric)
# ---------------------------------------------------------------------------

def classify_outcome(exc) -> str:
    """Map a query's terminal exception onto the
    ``auron_query_duration_seconds`` outcome vocabulary:

    - ``ok`` — no exception;
    - ``shed`` — the runtime refused/evicted the query to protect the
      process (MemoryExhausted, AdmissionRejected);
    - ``cancelled`` — the caller's verdict (QueryCancelled, including
      DeadlineExceeded: the budget was the caller's) or a serving
      task-kill (TaskCancelled);
    - ``failed`` — everything else.
    """
    if exc is None:
        return "ok"
    from auron_tpu import errors
    if isinstance(exc, (errors.MemoryExhausted, errors.AdmissionRejected)):
        return "shed"
    if isinstance(exc, errors.QueryCancelled):
        return "cancelled"
    if type(exc).__name__ in ("TaskCancelled", "_Cancelled"):
        return "cancelled"
    return "failed"


def observe_query(duration_s: float, outcome: str,
                  served_from: Optional[str] = None) -> None:
    """One top-level query's end-to-end latency observation, labelled by
    outcome — fed by Session's admission scope and the serving handler,
    so SLO burn is computable from ``/metrics`` alone (gated by
    auron.metrics.registry). ``served_from="cache"`` distinguishes
    warm-path answers (auron_tpu/cache) from executed ones, so cached
    hits can't silently flatter the executed-latency SLO."""
    if not enabled():
        return
    labels = {"outcome": outcome}
    if served_from:
        labels["served_from"] = served_from
    _REGISTRY.histogram("auron_query_duration_seconds",
                        **labels).observe(duration_s)


# ---------------------------------------------------------------------------
# strict text-format parser (conformance audit + ops-plane gates)
# ---------------------------------------------------------------------------

_NAME_RE = r"[a-zA-Z_:][a-zA-Z0-9_:]*"
_SAMPLE_RE = re.compile(
    rf"^({_NAME_RE})(\{{.*\}})? "
    r"(-?(?:[0-9]*\.?[0-9]+(?:[eE][+-]?[0-9]+)?|Inf|NaN)|\+Inf)$")
_LABEL_RE = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')
_VALID_TYPES = ("counter", "gauge", "histogram", "summary", "untyped")


def _unescape_label(v: str) -> str:
    """Single left-to-right scan: sequential str.replace would corrupt
    values where an escaped backslash precedes an 'n' (``\\\\n`` must
    read as backslash+n, not newline)."""
    out = []
    i = 0
    while i < len(v):
        c = v[i]
        if c == "\\" and i + 1 < len(v):
            n = v[i + 1]
            if n == "n":
                out.append("\n")
                i += 2
                continue
            if n in ('"', "\\"):
                out.append(n)
                i += 2
                continue
        out.append(c)
        i += 1
    return "".join(out)


def _parse_labels(body: str) -> dict:
    """Strict ``{k="v",...}`` parse: every byte must be consumed by
    well-formed pairs (a malformed pair silently dropped is exactly the
    torn-table bug the audit exists to catch)."""
    inner = body[1:-1].rstrip(",")
    if not inner:
        return {}
    out = {}
    pos = 0
    while pos < len(inner):
        m = _LABEL_RE.match(inner, pos)
        if m is None:
            raise ValueError(f"malformed label pair at {inner[pos:]!r}")
        out[m.group(1)] = _unescape_label(m.group(2))
        pos = m.end()
        if pos < len(inner):
            if inner[pos] != ",":
                raise ValueError(f"expected ',' at {inner[pos:]!r}")
            pos += 1
    return out


def render_federated(local_text: str, replica_texts: list) -> str:
    """Fleet-scope /metrics: merge this process's exposition with each
    replica's scraped exposition, every replica sample re-labeled
    ``replica="rN"`` — the router's one-scrape-path contract.

    Both inputs and the output go through :func:`parse_prometheus`
    strictness: the local text is parsed STRICTLY (we rendered it — a
    violation is a bug), while an unparseable replica text (a replica
    dying mid-scrape, a version skew) drops THAT replica's samples
    rather than failing the whole federation. ``replica_texts`` is
    ``[(label, exposition_text), ...]``.

    The merged text is conformant by construction: one HELP/TYPE per
    family before its first sample, and every histogram series is
    distinguished by the ``replica`` label, so each keeps its own
    +Inf==_count invariant.
    """
    fams: dict[str, dict] = {}

    def fold(parsed: dict, label) -> None:
        for fam, info in parsed.items():
            ent = fams.get(fam)
            if ent is None:
                ent = fams[fam] = {"type": info["type"],
                                   "help": info["help"] or "",
                                   "samples": []}
            elif ent["type"] != info["type"]:
                continue   # version-skewed family: first writer owns it
            for name, labels, value in info["samples"]:
                if label is not None:
                    labels = dict(labels, replica=label)
                ent["samples"].append((name, labels, value))

    fold(parse_prometheus(local_text), None)
    for label, text in replica_texts:
        try:
            fold(parse_prometheus(text), label)
        except ValueError:
            continue
    lines = []
    for fam in sorted(fams):
        ent = fams[fam]
        lines.append(f"# HELP {fam} {ent['help'] or _help_text(fam)}")
        lines.append(f"# TYPE {fam} {ent['type']}")
        for name, labels, value in ent["samples"]:
            lines.append(
                f"{name}{_fmt_labels(_label_key(labels))} {value:g}")
    return "\n".join(lines) + "\n"


def parse_prometheus(text: str) -> dict:
    """STRICT Prometheus text-format parser — the conformance oracle the
    regression tests and the perf-gate ops arm scrape through. Raises
    ``ValueError`` on any violation of the contract render_prometheus
    promises:

    - every non-comment line is a well-formed sample (name, optional
      escaped label set, float value);
    - exactly one ``# HELP`` and one ``# TYPE`` per family, before the
      family's first sample;
    - every sample belongs to a declared family (histogram samples via
      their ``_bucket``/``_sum``/``_count`` suffixes);
    - per histogram series: the ``+Inf`` bucket exists, equals
      ``_count``, and bucket counts are monotone in ``le``.

    Returns ``{family: {"type", "help", "samples": [(name, labels,
    value)]}}``.
    """
    fams: dict[str, dict] = {}

    def family_of(name: str) -> Optional[str]:
        if name in fams:
            return name
        for suffix in ("_bucket", "_sum", "_count"):
            if name.endswith(suffix):
                base = name[: -len(suffix)]
                if base in fams and fams[base]["type"] == "histogram":
                    return base
        return None

    for lineno, line in enumerate(text.splitlines(), 1):
        if not line.strip():
            continue
        if line.startswith("#"):
            parts = line.split(" ", 3)
            if len(parts) < 3 or parts[1] not in ("HELP", "TYPE"):
                raise ValueError(f"line {lineno}: malformed comment "
                                 f"{line!r}")
            kind, name = parts[1], parts[2]
            if not re.fullmatch(_NAME_RE, name):
                raise ValueError(f"line {lineno}: bad metric name "
                                 f"{name!r}")
            ent = fams.setdefault(
                name, {"type": None, "help": None, "samples": []})
            if kind == "HELP":
                if ent["help"] is not None:
                    raise ValueError(
                        f"line {lineno}: duplicate HELP for {name}")
                if ent["samples"]:
                    raise ValueError(
                        f"line {lineno}: HELP for {name} after samples")
                ent["help"] = parts[3] if len(parts) > 3 else ""
            else:
                if ent["type"] is not None:
                    raise ValueError(
                        f"line {lineno}: duplicate TYPE for {name}")
                if ent["samples"]:
                    raise ValueError(
                        f"line {lineno}: TYPE for {name} after samples")
                typ = parts[3].strip() if len(parts) > 3 else ""
                if typ not in _VALID_TYPES:
                    raise ValueError(
                        f"line {lineno}: invalid type {typ!r} for {name}")
                ent["type"] = typ
            continue
        m = _SAMPLE_RE.match(line)
        if m is None:
            raise ValueError(f"line {lineno}: malformed sample {line!r}")
        name, labels_body, raw = m.group(1), m.group(2), m.group(3)
        fam = family_of(name)
        if fam is None:
            raise ValueError(
                f"line {lineno}: sample {name!r} has no declared family")
        if fams[fam]["type"] is None:
            raise ValueError(
                f"line {lineno}: sample {name!r} before its TYPE")
        labels = _parse_labels(labels_body) if labels_body else {}
        value = float(raw.replace("+Inf", "inf").replace("Inf", "inf")
                      .replace("NaN", "nan"))
        fams[fam]["samples"].append((name, labels, value))
    for name, ent in fams.items():
        if ent["type"] is None:
            raise ValueError(f"family {name}: HELP without TYPE")
        if ent["help"] is None:
            raise ValueError(f"family {name}: TYPE without HELP")
        if ent["type"] == "histogram":
            _check_histogram(name, ent["samples"])
    return fams


def _check_histogram(fam: str, samples: list) -> None:
    """Per-series histogram invariants: +Inf bucket present and equal to
    _count; cumulative bucket counts monotone in le."""
    series: dict[tuple, dict] = {}
    for name, labels, value in samples:
        key = _label_key({k: v for k, v in labels.items() if k != "le"})
        ent = series.setdefault(key, {"buckets": [], "count": None})
        if name == fam + "_bucket":
            if "le" not in labels:
                raise ValueError(f"{fam}: bucket sample without le")
            ent["buckets"].append((float(labels["le"]
                                         .replace("+Inf", "inf")), value))
        elif name == fam + "_count":
            ent["count"] = value
    for key, ent in series.items():
        if ent["count"] is None and not ent["buckets"]:
            continue
        buckets = sorted(ent["buckets"])
        if not buckets or buckets[-1][0] != float("inf"):
            raise ValueError(f"{fam}{dict(key)}: no +Inf bucket")
        if ent["count"] is None:
            raise ValueError(f"{fam}{dict(key)}: buckets without _count")
        if buckets[-1][1] != ent["count"]:
            raise ValueError(
                f"{fam}{dict(key)}: +Inf bucket {buckets[-1][1]} != "
                f"_count {ent['count']}")
        prev = 0.0
        for le, v in buckets:
            if v < prev:
                raise ValueError(
                    f"{fam}{dict(key)}: bucket le={le} count {v} < "
                    f"previous {prev} (not cumulative)")
            prev = v
