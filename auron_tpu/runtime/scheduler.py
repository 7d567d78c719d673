"""Concurrent query scheduler: admission control, bounded run queue,
weighted-round-robin task fairness, overload shedding.

Everything below Session assumed one query at a time; "millions of
users" (ROADMAP [serving]) means a Session — and the serving engine
process — must multiplex. This module is the control plane that makes
that safe:

- **Admission control.** ``acquire`` is the single door every top-level
  query enters through (Session.execute, the AuronServer handler). At
  most ``auron.sched.max_concurrent`` queries RUN; up to
  ``auron.sched.queue_depth`` more wait in the bounded run queue; past
  that — or when a registry signal breaches its threshold (queue-wait
  p99, memmgr used/budget ratio) — the query is rejected FAST with the
  classified ``errors.AdmissionRejected`` (transient, ``retry_after_s``
  hint). Rejection happens before any executor, memmgr consumer or
  durable-tier artifact exists, so shedding is free.

- **Queue-time lifecycle.** A queued query's CancelToken stays live:
  a serving CANCEL frame, a client disconnect, ``session.cancel``,
  ``Session.close`` ("session-closed") or the deadline expiring while
  queued all DEQUEUE it without ever starting — the waiting loop polls
  the token and unwinds with its classified verdict (QueryCancelled /
  DeadlineExceeded), never spinning up a runtime for a dead query.

- **Fair task scheduling.** Running queries interleave at TASK
  granularity by weighted round-robin: before each task the driver
  calls ``Slot.task_turn``, which lets a query proceed only while it is
  within one virtual-time unit of the most-behind running query (a
  task advances virtual time by 1/weight, so heavier queries run more
  tasks per round) — fair queueing with the cheapest possible bookkeeping
  (one lock + compare per task; the most-behind slot NEVER waits, so
  some thread always progresses). A solo query takes the uncontended
  fast path, measured by the perf-gate smoke's concurrency-tax gate
  (< 2%).

- **Nested executes inherit.** A host-fn child or scalar subquery runs
  on the thread of a query that already HOLDS a slot; queueing it
  behind the parent would deadlock the pair (parent waits for child,
  child waits for parent's slot). Session.execute therefore enters the
  scheduler only for top-level queries — nested ones ride the enclosing
  token (and its slot), so one admission covers the whole tree.

Observability: every decision lands on the process registry
(``auron_sched_{admitted,rejected,dequeued}_total``, running/queued
gauges, the ``auron_sched_queue_wait_seconds`` histogram that feeds the
queue-wait admission signal back) and the ``sched`` trace category
(``sched.admit`` / ``sched.reject`` / ``sched.dequeue`` events), and
the scheduler keeps registry-independent internal counters so
``tools/load_report.py`` prints the same table with telemetry off.
"""

from __future__ import annotations

import threading
import time
import weakref
from typing import Optional

#: every live scheduler, weakly held — the scrape-time source of the
#: running/queued gauges (obs/registry._collect_runtime sums states BY
#: NAME across live schedulers; per-change gauge sets would collide
#: last-writer-wins when several Sessions share the "session" name)
_SCHEDULERS: "weakref.WeakSet" = weakref.WeakSet()


def aggregate_states() -> dict:
    """{scheduler name: {"running": n, "queued": n}} summed across the
    process's live schedulers."""
    out: dict = {}
    for s in list(_SCHEDULERS):
        with s._cond:
            r, q = len(s._running), len(s._queued)
        ent = out.setdefault(s.name, {"running": 0, "queued": 0})
        ent["running"] += r
        ent["queued"] += q
    return out


def aggregate_query_table() -> list[dict]:
    """Live query table across every scheduler in the process — the ops
    plane's ``/queries`` body and the serving STATS frame's table."""
    rows: list[dict] = []
    for s in list(_SCHEDULERS):
        rows.extend(s.query_table())
    rows.sort(key=lambda r: (r["scheduler"], r["query"]))
    return rows


class Slot:
    """One admitted query's seat in the scheduler.

    Carries the fairness state (``tasks_run`` / ``weight`` — virtual
    time is their ratio) and the scheduler-overhead ledger the
    concurrency-tax gate reads (``overhead_ns``: time spent inside
    acquire + every task_turn + release, NOT time spent waiting for
    fairness or a queue slot — the tax is the bookkeeping, the waits
    are the policy)."""

    __slots__ = ("scheduler", "token", "query_id", "weight", "tasks_run",
                 "vbase", "queue_wait_s", "overhead_ns", "granted",
                 "released", "granted_at")

    def __init__(self, scheduler: "QueryScheduler", token, weight: float):
        self.scheduler = scheduler
        self.token = token
        self.query_id = getattr(token, "query_id", "") or ""
        # graft: disable=GL001 -- a constructor argument, host data
        self.weight = max(float(weight), 1e-6)
        self.tasks_run = 0
        #: virtual-time origin, set at GRANT to the current minimum
        #: vtime of the running slots (start-time fair queueing): a
        #: newcomer joins the round in progress instead of at zero,
        #: which would stall every established query until it catches
        #: up on their whole task history
        self.vbase = 0.0
        self.queue_wait_s = 0.0
        self.overhead_ns = 0
        self.granted = False
        self.released = False
        #: monotonic stamp of the grant — the /queries table's
        #: wall-so-far origin (0.0 until seated)
        self.granted_at = 0.0

    @property
    def vtime(self) -> float:
        """Weighted virtual time: origin at admission + tasks run per
        unit weight. The WRR invariant is vtime(any running slot) <
        min(vtime) + 1."""
        return self.vbase + self.tasks_run / self.weight

    def task_turn(self) -> None:
        """Block until this query may start its next task (weighted
        round-robin across the running queries); raises the token's
        classified error when cancelled while waiting."""
        self.scheduler.task_turn(self)

    def release(self) -> None:
        self.scheduler.release(self)


#: poll granularity of queue/turn waits: a cancel or promotion lands
#: within one tick (condition notify usually wakes sooner)
_WAIT_POLL_S = 0.05

#: age window of queue-wait samples feeding the ADMISSION signal: the
#: p99 that sheds new arrivals must describe the RECENT queue, not a
#: burst an hour ago — without the window the signal latches (a tripped
#: threshold blocks the queued admissions that would refresh the ring,
#: so a stale p99 rejects over-capacity arrivals forever)
_WAIT_SIGNAL_WINDOW_S = 30.0

#: work-conserving bound on one fairness wait: a leader parks at most
#: this long for a laggard to advance, then takes its turn anyway.
#: Without the cap, a laggard stuck inside ONE long task (vtime only
#: advances at task START) would freeze min_v and idle-block every
#: other running query for the task's full duration — head-of-line
#: blocking that costs more throughput than the fairness it buys. With
#: it, heterogeneous workloads lose at most this much per turn to
#: fairness, while homogeneous short-task queries still interleave
#: tightly (their laggards advance within the window).
_TURN_WAIT_CAP_S = 2.0


class QueryScheduler:
    """One Session's (or one serving process's) query-admission plane."""

    def __init__(self, name: str = "session", mem_manager=None,
                 config=None):
        self.name = name
        #: admission memory signal source (auron.sched.admit.mem_ratio);
        #: attach_mem_manager late-binds it for sessions built before
        #: their manager
        self.mem_manager = mem_manager
        #: knob source: the owning Session's config when given (its
        #: auron.sched.* overrides are honored — scheduler state is
        #: per-Session),
        #: else the process config (the serving process)
        self.config = config
        # RLock-backed: admission helpers (_reject, _retry_after_s) run
        # under the condition from inside acquire's critical section
        self._cond = threading.Condition(threading.RLock())
        self._running: list[Slot] = []
        self._queued: list[Slot] = []
        #: registry-independent counters (tools/load_report.py reads
        #: these via stats() so the table works with telemetry off)
        self._counts = {"admitted": 0, "rejected": 0, "dequeued": 0}
        self._reject_reasons: dict[str, int] = {}
        self._dequeue_reasons: dict[str, int] = {}
        #: recent queue waits as (monotonic stamp, seconds) — the local
        #: p50/p99 source for the admission signal (age-windowed) and
        #: the retry-after hint; the registry histogram mirrors it for
        #: scrapes
        self._waits: list[tuple[float, float]] = []
        #: scheduler bookkeeping cost of the most recently RELEASED
        #: slot — the perf-gate smoke's concurrency-tax numerator
        self.last_overhead_ns = 0
        _SCHEDULERS.add(self)

    # -- admission -----------------------------------------------------------

    def attach_mem_manager(self, mem_manager) -> None:
        if mem_manager is not None:
            self.mem_manager = mem_manager

    def _conf(self):
        from auron_tpu import config as cfg
        return self.config if self.config is not None else cfg.get_config()

    def _knobs(self) -> tuple[int, int]:
        from auron_tpu import config as cfg
        conf = self._conf()
        # graft: disable=GL001 -- configuration values, host data
        return (max(int(conf.get(cfg.SCHED_MAX_CONCURRENT)), 1),
                # graft: disable=GL001 -- configuration values, host data
                max(int(conf.get(cfg.SCHED_QUEUE_DEPTH)), 0))

    def _queue_wait_p(self, p: float,
                      window_s: Optional[float] = None) -> float:
        """Observed queue-wait percentile; ``window_s`` restricts the
        sample to the last N seconds (the admission signal's recency
        contract) AND folds in the ages of the queries queued RIGHT NOW
        — under sustained saturation nothing is granted, so completed
        samples alone would read 0.0 exactly when the signal must shed.
        None uses every retained completed sample (stats/hints)."""
        now = time.monotonic()
        cutoff = now - window_s if window_s is not None else None
        with self._cond:
            waits = [w for t, w in self._waits
                     if cutoff is None or t >= cutoff]
            if window_s is not None:
                # queue_wait_s holds the ENQUEUE stamp until grant
                waits += [now - s.queue_wait_s for s in self._queued]
        if not waits:
            return 0.0
        waits.sort()
        # graft: disable=GL001 -- host arithmetic on python numbers
        idx = min(int(p * len(waits)), len(waits) - 1)
        return waits[idx]

    def _retry_after_s(self) -> float:
        """Caller backoff hint: roughly one median queue-wait per
        occupant ahead, floored so a cold scheduler still spreads
        resubmissions instead of answering 'now'."""
        with self._cond:
            backlog = len(self._queued) + len(self._running)
        p50 = self._queue_wait_p(0.50)
        return round(max(p50, 0.05) * max(backlog, 1), 3)

    def _reject(self, reason: str, detail: str):
        from auron_tpu import errors
        from auron_tpu.obs import trace
        hint = self._retry_after_s()
        with self._cond:
            self._counts["rejected"] += 1
            self._reject_reasons[reason] = \
                self._reject_reasons.get(reason, 0) + 1
        trace.event("sched", "sched.reject", scheduler=self.name,
                    reason=reason, retry_after_s=hint)
        self._observe(lambda r: r.counter(
            "auron_sched_rejected_total", reason=reason).inc())
        raise errors.AdmissionRejected(
            f"query admission rejected ({reason}): {detail}; "
            f"retry after ~{hint}s", reason=reason, retry_after_s=hint,
            site="sched.admit")

    def acquire(self, token, weight: float = 1.0) -> Slot:
        """Admit one top-level query: returns its granted Slot, raises
        ``AdmissionRejected`` (shed) or the token's classified error
        (cancelled/deadline while queued). The caller MUST release the
        slot in a finally."""
        from auron_tpu.obs import trace
        from auron_tpu.runtime import faults
        t0 = time.perf_counter_ns()
        slot = Slot(self, token, weight)
        # the sched.admit chaos site: a seeded deny sheds this query as
        # if a threshold were breached — overload behavior on demand
        if faults.fires("sched.admit", "deny"):
            self._reject("injected", "injected sched.admit deny")
        # memory signal: checked for EVERY arrival (a free slot does
        # not make an exhausted budget admissible)
        self._check_memory_signal()
        queued = False
        with self._cond:
            while not slot.granted:
                maxc, depth = self._knobs()
                if not queued:
                    if len(self._running) < maxc and not self._queued:
                        self._grant_locked(slot)
                        break
                    # would queue: hard depth bound, then the observed
                    # queue-latency signal
                    if len(self._queued) >= depth:
                        self._reject(
                            "queue_full",
                            f"{len(self._running)} running, "
                            f"{len(self._queued)}/{depth} queued")
                    limit = self._admit_wait_limit()
                    if limit > 0:
                        p99 = self._queue_wait_p(
                            0.99, window_s=_WAIT_SIGNAL_WINDOW_S)
                        if p99 > limit:
                            self._reject(
                                "queue_wait",
                                f"queue-wait p99 {p99:.3f}s > "
                                f"{limit:.3f}s (last "
                                f"{_WAIT_SIGNAL_WINDOW_S:.0f}s)")
                    queued = True
                    slot.queue_wait_s = time.monotonic()   # t-enqueue
                    self._queued.append(slot)
                elif self._queued and self._queued[0] is slot \
                        and len(self._running) < maxc:
                    # FIFO self-promotion (covers capacity freed by a
                    # knob change between releases)
                    self._queued.pop(0)
                    self._grant_locked(slot)
                    break
                # park: promotion (release) or cancellation wakes us
                slot.overhead_ns += time.perf_counter_ns() - t0
                self._cond.wait(_WAIT_POLL_S)
                t0 = time.perf_counter_ns()
                if not slot.granted and token is not None \
                        and token.is_set():
                    # dequeued without ever starting: the queued-cancel
                    # contract (serving CANCEL/disconnect, deadline,
                    # session close)
                    if slot in self._queued:
                        self._queued.remove(slot)
                    reason = getattr(token, "reason", None) or "cancelled"
                    self._counts["dequeued"] += 1
                    self._dequeue_reasons[reason] = \
                        self._dequeue_reasons.get(reason, 0) + 1
                    trace.event("sched", "sched.dequeue",
                                scheduler=self.name, reason=reason,
                                query=slot.query_id)
                    self._observe(lambda r: r.counter(
                        "auron_sched_dequeued_total", reason=reason).inc())
                    token.raise_for_status()
                    raise AssertionError(   # pragma: no cover - above
                        "cancelled token did not raise")
            if queued:
                slot.queue_wait_s = time.monotonic() - slot.queue_wait_s
                self._waits.append((time.monotonic(),
                                    slot.queue_wait_s))
                if len(self._waits) > 256:
                    del self._waits[:-256]
            else:
                slot.queue_wait_s = 0.0
            self._counts["admitted"] += 1
        slot.overhead_ns += time.perf_counter_ns() - t0
        trace.event("sched", "sched.admit", scheduler=self.name,
                    query=slot.query_id,
                    queue_wait_s=round(slot.queue_wait_s, 4))
        self._observe(self._admit_observation(slot))
        return slot

    def _grant_locked(self, slot: Slot) -> None:
        """Seat a slot (caller holds the condition lock): start-time
        fair queueing — the newcomer's virtual clock begins at the
        running round's minimum, so admission neither stalls the
        established queries nor grants the newcomer their history."""
        slot.vbase = (min(s.vtime for s in self._running)
                      if self._running else 0.0)
        slot.granted = True
        slot.granted_at = time.monotonic()
        self._running.append(slot)

    def _admit_wait_limit(self) -> float:
        from auron_tpu import config as cfg
        # graft: disable=GL001 -- a configuration value, host data
        return float(self._conf().get(cfg.SCHED_ADMIT_QUEUE_WAIT_P99_S))

    def _check_memory_signal(self) -> None:
        from auron_tpu import config as cfg
        # graft: disable=GL001 -- a configuration value, host data
        ratio_limit = float(self._conf().get(cfg.SCHED_ADMIT_MEM_RATIO))
        if ratio_limit <= 0:
            return
        mm = self.mem_manager
        if mm is None:
            # the knob is ARMED but this scheduler has no manager to
            # read (Session built without mem_manager, or the serving
            # process which runs managerless): say so ONCE instead of
            # silently admitting into the pressure the knob exists to
            # reject
            if not getattr(self, "_warned_no_mm", False):
                self._warned_no_mm = True
                import logging
                logging.getLogger("auron_tpu").warning(
                    "auron.sched.admit.mem_ratio=%s is set but scheduler "
                    "%r has no attached MemManager — the memory admission "
                    "signal is DISARMED (pass mem_manager= to Session, or "
                    "attach_mem_manager())", ratio_limit, self.name)
            return
        try:
            used, total = mm.used_total, mm.total
        except Exception:   # pragma: no cover - duck-typed manager
            return
        if total > 0 and used / total > ratio_limit:
            self._reject("memory",
                         f"memmgr used/budget {used}/{total} = "
                         f"{used / total:.2f} > {ratio_limit:.2f}")

    @staticmethod
    def _admit_observation(slot: Slot):
        def observe(r):
            r.counter("auron_sched_admitted_total").inc()
            r.histogram("auron_sched_queue_wait_seconds").observe(
                slot.queue_wait_s)
        return observe

    # -- fairness ------------------------------------------------------------

    def task_turn(self, slot: Slot) -> None:
        """Weighted round-robin gate, called by the driver before each
        task: proceed while within ONE VIRTUAL-TIME UNIT of the
        most-behind RUNNING query (each task advances a query's virtual
        time by 1/weight, so a weight-2 query runs two tasks per round);
        otherwise wait for the laggard to advance (or finish). The
        most-behind slot never waits, so some thread always progresses;
        and every wait is capped at ``_TURN_WAIT_CAP_S`` so a laggard
        wedged inside one long task cannot idle-block its neighbors
        (work conservation beats strict fairness past the cap). Raises
        the token's classified error on cancel/deadline — fairness
        waits must not outlive the query."""
        token = slot.token
        t0 = time.perf_counter_ns()
        wait_deadline = None
        with self._cond:
            while len(self._running) > 1 and slot in self._running:
                min_v = min(s.vtime for s in self._running)
                if slot.vtime < min_v + 1.0 - 1e-9:
                    break
                now = time.monotonic()
                if wait_deadline is None:
                    wait_deadline = now + _TURN_WAIT_CAP_S
                elif now >= wait_deadline:
                    break       # work-conserving: stop paying for the laggard
                slot.overhead_ns += time.perf_counter_ns() - t0
                self._cond.wait(_WAIT_POLL_S)
                t0 = time.perf_counter_ns()
                if token is not None and token.is_set():
                    token.raise_for_status()
            slot.tasks_run += 1
            # my vtime rose: wake waiters whose window may have moved
            # (they recompute; spurious wakes cost one compare each)
            self._cond.notify_all()
        slot.overhead_ns += time.perf_counter_ns() - t0

    # -- release / drain -----------------------------------------------------

    def release(self, slot: Slot) -> None:
        """Return a granted slot and promote the queue head into the
        freed capacity. Idempotent (close paths race the normal
        finally)."""
        t0 = time.perf_counter_ns()
        with self._cond:
            if slot.released:
                return
            slot.released = True
            if slot in self._running:
                self._running.remove(slot)
            maxc, _depth = self._knobs()
            while self._queued and len(self._running) < maxc:
                head = self._queued[0]
                tok = head.token
                if tok is not None and tok.is_set():
                    # cancelled/deadline while queued: NEVER grant a
                    # dead query (the 'dequeued without ever starting'
                    # contract). Pop it; the dequeue accounting and the
                    # classified raise happen on its own acquire
                    # thread's next poll.
                    self._queued.pop(0)
                    continue
                self._queued.pop(0)
                self._grant_locked(head)
            self._cond.notify_all()
        slot.overhead_ns += time.perf_counter_ns() - t0
        self.last_overhead_ns = slot.overhead_ns

    def drain(self, reason: str = "session-closed") -> None:
        """Deterministic shutdown order (Session.close): cancel QUEUED
        queries first — their waiting acquires dequeue without ever
        starting — then the running tokens. Cancellation stays
        cooperative; the caller waits for unwind as before."""
        with self._cond:
            queued = list(self._queued)
            running = list(self._running)
        for s in queued:
            if s.token is not None:
                s.token.cancel(reason)
        for s in running:
            if s.token is not None:
                s.token.cancel(reason)
        with self._cond:
            self._cond.notify_all()

    # -- introspection -------------------------------------------------------

    def stats(self) -> dict:
        """Registry-independent counter snapshot (load_report's table)."""
        with self._cond:
            out = {
                "admitted": self._counts["admitted"],
                "rejected": self._counts["rejected"],
                "rejected_by_reason": dict(self._reject_reasons),
                "dequeued": self._counts["dequeued"],
                "dequeued_by_reason": dict(self._dequeue_reasons),
                "running": len(self._running),
                "queued": len(self._queued),
                "queue_wait_p50_s": round(self._queue_wait_p(0.50), 4),
                "queue_wait_p99_s": round(self._queue_wait_p(0.99), 4),
            }
        # gang-slot accounting: a sharded round occupies the WHOLE mesh
        # (one slot = the mesh — parallel/mesh.MeshPlane.gang takes this
        # scheduler's WRR turn on entry, so fairness operates BETWEEN
        # sharded rounds); surfaced here so load/mesh reports show the
        # mesh occupancy next to the query-slot numbers. The plane's
        # stats also carry its FAULT DOMAIN ledger (quarantined devices,
        # usable width, demotions by reason, straggler/device-loss
        # counts) — an operator reading the scheduler surface sees a
        # degraded mesh, not just a slow one
        try:
            from auron_tpu.parallel import mesh as _mesh
            plane = _mesh.current_plane()
            if plane is not None:
                out["mesh_gang"] = plane.stats()
        except Exception:   # pragma: no cover  # graft: disable=GL004 -- gang stats are best-effort
            pass
        return out

    def query_table(self) -> list[dict]:
        """The live query table (the ops plane's ``/queries`` rows):
        one row per running/queued slot — query id, state, wall so far,
        driver task progress (the token's collect-loop counters),
        per-query memory usage vs quota (the attached manager's
        ledger), and the query's program-cache builds/hits. Reads are
        lock-bounded snapshots; a row is internally consistent but the
        table is not a transaction across queries (scrape semantics)."""
        now = time.monotonic()
        with self._cond:
            seats = ([("running", s) for s in self._running]
                     + [("queued", s) for s in self._queued])
            rows = []
            for state, s in seats:
                tok = s.token
                wall = (now - s.granted_at if state == "running"
                        # queue_wait_s holds the ENQUEUE stamp until
                        # the slot is granted (acquire's contract)
                        else now - s.queue_wait_s)
                rows.append({
                    "query": s.query_id,
                    "scheduler": self.name,
                    "state": state,
                    "wall_s": round(max(wall, 0.0), 3),
                    "tasks_run": s.tasks_run,
                    "tasks_done": getattr(tok, "tasks_done", 0),
                    "tasks_total": getattr(tok, "tasks_total", 0),
                })
        mm = self.mem_manager
        for row in rows:
            if mm is not None:
                try:
                    row["mem_used_bytes"] = mm.query_used(row["query"])
                    row["mem_quota_bytes"] = mm.query_quota()
                except Exception:   # pragma: no cover  # graft: disable=GL004 -- duck-typed mm; the live table renders without memory columns
                    pass
            try:
                from auron_tpu.runtime import programs
                snap = programs.query_totals(row["query"])
                row["program_builds"] = snap.builds
                row["program_hits"] = snap.hits
            except Exception:   # pragma: no cover  # graft: disable=GL004 -- program-ledger stats are best-effort
                pass
        return rows

    def running_count(self) -> int:
        with self._cond:
            return len(self._running)

    def queued_count(self) -> int:
        with self._cond:
            return len(self._queued)

    @staticmethod
    def _observe(fn) -> None:
        """Apply ``fn`` to the process registry when enabled;
        best-effort — telemetry must never fail an admission decision."""
        try:
            from auron_tpu.obs import registry as obs_registry
            if not obs_registry.enabled():
                return
            fn(obs_registry.get_registry())
        except Exception:   # pragma: no cover  # graft: disable=GL004 -- registry telemetry is best-effort by contract
            pass


def turn(cancel_token) -> None:
    """Driver-side fairness hook (runtime/executor.collect): take the
    query's task turn when its token carries a scheduler slot; a bare
    token / direct collect() call costs one getattr."""
    slot = getattr(cancel_token, "slot", None)
    if slot is not None:
        slot.task_turn()
