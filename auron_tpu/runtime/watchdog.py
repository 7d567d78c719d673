"""Task stall watchdog: the heartbeat plane and the mesh round guard.

Executor and shuffle/spill loops beat a per-attempt TaskHeartbeat
through ExecContext.checkpoint(site); a monitor thread flags any task
silent past auron.watchdog.stall_timeout_s, emits a structured
StallReport (task identity, last heartbeat site, driving thread's
stack) into auron.trace.dir, and sets the heartbeat's ``stalled`` flag —
the next cooperative poll raises the classified ``errors.TaskStalled``,
which the retry driver treats as transient ONCE. A truly wedged native
call never polls again; the report is then the diagnosis and the query
deadline remains the hard bound.

Nothing here probes, bounds or replaces the BACKEND: a process runs on
the platform jax gives it, and a backend that cannot initialize or
compile fails the process with jax's own error.
"""

from __future__ import annotations

import json
import logging
import threading
from collections import deque
from dataclasses import dataclass, field
from typing import Optional

logger = logging.getLogger("auron_tpu")

_LOCK = threading.Lock()
_STATS = {"stalls": 0, "mesh_rounds_forgiven": 0}


def stats() -> dict:
    with _LOCK:
        return dict(_STATS)


def stall_totals() -> int:
    """Monotonic process-level stall-detection count (registry +
    chaos-report surface)."""
    with _LOCK:
        return _STATS["stalls"]


def _count(key: str) -> None:
    with _LOCK:
        _STATS[key] += 1


#: bump when StallReport.to_dict() keys change
STALL_SCHEMA_VERSION = 1


@dataclass
class TaskHeartbeat:
    """One task attempt's liveness record. ``beat`` is the hot path —
    two attribute stores, no lock (torn reads merely skew the stall
    estimate by one beat)."""

    task_id: int = 0
    stage_id: int = 0
    partition_id: int = 0
    attempt: int = 0
    #: stall timeout RESOLVED AT REGISTRATION from the registering
    #: task's config (a session-scoped knob must arm detection for its
    #: own tasks even when the process-global default is 0)
    timeout_s: float = 0.0
    last_site: str = ""
    last_beat_ns: int = 0
    started_ns: int = 0
    #: set by the monitor; the task's next checkpoint raises TaskStalled
    stalled: bool = False
    stalled_at_ns: int = 0
    thread_ident: Optional[int] = None

    def beat(self, site: str = "") -> None:
        self.last_beat_ns = _now_ns()
        if site:
            self.last_site = site

    def silent_s(self) -> float:
        return (_now_ns() - self.last_beat_ns) * 1e-9


@dataclass
class StallReport:
    """Structured stall diagnosis written next to the traces
    (``stall_report_<task>.json``): which task went silent, where its
    last heartbeat came from, and what the driving thread was doing when
    the monitor caught it."""

    task_id: int
    stage_id: int
    partition_id: int
    attempt: int
    last_site: str
    silent_s: float
    stall_timeout_s: float
    thread_stack: list = field(default_factory=list)
    schema_version: int = STALL_SCHEMA_VERSION

    def to_dict(self) -> dict:
        return {"schema_version": self.schema_version,
                "task_id": self.task_id, "stage_id": self.stage_id,
                "partition_id": self.partition_id, "attempt": self.attempt,
                "last_site": self.last_site,
                "silent_s": round(self.silent_s, 3),
                "stall_timeout_s": self.stall_timeout_s,
                "thread_stack": self.thread_stack}

    def to_json(self) -> str:
        return json.dumps(self.to_dict())


def _now_ns() -> int:
    import time
    return time.monotonic_ns()


_HB_LOCK = threading.Lock()
_HEARTBEATS: dict[int, TaskHeartbeat] = {}
_MONITOR: Optional[threading.Thread] = None


def stall_timeout_s(config=None) -> float:
    from auron_tpu import config as cfg
    conf = config if config is not None else cfg.get_config()
    # graft: disable=GL001 -- a configuration value, host data
    return float(conf.get(cfg.WATCHDOG_STALL_TIMEOUT_S))


def register_heartbeat(task_id: int = 0, stage_id: int = 0,
                       partition_id: int = 0, attempt: int = 0,
                       config=None) -> Optional[TaskHeartbeat]:
    """Register one task attempt with the stall monitor; returns None
    when the plane is disarmed (``auron.watchdog.stall_timeout_s`` <= 0)
    so the disarmed path costs one config read per attempt. Starts the
    monitor thread lazily on the first armed registration."""
    timeout = stall_timeout_s(config)
    if timeout <= 0:
        return None
    hb = TaskHeartbeat(task_id=task_id, stage_id=stage_id,
                       partition_id=partition_id, attempt=attempt,
                       timeout_s=timeout, started_ns=_now_ns(),
                       thread_ident=threading.get_ident())
    hb.beat("task.start")
    with _HB_LOCK:
        _HEARTBEATS[id(hb)] = hb
        _ensure_monitor_locked()
    return hb


def unregister_heartbeat(hb: Optional[TaskHeartbeat]) -> None:
    if hb is None:
        return
    with _HB_LOCK:
        _HEARTBEATS.pop(id(hb), None)


def live_heartbeats() -> int:
    with _HB_LOCK:
        return len(_HEARTBEATS)


def _ensure_monitor_locked() -> None:
    """Start the monitor thread if none is running (caller holds
    _HB_LOCK). The thread exits when the registry drains, so an idle
    process carries no watchdog thread."""
    global _MONITOR
    if _MONITOR is not None and _MONITOR.is_alive():
        return
    _MONITOR = threading.Thread(target=_monitor_loop, daemon=True,
                                name="auron-stall-watchdog")
    _MONITOR.start()


def _monitor_loop() -> None:
    import time
    last_compiles = -1
    poll = 0.25
    while True:
        time.sleep(poll)
        with _HB_LOCK:
            if not _HEARTBEATS:
                return          # registry drained: thread retires
            beats = list(_HEARTBEATS.values())
        # each heartbeat carries ITS OWN timeout (resolved from the
        # registering task's config — a session-scoped knob must work
        # with the global default at 0); poll at a quarter of the
        # tightest live timeout so detection latency stays bounded by
        # timeout + poll <= 1.25 x timeout, inside the 2x gate
        tightest = min(hb.timeout_s for hb in beats)
        poll = max(min(tightest / 4.0, 1.0), 0.01)
        # compile-aware: an XLA backend compile runs ON the driving
        # thread with no chance to beat — when compiles completed since
        # the last poll, credit every live task with a beat so a slow
        # first-compile is never misread as a stall (a single compile
        # LONGER than the timeout still flags: size the knob above the
        # platform's worst single-program compile time)
        try:
            from auron_tpu.utils import compile_stats
            n = compile_stats.snapshot().count
        except Exception:   # pragma: no cover
            n = last_compiles
        if n != last_compiles:
            if last_compiles >= 0:
                for hb in beats:
                    if not hb.stalled:
                        hb.beat("xla.compile")
            last_compiles = n
            continue
        for hb in beats:
            if not hb.stalled and hb.silent_s() > hb.timeout_s:
                _flag_stalled(hb, hb.timeout_s)


def _flag_stalled(hb: TaskHeartbeat, timeout: float) -> None:
    """One stall verdict: count it, put it on the timeline, persist the
    StallReport, THEN set the flag (the report must exist before the
    task can observe the flag and unwind past its trace scope)."""
    report = StallReport(
        task_id=hb.task_id, stage_id=hb.stage_id,
        partition_id=hb.partition_id, attempt=hb.attempt,
        last_site=hb.last_site, silent_s=hb.silent_s(),
        stall_timeout_s=timeout,
        thread_stack=_thread_stack(hb.thread_ident))
    _count("stalls")
    logger.error(
        "stall watchdog: task %d (stage %d, partition %d, attempt %d) "
        "silent %.2fs past the last heartbeat at %r — flagging TaskStalled",
        hb.task_id, hb.stage_id, hb.partition_id, hb.attempt,
        report.silent_s, hb.last_site)
    try:
        from auron_tpu.obs import trace
        trace.event("watchdog", "watchdog.stall", task=hb.task_id,
                    stage=hb.stage_id, partition=hb.partition_id,
                    attempt=hb.attempt, last_site=hb.last_site,
                    silent_s=round(report.silent_s, 3))
    except Exception:   # pragma: no cover  # graft: disable=GL004 -- stall-event tee is best-effort; the StallReport is the verdict
        pass
    try:
        from auron_tpu.obs import registry as obs_registry
        if obs_registry.enabled():
            obs_registry.get_registry().counter(
                "auron_stall_detections_total").inc()
    except Exception:   # pragma: no cover  # graft: disable=GL004 -- counter tee is best-effort; the StallReport is the verdict
        pass
    write_stall_report(report)
    hb.stalled_at_ns = _now_ns()
    hb.stalled = True


def _thread_stack(ident: Optional[int]) -> list:
    """The driving thread's current stack (frames innermost-last), the
    StallReport's 'what was it doing' payload. Best-effort."""
    if ident is None:
        return []
    import sys
    import traceback
    try:
        frame = sys._current_frames().get(ident)
        if frame is None:
            return []
        return [f"{f.filename}:{f.lineno} {f.name}"
                for f in traceback.extract_stack(frame)][-20:]
    except Exception:   # pragma: no cover
        return []


def write_stall_report(report: StallReport,
                       dir_path: Optional[str] = None) -> Optional[str]:
    """Persist a StallReport as ``stall_report_<task>.json`` next to the
    traces (``auron.trace.dir``); returns the path, or None when no
    directory is configured. Best-effort — a diagnosis must never
    become a failure of its own."""
    import os
    if dir_path is None:
        try:
            from auron_tpu import config as cfg
            dir_path = cfg.get_config().get(cfg.TRACE_DIR)
        except Exception:   # pragma: no cover
            dir_path = ""
    if not dir_path:
        return None
    try:
        os.makedirs(dir_path, exist_ok=True)
        path = os.path.join(dir_path,
                            f"stall_report_{report.task_id}.json")
        tmp = path + ".part"
        with open(tmp, "w") as f:
            f.write(report.to_json() + "\n")
        os.replace(tmp, path)
        return path
    except Exception:   # pragma: no cover - best-effort sink
        logger.exception("stall report write to %r failed", dir_path)
        return None


# ---------------------------------------------------------------------------
# mesh fault domain: per-round gang-aware liveness + straggler defense
# ---------------------------------------------------------------------------
#
# A gang-scheduled all-to-all round blocks the driving thread inside an
# uninterruptible collective, so the stall monitor above will flag the
# task silent — but a flagged ROUND is not automatically a dead one. The
# guard below is the arbiter at the round boundary:
#
# - a round that COMPLETES after being flagged was merely SLOW (a
#   straggling chip): the guard forgives the stall (clears the flag and
#   re-beats, exactly like the compile-credit precedent — waiting out a
#   slow collective is liveness, not a wedge) and hands the duration to
#   the straggler defense;
# - a round that RAISES is DEAD: the error classifies at the collective
#   boundary (errors.classify_runtime → MeshUnavailable) and the
#   exchange's demotion handler routes the remaining rounds host-side;
# - a round that NEVER RETURNS is beyond cooperative recovery — the
#   StallReport is the diagnosis and the query deadline the hard bound.


class MeshRoundStats:
    """Rolling per-round duration window: the straggler defense's
    baseline. ``observe`` feeds a bounded deque (and the registry
    histogram ``auron_mesh_round_seconds``); ``is_straggler`` compares
    one round against ``factor`` × the rolling p50, arming only after
    ``min_rounds`` observations so the first cold-compile rounds never
    self-report. Pure host arithmetic — unit-testable without a mesh."""

    def __init__(self, window: int = 64, min_rounds: int = 4):
        self.min_rounds = min_rounds
        self._durations: deque = deque(maxlen=window)
        self._lock = threading.Lock()

    def p50(self) -> Optional[float]:
        with self._lock:
            if len(self._durations) < self.min_rounds:
                return None
            ordered = sorted(self._durations)
            return ordered[len(ordered) // 2]

    def is_straggler(self, seconds: float, factor: float) -> bool:
        """Verdict BEFORE ``seconds`` joins the window (a straggler must
        not drag the baseline it is judged against)."""
        if factor <= 0:
            return False
        p50 = self.p50()
        return p50 is not None and p50 > 0 and seconds > factor * p50

    def observe(self, seconds: float) -> None:
        with self._lock:
            self._durations.append(seconds)
        try:
            from auron_tpu.obs import registry as obs_registry
            if obs_registry.enabled():
                obs_registry.get_registry().histogram(
                    "auron_mesh_round_seconds").observe(seconds)
        except Exception:   # pragma: no cover  # graft: disable=GL004 -- round histogram is best-effort telemetry
            pass


class MeshRoundGuard:
    """Context manager around ONE all-to-all round (dispatch + the
    output-boundary readback): beats the task heartbeat on entry with
    the ``mesh.round`` site, measures the round, and — when the stall
    monitor flagged the task MID-round but the round then completed —
    forgives the stall (slow, not dead; see the module section comment).
    After exit, ``elapsed_s`` carries the round duration for the
    straggler defense and ``forgiven`` whether a stall verdict was
    downgraded."""

    def __init__(self, heartbeat: Optional[TaskHeartbeat]):
        self.heartbeat = heartbeat
        self.elapsed_s = 0.0
        self.forgiven = False
        self._t0 = 0
        self._stalled_on_entry = False

    def __enter__(self) -> "MeshRoundGuard":
        hb = self.heartbeat
        if hb is not None:
            self._stalled_on_entry = hb.stalled
            if not hb.stalled:
                hb.beat("mesh.round")
        self._t0 = _now_ns()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.elapsed_s = (_now_ns() - self._t0) * 1e-9
        hb = self.heartbeat
        if hb is None:
            return
        if exc_type is None and hb.stalled and not self._stalled_on_entry:
            # flagged DURING a round that completed: slow, not dead —
            # forgive (a pre-existing flag is someone else's verdict and
            # survives; the exchange's straggler defense takes it from
            # here)
            self.forgive_stall()
        elif exc_type is None and not hb.stalled:
            hb.beat("mesh.round")

    def forgive_stall(self) -> None:
        """Downgrade a stall flagged MID-round to a slow round. Called
        by ``__exit__`` for completed rounds, and by the exchange's
        DEMOTION handler for failed ones — the loss is being recovered
        in place, and a pending TaskStalled would abort (at the next
        checkpoint) exactly the recovery it was supposed to enable. A
        flag that predates the round is someone else's verdict and is
        never cleared here."""
        hb = self.heartbeat
        if hb is None or not hb.stalled or self._stalled_on_entry:
            return
        hb.stalled = False
        hb.stalled_at_ns = 0
        hb.beat("mesh.round")
        self.forgiven = True
        _count("mesh_rounds_forgiven")
        try:
            from auron_tpu.obs import trace
            trace.event("watchdog", "watchdog.round_slow",
                        task=hb.task_id,
                        elapsed_s=round(self.elapsed_s, 3),
                        stall_timeout_s=hb.timeout_s)
        except Exception:   # pragma: no cover  # graft: disable=GL004 -- slow-round event is best-effort telemetry
            pass


def mesh_rounds_forgiven() -> int:
    """Monotonic count of stall verdicts downgraded to slow rounds."""
    with _LOCK:
        return _STATS["mesh_rounds_forgiven"]
