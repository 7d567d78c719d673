"""Deterministic, seeded fault-injection plane.

Every recovery-relevant boundary in the engine carries a *named
injection site*; a fault plan — one config string — arms sites with
failure kinds and probabilities, and every decision is a pure function
of ``(seed, site, kind, per-rule event index)``, so a failing chaos run
replays EXACTLY by re-running with the same seed. No fault plan armed
(the default) costs one config-epoch compare per site check (the
armed/disarmed verdict is cached until a config mutation).

Plan grammar (``auron.faults.plan``)::

    site:kind@prob[;site:kind@prob...]
    rss.fetch:corrupt@0.05;spill.read:io_error@0.1;device.compute:io_error

``@prob`` defaults to 1.0. Kinds:

- ``io_error``  — raise the site's transient error class (the call site
  passes it; e.g. ``RssUnavailableError`` at rss.*, ``SpillIOError`` at
  spill.*, ``DeviceExecutionError`` at device.compute/program.build).
- ``fatal``     — raise ``errors.InjectedFatalError`` (deterministic:
  chaos tests assert it is never retried).
- ``corrupt``   — at byte boundaries (``maybe_corrupt``), flip one byte
  of the payload AFTER its checksum was computed, simulating storage
  bit rot the integrity layer must catch. Ignored at fail-only sites.
- ``hang``      — sleep ``auron.faults.hang_s`` seconds (a straggling
  task or chip; pair with the stall-watchdog deadline). The sleep
  POLLS the caller's cancel registry (``maybe_fail(..., cancel=ctx)``)
  so a cooperative cancel — or a stall-watchdog flag — terminates an
  injected hang promptly instead of blocking the full interval.
- ``cancel``    — lifecycle chaos (``maybe_cancel``): fire the task's
  cancel registry at a seeded event index, racing cancellation against
  live batch traffic (the ``cancel.race`` site).
- ``deny``      — forced-decision chaos (``fires``): make a survivable
  refusal happen as if its threshold were breached — the memory
  manager's degradation ladder at ``memmgr.deny``, an admission-control
  rejection (``errors.AdmissionRejected``) at ``sched.admit``.

Named sites threaded through the engine:

    rss.write | rss.flush | rss.commit | rss.fetch      (shuffle tier)
    spill.write | spill.read                            (spill tier)
    device.compute                                      (per batch)
    task.hang                                           (per batch, mid-drive)
    cancel.race                                         (per batch, lifecycle)
    program.build                                       (compile sites)
    memmgr.deny                                         (pressure ladder)
    sched.admit                                         (admission control)
    mesh.all_to_all                                     (per sharded round)
    mesh.gang                                           (gang door, cancel)
    journal.write | journal.commit | journal.load       (query journal)

``journal.write``/``journal.commit`` fire on the crash-safe query
journal's append/fsync path (runtime/journal.py): ``io_error``/``fatal``
are SWALLOWED by the journal — journaling degrades to off for that
query (a ``journal.disable`` event on the timeline), the query itself
completes identically; ``corrupt`` flips a byte of the appended record
AFTER its CRC, surfacing as ``JournalCorrupt`` only when a later resume
loads the file. ``journal.load`` fires on resume/reuse loads: the
classified ``JournalCorrupt`` (resume) or a logged fresh-run fallback
(reuse).

``mesh.all_to_all`` fires once per all-to-all round of a mesh-routed
exchange: ``io_error`` raises the classified ``errors.MeshUnavailable``
(a lost device — the demotion ladder must route the exchange's
remaining rounds host-side), ``fatal`` an InjectedFatalError carrying
the mesh site (same demotion path: a deterministic mesh failure is
recovered by routing AROUND the mesh, not by retrying into it), and
``hang`` a straggling chip (the straggler defense's signal).
``mesh.gang`` (kind ``cancel``) fires the task's cancel registry while
it queues at the gang door — the parked ticket must dequeue without
ever starting a round.

The plane is resolved from the PROCESS-GLOBAL config (the sites live in
code paths with no ExecContext at hand — file services, spill files),
and injection counters are exposed via ``snapshot``/``totals`` so the
per-task metrics snapshot can attribute injected faults.
"""

from __future__ import annotations

import threading
import time
import zlib
from dataclasses import dataclass
from typing import Optional

from auron_tpu import errors

#: the engine's named injection sites (documentation + plan validation)
SITES = (
    "rss.write", "rss.flush", "rss.commit", "rss.fetch",
    "spill.write", "spill.read",
    "device.compute", "program.build",
    "task.hang", "cancel.race", "memmgr.deny", "sched.admit",
    "mesh.all_to_all", "mesh.gang",
    "journal.write", "journal.commit", "journal.load",
    "fleet.route", "fleet.forward",
)

KINDS = ("io_error", "fatal", "corrupt", "hang", "cancel", "deny")


@dataclass(frozen=True)
class Rule:
    site: str
    kind: str
    prob: float


def parse_plan(plan: str) -> list[Rule]:
    """Parse the ``site:kind@prob;...`` grammar; raises ValueError on an
    unknown site/kind or malformed probability (a typo'd chaos plan must
    fail loudly, not silently inject nothing)."""
    rules = []
    for part in filter(None, (p.strip() for p in plan.split(";"))):
        try:
            site, rest = part.split(":", 1)
            kind, _, prob_s = rest.partition("@")
            # graft: disable=GL001 -- a fault-plan token, host text
            prob = float(prob_s) if prob_s else 1.0
        except ValueError as e:
            raise ValueError(f"malformed fault rule {part!r} "
                             f"(want site:kind@prob)") from e
        site, kind = site.strip(), kind.strip()
        if site not in SITES:
            raise ValueError(f"unknown fault site {site!r}; known: {SITES}")
        if kind not in KINDS:
            raise ValueError(f"unknown fault kind {kind!r}; known: {KINDS}")
        if not 0.0 <= prob <= 1.0:
            raise ValueError(f"fault probability out of range: {part!r}")
        rules.append(Rule(site, kind, prob))
    return rules


class FaultPlane:
    """One parsed plan + its deterministic decision state."""

    def __init__(self, plan: str, seed: int, hang_s: float = 2.0):
        self.plan = plan
        self.seed = seed
        self.hang_s = hang_s
        self._rules: dict[str, list[Rule]] = {}
        for r in parse_plan(plan):
            self._rules.setdefault(r.site, []).append(r)
        self._lock = threading.Lock()
        #: per-rule event index — each rule sees its own deterministic
        #: Bernoulli sequence, independent of other rules' traffic
        self._events: dict[tuple[str, str], int] = {}
        self.injected: dict[tuple[str, str], int] = {}

    def _decide(self, rule: Rule) -> Optional[int]:
        """Advance the rule's event counter; return the event index when
        this event injects, else None. hash(seed|site|kind|n) → [0,1)."""
        with self._lock:
            n = self._events.get((rule.site, rule.kind), 0)
            self._events[(rule.site, rule.kind)] = n + 1
            h = zlib.crc32(
                f"{self.seed}|{rule.site}|{rule.kind}|{n}".encode())
            if (h & 0xFFFFFFFF) / 2**32 >= rule.prob:
                return None
            self.injected[(rule.site, rule.kind)] = \
                self.injected.get((rule.site, rule.kind), 0) + 1
            return n

    def fire(self, site: str, kinds: tuple[str, ...]) -> Optional[Rule]:
        """First armed rule of ``site`` among ``kinds`` that injects on
        this event, advancing every matching rule's counter."""
        hit = None
        for rule in self._rules.get(site, ()):
            if rule.kind in kinds and self._decide(rule) is not None \
                    and hit is None:
                hit = rule
        return hit

    def snapshot(self) -> dict[str, dict[str, int]]:
        with self._lock:
            out: dict[str, dict[str, int]] = {}
            for (site, kind), n in self.injected.items():
                out.setdefault(site, {})[kind] = n
            return out

    def totals(self) -> int:
        with self._lock:
            return sum(self.injected.values())


# ---------------------------------------------------------------------------
# process-global plane, resolved from config
# ---------------------------------------------------------------------------

_LOCK = threading.Lock()
_PLANE: Optional[FaultPlane] = None
_PLANE_KEY: Optional[tuple] = None
#: (config epoch, plane) verdict cache: the unarmed hot path — every
#: batch, frame and program build calls a site check — must cost one
#: tuple read + int compare, not a config lock + os.environ lookup.
#: A single-assignment tuple so readers never see a torn update.
_CACHED: tuple[int, Optional[FaultPlane]] = (-1, None)
#: monotonic injected-fault count across plane rebuilds (per-task metric
#: attribution survives a reconfigure mid-process)
_TOTAL_BASE = 0


def _active() -> Optional[FaultPlane]:
    """The plane for the current config; None when no plan is armed.
    The verdict is cached against the config-mutation epoch, so the
    common unarmed check is one int compare — plan changes go through
    ``AuronConfig.set/unset`` (or :func:`reset`), which bump the epoch."""
    from auron_tpu import config as cfg
    epoch, plane = _CACHED
    if epoch == cfg.config_epoch():
        return plane
    return _resolve()


def _resolve() -> Optional[FaultPlane]:
    global _PLANE, _PLANE_KEY, _TOTAL_BASE, _CACHED
    from auron_tpu import config as cfg
    # read the epoch BEFORE the config values: a concurrent set() bumps
    # it after we read, so the stale cache entry misses on the next call
    epoch = cfg.config_epoch()
    conf = cfg.get_config()
    plan = conf.get(cfg.FAULTS_PLAN)
    if not plan:
        if _PLANE is not None:
            with _LOCK:
                if _PLANE is not None:
                    _TOTAL_BASE += _PLANE.totals()
                    _PLANE, _PLANE_KEY = None, None
        _CACHED = (epoch, None)
        return None
    key = (plan, conf.get(cfg.FAULTS_SEED), conf.get(cfg.FAULTS_HANG_S))
    plane = _PLANE
    if plane is None or _PLANE_KEY != key:
        with _LOCK:
            if _PLANE is None or _PLANE_KEY != key:
                if _PLANE is not None:
                    _TOTAL_BASE += _PLANE.totals()
                _PLANE = FaultPlane(*key)
                _PLANE_KEY = key
            plane = _PLANE
    _CACHED = (epoch, plane)
    return plane


def reset() -> None:
    """Drop the active plane's decision state so the NEXT site check
    replays event 0 (chaos harness: one reset per run = exact replay).
    Also invalidates the verdict cache — the one hook that notices a
    direct os.environ change."""
    global _PLANE, _PLANE_KEY, _TOTAL_BASE, _CACHED
    with _LOCK:
        if _PLANE is not None:
            _TOTAL_BASE += _PLANE.totals()
        _PLANE, _PLANE_KEY = None, None
        _CACHED = (-1, None)


def _stop_requested(cancel) -> bool:
    """Duck-typed poll of a cancel registry: ExecContext (``should_stop``
    covers both the cancel event and the stall flag), CancelToken /
    threading.Event (``is_set``)."""
    if cancel is None:
        return False
    stop = getattr(cancel, "should_stop", None)
    if stop is not None:
        return bool(stop)
    is_set = getattr(cancel, "is_set", None)
    return bool(is_set()) if is_set is not None else False


#: poll granularity of interruptible injected hangs (a cancel lands
#: within one tick, far inside the watchdog's stall resolution)
_HANG_POLL_S = 0.02


def maybe_fail(site: str, exc_cls=errors.TransientError,
               cancel=None) -> None:
    """Injection hook for failure sites: raises the plan's armed fault
    (``exc_cls`` for io_error — the call site's transient error class —
    InjectedFatalError for fatal), or sleeps for hang. No-op when the
    site is unarmed.

    ``cancel`` (an ExecContext, CancelToken or Event) makes an injected
    hang INTERRUPTIBLE: the sleep polls it and returns early on a
    cooperative cancel or stall flag, so chaos cancel tests terminate
    promptly — the caller's next checkpoint raises the classified
    error."""
    plane = _active()
    if plane is None:
        return
    rule = plane.fire(site, ("io_error", "fatal", "hang"))
    if rule is None:
        return
    # injected faults carry site/kind on the timeline so chaos runs are
    # self-explaining (obs/trace.py; correlated by tools/chaos_report)
    from auron_tpu.obs import trace
    trace.event("fault", "fault.injected", site=site, kind=rule.kind,
                seed=plane.seed)
    if rule.kind == "hang":
        _interruptible_sleep(plane.hang_s, cancel)
        return
    if rule.kind == "fatal":
        raise errors.InjectedFatalError(
            f"injected deterministic fault at {site} "
            f"(seed={plane.seed})", site=site)
    raise exc_cls(f"injected {rule.kind} at {site} (seed={plane.seed})",
                  site=site)


def _interruptible_sleep(seconds: float, cancel) -> None:
    """The injected-hang sleep: returns early the moment the caller's
    cancel registry (or stall flag) trips."""
    end = time.monotonic() + seconds
    while True:
        left = end - time.monotonic()
        if left <= 0 or _stop_requested(cancel):
            return
        wait = getattr(cancel, "wait", None)
        if wait is not None:
            # event/token wait wakes the instant a cancel lands
            wait(min(_HANG_POLL_S, left))
        else:
            time.sleep(min(_HANG_POLL_S, left))


def maybe_hang(site: str, cancel=None) -> bool:
    """Hang-only injection hook for checkpoint sites (``task.hang``):
    sleeps the armed hang interval — interruptibly, polling ``cancel``
    — and reports whether a hang was injected. Never raises: checkpoint
    callers surface whatever the hang provoked (stall flag, cancel)
    through check_cancelled."""
    plane = _active()
    if plane is None:
        return False
    rule = plane.fire(site, ("hang",))
    if rule is None:
        return False
    from auron_tpu.obs import trace
    trace.event("fault", "fault.injected", site=site, kind="hang",
                seed=plane.seed)
    _interruptible_sleep(plane.hang_s, cancel)
    return True


def lifecycle_poll(ctx) -> None:
    """The checkpoint-site fast path: ONE armed/disarmed verdict check
    covering both lifecycle sites (``cancel.race`` + ``task.hang``).
    ExecContext.checkpoint calls this per loop iteration, so the
    unarmed cost must stay one function call + one epoch compare."""
    if _active() is None:
        return
    maybe_cancel("cancel.race", ctx)
    maybe_hang("task.hang", cancel=ctx)


def maybe_cancel(site: str, target) -> bool:
    """Lifecycle injection hook (site ``cancel.race``, kind ``cancel``):
    fire the task's cancel registry at this seeded event index — racing
    cancellation against live traffic so the chaos battery proves every
    interleaving unwinds classified and leak-free. ``target`` is
    anything with ``cancel()`` (ExecContext, CancelToken). Returns True
    when the cancel fired."""
    plane = _active()
    if plane is None:
        return False
    rule = plane.fire(site, ("cancel",))
    if rule is None:
        return False
    from auron_tpu.obs import trace
    trace.event("fault", "fault.injected", site=site, kind="cancel",
                seed=plane.seed)
    cancel = getattr(target, "cancel", None)
    if cancel is not None:
        cancel()
    return True


def fires(site: str, kind: str) -> bool:
    """Boolean injection hook for sites whose fault is a forced DECISION
    rather than a raise (``memmgr.deny``: pretend the budget is
    exhausted and walk the degradation ladder). Advances the rule's
    deterministic event counter like every other site."""
    plane = _active()
    if plane is None:
        return False
    rule = plane.fire(site, (kind,))
    if rule is None:
        return False
    from auron_tpu.obs import trace
    trace.event("fault", "fault.injected", site=site, kind=kind,
                seed=plane.seed)
    return True


def maybe_corrupt(site: str, data: bytes) -> bytes:
    """Injection hook for byte boundaries: flips one deterministic byte
    of ``data`` when the site's ``corrupt`` rule injects. Call AFTER the
    checksum over the clean bytes is computed — the corruption must be
    the integrity layer's problem, not the writer's."""
    plane = _active()
    if plane is None or not data:
        return data
    rule = plane.fire(site, ("corrupt",))
    if rule is None:
        return data
    from auron_tpu.obs import trace
    trace.event("fault", "fault.injected", site=site, kind="corrupt",
                seed=plane.seed, bytes=len(data))
    pos = zlib.crc32(f"{plane.seed}|{site}|pos|{len(data)}".encode()) \
        % len(data)
    corrupted = bytearray(data)
    corrupted[pos] ^= 0xFF
    return bytes(corrupted)


def snapshot() -> dict[str, dict[str, int]]:
    """{site: {kind: injected count}} of the active plane ({} unarmed)."""
    plane = _PLANE
    return plane.snapshot() if plane is not None else {}


def totals() -> int:
    """Monotonic injected-fault count (survives plane rebuilds) — the
    per-task metrics delta source."""
    with _LOCK:
        base = _TOTAL_BASE
        plane = _PLANE
    return base + (plane.totals() if plane is not None else 0)
