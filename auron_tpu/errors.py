"""Structured error taxonomy for the recovery plane.

The retry driver (runtime/executor.run_task_with_retries) used to decide
transient-vs-deterministic by matching substrings of RuntimeError
messages — fragile against XLA version drift and impossible to extend
from the durable tiers. This module replaces that with a typed
hierarchy: every recovery-relevant boundary (RSS write/fetch, spill
write/read, device compute, program build) raises an ``AuronError``
subclass whose ``transient`` attribute IS the retry decision, and the
retry driver routes purely on the taxonomy
(``is_transient``) — no message inspection anywhere on the retry path.

The one place pattern knowledge survives is ``classify_runtime``: the
*device-compute boundary* (ExecutionRuntime._batches_inner) calls it to
split XLA's ambiguous bare RuntimeError into its deterministic
(lowering/shape defect → ``KernelLoweringError``) and transient
(resource/backend blip → ``DeviceExecutionError``) halves at the moment
the error crosses out of the engine. That is classification at the
boundary that owns the ambiguity, not string matching in the scheduler —
the shape Spark's task scheduler + shuffle-integrity layer give the
reference (SURVEY §5.3).

Subclasses double-inherit the legacy builtin class they replace
(``KernelLoweringError`` is-a RuntimeError, ``StorageIOError`` is-a
OSError) so existing ``except`` sites and tests keep working while new
code routes on the taxonomy.
"""

from __future__ import annotations

from typing import Optional


class AuronError(Exception):
    """Base of the engine's classified errors.

    ``transient`` is the retry contract: True means a clean re-execution
    of the failed unit (task attempt, map recompute) can succeed — the
    failure lives outside the plan (IO blip, backend hiccup, corrupted
    durable frame that a recompute rewrites). False means recomputing
    the same unit is guaranteed to fail again (plan/schema/engine
    defect) or must be recovered at a DIFFERENT granularity than a blind
    retry (e.g. ShuffleCorruption needs a map recompute, not a reducer
    rerun), so the retry driver surfaces it immediately.
    """

    #: class-level default; instances may override via __init__
    transient: bool = False
    #: optional fault-plane site name this error was raised at
    site: Optional[str] = None

    def __init__(self, *args, site: Optional[str] = None):
        super().__init__(*args)
        if site is not None:
            self.site = site


# ---------------------------------------------------------------------------
# deterministic classes — retrying cannot succeed
# ---------------------------------------------------------------------------

class PlanError(AuronError):
    """Deterministic plan/schema/engine defect (the no-retry class)."""
    transient = False


class KernelLoweringError(PlanError, RuntimeError):
    """XLA lowering / shape / Mosaic defect: the compiled-program
    analogue of a syntax error. RuntimeError subclass so legacy
    ``except RuntimeError`` sites (and tests matching on the message)
    keep working."""


class InjectedFatalError(PlanError):
    """A fault plan's ``fatal`` kind: a deliberately deterministic
    injected failure (chaos tests assert it is never retried)."""


class ShuffleCorruption(AuronError):
    """A committed RSS map-output frame failed its checksum (or carries
    an unknown format version). NOT transient: the bytes on storage are
    stable, so a blind reducer retry re-reads the same corrupt frame —
    recovery is map-output invalidation + map-task recompute, which
    RssShuffleExchangeOp performs itself (it owns the map subtree);
    a foreign-host RssShuffleReadOp surfaces this classified error to
    whoever can reschedule the map."""
    transient = False

    def __init__(self, message: str, *, shuffle_id: Optional[int] = None,
                 map_id: Optional[int] = None, path: Optional[str] = None,
                 site: Optional[str] = None):
        super().__init__(message, site=site)
        self.shuffle_id = shuffle_id
        self.map_id = map_id
        self.path = path


class ScalarSubqueryError(PlanError, RuntimeError):
    """A scalar subquery used as an expression returned more than one
    row: a deterministic plan/data defect — recomputing the partition
    re-reads the same rows. RuntimeError subclass so legacy ``except
    RuntimeError`` sites (and tests matching on the message) keep
    working."""


class RemoteEngineError(AuronError, RuntimeError):
    """The serving tier's client half received a structured ERROR frame
    the server did not classify further: the failure already happened
    (and was classified, retried, or shed) SERVER-side, so a blind
    client-side retry of the same submission is not the recovery — the
    caller decides. RuntimeError subclass so existing ``except
    RuntimeError``/``pytest.raises(RuntimeError, match='engine error')``
    consumers keep working."""
    transient = False


class ReplicaUnavailable(AuronError):
    """A fleet replica could not serve this submission: connect refused,
    the connection dropped mid-conversation, or the liveness plane's
    pid+epoch verdict says the engine process is dead. TRANSIENT by
    design — the replica's death says nothing about the query, and the
    router's recovery (spill-over to a survivor, or journal-backed
    RESUME) is exactly a retry elsewhere. Only the router raises this;
    a client talking straight to one server keeps seeing
    ``RemoteEngineError``."""
    transient = True

    def __init__(self, *args, replica: Optional[str] = None,
                 reason: Optional[str] = None,
                 site: Optional[str] = None):
        super().__init__(*args, site=site)
        #: "host:port" of the replica that failed
        self.replica = replica
        #: connect | io | dead | hello
        self.reason = reason


# ---------------------------------------------------------------------------
# lifecycle classes — the query lifecycle control plane (PR 8)
# ---------------------------------------------------------------------------

class QueryCancelled(AuronError):
    """The query's CancelToken was flipped (host cancel, serving CANCEL
    frame, or session.cancel(query_id)): the task unwinds cooperatively
    with full resource cleanup. NOT transient — a cancelled query must
    never be silently recomputed; the retry driver surfaces it
    immediately (and the executor treats it as teardown, not failure,
    exactly like the legacy TaskCancelled)."""
    transient = False

    def __init__(self, *args, query_id: Optional[str] = None,
                 site: Optional[str] = None):
        super().__init__(*args, site=site)
        self.query_id = query_id


class DeadlineExceeded(QueryCancelled):
    """The query ran past its deadline (``df.collect(timeout_s=...)`` /
    ``auron.query.deadline_s`` / a serving-frame timeout): same
    cooperative unwind as QueryCancelled, but surfaced to the caller as
    a budget failure rather than swallowed as teardown."""


class TaskStalled(AuronError):
    """The stall watchdog flagged this task silent past
    ``auron.watchdog.stall_timeout_s`` (no heartbeat from the drive
    loop, shuffle frames, or spill consumers). Transient ONCE: the retry
    driver re-runs a stalled task a single time (a wedged external
    dependency may have healed), then surfaces it — an infinite
    stall-retry loop would hide a deterministic wedge forever."""
    transient = True


class MemoryExhausted(AuronError):
    """The memory-pressure degradation ladder ran out of rungs (shrink →
    force-spill → shed) or a per-query quota was breached: THIS query is
    shed with a classified error — never the process. Not transient: an
    immediate identical recompute meets the same pressure; admission
    control / the caller decides when to resubmit."""
    transient = False


class AdmissionRejected(AuronError):
    """The query scheduler refused to ADMIT this query (run queue full,
    queue-wait p99 past the admission threshold, memory used/budget
    ratio past its threshold, or an injected ``sched.admit`` fault):
    the query never started — no executor, no memmgr consumers, no
    durable-tier artifacts exist for it. TRANSIENT by design: this is
    load shedding, not failure — the same query resubmitted after
    ``retry_after_s`` can succeed once the backlog drains. The retry
    driver never sees it (admission happens before any task exists);
    the hint is for the CALLER's backoff."""
    transient = True

    def __init__(self, *args, reason: Optional[str] = None,
                 retry_after_s: Optional[float] = None,
                 site: Optional[str] = None):
        super().__init__(*args, site=site)
        #: queue_full | queue_wait | memory | injected
        self.reason = reason
        #: caller backoff hint (seconds); estimated from the observed
        #: queue-wait distribution when available
        self.retry_after_s = retry_after_s


# ---------------------------------------------------------------------------
# journal classes — the crash-safe query journal (runtime/journal.py)
# ---------------------------------------------------------------------------

class JournalError(AuronError):
    """Base of the query-journal verdicts. NOT transient: a journal
    problem is never recovered by blindly re-running the resume — the
    recovery is always explicit (fall back to a fresh run, or surface
    the structured reason to the caller)."""
    transient = False

    def __init__(self, *args, query_id: Optional[str] = None,
                 reason: Optional[str] = None,
                 site: Optional[str] = None):
        super().__init__(*args, site=site)
        self.query_id = query_id
        #: machine-readable verdict (no_journal | corrupt | ambiguous |
        #: fingerprint_mismatch | journaling_disabled | missing_source)
        self.reason = reason


class JournalCorrupt(JournalError):
    """A journal file failed its per-record CRC, carries an unknown
    format version, or cannot be parsed. The committed RSS data it
    described may be fine, but its inventory is not trustworthy — the
    ONLY safe recovery is a fresh run (which the reuse path performs
    automatically); resume() surfaces this classified verdict so the
    caller decides. Never a wrong answer: a corrupt journal is
    discarded, not believed."""


class JournalInvalidated(JournalError):
    """The journal's plan or source-snapshot fingerprints no longer
    match the live plan/sources (a source file was rewritten, a catalog
    table changed): the journaled shuffle outputs were computed from
    DIFFERENT data, so reusing them would return stale rows. The
    classified invalidation: journal + its RSS run directory are
    garbage-collected and the query must run fresh."""


class ResumeUnavailable(JournalError):
    """``Session.resume`` (or the serving RESUME frame) named a query
    id with no resumable journal behind it: unknown id, already
    completed (journals are deleted at completion), journaling
    disabled, or a plan whose sources this process cannot re-bind.
    Carries the machine-readable ``reason`` the serving tier puts on
    the structured ERROR frame's first line."""


class UnknownQuery(JournalError):
    """A by-id control operation (the serving CANCEL-by-id frame)
    named a query id that is not live on this server: unknown, or
    already finished (cancel-after-DONE is a no-op by contract, but a
    FIRST-frame CANCEL for an id the server never saw deserves a
    structured verdict, not a generic traceback)."""


# ---------------------------------------------------------------------------
# transient classes — a clean re-execution can succeed
# ---------------------------------------------------------------------------

class TransientError(AuronError):
    """Base of the retryable classes."""
    transient = True


class DeviceExecutionError(TransientError, RuntimeError):
    """A device/backend execution failure that is not a deterministic
    lowering defect (resource exhaustion, injected device fault): an
    exact partition recompute can succeed."""


class MeshUnavailable(DeviceExecutionError):
    """A device of the SPMD mesh is lost (or the collective fabric
    failed) mid-exchange: the gang-scheduled ``all_to_all`` round holds
    every chip's in-flight shard in volatile HBM, so the round cannot
    complete on the mesh. Recovery is ROUTE DEMOTION, not a blind rerun
    of the same collective: the exchange re-routes its remaining rounds
    down the existing ladder (``all_to_all`` → host ``device_buffer`` →
    RSS) re-using the lost round's still-live map inputs (inputs are
    never donated into the exchange program by contract), and the plane
    quarantines the device so SUBSEQUENT exchanges rebuild a smaller
    submesh or route host-side (``parallel/exchange.py`` /
    ``parallel/mesh.py``). Transient by type — if it escapes the
    in-place demotion (e.g. the prior rounds' mesh-resident shards are
    unreadable too), a task-level recompute re-routes against the
    already-quarantined plane and succeeds host-side."""

    def __init__(self, *args, device: Optional[int] = None,
                 site: Optional[str] = None):
        super().__init__(*args, site=site)
        #: mesh device index the failure was attributed to (None when
        #: XLA's error carries no device identity)
        self.device = device


class StorageIOError(TransientError, OSError):
    """IO failure against a durable tier (shared-storage RSS root,
    spill directory): the storage substrate heals between attempts.
    OSError subclass so legacy ``except OSError`` sites keep working."""


class RssUnavailableError(StorageIOError):
    """The RSS service root failed a write/flush/commit/fetch."""


class SpillIOError(StorageIOError):
    """A spill-file write/read failed."""


class JournalIOError(StorageIOError):
    """A query-journal append/fsync/load failed at the IO layer. The
    journal plane SWALLOWS this on the write path (journaling degrades
    to off for that query — losing resumability, never the query); the
    load path converts it to the deterministic JournalCorrupt verdict."""


class SpillCorruption(TransientError):
    """A spill frame failed its checksum. Transient at TASK granularity:
    spill files are per-attempt artifacts, so a fresh attempt of the
    same partition rewrites them from source — the retry driver's
    normal recompute is the recovery."""


# ---------------------------------------------------------------------------
# boundary classification
# ---------------------------------------------------------------------------

#: RuntimeError message signatures of XLA's deterministic defect class.
#: Used ONLY by classify_runtime at the device-compute boundary — the
#: retry driver never sees these (formerly executor._NO_RETRY_RUNTIME_
#: PATTERNS, matched inside the retry loop itself).
_XLA_DETERMINISTIC_PATTERNS = (
    "lowering", "invalid argument", "invalid_argument", "mosaic",
    "incompatible shapes", "rank mismatch", "unimplemented",
)

#: RuntimeError signatures of DEVICE LOSS — the failure class where the
#: chip (or the collective fabric between chips) died under a running
#: program, as opposed to the program being wrong. Checked BEFORE the
#: deterministic split: these become ``MeshUnavailable`` so the SPMD
#: exchange's demotion handler (and the plane's quarantine) can route
#: around the dead device instead of retrying into it.
_DEVICE_LOSS_PATTERNS = (
    "device lost", "device unavailable", "device failure",
    "device halted", "device is in an invalid state", "slice health",
    "interconnect", "data transfer failure", "chip unreachable",
)


def classify_runtime(e: RuntimeError) -> BaseException:
    """Classify a bare RuntimeError crossing the device-compute boundary
    into the taxonomy. Deterministic lowering/shape signatures become
    KernelLoweringError (no retry); everything else — XLA wraps
    resource and external-service failures in plain RuntimeError — is
    DeviceExecutionError (retry).

    Taxonomy trap guarded FIRST: ``NotImplementedError`` IS-A
    RuntimeError (and jax raises TypeError-adjacent errors for trace/
    lowering defects), so the deterministic builtin types must be
    checked before the message split — otherwise the engine's
    deliberate unsupported-plan rejections would be re-wrapped as a
    *transient* DeviceExecutionError and retried ``retries+1`` times.
    They return UNCHANGED (``raise classify_runtime(e) from e`` keeps
    the original type) because callers catch them by type to reject
    unsupported plans; ``is_transient`` already routes them
    non-transient by NO_RETRY_TYPES membership."""
    if isinstance(e, NO_RETRY_TYPES):
        return e
    msg = str(e)
    low = msg.lower()
    # device loss outranks the deterministic split: "device lost during
    # lowering cleanup"-style messages are a dead chip, not a plan
    # defect, and must reach the mesh demotion/quarantine path
    if any(p in low for p in _DEVICE_LOSS_PATTERNS):
        return MeshUnavailable(msg)
    if any(p in low for p in _XLA_DETERMINISTIC_PATTERNS):
        return KernelLoweringError(msg)
    return DeviceExecutionError(msg)


#: exception classes that are deterministic plan/schema/engine defects
#: by TYPE: recomputing the partition cannot succeed (ValueError joined
#: in round 6 — shape mismatches, invalid kernel bounds and parse
#: failures are ValueErrors, and retrying them paid retries+1 full
#: computes with misleading "retrying" logs)
NO_RETRY_TYPES = (NotImplementedError, TypeError, AssertionError,
                  KeyError, IndexError, AttributeError, ValueError)


def is_transient(e: BaseException) -> bool:
    """The retry driver's routing function: True when a clean task-level
    recompute may succeed. Routes purely on types — classified errors
    carry their own ``transient`` verdict; bare builtins keep the
    legacy type-based split (NO_RETRY_TYPES fail fast, IO and unknown
    failures retry). No message inspection."""
    if isinstance(e, AuronError):
        return e.transient
    if isinstance(e, NO_RETRY_TYPES):
        return False
    # bare OSError/RuntimeError/Exception: the legacy default — retry
    # (boundaries classify their own errors before they get here; this
    # is the conservative fallback for third-party raises)
    return True
