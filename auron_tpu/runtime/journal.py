"""Crash-safe query journal: process-restart recovery from committed
shuffle stages.

Every robustness plane before this one (fault injection, lifecycle,
admission, mesh fault domain) assumes the Python process survives; a
SIGKILL/OOM/preemption lost every in-flight query even though the RSS
tier already persists map outputs with CRC'd frames and an atomic
commit trailer (``parallel/shuffle_service.py``).  This module closes
that gap with the checkpoint/resume discipline the host engine's
lineage contract implies (Spark stage retry; Flare's rule that a native
engine must preserve the host's fault-tolerance semantics):

- **QueryJournal** — one append-only file per top-level query under
  ``auron.journal.dir``: a header naming the plan fingerprint, the
  source-snapshot fingerprints, the owner process tag
  (``utils/liveness``) and the serialized plan itself, followed by
  exchange-DAG records and an append-only log of committed RSS map
  outputs (shuffle_id/map_id/size/trailer CRC).  Map records are
  appended AFTER the durable tier's atomic rename — the journal never
  claims more than storage holds — and ride an **async appender**
  thread so the hot path pays an enqueue, with fsync only at the
  header and at shuffle-level commit records (``auron.journal.fsync``).
  Every record carries its own CRC; a torn tail (crash mid-append) is
  dropped on load, a corrupt interior line is ``JournalCorrupt``.

- **Routing** — while a journal is active for the driving thread's
  query (``active_journal()``), the planner lowers the plan's shuffle
  writers through the durable RSS tier under the journal's own run
  directory with deterministically assigned shuffle ids
  (``next_shuffle_id``: plan-walk order, identical across processes
  for identical plan bytes), so every shuffle stage is resumable.

- **Resume** — ``Session.resume(query_id)`` (and adoption of a
  matching journal by an identical re-submission under
  ``auron.journal.reuse``) re-plans from the journal's plan bytes,
  validates both fingerprint sets (mismatch → the classified
  ``JournalInvalidated``; stale state is garbage-collected, never
  believed), then lets each RSS exchange consult the journal: a
  fully-committed exchange is **satisfied** (map side skipped entirely,
  reducers fetch straight from the journaled files), a
  partially-committed hash/round-robin/single exchange skips exactly
  its committed maps, and everything else recomputes.  Resumed results
  are bit-identical to a fresh run, group order included — the RSS
  reducer read path is map-major and deterministic, and the engine is
  functional so recomputed maps rewrite identical bytes.

- **Sweep** — ``sweep_orphans`` garbage-collects journal artifacts of
  DEAD processes (pid+epoch liveness): ``.part`` temp files, journals
  that are not resumable (corrupt/torn-header), and RSS run
  directories whose journal is gone.  A dead process's *resumable*
  journal is deliberately KEPT — it is the resume inventory.

Fault sites (runtime/faults.py): ``journal.write`` / ``journal.commit``
(swallowed — journaling degrades to off for that query, the query
completes identically) and ``journal.load`` (classified).

Overhead contract: the hot path (enqueue + commit-drain/fsync waits) is
self-ledgered in ``hot_ns`` and gated <2% of query wall by
``tools/perf_gate.py --smoke`` — deterministic like the PR 9 scheduler
tax, immune to this container's wall-clock noise.
"""

from __future__ import annotations

import base64
import hashlib
import json
import logging
import os
import queue
import threading
import time
import zlib
from typing import Optional

from auron_tpu import errors

logger = logging.getLogger("auron_tpu")

#: journal format version; unknown versions are rejected as corrupt
#: (version skew must never be misread into a wrong resume decision)
VERSION = 1

#: record kinds: h header | x exchange | m map commit | c shuffle commit
_KINDS = ("h", "x", "m", "c")

#: newest resume reports (report_*.json) the startup sweep keeps
REPORT_RETENTION = 64


# ---------------------------------------------------------------------------
# record codec: one CRC-framed JSON record per line
# ---------------------------------------------------------------------------

def _encode(rec: dict) -> bytes:
    payload = json.dumps(rec, sort_keys=True,
                         separators=(",", ":")).encode()
    return b"%08x %s\n" % (zlib.crc32(payload) & 0xFFFFFFFF, payload)


def _decode_line(line: bytes):
    """(rec, ok): ok=False marks an undecodable line (caller decides
    whether it is a tolerable torn tail or corruption)."""
    try:
        crc_s, payload = line.split(b" ", 1)
        if int(crc_s, 16) != (zlib.crc32(payload) & 0xFFFFFFFF):
            return None, False
        return json.loads(payload), True
    except (ValueError, json.JSONDecodeError):
        return None, False


# ---------------------------------------------------------------------------
# fingerprints
# ---------------------------------------------------------------------------

def plan_fingerprint(plan_bytes: bytes) -> str:
    """Stable fingerprint of a serialized TaskDefinition (plan shape,
    expressions, partition counts — everything the proto carries)."""
    return hashlib.sha256(plan_bytes).hexdigest()[:32]


def _walk_plan(node, visit) -> None:
    """Pre-order walk over a PlanNode tree (the session host-fn walk's
    shape), calling ``visit(kind, inner)`` per node."""
    from auron_tpu.ir import pb
    kind = node.WhichOneof("node")
    if kind is None:
        return
    inner = getattr(node, kind)
    visit(kind, inner)
    for _f, sub in inner.ListFields():
        if isinstance(sub, pb.PlanNode):
            _walk_plan(sub, visit)
        elif hasattr(sub, "__iter__") and not isinstance(sub, (str, bytes)):
            for item in sub:
                if isinstance(item, pb.PlanNode):
                    _walk_plan(item, visit)


def _table_digest(tbl) -> str:
    """Bounded content digest of an Arrow table: CRC over the first
    4 KiB + length of every column buffer.  (schema, rows, nbytes)
    alone cannot tell two same-shape tables apart — fixed-width
    columns with different VALUES have identical byte counts — and a
    snapshot fingerprint that misses a content change would resume
    against different data."""
    crc = 0
    try:
        for col in tbl.columns:
            for chunk in col.chunks:
                for buf in chunk.buffers():
                    if buf is None:
                        continue
                    crc = zlib.crc32(memoryview(buf)[:4096], crc)
                    crc = zlib.crc32(
                        len(buf).to_bytes(8, "little"), crc)
    except Exception:   # noqa: BLE001 — exotic layout: degrade honest
        return "nodigest"
    return f"{crc & 0xFFFFFFFF:08x}"


def source_fingerprints(plan_bytes: bytes, catalog: dict) -> dict:
    """Snapshot fingerprints of every source the plan reads: file scans
    by (size, mtime_ns) — cheap stats that catch a rewrite — and
    catalog tables by (schema, rows, nbytes, sampled content CRC).  A
    source the process cannot see fingerprints as ``missing:`` so
    resume validation fails loudly instead of recomputing against
    different data."""
    from auron_tpu.ir import pb
    task = pb.TaskDefinition.FromString(plan_bytes)
    out: dict = {}

    def visit(kind, inner):
        if kind in ("parquet_scan", "orc_scan"):
            for path in inner.files:
                key = f"file:{path}"
                if key in out:
                    continue
                try:
                    st = os.stat(path)
                    out[key] = f"{st.st_size}:{st.st_mtime_ns}"
                except OSError:
                    out[key] = "missing:"
        elif kind == "memory_scan":
            name = inner.table_name
            key = f"table:{name}"
            if key in out:
                return
            tbl = catalog.get(name)
            if tbl is None:
                out[key] = "missing:"
            elif hasattr(tbl, "schema") and hasattr(tbl, "num_rows"):
                schema_fp = hashlib.sha256(
                    str(tbl.schema).encode()).hexdigest()[:12]
                out[key] = (f"{schema_fp}:{tbl.num_rows}"
                            f":{getattr(tbl, 'nbytes', 0)}"
                            f":{_table_digest(tbl)}")
            else:
                # per-partition RecordBatch lists (planner catalogs)
                try:
                    rows = sum(b.num_rows for part in tbl for b in part)
                except Exception:
                    rows = -1
                out[key] = f"batches:{rows}"

    _walk_plan(task.plan, visit)
    return out


def plan_has_host_fns(plan_bytes: bytes) -> bool:
    """Plans referencing host-fallback tables are excluded from
    journaling: their children execute as separate nested queries whose
    shuffle-id sequence a fresh process cannot replay."""
    from auron_tpu.ir import pb
    task = pb.TaskDefinition.FromString(plan_bytes)
    found = [False]

    def visit(kind, inner):
        if kind == "memory_scan" \
                and inner.table_name.startswith("__hostfn_"):
            found[0] = True

    _walk_plan(task.plan, visit)
    return found[0]


# ---------------------------------------------------------------------------
# process-level ledgers
# ---------------------------------------------------------------------------

_LEDGER_LOCK = threading.Lock()
#: journal stems currently OPEN (being written/resumed) in THIS process
#: — the reuse path must never adopt a journal another live query of
#: this process is driving, and the leak-audit fixture reads the count
_OPEN_STEMS: set = set()
#: every journal dir this process touched (the leak audit's glob roots)
_SEEN_DIRS: set = set()
#: stats of the most recently completed journal (the perf-gate smoke
#: arm reads them right after its journaled run finishes)
_LAST_STATS: dict = {}


def open_journal_count() -> int:
    with _LEDGER_LOCK:
        return len(_OPEN_STEMS)


def seen_dirs() -> list:
    with _LEDGER_LOCK:
        return sorted(_SEEN_DIRS)


def last_stats() -> dict:
    """Hot-path ledger of the most recently COMPLETED journal:
    {hot_ns, records, commits, maps_skipped, maps_recomputed,
    bytes_reused}."""
    with _LEDGER_LOCK:
        return dict(_LAST_STATS)


def _register_open(stem: str, path_dir: str) -> None:
    with _LEDGER_LOCK:
        _OPEN_STEMS.add(stem)
        _SEEN_DIRS.add(path_dir)


def _unregister_open(stem: str) -> None:
    with _LEDGER_LOCK:
        _OPEN_STEMS.discard(stem)


def _forget_open_stems() -> None:
    """TEST HOOK: simulate a process restart — every journal this
    process holds open becomes adoptable/resumable, exactly as if the
    process had died and a fresh one started."""
    with _LEDGER_LOCK:
        _OPEN_STEMS.clear()


# ---------------------------------------------------------------------------
# config resolution
# ---------------------------------------------------------------------------

def journal_dir(conf=None) -> str:
    from auron_tpu import config as cfg
    conf = conf or cfg.get_config()
    return conf.get(cfg.JOURNAL_DIR)


def enabled(conf=None) -> bool:
    return bool(journal_dir(conf))


def active_journal():
    """The driving thread's bound query journal (the planner's routing
    oracle); None when journaling is off or this query opted out."""
    from auron_tpu.runtime import lifecycle
    tok = lifecycle.current_token()
    return getattr(tok, "journal", None) if tok is not None else None


# ---------------------------------------------------------------------------
# the journal
# ---------------------------------------------------------------------------

class QueryJournal:
    """One query's crash-safe journal (see module docstring).

    Hot-path surface: ``next_shuffle_id`` / ``record_exchange`` (plan
    time), ``record_map`` (async append after each map-output rename),
    ``record_shuffle_commit`` (drain + flush + fsync — the durability
    boundary), the resume oracles ``satisfied``/``reusable_map``, and
    ``complete``/``suspend``.  All appends are swallowed-on-error: the
    journal degrades to disabled for this query (``journal.disable``
    event), never failing the query it exists to protect."""

    def __init__(self, path: str, query_id: str, plan_bytes: bytes,
                 num_partitions: int, plan_fp: str, sources: dict,
                 fsync: bool = True, resumed: bool = False,
                 state: Optional[dict] = None, scope: str = "collect"):
        self.path = path
        self.dir = os.path.dirname(path)
        self.stem = os.path.splitext(os.path.basename(path))[0]
        self.query_id = query_id
        self.plan_bytes = plan_bytes
        self.num_partitions = num_partitions
        self.plan_fp = plan_fp
        self.sources = sources
        self.fsync = fsync
        #: which partitions the journaled run DRIVES — "collect"
        #: (Session: the driver collects every partition 0..N-1) or
        #: "task" (serving SUBMIT: the host engine owns the partition
        #: fan-out, this journal covers exactly the task's own
        #: partition_id).  Resume must replay the same scope: a
        #: collect-scoped query resumed at task scope would silently
        #: drop every partition after the first.
        self.scope = scope
        #: True when this journal was loaded from disk (resume/adopt):
        #: only then do the resume oracles consult committed state
        self.resumed = resumed
        #: committed map outputs {(shuffle_id, map_id): {size, crc}}
        self.committed: dict = (state or {}).get("committed", {})
        #: shuffle-level commits {shuffle_id: num_maps}
        self.shuffle_commits: dict = (state or {}).get(
            "shuffle_commits", {})
        #: planned exchange DAG {shuffle_id: {maps, partitions, kind}}
        self.exchanges: dict = (state or {}).get("exchanges", {})
        #: the journal's own RSS run directory (all journal-routed
        #: shuffles of this query live under it)
        self.rss_root = os.path.join(self.dir, "rss", self.stem)
        self._shuffle_seq = 0
        self._seq_lock = threading.Lock()
        #: hot-path cost ledger (ns): enqueue + commit-drain waits —
        #: what the perf-gate smoke arm divides by wall
        self.hot_ns = 0
        self.records = 0
        self.commits = 0
        #: resume outcome ledger (per shuffle) for the report/tools
        self.resume_log: dict = {}
        self.maps_skipped = 0
        self.maps_recomputed = 0
        self.bytes_reused = 0
        self._failed = False
        self._closed = False
        #: True while this process holds the cross-process
        #: ``<stem>.claim`` (adoption/resume paths only)
        self._claimed = False
        self._file = None
        self._q: queue.Queue = queue.Queue()
        self._appender: Optional[threading.Thread] = None
        #: guards the lazy appender start: two partition drivers'
        #: FIRST records racing would spawn two threads draining one
        #: queue (and _stop_appender's single sentinel joins only one)
        self._appender_lock = threading.Lock()
        _register_open(self.stem, self.dir)

    # -- construction --------------------------------------------------------

    @classmethod
    def create(cls, dir_: str, query_id: str, plan_bytes: bytes,
               num_partitions: int, catalog: dict,
               conf=None, scope: str = "collect") -> Optional["QueryJournal"]:
        """Mint a fresh journal (header written + fsynced before any
        execution).  Returns None — journaling disabled for this query
        — when the header cannot be written: the journal must never
        fail the query it protects."""
        from auron_tpu import config as cfg
        from auron_tpu.utils import liveness
        conf = conf or cfg.get_config()
        stem = f"{query_id}_{os.getpid()}"
        path = os.path.join(dir_, f"{stem}.journal")
        jr = cls(path, query_id, plan_bytes, num_partitions,
                 plan_fingerprint(plan_bytes),
                 source_fingerprints(plan_bytes, catalog),
                 fsync=conf.get(cfg.JOURNAL_FSYNC), scope=scope)
        header = {
            "k": "h", "v": VERSION, "query_id": query_id,
            "owner": liveness.own_tag(),
            "plan_fp": jr.plan_fp, "sources": jr.sources,
            "num_partitions": num_partitions, "scope": scope,
            "plan_b64": base64.b64encode(plan_bytes).decode(),
            "created": time.time(),
        }
        try:
            from auron_tpu.runtime import faults
            faults.maybe_fail("journal.write", errors.JournalIOError)
            os.makedirs(dir_, exist_ok=True)
            os.makedirs(jr.rss_root, exist_ok=True)
            with open(os.path.join(jr.rss_root, ".owner"), "w") as f:
                f.write(liveness.own_tag())
            # header staged on a .part and RENAMED into place (the RSS
            # tier's commit discipline): a *.journal file therefore
            # NEVER exists with an empty/torn header, so a concurrent
            # process's startup sweep — which treats an unreadable-
            # header journal with no provable owner as a dead husk —
            # cannot unlink a live journal mid-create.  The appends
            # keep riding the same fd across the rename.
            jr._file = open(path + ".part", "ab")
            jr._file.write(_encode(header))
            jr._file.flush()
            if jr.fsync:
                os.fsync(jr._file.fileno())
            os.rename(path + ".part", path)
        except Exception as e:   # noqa: BLE001 — degrade, never fail
            logger.warning("query journal disabled for %s: header "
                           "write failed (%s)", query_id, e)
            jr._teardown_failed()
            return None
        return jr

    # -- plan-time routing ---------------------------------------------------

    def next_shuffle_id(self) -> int:
        """Deterministic shuffle-id assignment: plan-walk encounter
        order.  Identical plan bytes planned in a fresh process replay
        the identical sequence — the resume contract's key."""
        with self._seq_lock:
            sid = self._shuffle_seq
            self._shuffle_seq += 1
        return sid

    def begin_plan(self) -> None:
        """Reset the shuffle-id sequence for one planning pass (resume
        re-plans the same bytes and must re-assign the same ids)."""
        with self._seq_lock:
            self._shuffle_seq = 0

    def record_exchange(self, shuffle_id: int, num_maps: int,
                        num_partitions: int, kind: str) -> None:
        self.exchanges[shuffle_id] = {
            "maps": num_maps, "partitions": num_partitions, "kind": kind}
        self._append({"k": "x", "sid": shuffle_id, "maps": num_maps,
                      "partitions": num_partitions, "kind": kind})

    # -- commit-boundary records ---------------------------------------------

    def record_map(self, shuffle_id: int, map_id: int, size: int,
                   trailer_crc: int) -> None:
        """One committed map output (called AFTER the atomic rename —
        the journal never claims more than the durable tier holds)."""
        self.committed[(shuffle_id, map_id)] = {
            "size": size, "crc": trailer_crc}
        self._append({"k": "m", "sid": shuffle_id, "mid": map_id,
                      "size": size, "crc": trailer_crc})

    def record_shuffle_commit(self, shuffle_id: int,
                              num_maps: int) -> None:
        """Shuffle-level commit: drain the appender, flush, fsync —
        the journal's only durability waits (the <2% gate's subject;
        ``_append`` ledgers the enqueue + drain wait on ``hot_ns``
        itself — timing it here too would double-count the fsync)."""
        self.shuffle_commits[shuffle_id] = num_maps
        try:
            from auron_tpu.runtime import faults
            faults.maybe_fail("journal.commit", errors.JournalIOError)
            self._append({"k": "c", "sid": shuffle_id,
                          "maps": num_maps}, flush=True)
            self.commits += 1
        except Exception as e:   # noqa: BLE001 — degrade, never fail
            self._disable(e)

    # -- async appender ------------------------------------------------------

    def _append(self, rec: dict, flush: bool = False) -> None:
        if self._failed or self._closed:
            return
        t0 = time.perf_counter_ns()
        try:
            if self._appender is None:
                with self._appender_lock:
                    if self._appender is None:
                        self._appender = threading.Thread(
                            target=self._append_loop, daemon=True,
                            name=f"journal-{self.stem}")
                        self._appender.start()
            if flush:
                done = threading.Event()
                self._q.put((rec, done))
                done.wait(timeout=30.0)
            else:
                self._q.put((rec, None))
            self.records += 1
        finally:
            self.hot_ns += time.perf_counter_ns() - t0

    def _append_loop(self) -> None:
        from auron_tpu.runtime import faults
        while True:
            item = self._q.get()
            if item is None:
                return
            rec, done = item
            try:
                if not self._failed:
                    faults.maybe_fail("journal.write",
                                      errors.JournalIOError)
                    line = faults.maybe_corrupt("journal.write",
                                                _encode(rec))
                    self._file.write(line)
                    # flush EVERY record (appender thread — off the hot
                    # path): the page cache survives a SIGKILL, so a
                    # crash between shuffle commits still leaves the
                    # already-appended map records resumable; a record
                    # stuck in the USER-SPACE buffer would die with the
                    # process. fsync stays commit-only — map records
                    # claim only what the durable tier already holds,
                    # so losing them to a MACHINE crash just recomputes.
                    self._file.flush()
                    if done is not None and self.fsync:
                        os.fsync(self._file.fileno())
            except Exception as e:   # noqa: BLE001 — degrade
                self._disable(e)
            finally:
                if done is not None:
                    done.set()

    def _disable(self, exc) -> None:
        if self._failed:
            return
        self._failed = True
        logger.warning("query journal %s disabled mid-query (%s: %s) — "
                       "the query continues without resumability",
                       self.stem, type(exc).__name__, exc)
        try:
            from auron_tpu.obs import trace
            trace.event("journal", "journal.disable", stem=self.stem,
                        error=type(exc).__name__)
        except Exception:  # graft: disable=GL004 -- degrade-event tee is best-effort; the degrade itself already logged
            pass

    @property
    def failed(self) -> bool:
        return self._failed

    # -- resume oracles ------------------------------------------------------

    def _validate_map(self, service, shuffle_id: int,
                      map_id: int) -> Optional[int]:
        """Size of the committed map output when the journal record
        matches the on-storage file (existence + size + trailer CRC);
        None otherwise."""
        rec = self.committed.get((shuffle_id, map_id))
        if rec is None:
            return None
        stat = service.map_output_stat(shuffle_id, map_id)
        if stat is None:
            return None
        size, crc = stat
        if size != rec["size"] or crc != rec["crc"]:
            return None
        return size

    def satisfied(self, shuffle_id: int, num_maps: int,
                  service) -> bool:
        """Is this exchange fully committed AND intact on storage?  A
        satisfied exchange's map side is skipped; reducers fetch the
        journaled files directly."""
        if not self.resumed:
            return False
        if self.shuffle_commits.get(shuffle_id) != num_maps:
            return False
        if service.manifest_maps(shuffle_id) != num_maps:
            return False
        total = 0
        for m in range(num_maps):
            size = self._validate_map(service, shuffle_id, m)
            if size is None:
                return False
            total += size
        self.note_satisfied(shuffle_id, num_maps, total)
        return True

    def reusable_map(self, shuffle_id: int, map_id: int,
                     service) -> Optional[int]:
        """Map-level resume oracle for a partially-committed exchange:
        the committed size when this single map output can be skipped,
        else None (recompute)."""
        if not self.resumed:
            return None
        return self._validate_map(service, shuffle_id, map_id)

    def has_shuffle_state(self, shuffle_id: int) -> bool:
        """Does the journal carry ANY durable state for this exchange —
        a full shuffle commit or at least one committed map output?
        The planner's route oracle: a resumed exchange with journaled
        state must re-plan onto the RSS tier (where that state lives);
        one with none is free to take the current mesh fast path."""
        if shuffle_id in self.shuffle_commits:
            return True
        return any(sid == shuffle_id for sid, _ in self.committed)

    # -- resume ledger -------------------------------------------------------

    def _log_entry(self, shuffle_id: int) -> dict:
        return self.resume_log.setdefault(
            shuffle_id, {"satisfied": False, "maps_skipped": 0,
                         "maps_recomputed": 0, "bytes_reused": 0})

    def note_satisfied(self, shuffle_id: int, num_maps: int,
                       nbytes: int) -> None:
        e = self._log_entry(shuffle_id)
        e["satisfied"] = True
        e["maps_skipped"] = num_maps
        e["bytes_reused"] += nbytes
        self.maps_skipped += num_maps
        self.bytes_reused += nbytes

    def note_map_skipped(self, shuffle_id: int, nbytes: int) -> None:
        e = self._log_entry(shuffle_id)
        e["maps_skipped"] += 1
        e["bytes_reused"] += nbytes
        self.maps_skipped += 1
        self.bytes_reused += nbytes

    def note_map_recomputed(self, shuffle_id: int) -> None:
        self._log_entry(shuffle_id)["maps_recomputed"] += 1
        self.maps_recomputed += 1

    def stats(self) -> dict:
        return {"hot_ns": self.hot_ns, "records": self.records,
                "commits": self.commits,
                "maps_skipped": self.maps_skipped,
                "maps_recomputed": self.maps_recomputed,
                "bytes_reused": self.bytes_reused,
                "resume_log": {str(k): dict(v)
                               for k, v in self.resume_log.items()}}

    # -- lifecycle -----------------------------------------------------------

    def _stop_appender(self) -> None:
        if self._appender is not None:
            self._q.put(None)
            self._appender.join(timeout=10.0)
            self._appender = None
        if self._file is not None:
            try:
                self._file.flush()
                self._file.close()
            except Exception:  # graft: disable=GL004 -- closing a possibly-degraded journal; the degrade path logged the cause
                pass
            self._file = None

    def _release_cross_claim(self) -> None:
        if self._claimed:
            _release_claim(self.dir, self.stem)
            self._claimed = False

    def _teardown_failed(self) -> None:
        self._failed = True
        self._closed = True
        self._stop_appender()
        for p in (self.path, self.path + ".part"):
            try:
                if os.path.exists(p):
                    os.unlink(p)
            except OSError:
                pass
        self._release_cross_claim()
        _unregister_open(self.stem)

    def suspend(self) -> None:
        """The query failed in-process: flush and keep the journal on
        disk (an identical re-submission under ``auron.journal.reuse``
        — or a Session.resume — can pick the committed stages up), but
        release the open-stem claim so adoption is possible."""
        if self._closed:
            return
        self._closed = True
        self._stop_appender()
        self._release_cross_claim()
        _unregister_open(self.stem)

    def complete(self, write_report: bool = False) -> None:
        """The query finished: its journal and RSS run directory are
        garbage.  Optionally persists the resume report first (the
        tools/journal_report.py input for completed resumes)."""
        global _LAST_STATS
        if self._closed and not os.path.exists(self.path):
            return
        self._closed = True
        self._stop_appender()
        if write_report and (self.resumed or self.maps_skipped):
            try:
                report = {
                    "query_id": self.query_id, "stem": self.stem,
                    "plan_fp": self.plan_fp,
                    "exchanges": {str(k): dict(v)
                                  for k, v in self.exchanges.items()},
                    "stats": self.stats(),
                    "completed": time.time(),
                }
                rp = os.path.join(self.dir, f"report_{self.stem}.json")
                with open(rp, "w") as f:
                    json.dump(report, f, indent=1, sort_keys=True)
            except OSError:
                pass
        import shutil
        shutil.rmtree(self.rss_root, ignore_errors=True)
        try:
            os.unlink(self.path)
        except OSError:
            pass
        self._release_cross_claim()
        with _LEDGER_LOCK:
            _LAST_STATS = {
                "hot_ns": self.hot_ns, "records": self.records,
                "commits": self.commits,
                "maps_skipped": self.maps_skipped,
                "maps_recomputed": self.maps_recomputed,
                "bytes_reused": self.bytes_reused,
            }
            _OPEN_STEMS.discard(self.stem)
        try:
            from auron_tpu.obs import trace
            trace.event("journal", "journal.complete", stem=self.stem,
                        maps_skipped=self.maps_skipped,
                        maps_recomputed=self.maps_recomputed,
                        bytes_reused=self.bytes_reused)
        except Exception:  # graft: disable=GL004 -- completion-event tee is best-effort
            pass


# ---------------------------------------------------------------------------
# load / resume / reuse
# ---------------------------------------------------------------------------

def _read_records(path: str):
    """(header, records, valid_len) of a journal file — ``valid_len``
    is the byte length of the intact prefix, which the adopt/resume
    reopen truncates to before appending (appending AFTER torn bytes
    would fuse them with the next record into one CRC-invalid interior
    line, turning a second crash into JournalCorrupt instead of a
    clean tail drop).  Raises JournalCorrupt on an unreadable header,
    unknown version, or a corrupt interior line; a torn FINAL line
    (crash mid-append — no trailing newline) is dropped silently."""
    from auron_tpu.runtime import faults
    try:
        faults.maybe_fail("journal.load", errors.JournalIOError)
        with open(path, "rb") as f:
            data = f.read()
    except (OSError, errors.JournalIOError) as e:
        # an unreadable journal and a corrupt one get the SAME verdict:
        # the inventory is not trustworthy, the safe recovery is a
        # fresh run (resume surfaces it; reuse falls back silently)
        raise errors.JournalCorrupt(
            f"journal unreadable: {path} ({e})", reason="corrupt",
            site="journal.load") from e
    data = faults.maybe_corrupt("journal.load", data)
    lines = data.split(b"\n")
    torn_tail_len = 0
    if not data.endswith(b"\n"):
        # the crash-interrupted final fragment: dropped WHOLE even if
        # it happens to CRC (a record missing only its newline would
        # otherwise fuse with the next append)
        torn_tail_len = len(lines[-1])
        lines = lines[:-1]
    body = [ln for ln in lines if ln]
    if not body:
        raise errors.JournalCorrupt(f"journal empty: {path}",
                                    reason="corrupt",
                                    site="journal.load")
    header, ok = _decode_line(body[0])
    if not ok or header.get("k") != "h":
        raise errors.JournalCorrupt(
            f"journal header corrupt: {path}", reason="corrupt",
            site="journal.load")
    if header.get("v") != VERSION:
        raise errors.JournalCorrupt(
            f"journal version skew: {path} carries v"
            f"{header.get('v')!r}, this engine reads v{VERSION} — "
            "rejected, not misread", reason="corrupt",
            site="journal.load")
    records = []
    for i, ln in enumerate(body[1:], start=1):
        rec, ok = _decode_line(ln)
        if not ok:
            raise errors.JournalCorrupt(
                f"journal record {i} corrupt: {path}",
                reason="corrupt", site="journal.load")
        records.append(rec)
    return header, records, len(data) - torn_tail_len


def _load(path: str, conf=None) -> QueryJournal:
    """Parse one journal file into a resumed QueryJournal (no
    fingerprint validation here — see load_for_resume)."""
    from auron_tpu import config as cfg
    conf = conf or cfg.get_config()
    header, records, valid_len = _read_records(path)
    state = {"committed": {}, "shuffle_commits": {}, "exchanges": {}}
    for rec in records:
        k = rec.get("k")
        if k == "m":
            state["committed"][(rec["sid"], rec["mid"])] = {
                "size": rec["size"], "crc": rec["crc"]}
        elif k == "c":
            state["shuffle_commits"][rec["sid"]] = rec["maps"]
        elif k == "x":
            state["exchanges"][rec["sid"]] = {
                "maps": rec["maps"], "partitions": rec["partitions"],
                "kind": rec["kind"]}
    try:
        plan_bytes = base64.b64decode(header["plan_b64"])
    except (KeyError, ValueError) as e:
        raise errors.JournalCorrupt(
            f"journal plan bytes unreadable: {path}", reason="corrupt",
            site="journal.load") from e
    jr = QueryJournal(path, header.get("query_id", ""), plan_bytes,
                      # graft: disable=GL001 -- a JSON header field, host data
                      int(header.get("num_partitions", 1)),
                      header.get("plan_fp", ""),
                      header.get("sources", {}),
                      fsync=conf.get(cfg.JOURNAL_FSYNC), resumed=True,
                      state=state, scope=header.get("scope", "collect"))
    jr.owner = header.get("owner", "")
    jr._valid_len = valid_len
    return jr


def _owner_is_other_live_process(owner: str) -> bool:
    """True when a journal's header names a DIFFERENT process that is
    still alive — the cross-process complement of the in-process
    ``_OPEN_STEMS`` claim: such a journal may still be actively driven
    (its suspend/complete state is unknowable from here), so adoption
    and resume must refuse it.  This very process's own tag — the
    suspended-after-in-process-failure case — and dead owners are both
    fair game."""
    from auron_tpu.utils import liveness
    return bool(owner) and owner != liveness.own_tag() \
        and liveness.is_live(owner)


def _peek_header(path: str) -> Optional[dict]:
    """Best-effort decode of a journal (or ``.part`` staging) file's
    first line — the header carries owner/plan_fp/scope, letting hot
    paths screen candidates WITHOUT the full read+CRC+base64 of
    ``_load``; None when the header is unreadable/torn."""
    try:
        with open(path, "rb") as f:
            line = f.readline().rstrip(b"\n")
    except OSError:
        return None
    rec, ok = _decode_line(line)
    if ok and isinstance(rec, dict) and rec.get("k") == "h":
        return rec
    return None


def _try_read_owner(path: str) -> str:
    """Best-effort owner tag from a journal (or ``.part`` staging)
    file's first line; '' when the header is unreadable/torn."""
    header = _peek_header(path)
    return header.get("owner", "") if header else ""


def _claim_stem(dir_: str, stem: str) -> bool:
    """Cross-process adoption/resume claim: atomically create
    ``<stem>.claim`` (O_EXCL) naming this process.  The in-process
    ``_OPEN_STEMS`` set cannot arbitrate BETWEEN processes sharing a
    journal dir — without this, two processes resuming/adopting one
    dead owner's journal would interleave appenders in one file and
    race complete()'s rss_root rmtree.  A dead claimer's stale claim
    is broken (liveness-checked) and retried once; released via
    ``_release_claim`` on every journal unwind."""
    from auron_tpu.utils import liveness
    path = os.path.join(dir_, f"{stem}.claim")
    for _ in range(2):
        try:
            fd = os.open(path, os.O_CREAT | os.O_EXCL | os.O_WRONLY)
            try:
                os.write(fd, liveness.own_tag().encode())
            finally:
                os.close(fd)
            return True
        except FileExistsError:
            try:
                with open(path) as f:
                    owner = f.read().strip()
            except OSError:
                continue   # claimer mid-write or just released: retry
            if owner == liveness.own_tag() or not liveness.is_live(owner):
                try:
                    os.unlink(path)   # stale (dead claimer) / our own
                except OSError:
                    pass
                continue
            return False   # another LIVE process holds the claim
        except OSError:
            return False
    return False


def _release_claim(dir_: str, stem: str) -> None:
    try:
        os.unlink(os.path.join(dir_, f"{stem}.claim"))
    except OSError:
        pass


def _reopen_for_append(jr: QueryJournal) -> None:
    """Open a LOADED journal for continued appends, truncating the
    crash-torn trailing fragment (if any) first — see _read_records."""
    valid = getattr(jr, "_valid_len", None)
    try:
        if valid is not None and os.path.getsize(jr.path) > valid:
            with open(jr.path, "rb+") as f:
                f.truncate(valid)
    except OSError:   # heal is best-effort; the append may still work
        pass
    jr._file = open(jr.path, "ab")


def _candidates(dir_: str, query_id: str) -> list:
    """Journal paths whose stem matches ``query_id`` (exact stem or the
    ``<qid>_<pid>`` form a fresh process must find)."""
    try:
        names = os.listdir(dir_)
    except OSError:
        return []
    out = []
    for n in sorted(names):
        if not n.endswith(".journal"):
            continue
        stem = n[:-len(".journal")]
        if stem == query_id or stem.rsplit("_", 1)[0] == query_id:
            out.append(os.path.join(dir_, n))
    return out


def load_for_resume(dir_: str, query_id: str, catalog: dict,
                    conf=None) -> QueryJournal:
    """Load + validate the journal behind ``query_id`` for resumption.

    Raises the classified taxonomy: ResumeUnavailable (no/ambiguous
    journal, journaling disabled, missing sources), JournalCorrupt
    (unreadable/version-skewed/CRC-failed), JournalInvalidated
    (fingerprint mismatch — the stale journal AND its RSS run dir are
    garbage-collected so the wrong answer can never be produced)."""
    if not dir_:
        raise errors.ResumeUnavailable(
            "journaling is disabled (auron.journal.dir is empty)",
            query_id=query_id, reason="journaling_disabled")
    cands = _candidates(dir_, query_id)
    if not cands:
        raise errors.ResumeUnavailable(
            f"no journal for query {query_id!r} under {dir_} (unknown "
            "id, or the query completed and its journal was deleted)",
            query_id=query_id, reason="no_journal")
    if len(cands) > 1:
        # query ids recycle across process restarts (serving's per-
        # process counter: server A's crashed 'serving-1' and server
        # B's LIVE 'serving-1' coexist as different stems) — candidates
        # another live process owns would be refused with reason='open'
        # anyway, so they cannot make the id ambiguous; only a tie
        # among genuinely-resumable journals does
        resumable = [c for c in cands
                     if not _owner_is_other_live_process(
                         _try_read_owner(c))]
        if len(resumable) != 1:
            raise errors.ResumeUnavailable(
                f"query id {query_id!r} is ambiguous under {dir_}: "
                f"{[os.path.basename(c) for c in (resumable or cands)]}",
                query_id=query_id, reason="ambiguous")
        cands = resumable
    path = cands[0]
    stem = os.path.splitext(os.path.basename(path))[0]
    # check-and-CLAIM atomically: two concurrent resumes of one query
    # id must never both pass the gate and double-drive the journal
    # (separate appender handles interleaving one file, one complete()
    # rmtree-ing the rss_root under the other's reducers)
    with _LEDGER_LOCK:
        if stem in _OPEN_STEMS:
            raise errors.ResumeUnavailable(
                f"journal {stem} is open in this process (the query is "
                "still running)", query_id=query_id, reason="open")
        _OPEN_STEMS.add(stem)
        _SEEN_DIRS.add(dir_)
    # ...and the CROSS-process half of the same gate: the stem ledger
    # dies with its process, so concurrent resumes from two surviving
    # processes arbitrate through an O_EXCL claim file instead
    if not _claim_stem(dir_, stem):
        _unregister_open(stem)
        raise errors.ResumeUnavailable(
            f"journal {stem} is claimed by another live process",
            query_id=query_id, reason="open")
    try:
        jr = _load(path, conf)
    except BaseException:
        _release_claim(dir_, stem)
        _unregister_open(stem)   # suspend/_teardown below release the
        raise                    # claim; a failed load must too
    jr._claimed = True
    if _owner_is_other_live_process(getattr(jr, "owner", "")):
        # the stem ledger is per-process; on a SHARED journal dir the
        # header's owner tag is the cross-process half of the same
        # guard — another live process may still be driving this query
        jr.suspend()
        raise errors.ResumeUnavailable(
            f"journal {stem} is owned by a live process "
            f"({jr.owner}) — the query may still be running there",
            query_id=query_id, reason="open")
    live_fps = source_fingerprints(jr.plan_bytes, catalog)
    if any(v == "missing:" for v in live_fps.values()):
        missing = sorted(k for k, v in live_fps.items()
                         if v == "missing:")
        jr.suspend()
        raise errors.ResumeUnavailable(
            f"cannot re-bind sources for query {query_id!r}: "
            f"{missing} (register the catalog tables / restore the "
            "files before resuming)", query_id=query_id,
            reason="missing_source")
    if live_fps != jr.sources:
        changed = sorted(k for k in set(live_fps) | set(jr.sources)
                         if live_fps.get(k) != jr.sources.get(k))
        # stale state must never be believed NOR linger: GC it
        jr._teardown_failed()
        import shutil
        shutil.rmtree(jr.rss_root, ignore_errors=True)
        raise errors.JournalInvalidated(
            f"journal {stem} snapshot fingerprints no longer match the "
            f"live sources ({changed}): the journaled shuffle outputs "
            "were computed from different data — invalidated, run "
            "fresh", query_id=query_id, reason="fingerprint_mismatch")
    try:
        from auron_tpu.obs import trace
        trace.event("journal", "journal.resume", stem=stem,
                    shuffles_committed=len(jr.shuffle_commits),
                    maps_committed=len(jr.committed))
    except Exception:  # graft: disable=GL004 -- resume-event tee is best-effort
        pass
    return jr


def resume_inventory(dir_: str) -> list:
    """The router's failover inventory: every journal under ``dir_``
    summarized from its header line alone (``_peek_header`` — no full
    read/CRC/base64), with a liveness verdict per owner.  A fleet
    router scrapes this to answer "which crashed queries can a survivor
    RESUME, and under which stem?" without importing any engine state.
    Entries whose owner is still alive are included (flagged) so the
    caller can distinguish in-flight from resumable; torn headers are
    skipped — an unreadable journal is not inventory."""
    from auron_tpu.utils import liveness
    out = []
    try:
        names = sorted(os.listdir(dir_))
    except OSError:
        return out
    for n in names:
        if not n.endswith(".journal"):
            continue
        stem = n[:-len(".journal")]
        header = _peek_header(os.path.join(dir_, n))
        if header is None:
            continue
        owner = header.get("owner", "")
        out.append({
            "stem": stem,
            "query_id": header.get("query_id", stem),
            "owner": owner,
            "owner_alive": bool(owner) and liveness.is_live(owner),
            "claimed": os.path.exists(
                os.path.join(dir_, f"{stem}.claim")),
            "plan_fp": header.get("plan_fp", ""),
            "num_partitions": int(header.get("num_partitions", 1)),   # graft: disable=GL001 -- JSON header field, host data
            "scope": header.get("scope", "collect"),
        })
    return out


def find_reusable(dir_: str, plan_bytes: bytes, catalog: dict,
                  conf=None, scope: str = "collect") -> Optional[QueryJournal]:
    """The ``auron.journal.reuse`` path: an existing resumable journal
    whose plan AND source fingerprints — and driving ``scope`` — match
    ``plan_bytes``, adopted by an identical re-submission.  Every
    failure mode (corrupt, open, mismatch) falls back to None = fresh
    run; never a wrong answer.

    The screening itself lives in ``cache/identity.py`` — ONE
    implementation of "same plan over the same data" shared with the
    warm-path result cache, so journal adoption and cache lookup can
    never drift apart about staleness."""
    from auron_tpu.cache import identity
    fp = plan_fingerprint(plan_bytes)
    try:
        names = sorted(os.listdir(dir_))
    except OSError:
        return None
    probe = identity.SourceProbe(plan_bytes, catalog)
    for n in names:
        if not n.endswith(".journal"):
            continue
        stem = n[:-len(".journal")]
        path = os.path.join(dir_, n)
        # header screen BEFORE the full load: every journaled
        # submission scans the whole pending inventory here, and
        # _load is a full read + per-record CRC + base64 plan decode —
        # the one-line header already names plan_fp/scope/owner, which
        # rejects nearly every candidate for pennies (mismatches are
        # re-checked authoritatively after the load)
        if not identity.screen_header(_peek_header(path), fp, scope):
            continue
        # check-and-CLAIM atomically (the load_for_resume discipline):
        # two identical concurrent re-submissions must never both
        # adopt one journal — the loser of the claim mints fresh
        with _LEDGER_LOCK:
            if stem in _OPEN_STEMS:
                continue
            _OPEN_STEMS.add(stem)
            _SEEN_DIRS.add(dir_)
        # the cross-process half (O_EXCL claim file): the stem ledger
        # cannot see another surviving process's adoption in flight
        if not _claim_stem(dir_, stem):
            _unregister_open(stem)
            continue
        try:
            jr = _load(path, conf)
        except errors.JournalError as e:
            logger.warning("journal reuse skipped %s: %s", n, e)
            _release_claim(dir_, stem)
            _unregister_open(stem)
            continue
        jr._claimed = True
        if not identity.screen_loaded(jr, fp, scope):
            # a scope mismatch (a serving task adopting a Session
            # collect journal or vice versa) would re-head the file
            # with the WRONG replay contract for a later crash-resume;
            # a live FOREIGN owner may still be driving the query —
            # adopting it would interleave two appenders in one file
            # and race its complete()'s rss_root rmtree
            jr.suspend()
            continue
        if not probe.matches(jr.sources):
            logger.warning(
                "journal reuse skipped %s: source fingerprints "
                "changed — stale journal invalidated", n)
            jr._teardown_failed()
            import shutil
            shutil.rmtree(jr.rss_root, ignore_errors=True)
            continue
        # adopt: re-open the file for continued appends (healing a
        # torn tail so new records never fuse with crash debris)
        try:
            _reopen_for_append(jr)
        except OSError as e:
            logger.warning("journal reuse skipped %s: %s", n, e)
            jr.suspend()
            continue
        try:
            from auron_tpu.obs import trace
            trace.event("journal", "journal.reuse", stem=stem,
                        shuffles_committed=len(jr.shuffle_commits))
        except Exception:  # graft: disable=GL004 -- reuse-event tee is best-effort
            pass
        return jr
    return None


# ---------------------------------------------------------------------------
# session/serving glue
# ---------------------------------------------------------------------------

def begin(token, plan_bytes: bytes, num_partitions: int, catalog: dict,
          conf=None, scope: str = "collect") -> Optional[QueryJournal]:
    """Open (adopt or mint) the journal for one top-level query and
    bind it to the query's CancelToken. None = journaling off for this
    query (disarmed, host-fn plan, or a degraded header write)."""
    from auron_tpu import config as cfg
    conf = conf or cfg.get_config()
    dir_ = journal_dir(conf)
    if not dir_:
        return None
    if plan_has_host_fns(plan_bytes):
        logger.info("query %s not journaled: plan references host-"
                    "fallback tables", getattr(token, "query_id", "?"))
        return None
    jr = None
    if conf.get(cfg.JOURNAL_REUSE):
        jr = find_reusable(dir_, plan_bytes, catalog, conf, scope=scope)
        if jr is not None:
            _register_open(jr.stem, dir_)
    if jr is None:
        jr = QueryJournal.create(dir_, token.query_id, plan_bytes,
                                 num_partitions, catalog, conf,
                                 scope=scope)
    if jr is not None:
        token.journal = jr
        jr.begin_plan()
    return jr


def attach_resumed(token, jr: QueryJournal) -> QueryJournal:
    """Bind an already-loaded (resume-path) journal to the resuming
    query's token and re-open it for continued appends (healing a
    torn tail so new records never fuse with crash debris)."""
    if jr._file is None:
        _reopen_for_append(jr)
    jr._closed = False
    _register_open(jr.stem, jr.dir)
    token.journal = jr
    jr.begin_plan()
    return jr


# ---------------------------------------------------------------------------
# startup orphan sweep
# ---------------------------------------------------------------------------

_SWEPT_DIRS_LOCK = threading.Lock()
_SWEPT_DIRS: set = set()


def sweep_orphans(dir_: str, force: bool = False) -> int:
    """Garbage-collect journal artifacts of DEAD processes under
    ``dir_`` (once per process per dir unless ``force``):

    - ``*.part`` / stray temp files of dead owners,
    - ``*.claim`` adoption/resume claims whose claimer died mid-run,
    - journals that are NOT resumable (corrupt/torn header) with a
      dead owner — a resumable dead-owner journal is KEPT: it is the
      resume inventory, capped by ``auron.journal.retention_s`` (aged
      inventory nobody resumes GCs along with its RSS run dir),
    - ``rss/<stem>`` run directories whose journal file is gone and
      whose ``.owner`` tag is dead (a completed query removes its own;
      these are crash leftovers past their journal's deletion),
    - ``report_*.json`` resume reports beyond the newest
      ``REPORT_RETENTION`` (they are pure telemetry for
      tools/journal_report.py; without a cap a long-lived deployment
      grows one per resumed query forever).

    Returns how many artifacts were removed; counted on
    ``auron_journal_orphans_swept_total``."""
    import shutil

    from auron_tpu.utils import liveness
    if not dir_ or not os.path.isdir(dir_):
        return 0
    with _SWEPT_DIRS_LOCK:
        if dir_ in _SWEPT_DIRS and not force:
            return 0
        _SWEPT_DIRS.add(dir_)
    from auron_tpu import config as cfg
    # graft: disable=GL001 -- a configuration value, host data
    retention_s = float(cfg.get_config().get(cfg.JOURNAL_RETENTION_S))
    now = time.time()

    def _mtime(p: str) -> float:
        try:
            return os.path.getmtime(p)
        except OSError:
            return now   # unknowable age: conservative = fresh
    removed = 0
    live_stems = set()
    for n in sorted(os.listdir(dir_)):
        path = os.path.join(dir_, n)
        if n.endswith(".journal"):
            stem = n[:-len(".journal")]
            try:
                header = _read_records(path)[0]
                owner = header.get("owner", "")
                resumable = True
            except errors.JournalError as e:
                if isinstance(e.__cause__,
                              (OSError, errors.JournalIOError)):
                    # could not READ the file just now (transient IO,
                    # injected journal.load fault) — that is not proof
                    # of a husk; keep it, a later sweep decides
                    live_stems.add(n[:-len(".journal")])
                    continue
                # corrupt journal: salvage the owner from the header
                # line if it survived — a LIVE owner's corrupt-interior
                # journal (e.g. an injected journal.write corrupt
                # fault) is the owner's to reclaim, not ours to sweep
                owner, resumable = _try_read_owner(path), False
            if resumable and (not owner or liveness.is_live(owner)):
                live_stems.add(stem)
                continue
            if resumable and owner and not liveness.is_live(owner):
                # dead owner, resumable: KEEP — the resume inventory —
                # unless it has aged past auron.journal.retention_s
                # (mtime = last append = the crash/suspend instant):
                # inventory nobody resumes must not hold journal + RSS
                # shuffle bytes forever
                if 0 < retention_s < now - _mtime(path):
                    try:
                        os.unlink(path)
                        removed += 1
                    except OSError:
                        live_stems.add(stem)
                    continue
                live_stems.add(stem)
                continue
            # not resumable: with a live owner the writer may be mid-
            # header; only a dead (or unknowable) owner's husk sweeps
            if owner and liveness.is_live(owner):
                live_stems.add(stem)
                continue
            try:
                os.unlink(path)
                removed += 1
            except OSError:
                pass
        elif n.endswith(".part"):
            # a ``<stem>.journal.part`` may be a LIVE process's header
            # staging file (QueryJournal.create writes+flushes the
            # header there before the atomic rename): once the header
            # hits the file its owner is readable — keep the live
            # owner's.  An unparseable .part is swept; the remaining
            # open→first-flush window is microseconds and losing the
            # race merely degrades that query's journaling (create's
            # rename fails → logged fresh-run posture, never a wrong
            # answer).
            owner = _try_read_owner(path)
            if owner and liveness.is_live(owner):
                continue
            try:
                os.unlink(path)
                removed += 1
            except OSError:
                pass
        elif n.endswith(".claim"):
            # adoption/resume claim whose claimer died mid-run: the
            # claim breaks lazily on the next _claim_stem anyway, this
            # just keeps the dir tidy (a LIVE claimer's is kept)
            try:
                with open(path) as f:
                    claimer = f.read().strip()
            except OSError:
                continue
            # an EMPTY tag is a claimer between its O_EXCL create and
            # the tag write — treat as live (is_live's conservative
            # default, and what _claim_stem itself does); the lazy
            # break in _claim_stem handles genuinely dead claimers
            if not claimer or liveness.is_live(claimer):
                continue
            try:
                os.unlink(path)
                removed += 1
            except OSError:
                pass
    reports = [os.path.join(dir_, n) for n in os.listdir(dir_)
               if n.startswith("report_") and n.endswith(".json")]
    if len(reports) > REPORT_RETENTION:
        reports.sort(key=lambda p: (os.path.getmtime(p)
                                    if os.path.exists(p) else 0))
        for p in reports[:-REPORT_RETENTION]:
            try:
                os.unlink(p)
                removed += 1
            except OSError:
                pass
    rss_dir = os.path.join(dir_, "rss")
    if os.path.isdir(rss_dir):
        for stem in sorted(os.listdir(rss_dir)):
            if stem in live_stems:
                continue
            run_dir = os.path.join(rss_dir, stem)
            if not os.path.isdir(run_dir):
                continue
            owner = ""
            try:
                with open(os.path.join(run_dir, ".owner")) as f:
                    owner = f.read().strip()
            except OSError:
                pass
            if owner and liveness.is_live(owner):
                continue
            shutil.rmtree(run_dir, ignore_errors=True)
            removed += 1
    liveness.note_swept("auron_journal_orphans_swept_total", removed,
                        dir_, "journal")
    return removed
