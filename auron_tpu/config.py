"""Typed configuration registry.

The reference runs a three-layer config system: typed ``ConfigOption``
declarations with defaults and docs (reference:
auron-core/src/main/java/org/apache/auron/configuration/ConfigOption.java),
a Spark binding exposing ~70 ``spark.auron.*`` options (reference:
spark-extension/src/main/java/org/apache/spark/sql/auron/
SparkAuronConfiguration.java:42-526), and a native mirror that reads
through JNI at use-site so the host config is the single source of truth
(reference: native-engine/auron-jni-bridge/src/conf.rs:20-63).

Here the same shape, TPU-side: declared options with defaults + docs,
resolved at use-site through ``AuronConfig.get`` with precedence

    session/programmatic override  >  env var  >  default

Env binding: ``auron.agg.partial_skip.ratio`` ←
``AURON_CONF_AGG_PARTIAL_SKIP_RATIO`` (prefix stripped, dots → ``_``,
upper-cased). ``generate_docs()`` emits the markdown config reference
(the reference generates docs the same way:
SparkAuronConfigurationDocGenerator.java).
"""

from __future__ import annotations

import os
import threading
from dataclasses import dataclass
from typing import Any, Optional


@dataclass(frozen=True)
class ConfigOption:
    key: str
    dtype: type           # int | float | bool | str
    default: Any
    doc: str

    @property
    def env_var(self) -> str:
        return "AURON_CONF_" + self.key.replace("auron.", "", 1) \
            .replace(".", "_").upper()

    def parse(self, raw: str) -> Any:
        if self.dtype is bool:
            v = raw.strip().lower()
            if v in ("1", "true", "yes", "on"):
                return True
            if v in ("0", "false", "no", "off"):
                return False
            raise ValueError(f"{self.key}: invalid bool {raw!r}")
        return self.dtype(raw)


_REGISTRY: dict[str, ConfigOption] = {}


def _opt(key: str, dtype: type, default, doc: str) -> str:
    assert key not in _REGISTRY, f"duplicate config option {key}"
    _REGISTRY[key] = ConfigOption(key, dtype, default, doc)
    return key


# --------------------------------------------------------------------------
# option declarations (grouped like the reference's config sections)
# --------------------------------------------------------------------------

# batching / shapes
BATCH_CAPACITY = _opt(
    "auron.batch.capacity", int, 1 << 16,
    "Default rows per device batch (scan batch size and the planner's "
    "capacity bucketing target). Larger batches amortize kernel launches; "
    "smaller ones reduce padding waste on ragged inputs.")
PARQUET_BATCH_ROWS = _opt(
    "auron.io.parquet.batch_rows", int, 1 << 16,
    "Row-group read granularity for the parquet/ORC scans when the plan "
    "does not pin batch_rows explicitly and auron.scan.batch_rows is 0 "
    "on a non-CPU platform (legacy knob; auron.scan.batch_rows wins "
    "when set).")
SCAN_BATCH_ROWS = _opt(
    "auron.scan.batch_rows", int, 0,
    "Rows per file-scan device batch (parquet/ORC). 0 (default) = auto: "
    "2^17 on the CPU mesh — larger batches amortize the per-batch host "
    "glue that dominates CPU throughput (PERF.md 'Pipelined "
    "execution') — and auron.io.parquet.batch_rows (2^16) on "
    "accelerators. The scan clamps the conversion capacity to the "
    "partition's actual row count bucket, so small files never pad to "
    "the full batch size. One flag for batch-size experiments.")

# prefetching scan (io/parquet.ScanPrefetcher)
SCAN_PREFETCH_BATCHES = _opt(
    "auron.scan.prefetch_batches", int, 2,
    "Decoded-batch lookahead of the prefetching file scan (bounded "
    "queue depth between the background decode worker and the drive "
    "loop). The prefetcher registers "
    "its buffered decoded bytes with the memory manager and degrades "
    "to depth 1 while the pressure ladder's shrink rung is active. "
    "<= 1 keeps the decode worker but no lookahead beyond the batch "
    "in flight.")

# SPMD mesh execution plane (parallel/mesh.py)
MESH_ENABLED = _opt(
    "auron.mesh.enabled", bool, False,
    "SPMD execution plane (parallel/mesh.py): Session init builds a "
    "jax Mesh/NamedSharding layout over the visible devices and eligible "
    "hash-repartition exchanges lower to the on-device "
    "lax.all_to_all stage program (parallel/mesh_exchange.py) — the "
    "fused stage chain, the partition-id compute, the sort-by-pid split "
    "and the collective run as ONE shard_map program partition-parallel "
    "across all mesh devices, fencing once at the output boundary. "
    "Ineligible exchanges (range/round-robin/single partitioning, fan-in "
    "wider than the mesh) keep the host-orchestrated device-buffer path; "
    "RSS stays the durable/multihost tier. The route taken is recorded "
    "per exchange in the metric tree (exchange_route_* counters) and the "
    "trace ('mesh' category exchange.route events — "
    "tools/mesh_report.py). A device lost mid-exchange "
    "(errors.MeshUnavailable) demotes the remaining rounds to the host "
    "path and quarantines the chip (auron.mesh.quarantine) — the plane "
    "degrades, never the query. PROCESS-GLOBAL by contract (the device "
    "set is process state): resolved from "
    "get_config(), per-Session overrides are not honored. Default off; "
    "tests/bench force a virtual CPU mesh via "
    "--xla_force_host_platform_device_count.")
MESH_DEVICES = _opt(
    "auron.mesh.devices", int, 0,
    "Devices in the SPMD mesh; 0 (default) = every device jax exposes. "
    "An exchange with num_partitions <= this width runs on the leading "
    "submesh of exactly num_partitions devices (one output partition "
    "per device — the all-to-all's square contract); wider exchanges "
    "fall back to the host device-buffer route, recorded per exchange.")
MESH_AXIS = _opt(
    "auron.mesh.axis", str, "data",
    "Name of the mesh's single batch-sharding axis (the PartitionSpec "
    "axis scan batches shard over; broadcast relations and hash-table "
    "build sides replicate — parallel/mesh.buffer_spec).")
MESH_STRAGGLER_FACTOR = _opt(
    "auron.mesh.straggler_factor", float, 4.0,
    "Straggler defense of the SPMD plane: an all-to-all round slower "
    "than this factor times the rolling per-round p50 (the plane's "
    "MeshRoundStats window, armed after a few observed rounds) emits a "
    "mesh.straggler trace event and counts on "
    "auron_mesh_stragglers_total — one slow chip becomes an observable "
    "signal instead of an invisible latency spike on every query in the "
    "gang queue. With auron.mesh.demote_on_straggler it also triggers "
    "the same mid-exchange route demotion a device loss does. "
    "<= 0 disables the detector.")
MESH_DEMOTE_ON_STRAGGLER = _opt(
    "auron.mesh.demote_on_straggler", bool, False,
    "Escalate a detected straggler round (auron.mesh.straggler_factor) "
    "from an observable event to the demotion path: the exchange's "
    "REMAINING rounds re-route through the host device-buffer tier — "
    "the completed slow round's received rows stay valid on the mesh — "
    "so one slow chip degrades throughput instead of latency-spiking "
    "the whole gang queue. Default off: stragglers are reported, not "
    "acted on (a transient OS hiccup would otherwise demote a healthy "
    "mesh).")
MESH_QUARANTINE = _opt(
    "auron.mesh.quarantine", bool, True,
    "On a device loss (errors.MeshUnavailable mid-exchange), record the "
    "failed device in the MeshPlane's quarantine set: subsequent "
    "exchanges rebuild a smaller submesh from the remaining healthy "
    "devices when the square contract (num_partitions == submesh "
    "width) still holds, and route host-side otherwise — the rest of "
    "the query keeps running without ever re-entering the dead chip. "
    "When XLA's error carries no device identity, the tail device of "
    "the failed submesh is retired (deterministic; a wrongly blamed "
    "healthy chip costs one device of capacity, never correctness). "
    "Off demotes the failing exchange but leaves the plane's device "
    "set intact (the next exchange will try the full mesh again).")

# concurrent query scheduler (runtime/scheduler.py)
SCHED_MAX_CONCURRENT = _opt(
    "auron.sched.max_concurrent", int, 4,
    "Queries one scheduler (one Session / one AuronServer) runs "
    "concurrently. Further admitted queries wait in the bounded run "
    "queue (auron.sched.queue_depth); nested executes (host-fn "
    "children, scalar subqueries) inherit the enclosing query's slot "
    "and NEVER queue — queueing a child while its parent holds a slot "
    "would deadlock the pair. Also the divisor of the automatic "
    "per-query memory quota (auron.memmgr.query_quota_bytes = 0).")
SCHED_QUEUE_DEPTH = _opt(
    "auron.sched.queue_depth", int, 16,
    "Bounded run-queue depth behind the concurrent slots: a query "
    "arriving past max_concurrent running + queue_depth queued is "
    "REJECTED fast with the classified errors.AdmissionRejected "
    "(transient, retry_after_s hint) instead of waiting unboundedly — "
    "the overload-shedding half of admission control. Queued queries "
    "honor their deadline/cancel token WHILE queued (dequeued without "
    "ever starting).")
SCHED_ADMIT_QUEUE_WAIT_P99_S = _opt(
    "auron.sched.admit.queue_wait_p99_s", float, 0.0,
    "Admission threshold on the observed queue-wait p99 (the "
    "auron_sched_queue_wait_seconds registry histogram): when queries "
    "admitted in the last 30 s waited longer than this, new queries "
    "are shed with AdmissionRejected(reason='queue_wait') even though "
    "the queue still has room — latency-based backpressure ahead of "
    "the hard depth bound. Age-windowed so one old burst cannot latch "
    "the signal shut after the backlog drains. 0 (default) disables "
    "the signal.")
SCHED_ADMIT_MEM_RATIO = _opt(
    "auron.sched.admit.mem_ratio", float, 0.0,
    "Admission threshold on the memory manager's used/budget ratio: "
    "past it new queries are shed with "
    "AdmissionRejected(reason='memory') instead of being admitted into "
    "a budget that is already spilling — rejecting at the door is "
    "cheaper than shedding mid-flight with MemoryExhausted. Read from "
    "the scheduler's attached MemManager at admission time — the "
    "Session's mem_manager; a scheduler with NO manager attached "
    "(Session() without one, the serving process) logs a one-time "
    "warning and leaves the signal disarmed. 0 (default) disables "
    "the signal.")

# memory / spill
MEMORY_FRACTION = _opt(
    "auron.memory.fraction", float, 0.6,
    "Fraction of device HBM the memory manager arbitrates across "
    "consumers (the reference's spark.auron.memoryFraction).")
HOST_SPILL_BUDGET = _opt(
    "auron.memory.host_spill_budget", int, 1 << 30,
    "Bytes of host DRAM the spill manager may hold before overflowing "
    "frames to disk (tier 2 of the HBM->DRAM->disk spill path).")
SPILL_DIR = _opt(
    "auron.memory.spill_dir", str, "",
    "Directory for disk spill files; empty = system temp dir.")
SPILL_FRAME_ROWS = _opt(
    "auron.spill.frame_rows", int, 1 << 16,
    "Rows per serialized spill frame (the unit of spill I/O and of the "
    "k-way merge restore).")
SPILL_CODEC_LEVEL = _opt(
    "auron.spill.codec_level", int, 1,
    "zstd compression level for spill/shuffle frames (the reference "
    "defaults its IPC compression to lz4/zstd level 1).")
MEMMGR_PRESSURE_POLICY = _opt(
    "auron.memmgr.pressure_policy", str, "degrade",
    "What the memory manager does when the spill loop exits still over "
    "budget (the old silent 'deny'): 'degrade' (default) walks the "
    "degradation ladder — shrink (advise smaller scan batches + ask the "
    "requester to shrink) -> force-spill (largest consumer, ignoring "
    "min_trigger) -> deny (survivable, counted) — so pressure degrades "
    "throughput before it fails anything; 'shed' ends the ladder by "
    "failing THIS query with the classified errors.MemoryExhausted "
    "(never the process) — the serving/admission-control posture; "
    "'legacy' restores the pre-ladder deny event only. A per-query "
    "quota breach (auron.memmgr.query_quota_bytes) sheds under every "
    "policy except 'legacy'. Each rung taken is counted on "
    "auron_memmgr_pressure_total{rung=...}.")
MEMMGR_QUERY_QUOTA_BYTES = _opt(
    "auron.memmgr.query_quota_bytes", int, 0,
    "Device-memory quota on ONE query's registered consumers (the "
    "manager keeps a per-query ledger — consumers are tagged with the "
    "lifecycle plane's current query id at registration): exceeded "
    "AFTER the spill loop and the degradation ladder ran, the "
    "requesting query is shed with errors.MemoryExhausted — never the "
    "process, never an innocent neighbor. 0 (default) = AUTO: "
    "budget / auron.sched.max_concurrent while more than one query is "
    "live on the manager (one query cannot starve the rest), no quota "
    "while a single query runs (a solo query may use the whole "
    "budget). Set negative to disable the quota entirely.")

# NOTE: options are declared only once a use-site exists — an option in
# CONFIG.md that nothing reads is a lie to the user. SMJ-fallback,
# exchange-spill, and dense-kernel-selection knobs land together with
# their features.

# compile-cache ceiling (default lives in utils/compile_stats so the
# mechanism and its documented value cannot drift)
from auron_tpu.utils.compile_stats import DEFAULT_MAX_LIVE_PROGRAMS

MAX_LIVE_PROGRAMS = _opt(
    "auron.max_live_programs", int, DEFAULT_MAX_LIVE_PROGRAMS,
    "Ceiling on live compiled programs per process, enforced through the "
    "central program-cache registry (runtime/programs.py): every kernel "
    "builder registers its cache there, and when either the registry's "
    "live-program count or the raw backend compiles since the last clear "
    "reach this value, utils/compile_stats.maybe_clear drops BOTH jax's "
    "compiled caches and the builder memos (the CPU backend's JIT can "
    "segfault once several hundred programs accumulate in one long-lived "
    "process). Checked only at quiescent boundaries (between serving "
    "tasks / runner queries); <= 0 disables.")

# compile-budget diet: persistent XLA compilation cache
XLA_CACHE_DIR = _opt(
    "auron.xla_cache_dir", str, "",
    "Directory for jax's persistent compilation cache when the "
    "environment does not place it: JAX_COMPILATION_CACHE_DIR, when "
    "set, wins and nothing in the engine touches "
    "jax_compilation_cache_dir. Empty (the default) means "
    "<checkout>/.jax_cache — the cache is never off "
    "(utils/xla_cache.py). Naming a directory here also arms the AOT "
    "plan inventory kept under it (auron.cache.aot_top_n).")

# warm-path serving plane (auron_tpu/cache): result/subplan cache + AOT
CACHE_ENABLED = _opt(
    "auron.cache.enabled", bool, False,
    "Master switch for the warm-path result/subplan cache "
    "(cache/result_cache.py). When on, exact re-submissions — same "
    "plan fingerprint, same source fingerprints, same trace salt "
    "(cache/identity.py, the journal's crash-tested identity) — are "
    "answered from a process-wide LRU of materialized Arrow results "
    "instead of re-executing; serving marks such answers with "
    "cache_hit and served_from=cache. Off by default: caching trades "
    "memory for latency and dashboards must opt in.")
CACHE_MAX_BYTES = _opt(
    "auron.cache.max_bytes", int, 256 << 20,
    "Capacity of the warm-path cache in bytes (LRU eviction on "
    "insert). Independent of auron.memmgr.*: the cache additionally "
    "registers as a sheddable memmgr consumer, so global pressure "
    "evicts it (ladder rung cache_evict) before any working state is "
    "force-spilled, whatever this cap says.")
CACHE_SUBPLAN = _opt(
    "auron.cache.subplan", bool, True,
    "Cache materialized SUBPLAN outputs (broadcast relations keyed by "
    "per-node fingerprints computed at planning time) in addition to "
    "full results, so queries that differ in their outer plan but "
    "share a broadcast subtree reuse the built relation. Only "
    "meaningful while auron.cache.enabled is on.")
CACHE_AOT_TOP_N = _opt(
    "auron.cache.aot_top_n", int, 0,
    "Ahead-of-time warming at Session init (cache/aot.py): execute the "
    "top-N plan signatures by submission count from the aot_plans "
    "inventory (recorded under auron.xla_cache_dir) and resumable "
    "journals, driving their compiles through the central program "
    "registry and the persistent XLA cache before the first user "
    "query. 0 (the default) disables; the warmer never raises — "
    "failures surface in cache/aot.last_stats() and fail the "
    "perf_gate cache arm.")

# failure recovery
TASK_MAX_RETRIES = _opt(
    "auron.task.max_retries", int, 2,
    "Transient-failure retries per (plan, partition) task in the driver "
    "collect path. The engine is functional, so a retry is an exact "
    "partition-granularity recompute (the recovery unit the reference "
    "delegates to Spark's task scheduler, SURVEY §5.3); cancellation is "
    "never retried. 0 disables.")
TASK_RETRY_BACKOFF_S = _opt(
    "auron.task.retry_backoff_s", float, 0.0,
    "Base backoff before each task retry attempt. The driver sleeps a "
    "uniform random amount in [0, min(cap, base * 2^attempt)] — "
    "exponential backoff with FULL jitter, so concurrently failed "
    "partitions don't retry in lockstep against the same external "
    "system. Keep 0 for in-process transients; set >0 when retries "
    "wait out external systems (remote FS, RSS service).")
TASK_RETRY_BACKOFF_MAX_S = _opt(
    "auron.task.retry_backoff_max_s", float, 30.0,
    "Cap on the exponential retry backoff window (the 'min(cap, ...)' "
    "bound): attempt k draws its sleep from [0, min(cap, "
    "retry_backoff_s * 2^k)].")

# crash-safe query journal (runtime/journal.py)
JOURNAL_DIR = _opt(
    "auron.journal.dir", str, "",
    "Directory of the crash-safe query journal. When set, every "
    "top-level query writes a per-query journal (plan fingerprint, "
    "source-snapshot fingerprints, the exchange DAG, and an "
    "append-only log of committed RSS map outputs recorded at the "
    "durable tier's existing commit() boundary), and the planner "
    "routes the query's shuffle exchanges through the durable RSS tier "
    "under <dir>/rss/<journal stem> so shuffle stages survive the "
    "process. After a crash, Session.resume(query_id) — or "
    "re-submission of the identical plan with auron.journal.reuse on — "
    "re-plans, validates the fingerprints, skips the map side of every "
    "fully-committed exchange (reducers fetch straight from the "
    "journaled RSS files), skips individual committed map outputs of "
    "partially-committed hash/round-robin/single exchanges, and "
    "recomputes only what the durable tier never received — resumed "
    "results are bit-identical to a fresh run, group order included. "
    "Journals are deleted at query completion (and by Session.close); "
    "a startup sweep garbage-collects journals/RSS run directories "
    "whose owning process is dead and whose state is not resumable "
    "(utils/liveness.py pid+epoch check). Empty (default) disables "
    "journaling entirely: shuffles stay on the in-memory device-buffer "
    "tier and a crash loses in-flight queries (the pre-journal "
    "posture).")
JOURNAL_REUSE = _opt(
    "auron.journal.reuse", bool, True,
    "Allow Session.execute to ADOPT an existing resumable journal "
    "whose plan fingerprint AND source-snapshot fingerprints match the "
    "submitted query (the crashed-and-resubmitted dashboard case): the "
    "adopted journal's committed exchanges are skipped exactly like "
    "Session.resume. Only journals not currently open in a live "
    "process are adoptable; fingerprint mismatch or a corrupt journal "
    "falls back to a fresh run (classified handling, never a wrong "
    "answer). Off mints a fresh journal per submission.")
JOURNAL_RETENTION_S = _opt(
    "auron.journal.retention_s", float, 7 * 24 * 3600.0,
    "Age cap on the resume inventory: the startup sweep garbage-"
    "collects a DEAD process's resumable journal — and with it the "
    "journal's RSS run directory holding real shuffle bytes — once "
    "the journal file has not been touched for this many seconds. "
    "Without a cap, a long-lived deployment with a steady trickle of "
    "failed-and-never-resumed queries (suspended serving tasks, "
    "crashed dashboards nobody re-opens) accumulates journals and "
    "multi-MB RSS dirs until the disk fills. <= 0 keeps the inventory "
    "indefinitely.")
JOURNAL_FSYNC = _opt(
    "auron.journal.fsync", bool, True,
    "fsync the journal at its durability boundaries only: the header "
    "write and each shuffle-level commit record (map-output records "
    "ride the async appender and are made durable by the next commit "
    "fsync — the journal never claims more than the RSS tier holds, "
    "because records are appended AFTER the durable tier's atomic "
    "rename). Off skips the fsync (journal durability then depends on "
    "the OS page cache surviving the crash — fine for tests, not for "
    "production).")

# fault injection (runtime/faults.py) — the deterministic chaos plane
FAULTS_PLAN = _opt(
    "auron.faults.plan", str, "",
    "Seeded fault-injection plan: 'site:kind@prob;...' over the named "
    "sites rss.{write,flush,commit,fetch}, spill.{write,read}, "
    "device.compute, task.hang, cancel.race, program.build, "
    "memmgr.deny, sched.admit, mesh.all_to_all (per "
    "sharded-exchange round: io_error/fatal simulate a device loss the "
    "demotion path must route around, hang a straggling chip), "
    "mesh.gang (kind cancel: a cancel racing the gang door) and "
    "journal.{write,commit,load} (the crash-safe query journal: write/"
    "commit faults degrade journaling to off for that query — the run "
    "completes identical, resumability is lost; load faults surface "
    "the classified JournalCorrupt / fresh-run fallback) and "
    "fleet.{route,forward} (the fleet router: route faults fail the "
    "routing decision before any replica is contacted, forward faults "
    "break/hang the router→replica conversation mid-stream — both "
    "exercise the spill-over and failover recovery paths) with kinds "
    "io_error | fatal | corrupt | "
    "hang | cancel | deny (prob defaults to 1.0). Injected hangs poll "
    "the task's cancel registry, 'cancel' fires the task's CancelToken "
    "mid-drive (the cancel-race site), 'deny' forces the memory "
    "manager's degradation ladder. Every injection "
    "decision is a pure function of (auron.faults.seed, site, kind, "
    "event index), so failing chaos runs replay exactly. Empty (the "
    "default) disarms every site at one cached epoch-compare of "
    "overhead; arm/disarm via AuronConfig.set/unset (a direct "
    "os.environ change after first use needs faults.reset()).")
FAULTS_SEED = _opt(
    "auron.faults.seed", int, 0,
    "Seed of the fault plane's deterministic Bernoulli sequences; "
    "chaos batteries sweep it to explore injection schedules.")
FAULTS_HANG_S = _opt(
    "auron.faults.hang_s", float, 2.0,
    "Sleep injected by the 'hang' fault kind (pair with "
    "auron.watchdog.stall_timeout_s below it to exercise the stall "
    "watchdog).")

# durable-tier integrity (shuffle_service.py, memmgr/spill.py)
DURABILITY_CHECKSUM = _opt(
    "auron.durability.checksum", bool, True,
    "Frame checksums (CRC32C when the image provides it, zlib CRC-32 "
    "otherwise) on RSS map-output frames and spill frames: every fetch "
    "verifies before deserializing, so a flipped byte surfaces as "
    "ShuffleCorruption (map recompute) or SpillCorruption (task "
    "recompute), never as silently wrong rows. Off writes algo-id 0 "
    "frames (same format, no verification) — the A/B knob for the "
    "checksum-overhead microbench (tools/microbench_shuffle.py).")

# task stall watchdog (runtime/watchdog.py)
WATCHDOG_STALL_TIMEOUT_S = _opt(
    "auron.watchdog.stall_timeout_s", float, 0.0,
    "Task-level stall watchdog: executor, shuffle and spill loops beat "
    "a per-attempt heartbeat (ExecContext.checkpoint); a monitor thread "
    "flags any task silent past this timeout, writes a structured "
    "StallReport (last heartbeat site, driving thread's stack) into "
    "auron.trace.dir, and raises the classified errors.TaskStalled at "
    "the task's next cooperative poll — which the retry driver retries "
    "exactly ONCE before surfacing. Detection latency is bounded by "
    "1.25x the timeout (the monitor polls at a quarter interval). "
    "0 (default) disarms the plane (no heartbeat registration, no "
    "monitor thread).")

# query lifecycle (runtime/lifecycle.py)
QUERY_DEADLINE_S = _opt(
    "auron.query.deadline_s", float, 0.0,
    "Default per-query deadline applied by Session.execute when the "
    "caller passes no explicit df.collect(timeout_s=...): past it, the "
    "query's CancelToken self-cancels with reason 'deadline' and every "
    "cooperative poll site unwinds with errors.DeadlineExceeded — full "
    "resource cleanup, task-level backoff sleeps clamped to the "
    "remaining budget. 0 (default) = no deadline.")

# profiling (a device profile is any jax profiler session around the
# process: the layer spans of obs/trace.py annotate it)
PROFILE_ENABLED = _opt(
    "auron.profile.enabled", bool, True,
    "Host/device time attribution (auron_tpu/obs/profile.py): every "
    "jitted-program invocation through the central registry "
    "(runtime/programs.py) is timed as dispatch (host python glue until "
    "the async call returns), the device wait is timed where execution "
    "synchronizes (device_fence at the materialization boundaries, "
    "timed_get at the control-scalar readbacks), and "
    "per-operator timers classify the remaining wall into named host "
    "buckets (elapsed_host_{dispatch,convert,serde,iter,other}) "
    "alongside elapsed_device in the metric tree / EXPLAIN ANALYZE. "
    "Feeds the per-batch dispatch-overhead registry histograms and the "
    "served task's program-call count (cost_ledger.counts). Measured "
    "overhead < 2% (bench A/B, CPU); off reduces the hot-path cost to "
    "one cached epoch compare per timer. Profiling adds no sync point: "
    "it times the waits execution already makes.")
PERF_GATE_TOLERANCE_PCT = _opt(
    "auron.perf_gate.tolerance_pct", float, 50.0,
    "Allowed q01 rows/s shortfall vs the checked-in per-platform "
    "baseline (tools/perf_baseline.json) before tools/perf_gate.py "
    "fails the run. Sized to this container's measured wall-clock "
    "variance (single-rep swings of +/-10-50%): the BENCH_r03->r05 "
    "regression (276k -> 108k rows/s, a 61% drop) fails the default "
    "while honest noise passes. CLI --tolerance-pct overrides.")

# tracing plane (auron_tpu/obs/trace.py)
TRACE_ENABLED = _opt(
    "auron.trace.enabled", bool, False,
    "Record the query→stage→task→operator→event span timeline "
    "(auron_tpu/obs/trace.py): task attempts and retry backoffs, "
    "program builds per compile site, shuffle write/flush/commit/fetch, "
    "spill tier decisions, injected faults (site/kind attributes) and "
    "watchdog probes. Spans are buffered lock-free per thread; the "
    "disabled hot path costs one cached epoch compare. Export with "
    "auron.trace.dir (per-query Chrome-trace JSON + JSONL) or the "
    "trace API (tools/trace_report.py summarizes a trace dir).")
TRACE_DIR = _opt(
    "auron.trace.dir", str, "",
    "Directory the tracer exports each top-level query's spans into "
    "(trace_<id>.json Chrome/Perfetto trace + trace_<id>.jsonl event "
    "log), written when the outermost Session.execute finishes. Empty "
    "(the default) keeps spans in memory for the trace API only.")
TRACE_EVENTS = _opt(
    "auron.trace.events", str, "",
    "Comma-separated span-category allowlist (query, task, program, "
    "shuffle, spill, fault, watchdog, memory, sched, mesh, journal, "
    "cache, fleet, layer); empty records every category. "
    "Narrowing the list bounds tracing overhead on hot paths — e.g. "
    "'task,shuffle,fault' drops the per-hit program events.")
TRACE_MAX_SPANS = _opt(
    "auron.trace.max_spans", int, 200_000,
    "Ceiling on buffered spans per process; past it new spans are "
    "dropped (counted — the Chrome export records dropped_spans) so an "
    "unbounded query can never turn the tracer into a memory leak. "
    "The cap is approximate: enforcement is lock-free like recording.")
TRACE_PROPAGATE = _opt(
    "auron.trace.propagate", bool, True,
    "Cross-process trace-context propagation over the serving wire "
    "protocol: when tracing is enabled and a trace is active, "
    "AuronClient prefixes SUBMIT/SUBMIT_PLAN/RESUME with a TRACE frame "
    "(trace id + parent span id), the fleet router adds a fleet.forward "
    "hop span and forwards the context, and the replica adopts the "
    "inbound id as its query-span parent — so exports from client, "
    "router, and every replica share ONE trace id and "
    "tools/trace_report.py --stitch renders a single cross-process "
    "timeline. With tracing off (or no active trace) nothing extra is "
    "sent on the wire; overhead with tracing on is gated < 2% by the "
    "perf-gate obs-fleet arm.")

# ops plane: live telemetry endpoint (auron_tpu/obs/ops_server.py)
OPS_ENABLED = _opt(
    "auron.ops.enabled", bool, False,
    "Run the in-process ops HTTP endpoint (auron_tpu/obs/ops_server.py, "
    "stdlib ThreadingHTTPServer — the role of the reference's runtime "
    "HTTP service, auron/src/http/mod.rs:25-108): /metrics serves the "
    "process registry's Prometheus exposition, /healthz the ok-vs-"
    "degraded probe/scheduler/memmgr/mesh verdict, /queries the live "
    "query table (state, wall, tasks done/total, per-query memory vs "
    "quota, program-cache hits), /flight the flight recorder's recent-"
    "event ring as JSONL. One server per process (refcounted across "
    "Sessions/AuronServers; the last close stops it). Default off.")
OPS_PORT = _opt(
    "auron.ops.port", int, 0,
    "TCP port of the ops HTTP endpoint; 0 (default) binds an ephemeral "
    "port, logged at startup and surfaced as Session.ops_address / the "
    "AuronServer stats 'ops_port' entry (and on the serving STATS "
    "frame), so a supervisor can discover it without parsing logs.")

# serving fleet (auron_tpu/fleet/: router in front of N AuronServers)
FLEET_REPLICAS = _opt(
    "auron.fleet.replicas", int, 2,
    "Replica count booted by the fleet tooling (tools/load_report.py "
    "--fleet, the perf-gate fleet arm, chaos fleet_failover). The "
    "router itself takes an explicit replica list and ignores this "
    "knob — it sizes HARNESSES, not the router.")
FLEET_POLL_S = _opt(
    "auron.fleet.poll_s", float, 0.5,
    "Bounded-staleness interval of the router's health poll loop: each "
    "tick scrapes every replica's /healthz + /queries (occupancy, "
    "memmgr pressure, watchdog state, warm plan fingerprints) into an "
    "immutable snapshot the pure routing functions decide over. A "
    "snapshot older than 4 poll intervals is treated as unreachable — "
    "routing never blocks on a scrape.")
FLEET_AFFINITY = _opt(
    "auron.fleet.affinity", bool, True,
    "Warm-affinity routing: a submission whose plan fingerprint (the "
    "cache/identity.py result-key fp) matches a replica's warm result-"
    "cache inventory — or that this router recently routed — lands on "
    "that replica so the plan-fingerprint cache's warm path survives "
    "going multi-process. Off routes purely by load.")
FLEET_FAILOVER = _opt(
    "auron.fleet.failover", bool, True,
    "Journal-backed failover: on replica death mid-query (connection "
    "loss confirmed by the liveness plane's pid+epoch verdict) the "
    "router RESUMEs the journaled query on a survivor from its "
    "committed shuffle stages (bit-identical), and re-executes non-"
    "journaled in-flight queries from scratch under a result-key "
    "idempotency guard. Off surfaces replica death to the client as a "
    "classified ReplicaUnavailable.")
FLEET_OPS_PORT = _opt(
    "auron.fleet.ops_port", int, -1,
    "TCP port of the ROUTER's own ops HTTP endpoint (federated "
    "/metrics merging every replica's scraped exposition re-labeled "
    "replica=\"rN\", /fleet/queries merging the live query tables, "
    "/healthz with per-replica up/down rows). 0 binds an ephemeral "
    "port (surfaced as FleetRouter.ops_address and on the router STATS "
    "frame); a negative value (default) disables the router endpoint.")
CLIENT_TIMEOUT_S = _opt(
    "auron.client.timeout_s", float, 30.0,
    "AuronClient socket budget: connect timeout per attempt and read "
    "timeout on every subsequent frame (a dead peer surfaces as a "
    "classified RemoteEngineError instead of hanging the client "
    "forever). Connection attempts retry with jittered backoff inside "
    "this same budget; <=0 disables (legacy block-forever behavior).")

# always-on flight recorder (auron_tpu/obs/flight_recorder.py)
FLIGHT_ENABLED = _opt(
    "auron.flight.enabled", bool, True,
    "Arm the always-on flight recorder: a bounded per-thread ring of "
    "the most recent structured events across ALL trace categories "
    "that records even while auron.trace.enabled is off (the trace "
    "plane tees into it at emit time), so the last seconds before any "
    "failure are reconstructable from /flight or a post-mortem bundle "
    "without having had tracing on. Overhead is measured by the bench "
    "three-arm A/B's 'norec' arm (flight_overhead_pct, gate <2% — "
    "PERF.md 'Ops plane'); off restores the bare cached-epoch-compare "
    "disabled path.")
FLIGHT_RING_EVENTS = _opt(
    "auron.flight.ring_events", int, 4096,
    "Events retained per THREAD by the flight recorder's ring (a "
    "collections.deque maxlen — O(1) memory, oldest evicted first). "
    "Sized so several seconds of control-plane history (retries, "
    "sheds, fault injections, admission decisions) survive on every "
    "thread without the ring ever becoming a leak.")

# post-mortem failure bundles (auron_tpu/obs/bundle.py)
BUNDLE_ENABLED = _opt(
    "auron.bundle.enabled", bool, False,
    "Write a self-contained post-mortem bundle directory "
    "(bundle_<query_id>/ under auron.bundle.dir) when a query ends in "
    "a CLASSIFIED failure — MemoryExhausted shed, DeadlineExceeded, "
    "TaskStalled exhaustion, unrecovered MeshUnavailable, "
    "JournalCorrupt/JournalInvalidated: flight-recorder dump, explain "
    "tree with metrics, scheduler/memmgr/mesh stats, probe + stall "
    "reports, journal state and a config snapshot with the trace "
    "salt. Plain cancels and admission sheds (no resources ever "
    "existed) write nothing. tools/ops_report.py renders a bundle "
    "into a human post-mortem. Default off.")
BUNDLE_DIR = _opt(
    "auron.bundle.dir", str, "",
    "Directory for post-mortem bundles; empty (default) places them "
    "under '<system temp>/auron-bundles'.")
BUNDLE_MAX_BUNDLES = _opt(
    "auron.bundle.max_bundles", int, 16,
    "Retention cap on bundle directories under auron.bundle.dir: past "
    "it the OLDEST bundles are evicted after each write, so a crash "
    "loop can never fill the disk with post-mortems. <= 0 keeps "
    "everything (tests only).")

# process metrics registry (auron_tpu/obs/registry.py)
METRICS_REGISTRY = _opt(
    "auron.metrics.registry", bool, True,
    "Aggregate per-task observations (task seconds histogram, retries, "
    "recovery/spill/program counters) into the process-wide metrics "
    "registry (auron_tpu/obs/registry.py), whose Prometheus text "
    "exposition (render_prometheus) is the scrape surface — the role "
    "of the reference's pprof HTTP endpoints. Off skips the per-task "
    "observation entirely.")

# per-query cost ledger (auron_tpu/obs/ledger.py)
LEDGER_ENABLED = _opt(
    "auron.ledger.enabled", bool, True,
    "Assemble a compact per-query cost ledger at query finalize "
    "(auron_tpu/obs/ledger.py): device seconds vs host-bucket splits, "
    "shuffle/spill/combine bytes and rows, cache hits, retries and "
    "recovery counts, replica hops. The record rides the serving DONE "
    "frame, lands in failure bundles (ledger.json), and surfaces in "
    "AuronClient.stats() and tools/load_report.py — the accounting "
    "unit for admission and capacity decisions at fleet scale. "
    "Overhead is gated < 2% by the perf-gate obs-fleet arm; off skips "
    "assembly entirely (no ledger on DONE, none retained).")

# sinks
SINK_BUFFER_ROWS = _opt(
    "auron.sink.buffer_rows", int, 1 << 17,
    "Rows a file sink buffers before flushing a row group / dataset "
    "fragment — bounds sink host memory for arbitrarily large "
    "partitions.")

# aggregation
AGG_INITIAL_CAPACITY = _opt(
    "auron.agg.initial_capacity", int, 4096,
    "Initial group-state capacity of the agg merge kernel; grows by "
    "power-of-two re-bucketing when exceeded.")
AGG_PARTIAL_SKIP_ENABLED = _opt(
    "auron.agg.partial_skip.enabled", bool, True,
    "Adaptive partial-agg skipping: when the observed group/input "
    "cardinality ratio stays high, the partial stage stops merging and "
    "passes rows through in state layout (the reference's "
    "spark.auron.partialAggSkipping.*, agg_ctx.rs:63-196).")
AGG_PARTIAL_SKIP_RATIO = _opt(
    "auron.agg.partial_skip.ratio", float, 0.8,
    "Cardinality ratio (distinct groups / input rows) at or above which "
    "the partial agg switches to pass-through.")
AGG_PARTIAL_SKIP_MIN_ROWS = _opt(
    "auron.agg.partial_skip.min_rows", int, 1 << 16,
    "Input rows to observe before the skip decision is made.")

# whole-stage fusion (ir/planner.fuse_stages + ops/fused.py)
FUSION_ENABLED = _opt(
    "auron.fusion.enabled", bool, True,
    "Whole-stage XLA fusion: the planner chains maximal runs of "
    "row-local operators (filter, project, expand, limit-within-batch, "
    "rename — plus the shuffle-split and hash-join-probe prologues) "
    "into one jit-compiled program per stage, so intermediates never "
    "materialize in HBM and the compile budget pays one program per "
    "chain instead of one per operator. Off executes every operator as "
    "its own program. The plan NORMALIZATION half of the pass (pre-agg "
    "key/value projection, pure-projection elision under aggs) applies "
    "under BOTH settings — that is what keeps on/off results "
    "bit-identical (eager vs jitted float arithmetic differs in the "
    "last ulp), so 'off' restores the per-operator program layout, not "
    "the exact pre-fusion plan shape.")
FUSION_MAX_STAGE_OPS = _opt(
    "auron.fusion.max_stage_ops", int, 8,
    "Longest operator chain a single fused stage may contain. Longer "
    "chains split into multiple stages — a bound on per-program trace "
    "size and compile time (an over-long chain compiles one huge XLA "
    "program whose build cost defeats the purpose).")

# hand-written kernels (auron_tpu/kernels)
KERNELS_ENABLED = _opt(
    "auron.kernels.enabled", bool, True,
    "Allow the dense grouped-aggregation kernels (Pallas VMEM / one-hot "
    "matmul) when the planner bounds the group-key domain; off forces "
    "every aggregation through the general sort-based path "
    "(kernels/dispatch.py).")
KERNELS_MAX_KEY_DOMAIN = _opt(
    "auron.kernels.max_key_domain", int, 1 << 16,
    "Largest bounded key domain eligible for the dense grouped-agg "
    "kernels; plans with a larger (or unknown) bound fall back to the "
    "sort path. Hard-capped at 2^16 by the kernels' (hi, lo) byte grid "
    "decomposition.")
# device-resident hash table (auron_tpu/hashtable)
HASHTABLE_ENABLED = _opt(
    "auron.hashtable.enabled", bool, True,
    "Allow the device-resident open-addressing hash table "
    "(auron_tpu/hashtable) on the general (unbounded-key) aggregation "
    "path, distinct dedup, and the hash-join candidate search; off "
    "forces the sort-based formulations everywhere "
    "(kernels/dispatch.select_hash_agg).")
HASHTABLE_BACKEND = _opt(
    "auron.hashtable.backend", str, "auto",
    "General-agg grouping backend: 'auto' routes aggregations whose "
    "accumulators are reassociation-exact (integer/decimal sums, "
    "min/max, first, count) through the hash table and keeps float "
    "sums on the sort path so results stay bit-identical either way; "
    "'hash' forces the hash table wherever its kinds are structurally "
    "supported (float scatter-adds may differ from the sort path in "
    "the last ulp); 'sort' disables the hash path entirely.")
HASHTABLE_LOAD_FACTOR = _opt(
    "auron.hashtable.load_factor", float, 0.5,
    "Maximum occupancy of the device hash table before a power-of-two "
    "growth re-bucket (the auron.agg.initial_capacity growth "
    "discipline). Lower values buy shorter probe chains with more "
    "device memory.")
HASHTABLE_MAX_PROBE_ROUNDS = _opt(
    "auron.hashtable.max_probe_rounds", int, 64,
    "Probe rounds (double-hashed open addressing) the vectorized "
    "insert/probe loop runs before declaring overflow; an overflowing "
    "insert grows the table and retries, and pathological repeat "
    "overflow falls back to the sort path for the rest of the stream.")

# map semantics
MAP_KEY_DEDUP_POLICY = _opt(
    "auron.map.key_dedup_policy", str, "LAST_WIN",
    "Duplicate-key policy of the map constructors (map, create_map, "
    "map_from_arrays, map_from_entries, map_concat): 'LAST_WIN' keeps "
    "the last entry per key (Spark's legacy policy — this engine's "
    "default, because a jit-compiled kernel cannot raise data-dependent "
    "errors); 'EXCEPTION' (Spark's default) raises a deterministic "
    "ValueError when the construction is evaluated eagerly, and inside "
    "a jit-fused stage — where raising is impossible — nulls the "
    "offending rows instead. TRACE-SEMANTIC knob: it changes what a "
    "compiled kernel computes, so it is resolved from the PROCESS-GLOBAL "
    "config (AuronConfig.set on get_config(), or the env var) and rides "
    "every program-cache key (runtime/programs.py trace salt); "
    "per-ExecContext session overrides are not honored for it.")

KERNELS_BACKEND = _opt(
    "auron.kernels.backend", str, "auto",
    "Dense grouped-agg backend: 'auto' compiles the Pallas VMEM kernel "
    "natively on a real TPU and uses the one-hot matmul formulation "
    "elsewhere; 'pallas' forces the Pallas kernel (interpreter on "
    "non-TPU platforms — how the differential battery verifies it on "
    "CPU); 'dense' forces the matmul path; 'sort' disables the dense "
    "path entirely.")


# --------------------------------------------------------------------------
# resolution
# --------------------------------------------------------------------------

class AuronConfig:
    """One resolved configuration: programmatic overrides > env > default."""

    def __init__(self, overrides: Optional[dict] = None):
        self._overrides: dict[str, Any] = {}
        self._lock = threading.Lock()
        for k, v in (overrides or {}).items():
            self.set(k, v)

    def set(self, key: str, value) -> "AuronConfig":
        opt = _REGISTRY.get(key)
        if opt is None:
            raise KeyError(f"unknown config option {key!r}; "
                           f"known: {sorted(_REGISTRY)}")
        if isinstance(value, str) and opt.dtype is not str:
            value = opt.parse(value)
        if opt.dtype is float and isinstance(value, int):
            value = float(value)
        if not isinstance(value, opt.dtype):
            raise TypeError(f"{key} expects {opt.dtype.__name__}, "
                            f"got {type(value).__name__}")
        with self._lock:
            self._overrides[key] = value
        _bump_epoch()
        return self

    def unset(self, key: str) -> None:
        with self._lock:
            self._overrides.pop(key, None)
        _bump_epoch()

    def get(self, key: str):
        opt = _REGISTRY.get(key)
        if opt is None:
            raise KeyError(f"unknown config option {key!r}")
        with self._lock:
            if key in self._overrides:
                return self._overrides[key]
        raw = os.environ.get(opt.env_var)
        if raw is not None:
            return opt.parse(raw)
        return opt.default


#: monotonic count of set()/unset() calls across ALL AuronConfig
#: instances — a cheap change signal for hot-path caches (the fault
#: plane keys its armed/disarmed verdict on it so an unarmed site check
#: costs one int compare, not a lock + env lookup). Direct os.environ
#: mutation after the first resolution is NOT detected; knobs consulted
#: on hot paths change via set()/unset().
_MUTATION_EPOCH = 0


def _bump_epoch() -> None:
    global _MUTATION_EPOCH
    _MUTATION_EPOCH += 1


def config_epoch() -> int:
    """Current config-mutation epoch (any instance, any key)."""
    return _MUTATION_EPOCH


#: process-wide default config; ExecContext carries a per-execution one
#: that defaults to this (the "session" layer)
_GLOBAL = AuronConfig()

#: options whose value is read DURING kernel tracing and changes what
#: the compiled program computes (not just how the plan is shaped).
#: Their current values ride every program-cache key as the trace salt
#: (runtime/programs.py), so flipping one can never serve a stale trace.
TRACE_SEMANTIC_KEYS = (MAP_KEY_DEDUP_POLICY,)


def trace_salt() -> tuple:
    """Current values of the trace-semantic options, resolved from the
    process-global config (these knobs are global by contract — see
    their docs)."""
    return tuple(_GLOBAL.get(k) for k in TRACE_SEMANTIC_KEYS)


def get_config() -> AuronConfig:
    return _GLOBAL


def options() -> list[ConfigOption]:
    return sorted(_REGISTRY.values(), key=lambda o: o.key)


def generate_docs() -> str:
    """Markdown config reference (the doc-generator analogue of the
    reference's SparkAuronConfigurationDocGenerator.java)."""
    lines = [
        "# Configuration reference",
        "",
        "Resolution order: session override (`AuronConfig.set`) > env var "
        "> default. Env binding: drop the `auron.` prefix, upper-case, "
        "dots to underscores, prepend `AURON_CONF_`.",
        "",
        "| Option | Type | Default | Env var | Description |",
        "|---|---|---|---|---|",
    ]
    for o in options():
        default = repr(o.default) if o.dtype is str else str(o.default)
        lines.append(f"| `{o.key}` | {o.dtype.__name__} | {default} "
                     f"| `{o.env_var}` | {o.doc} |")
    return "\n".join(lines) + "\n"
