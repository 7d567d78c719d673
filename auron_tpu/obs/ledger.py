"""Per-query cost ledger: one compact accounting record per query.

The fleet serves queries across process boundaries (client → router →
replica, possibly failing over), and every capacity decision — admission
weights, replica sizing, cache-vs-recompute — needs the same question
answered per query: *what did it cost?* The metric tree answers it
per-operator inside one process; this module folds the per-partition
``ExecutionRuntime.finalize()`` snapshots into ONE flat record at query
finalize:

- **device vs host split** — ``elapsed_compute`` summed into device
  seconds, the PR 6 host buckets (``elapsed_host_{dispatch,convert,
  serde,iter,other}``) summed per bucket;
- **data movement** — shuffle write/read seconds, live shuffle bytes,
  map-side combine rows in/out, mesh collective bytes, spill
  count/bytes, journal bytes reused by resume;
- **compile plane** — XLA compiles + seconds, program builds vs cache
  hits;
- **robustness** — retry/recovery counters (attempts, transient
  retries, corruption recomputes, injected faults);
- **serving identity** — rows, batches, partitions, cache hit,
  served_from, outcome, wall seconds, and the device that computed it
  (platform, kind, count — an answer names what it ran on).

Version 2 adds what the task's own accumulator measured
(``obs/trace.TaskAccumulator``, filled by the layer spans and not by
operator snapshots): ``queue_s``; ``layers_s``, exclusive seconds by
layer on the task's thread, summing to ``wall_s``; ``layers_cpu_s``,
the CPU the task's thread spent inside those same self times (no
``compile``, no ``other``: each at most its layer's wall, the rest of
which is waiting; None, like the other CPU fields, in a task that was
not CPU-timed: ``obs/trace.CPU_TIMED_EVERY``); ``exchange_s``, the
exchange layer's part of it by span; ``ops_s``, the operators' part of
it by operator (``host_s`` and the ``cpu_s`` inside it, ``device_wait_s``);
``scan_worker_s``, the prefetch
worker's decode / encode / transfer beside it, and ``scan_worker_cpu_s``,
their CPU; ``cpu_s``; ``counts`` of
program calls, readbacks, row-count reads and transfers; and
``compile.task_*``, the
compiles that fired on the task's own threads. Every version-1 key
keeps its meaning — ``device_s`` is still the inclusive sum of
``elapsed_compute``, a host wait — and its readers.

The record rides the serving DONE frame (``cost_ledger`` key), is
retained in a bounded process ring (``record``/``recent`` — the
``AuronClient.stats()`` and STATS-frame surface), lands in failure
bundles as ``ledger.json``, and the router augments it with fleet
facts (``fleet.hops``/``spillovers``/``failover``) before replaying
DONE to the client. ``auron.ledger.enabled`` gates assembly; overhead
is gated < 2% by the perf-gate obs-fleet arm.
"""

from __future__ import annotations

import threading
from collections import deque
from typing import Iterable, Optional

LEDGER_VERSION = 2

#: the PR 6 profiler's host-bucket vocabulary (ops/base per-op timers)
HOST_BUCKETS = ("dispatch", "convert", "serde", "iter", "other")

#: snapshot keys that are nested dicts but NOT per-op metric sets
_NON_OP_KEYS = frozenset({"recovery", "mesh"})

_RECOVERY_KEYS = ("attempts", "transient_retries",
                  "corruption_recomputes", "faults_injected")


_DEVICE: Optional[dict] = None


def _device() -> dict:
    """The engine process's device as jax reports it (resolved once)."""
    global _DEVICE
    if _DEVICE is None:
        import jax
        devs = jax.devices()
        _DEVICE = {"platform": devs[0].platform,
                   "kind": devs[0].device_kind, "count": len(devs)}
    return _DEVICE


def enabled(config=None) -> bool:
    from auron_tpu import config as cfg
    conf = config if config is not None else cfg.get_config()
    return bool(conf.get(cfg.LEDGER_ENABLED))


def build(snaps: Optional[Iterable[dict]], *, query_id: str = "",
          rows: int = 0, batches: int = 0, partitions: int = 0,
          wall_s: float = 0.0, cache_hit: bool = False,
          served_from: str = "", outcome: str = "ok",
          task=None) -> dict:
    """Fold per-partition ``finalize()`` snapshots, and the task's
    accumulator (``task``: an ``obs/trace.TaskAccumulator``), into one
    ledger.

    Tolerant by contract: snapshots are observability output, so a
    missing counter, a partial snapshot from a failed partition, an
    empty list or no accumulator all produce a valid (zeroed) ledger —
    assembly must never fail a finished query.
    """
    from auron_tpu.obs import trace as _trace
    if task is None:
        task = _trace.TaskAccumulator(query_id)
    v2 = task.sealed(wall_s)
    device_ns = 0
    host_ns = dict.fromkeys(HOST_BUCKETS, 0)
    shuffle_write_ns = shuffle_read_ns = 0
    shuffle_bytes = spill_bytes = spill_count = 0
    combine_in = combine_out = 0
    mesh_bytes = journal_reused = 0
    xla_compiles = program_builds = program_hits = 0
    compile_s = 0.0
    retries = dict.fromkeys(_RECOVERY_KEYS, 0)
    for snap in snaps or ():
        if not isinstance(snap, dict):
            continue
        xla_compiles += _i(snap.get("xla_compiles"))
        compile_s += _f(snap.get("xla_compile_seconds"))
        program_builds += _i(snap.get("program_builds"))
        program_hits += _i(snap.get("program_hits"))
        rec = snap.get("recovery")
        if isinstance(rec, dict):
            for k in _RECOVERY_KEYS:
                retries[k] += _i(rec.get(k))
        for op, vals in snap.items():
            if not isinstance(vals, dict) or op in _NON_OP_KEYS:
                continue
            device_ns += _i(vals.get("elapsed_compute"))
            for b in HOST_BUCKETS:
                host_ns[b] += _i(vals.get("elapsed_host_" + b))
            shuffle_write_ns += _i(vals.get("shuffle_write_total_time"))
            shuffle_read_ns += _i(vals.get("shuffle_read_total_time"))
            shuffle_bytes += _i(vals.get("shuffle_bytes_live"))
            spill_bytes += _i(vals.get("mem_spill_size"))
            spill_count += _i(vals.get("mem_spill_count"))
            combine_in += _i(vals.get("combine_rows_in"))
            combine_out += _i(vals.get("combine_rows_out"))
            mesh_bytes += _i(vals.get("mesh_bytes_moved"))
            journal_reused += _i(vals.get("journal_bytes_reused"))
    return {
        "version": LEDGER_VERSION,
        "query_id": str(query_id),
        "outcome": str(outcome),
        "device": dict(_device()),
        "wall_s": round(float(wall_s), 6),
        "queue_s": v2["queue_s"],
        "layers_s": v2["layers_s"],
        "layers_cpu_s": v2["layers_cpu_s"],
        "exchange_s": v2["exchange_s"],
        "ops_s": v2["ops_s"],
        "scan_worker_s": v2["scan_worker_s"],
        "scan_worker_cpu_s": v2["scan_worker_cpu_s"],
        "cpu_s": v2["cpu_s"],
        "counts": v2["counts"],
        "device_s": round(device_ns * 1e-9, 6),
        "host_s": {b: round(v * 1e-9, 6) for b, v in host_ns.items()},
        "host_total_s": round(sum(host_ns.values()) * 1e-9, 6),
        "shuffle": {
            "write_s": round(shuffle_write_ns * 1e-9, 6),
            "read_s": round(shuffle_read_ns * 1e-9, 6),
            "bytes": shuffle_bytes,
            "combine_rows_in": combine_in,
            "combine_rows_out": combine_out,
        },
        "spill": {"count": spill_count, "bytes": spill_bytes},
        "mesh_bytes": mesh_bytes,
        "journal_bytes_reused": journal_reused,
        "compile": {
            "xla_compiles": xla_compiles,
            "seconds": round(compile_s, 4),
            "program_builds": program_builds,
            "program_hits": program_hits,
            # the four above are process-wide deltas (concurrent tasks
            # each see the others' compiles); these two are the events
            # that fired on this task's own threads
            **v2["compile"],
        },
        "rows": _i(rows),
        "batches": _i(batches),
        "partitions": _i(partitions),
        "cache_hit": bool(cache_hit),
        "served_from": str(served_from),
        "retries": retries,
        # the router fills these before replaying DONE to the client
        "fleet": {"hops": 0, "spillovers": 0, "failover": "",
                  "replica": ""},
    }


def augment_fleet(ledger, *, hops: Optional[int] = None,
                  spillovers: Optional[int] = None,
                  failover: Optional[str] = None,
                  replica: Optional[str] = None) -> dict:
    """Router-side fleet augmentation of a DONE-frame ledger — tolerant
    of a non-dict / ledger-less payload (propagation off on either
    side), returning the input unchanged in that case."""
    if not isinstance(ledger, dict):
        return ledger
    fleet = ledger.setdefault("fleet", {})
    if not isinstance(fleet, dict):   # foreign payload: do not fight it
        return ledger
    if hops is not None:
        fleet["hops"] = _i(hops)
    if spillovers is not None:
        fleet["spillovers"] = _i(spillovers)
    if failover is not None:
        fleet["failover"] = str(failover)
    if replica is not None:
        fleet["replica"] = str(replica)
    return ledger


def fold(ledgers: Iterable[dict]) -> dict:
    """Aggregate many ledgers into fleet-scale totals (load_report's
    capacity view): sums for seconds/bytes/rows/counters, a count, how
    many were cache hits / failovers, and the devices that computed
    them (so a rate derived from the totals names its platform)."""
    devices: set = set()
    tot = {"queries": 0, "device_s": 0.0, "host_total_s": 0.0,
           "host_s": dict.fromkeys(HOST_BUCKETS, 0.0),
           "shuffle_bytes": 0, "spill_bytes": 0, "rows": 0,
           "cache_hits": 0, "retries": 0, "failovers": 0,
           "replica_hops": 0}
    for led in ledgers or ():
        if not isinstance(led, dict):
            continue
        tot["queries"] += 1
        dev = led.get("device")
        if isinstance(dev, dict):
            devices.add(f"{dev.get('platform')}:{dev.get('kind')}"
                        f"x{dev.get('count')}")
        tot["device_s"] += _f(led.get("device_s"))
        tot["host_total_s"] += _f(led.get("host_total_s"))
        host = led.get("host_s")
        if isinstance(host, dict):
            for b in HOST_BUCKETS:
                tot["host_s"][b] += _f(host.get(b))
        shuffle = led.get("shuffle")
        if isinstance(shuffle, dict):
            tot["shuffle_bytes"] += _i(shuffle.get("bytes"))
        spill = led.get("spill")
        if isinstance(spill, dict):
            tot["spill_bytes"] += _i(spill.get("bytes"))
        tot["rows"] += _i(led.get("rows"))
        tot["cache_hits"] += 1 if led.get("cache_hit") else 0
        rec = led.get("retries")
        if isinstance(rec, dict):
            tot["retries"] += _i(rec.get("transient_retries"))
        fleet = led.get("fleet")
        if isinstance(fleet, dict):
            tot["replica_hops"] += _i(fleet.get("hops"))
            tot["failovers"] += 1 if fleet.get("failover") else 0
    tot["device_s"] = round(tot["device_s"], 6)
    tot["host_total_s"] = round(tot["host_total_s"], 6)
    tot["host_s"] = {b: round(v, 6) for b, v in tot["host_s"].items()}
    tot["devices"] = sorted(devices)
    return tot


# ---------------------------------------------------------------------------
# bounded process retention (the stats()/STATS-frame surface)
# ---------------------------------------------------------------------------

_LOCK = threading.Lock()
_RECENT: deque = deque(maxlen=64)


def record(ledger: dict) -> None:
    """Retain one finished ledger in the bounded process ring."""
    if isinstance(ledger, dict):
        with _LOCK:
            _RECENT.append(ledger)


def recent(n: Optional[int] = None) -> list[dict]:
    with _LOCK:
        items = list(_RECENT)
    return items[-n:] if n else items


def reset() -> None:
    """Drop retained ledgers (tests, chaos-run isolation)."""
    with _LOCK:
        _RECENT.clear()


def _i(v) -> int:
    try:
        return int(v or 0)
    except (TypeError, ValueError):
        return 0


def _f(v) -> float:
    try:
        return float(v or 0.0)
    except (TypeError, ValueError):
        return 0.0
