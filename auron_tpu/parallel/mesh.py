"""SPMD mesh plane: device layout, sharding specs, gang scheduling.

The north star names "the compacted shuffle running as ICI all-to-all on
a pod slice" (PAPER.md); this module is the layout half of that plane —
the part that knows WHICH devices exist, HOW a buffer lays out across
them, and WHO may occupy the mesh right now:

- ``current_plane()`` resolves the ``auron.mesh.*`` knobs into one
  process-wide :class:`MeshPlane` (the device set is process state, so
  the plane is process-global by contract). The plane survives
  unrelated config flips: it is rebuilt only when its OWN parameters
  change, because it owns live scheduling state (the gang lock below).
- Per-buffer replicate-vs-shard decisions (:func:`buffer_spec`, the
  SNIPPETS.md [2]/[3] pattern): scan batches and shuffle entries shard
  on the batch dim (``PartitionSpec(axis)``), broadcast relations and
  hash-table build sides replicate (``PartitionSpec()``) — operators
  declare their buffer kind via ``PhysicalOp.mesh_buffer_kind`` and the
  planner's ``annotate_mesh`` pass stamps the resolved spec on each
  node (``op.mesh_spec``).
- :func:`stack_global_batch` / :func:`local_shard` move between the
  engine's per-partition DeviceBatches and mesh-global sharded arrays
  (one shard per map partition / one shard per reducer device).
- :meth:`MeshPlane.gang` is the gang-scheduling door: a sharded LAUNCH
  occupies the WHOLE mesh, so one round of one stage is between its
  stack and its fence at a time (FIFO tickets, cancel-aware waits); the
  PR 9 scheduler's weighted-round-robin turn is taken on entry, so
  fairness operates BETWEEN sharded rounds and never interleaves two
  inside the mesh. A stage's map side runs outside the door.

Works identically on a virtual CPU mesh
(``--xla_force_host_platform_device_count``, the tier-1 environment)
and a real TPU slice.
"""

from __future__ import annotations

import logging
import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import Optional

import numpy as np

logger = logging.getLogger("auron_tpu")

#: buffer-kind → layout decision (the replicate-vs-shard table). Kinds
#: are declared by operators (``mesh_buffer_kind``); anything undeclared
#: shards — replication is the exception (small, reused-by-every-shard
#: relations), sharding the rule (throughput scales with devices).
_BUFFER_SPECS = {
    "broadcast": "replicate",     # BroadcastExchangeOp collected batches
    "hash_build": "replicate",    # hash-join build side (probe shards)
    "scan_batch": "shard",        # file/memory scan output batches
    "shuffle_entry": "shard",     # exchange buffer entries
    "agg_partial": "shard",       # partial-agg state rows entering a shuffle
}


def buffer_spec(kind: Optional[str]) -> str:
    """'replicate' | 'shard' for a declared buffer kind (default shard)."""
    return _BUFFER_SPECS.get(kind or "", "shard")


def _token_raise(token) -> None:
    """Raise the token's classified error when it is set (QueryCancelled
    / DeadlineExceeded by reason; legacy TaskCancelled for bare Events)
    — the gang door's dequeue-without-starting check."""
    if token is None or not token.is_set():
        return
    raise_for = getattr(token, "raise_for_status", None)
    if raise_for is not None:
        raise_for()
    from auron_tpu.ops.base import TaskCancelled
    raise TaskCancelled("cancelled while queued for the mesh gang")


class MeshPlane:
    """One process's SPMD device layout + the sharded-stage gang door."""

    def __init__(self, devices, axis: str = "data"):
        self.devices = list(devices)
        self.axis = axis
        self._meshes: dict = {}
        # gang scheduling: FIFO ticket queue + condition. A sharded
        # round holds the WHOLE mesh (one slot = the mesh); contenders
        # park here, woken by release, polling their cancel token so a
        # dead query never waits out a long round.
        self._cond = threading.Condition()
        self._queue: deque = deque()
        self._holder: Optional[str] = None
        self._holder_thread: Optional[threading.Thread] = None
        #: slot-accounting counters (tests/test_scheduler.py pins these)
        self.gang_acquired = 0
        self.gang_contended = 0
        self.gang_wait_ns = 0
        # -- fault domain --------------------------------------------------
        #: quarantined device indices (into self.devices): chips a
        #: MeshUnavailable was attributed to. Submeshes rebuild from the
        #: remaining healthy devices; exchanges wider than the healthy
        #: set route host-side (exchange_route).
        self._quarantined: set = set()
        self._quarantine_epoch = 0
        #: demotion/straggler ledger (stats() + executor finalize "mesh")
        self.demotions: dict = {}
        self.stragglers = 0
        self.device_losses = 0
        #: rolling per-round duration window (straggler defense baseline)
        from auron_tpu.runtime.watchdog import MeshRoundStats
        self.round_stats = MeshRoundStats()

    @property
    def num_devices(self) -> int:
        return len(self.devices)

    # -- fault domain --------------------------------------------------------

    def healthy_devices(self) -> list:
        with self._cond:
            if not self._quarantined:
                return list(self.devices)
            return [d for i, d in enumerate(self.devices)
                    if i not in self._quarantined]

    @property
    def usable_width(self) -> int:
        """Devices still eligible for a submesh (total minus quarantine):
        the width exchange_route checks the square contract against."""
        with self._cond:
            return len(self.devices) - len(self._quarantined)

    def quarantine(self, device_index: Optional[int], reason: str) -> int:
        """Retire one device from every future submesh. ``device_index``
        None (XLA carried no device identity) retires the tail device of
        the current healthy set — deterministic, and shrinking the mesh
        by one either way (a wrongly blamed healthy chip costs capacity,
        never correctness). Returns the retired index."""
        with self._cond:
            if device_index is not None \
                    and device_index in self._quarantined:
                # a stale submesh (built pre-quarantine, e.g. a query
                # parked at the gang door) re-reporting the SAME dead
                # chip: already retired — blaming the tail here would
                # compound one real loss into one lost chip per
                # concurrent query
                return device_index
            healthy = [i for i in range(len(self.devices))
                       if i not in self._quarantined]
            if device_index is None or device_index not in healthy:
                device_index = healthy[-1] if healthy else 0
            self._quarantined.add(device_index)
            self._quarantine_epoch += 1
            self.device_losses += 1
            # submesh cache entries may include the dead device: drop
            # them all; mesh_for rebuilds from the healthy set
            self._meshes.clear()
        from auron_tpu.obs import trace
        trace.event("mesh", "mesh.quarantine", device=device_index,
                    reason=reason, usable=self.usable_width)
        logger.warning(
            "mesh fault domain: quarantined device %d (%s); %d/%d "
            "devices remain usable", device_index, reason,
            self.usable_width, self.num_devices)
        try:
            from auron_tpu.obs import registry as obs_registry
            if obs_registry.enabled():
                obs_registry.get_registry().counter(
                    "auron_mesh_quarantines_total").inc()
        except Exception:   # pragma: no cover - obs best-effort
            pass
        return device_index

    def quarantined(self) -> list:
        with self._cond:
            return sorted(self._quarantined)

    def clear_quarantine(self) -> None:
        """Re-admit every quarantined device (tests / operator reset
        after the hardware was actually serviced)."""
        with self._cond:
            if self._quarantined:
                self._quarantined.clear()
                self._quarantine_epoch += 1
                self._meshes.clear()

    def record_demotion(self, reason: str) -> None:
        with self._cond:
            self.demotions[reason] = self.demotions.get(reason, 0) + 1
        try:
            from auron_tpu.obs import registry as obs_registry
            if obs_registry.enabled():
                obs_registry.get_registry().counter(
                    "auron_mesh_demotions_total", reason=reason).inc()
        except Exception:   # pragma: no cover - obs best-effort
            pass

    def record_straggler(self) -> None:
        with self._cond:
            self.stragglers += 1
        try:
            from auron_tpu.obs import registry as obs_registry
            if obs_registry.enabled():
                obs_registry.get_registry().counter(
                    "auron_mesh_stragglers_total").inc()
        except Exception:   # pragma: no cover - obs best-effort
            pass

    def mesh_for(self, n: int):
        """The leading-n-HEALTHY-device submesh (cached per quarantine
        epoch): an exchange with n output partitions runs on exactly n
        devices — the all-to-all's square contract (one output
        partition per device). Quarantined devices never join a
        submesh."""
        from jax.sharding import Mesh
        with self._cond:
            epoch = self._quarantine_epoch
        key = (n, epoch)
        m = self._meshes.get(key)
        if m is None:
            healthy = self.healthy_devices()
            assert 1 <= n <= len(healthy), \
                f"submesh width {n} exceeds usable mesh ({len(healthy)})"
            m = Mesh(np.array(healthy[:n]), (self.axis,))
            self._meshes[key] = m
        return m

    # -- gang scheduling -----------------------------------------------------

    @contextmanager
    def gang(self, token=None, heartbeat=None):
        """Occupy the whole mesh for one sharded launch: a mesh
        exchange takes it once a ROUND, from the round's stack to its
        fence (and its quota re-run), and never across a pull of its map
        side (``ShuffleExchangeOp._materialize_mesh``).

        Takes the PR 9 scheduler's weighted-round-robin turn first (when
        the token carries a slot), so WRR fairness decides the order in
        which queries' sharded rounds reach the mesh — then serializes
        them FIFO: two sharded programs never interleave their
        per-device enqueue order inside the mesh.
        A cancel/deadline landing while parked dequeues with the token's
        classified error, never holding (or waiting for) a dead stage.
        ``heartbeat`` (the task's stall-watchdog TaskHeartbeat) is
        beaten every poll tick while parked: waiting behind another
        query's sharded round is legitimate liveness, not a stall
        — the compile-credit precedent from the lifecycle plane."""
        # RE-ENTRANT per thread: a holder that reaches the door again
        # (no exchange does since the map side left it: a child exchange
        # that mesh-routes is pulled with the door open and takes it for
        # its own rounds) belongs to the same occupation, and a second
        # acquisition on this thread would deadlock against itself.
        me = threading.current_thread()
        with self._cond:
            if self._holder_thread is me:
                reentrant = True
            else:
                reentrant = False
        if reentrant:
            yield self
            return
        from auron_tpu.obs import trace
        # the door is the stage's wait for the mesh: the scheduler's turn
        # and the FIFO behind another query's sharded stage
        with trace.layer_span("exchange", "gang_wait"):
            qid, wait_ns, contended = self._acquire_gang(token, heartbeat)
        # one a door actually taken (never a re-entrant pass): a stage's
        # frame reads it beside ``mesh_rounds``
        trace.count("mesh_gang_acquires")
        trace.event("mesh", "mesh.gang", query=qid,
                    wait_ms=round(wait_ns / 1e6, 3), contended=contended)
        try:
            yield self
        finally:
            with self._cond:
                self._holder = None
                self._holder_thread = None
                self._cond.notify_all()

    def _acquire_gang(self, token, heartbeat) -> tuple:
        """The gang door: returns ``(query id, wait ns, contended)``
        once this thread holds the mesh."""
        me = threading.current_thread()
        from auron_tpu.runtime import faults as _faults
        from auron_tpu.runtime import scheduler as _scheduler
        _scheduler.turn(token)
        # the gang-door chaos site (mesh.gang:cancel): a cancel racing
        # the door itself — fired before AND while parked, so both the
        # uncontended fast path and a parked ticket prove the dequeue-
        # without-starting contract
        _faults.maybe_cancel("mesh.gang", token)
        ticket = object()
        qid = (getattr(token, "query_id", "") or "") if token is not None \
            else ""
        t0 = time.perf_counter_ns()
        contended = False
        with self._cond:
            self._queue.append(ticket)
            try:
                # a cancel that landed BEFORE the door (or the injected
                # one above) dequeues here — the round never starts
                _token_raise(token)
                while self._holder is not None \
                        or self._queue[0] is not ticket:
                    contended = True
                    if heartbeat is not None:
                        heartbeat.beat("mesh.gang")
                    self._cond.wait(0.05)
                    _faults.maybe_cancel("mesh.gang", token)
                    _token_raise(token)
            except BaseException:
                self._queue.remove(ticket)
                self._cond.notify_all()
                raise
            self._queue.popleft()
            self._holder = qid or "anonymous"
            self._holder_thread = me
            self.gang_acquired += 1
            if contended:
                self.gang_contended += 1
            wait_ns = time.perf_counter_ns() - t0
            self.gang_wait_ns += wait_ns
        return qid, wait_ns, contended

    def gang_holder(self) -> Optional[str]:
        with self._cond:
            return self._holder

    def stats(self) -> dict:
        with self._cond:
            return {"devices": self.num_devices, "axis": self.axis,
                    "gang_acquired": self.gang_acquired,
                    "gang_contended": self.gang_contended,
                    "gang_wait_ms": round(self.gang_wait_ns / 1e6, 3),
                    "gang_holder": self._holder,
                    "gang_queued": len(self._queue),
                    "quarantined": sorted(self._quarantined),
                    "usable": (len(self.devices)
                               - len(self._quarantined)),
                    "demotions": dict(self.demotions),
                    "stragglers": self.stragglers,
                    "device_losses": self.device_losses}


#: (params, plane) — the plane persists across UNRELATED config flips
#: (it owns the live gang lock; rebuilding it mid-query would hand a
#: second sharded stage a fresh, free lock) and rebuilds only when its
#: own parameters (enabled/devices/axis) change
_PLANE_LOCK = threading.Lock()
_PLANE: tuple = (None, None)
_EPOCH: int = -1


def current_plane() -> Optional[MeshPlane]:
    """The process's MeshPlane, or None when ``auron.mesh.enabled`` is
    off or fewer than 2 devices are visible. Config-epoch cached: the
    armed hot path costs one int compare."""
    global _PLANE, _EPOCH
    from auron_tpu import config as cfg
    epoch = cfg.config_epoch()
    if epoch == _EPOCH:
        return _PLANE[1]
    conf = cfg.get_config()
    params = (bool(conf.get(cfg.MESH_ENABLED)),
              # graft: disable=GL001 -- a configuration value, host data
              int(conf.get(cfg.MESH_DEVICES)),
              str(conf.get(cfg.MESH_AXIS)))
    with _PLANE_LOCK:
        if _PLANE[0] == params:
            _EPOCH = epoch
            return _PLANE[1]
        plane = None
        if params[0]:
            import jax
            devs = list(jax.devices())
            limit = params[1] if params[1] > 0 else len(devs)
            devs = devs[:limit]
            multihost = jax.process_count() > 1
            # single-host only: the reducer read path slices addressable
            # shards; multihost deployments shuffle through the RSS tier
            # by construction (the durable fallback)
            if len(devs) >= 2 and not multihost:
                plane = MeshPlane(devs, axis=params[2])
        _PLANE = (params, plane)
        _EPOCH = epoch
        return plane


def reset_plane() -> None:
    """Drop the cached plane (tests)."""
    global _PLANE, _EPOCH
    with _PLANE_LOCK:
        _PLANE = (None, None)
        _EPOCH = -1


def clear_quarantine() -> None:
    """Re-admit quarantined devices on the cached plane regardless of
    the current ``auron.mesh.enabled`` value (test/chaos hygiene: a
    quarantine injected by one run must not silently reroute the
    next)."""
    plane = _PLANE[1]
    if plane is not None:
        plane.clear_quarantine()


# ---------------------------------------------------------------------------
# routing decision (the exchange's eligibility check, unit-testable pure)
# ---------------------------------------------------------------------------

def exchange_route(partitioning, num_partitions: int,
                   input_partitions: int,
                   plane: Optional[MeshPlane]) -> tuple[str, str]:
    """(route, reason) for one shuffle exchange: ``all_to_all`` when the
    source and sink stages can share the mesh, else ``device_buffer``
    (the host-orchestrated classic path). RSS exchanges are routed by
    construction (the durable/multihost tier) and never call this."""
    from auron_tpu.parallel.partitioning import HashPartitioning
    if plane is None:
        return "device_buffer", "mesh_disabled"
    if not isinstance(partitioning, HashPartitioning):
        return ("device_buffer",
                f"partitioning_{type(partitioning).__name__}")
    if num_partitions < 2:
        return "device_buffer", "single_output"
    # the square contract is checked against the HEALTHY width: after a
    # quarantine the plane rebuilds a smaller submesh while
    # 2 <= num_partitions <= usable still holds, and routes host-side
    # (with the reason telling you WHY) once it does not
    usable = getattr(plane, "usable_width", plane.num_devices)
    if num_partitions > usable:
        # blame the quarantine only when it is what actually broke the
        # square contract — an exchange wider than the FULL mesh never
        # had a mesh route to lose
        if usable < plane.num_devices \
                and num_partitions <= plane.num_devices:
            return ("device_buffer",
                    f"mesh_quarantined_{usable}<{num_partitions}")
        return ("device_buffer",
                f"mesh_too_narrow_{usable}<{num_partitions}")
    if input_partitions > num_partitions:
        return ("device_buffer",
                f"fan_in_exceeds_mesh_{input_partitions}>{num_partitions}")
    return "all_to_all", "mesh"


# ---------------------------------------------------------------------------
# layout helpers: per-partition batches <-> mesh-global sharded arrays
# ---------------------------------------------------------------------------

def replicate(tree, mesh):
    """Replicate every array leaf of ``tree`` across the mesh
    (``NamedSharding(mesh, P())`` — the SNIPPETS [2]/[3] pattern): the
    device_put half of the "replicate" spec for broadcast relations and
    hash-table build sides. NOT yet called on the execution hot path —
    today only the sharded EXCHANGE runs inside the mesh, and its
    programs close over nothing replicated; stage bodies that read a
    build side per shard (the fused-probe lowering, the HBM-tier item)
    are the consumers this helper exists for. Kept honest by a unit
    test asserting the fully-replicated layout."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    sharding = NamedSharding(mesh, P())
    return jax.tree_util.tree_map(
        lambda x: jax.device_put(x, sharding), tree)


def stack_global_batch(batches: list, mesh, axis: str):
    """Stack one round's per-map-partition batches into mesh-global
    sharded arrays: shard i of every leaf is map partition i's rows.

    Returns ``(columns, num_rows, capacity)`` where ``columns`` is the
    DeviceBatch column tuple with every leaf ``[n_dev * capacity, ...]``
    sharded on the batch dim, and ``num_rows`` is ``int32[n_dev]`` (one
    live count per shard). Ragged inputs are normalized first — string
    widths / list element counts unified, capacities padded to the
    round's max — so every shard is shape-identical (the static-shape
    contract every mesh kernel compiles against)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from auron_tpu.columnar.batch import resize, unify_column_widths

    n_dev = len(batches)
    assert n_dev == mesh.shape[axis], \
        f"{n_dev} shards for a {mesh.shape[axis]}-device mesh"
    cap = max(b.capacity for b in batches)
    batches = [resize(b, cap) if b.capacity != cap else b
               for b in batches]
    cols = []
    for i in range(batches[0].num_columns):
        cols.append(unify_column_widths([b.columns[i] for b in batches]))
    sharding = NamedSharding(mesh, P(axis))
    global_cols = tuple(
        jax.tree_util.tree_map(
            lambda *ls: jax.device_put(jnp.concatenate(ls, axis=0),
                                       sharding),
            *unified)
        for unified in cols)
    # per-shard live counts WITHOUT a host readback (num_rows scalars
    # stay device-resident; the stack is one tiny transfer)
    num_rows = jax.device_put(
        jnp.stack([jnp.asarray(b.num_rows, jnp.int32) for b in batches]),
        sharding)
    return global_cols, num_rows, cap


def local_shard(arr, d: int, mesh):
    """Device ``d``'s addressable shard of a mesh-global array — the
    zero-copy per-device view the reducer read path slices (single-host;
    multihost reducers go through the RSS tier by construction)."""
    dev = mesh.devices.flat[d]
    # graft: disable=GL001 -- documented single-host reducer read path; multihost routes RSS by construction (ROADMAP scale-out)
    for s in arr.addressable_shards:
        if s.device == dev:
            return s.data
    raise ValueError(f"no addressable shard on device {dev}")
