"""Stage exchange (shuffle + broadcast).

The reference's exchange is file-based: BufferedData staging → ONE
per-partition-sorted compaction → spill file + offset index, fetched
through Spark's block store (reference:
datafusion-ext-plans/src/shuffle/buffered_data.rs:48-225,
sort_repartitioner.rs:44-254; SURVEY.md §3.3). This engine keeps that
exact shape at HBM granularity:

- the split is ONE stable sort-by-partition-id per input batch (not P
  compaction passes): rows land contiguous per target partition with a
  host-side offset index — buffered_data.rs's sorted compaction verbatim;
- sorted batches stay device-resident and are REGISTERED with the memory
  manager; under pressure they spill to host storage via the columnar
  serde, offsets riding along as a frame extra — the
  SortShuffleRepartitioner spill contract;
- a reducer partition reads its row range from each entry (device slice
  or host-restored), never touching other partitions' rows;
- range partitioning samples its bounds from the FIRST batches of the
  same materialization pass (no second execution of the child).

ShuffleExchangeOp is a stage boundary: the upstream subtree runs once per
*input* partition (all materialized on first demand, memoized),
downstream partitions then stream their buckets. In SPMD execution the
same sorted-compaction rides `lax.all_to_all`
(auron_tpu.parallel.mesh_exchange).
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Iterator, NamedTuple, Optional

import numpy as np

import jax
import jax.numpy as jnp

from auron_tpu.columnar.batch import (DeviceBatch, concat_live_rows,
                                      gather_batch)
from auron_tpu.columnar.schema import Schema
from auron_tpu.exprs.eval import EvalContext, evaluate
from auron_tpu.obs import profile as _profile
from auron_tpu.ops import hashing
from auron_tpu.ops.base import (ExecContext, PhysicalOp, count_output,
                                timer, yields_owned_batches)
from auron_tpu.parallel.partitioning import (HashPartitioning,
                                             RangePartitioning,
                                             RoundRobinPartitioning,
                                             SinglePartitioning,
                                             range_bounds,
                                             range_partition_ids,
                                             sample_range_words)
from auron_tpu.runtime import programs
from auron_tpu.runtime.programs import program_cache
from auron_tpu.utils.shapes import bucket_rows

#: rows sampled for range bounds (reference samples client-side too,
#: NativeShuffleExchangeBase.scala:313+)
_RANGE_SAMPLE_ROWS = 10_000

logger = logging.getLogger("auron_tpu")


def _split_body(batch: DeviceBatch, pids, num_partitions: int):
    """Traced split body: stable sort rows by target partition id (dead
    rows to the end) + per-partition counts (reference:
    shuffle/buffered_data.rs:88-160)."""
    live = batch.row_mask()
    key = jnp.where(live, pids, num_partitions)
    perm = jnp.argsort(key, stable=True)
    sorted_batch = gather_batch(batch, perm, batch.num_rows)
    counts = jax.ops.segment_sum(
        live.astype(jnp.int32), jnp.clip(key, 0, num_partitions),
        num_segments=num_partitions + 1)[:num_partitions]
    return sorted_batch, counts


class _Slice(NamedTuple):
    """Rows ``[start, start + rows)`` of a column tree on the device:
    what one map sent one reducer in one batch — a range of a sorted
    map batch, of a mesh round's shard, or a restored frame (``whole``:
    all of a tree that holds nothing else, padding invalid)."""
    tree: tuple
    start: int
    rows: int
    whole: bool = False


@program_cache("parallel.exchange.read_cut", maxsize=256)
def _read_cut_kernel(tree_of_slice: tuple, window: int, capacity: int):
    """The reduce side's read: one row range (a slice) of each of
    ``len(tree_of_slice)`` column trees — slice ``k`` lies in tree
    ``tree_of_slice[k]`` — as the columns of ONE batch of ``capacity``
    rows, the slices' live rows one after another in slice order. Each
    slice is gathered as a window of ``window`` rows (no slice holds
    more) and the windows' live rows are gathered into the one batch
    (``columnar/batch.concat_live_rows``: output row ``j`` comes from
    the window that the running sum of the live counts says). The ranges are an OPERAND
    (``bounds``: int32[2, slices], starts over live counts), so the key
    holds shapes only and no run's counts compile; rows past the
    counts' sum are padding, masked invalid — the same rows in the same
    order the eager ``gather_batch`` of every slice gave."""

    def auron_parallel_exchange_read_cut(trees, bounds):
        rows = jnp.arange(window, dtype=jnp.int32)
        windows = []
        for k, t in enumerate(tree_of_slice):
            base = DeviceBatch(trees[t], bounds[1, k])
            windows.append(gather_batch(
                base, jnp.minimum(bounds[0, k] + rows, base.capacity - 1),
                bounds[1, k]))
        if len(windows) == 1:
            return windows[0].columns
        return concat_live_rows(windows, capacity).columns

    return programs.jit(auron_parallel_exchange_read_cut)


def _cut(run: list, batch_capacity: int) -> DeviceBatch:
    """One launch of the read-cut program over ``run`` (``_Slice``s on
    one device): their live rows, one slice after another, as ONE batch
    there. The host's starts and counts ride the call as its one small
    operand, and their sum is the batch's row count: a host integer,
    which no consumer has to read from the device. The batch's capacity
    follows the run's SHAPE — its slices x the bucket of its fullest
    (past ``batch_capacity``: the bucket of its live rows) — so a run
    compiles, here and in what consumes it, for the shapes its slices
    handed on one by one compiled for, not for every sum of them. A
    slice that is a whole batch is handed on as it is."""
    bounds = np.array([[piece.start for piece in run],
                       [piece.rows for piece in run]], np.int32)
    rows = np.int32(bounds[1].sum())
    if len(run) == 1 and run[0].whole:
        return DeviceBatch(run[0].tree, rows)
    # a tree (a round's shard) may hold several of the run's slices
    trees = list({id(piece.tree): piece.tree for piece in run}.values())
    at = {id(tree): i for i, tree in enumerate(trees)}
    window = bucket_rows(int(bounds[1].max()))   # graft: disable=GL001 -- the slices' host counts (numpy)
    capacity = len(run) * window
    if capacity > batch_capacity:
        capacity = int(rows)   # graft: disable=GL001 -- a numpy sum of host counts
    columns = _read_cut_kernel(
        tuple(at[id(piece.tree)] for piece in run), window,
        bucket_rows(capacity))(tuple(trees), bounds)
    return DeviceBatch(columns, rows)


def _merged(slices, batch_capacity: int) -> Iterator[DeviceBatch]:
    """What a reducer is handed of ``slices`` (``_Slice``s in the order
    their rows are due): the slices packed greedily, in that order,
    into runs whose live rows fit ``batch_capacity`` (a slice larger
    than that is a run of its own), ONE batch and one launch a run
    (``_cut``). An empty slice rides the
    run it falls in (a program's shapes follow the slices, never their
    counts); a run of empty slices is no batch. EVERY read of an
    exchange — the mesh buffer's, the host buffer's, a demoted one's,
    the shuffle service's — hands its slices on through here, so the
    same rows reach a reducer in the same batches whatever the route,
    whatever spilled and whatever was demoted on the way: a double sum
    adds up in the same order."""
    run, rows = [], 0
    for piece in slices:
        if run and rows + piece.rows > batch_capacity:
            if rows:
                yield _cut(run, batch_capacity)
            run, rows = [], 0
        run.append(piece)
        rows += piece.rows
    if rows:
        yield _cut(run, batch_capacity)


def _counted(batches) -> Iterator[DeviceBatch]:
    """``counts.mesh_read_batches``: the batches a reducer of a
    mesh-routed exchange is handed."""
    from auron_tpu.obs import trace
    for batch in batches:
        trace.count("mesh_read_batches")
        yield batch


def _frame_slice(frame: bytes) -> Optional[_Slice]:
    """A serialized frame (a spilled entry's partition, a shuffle
    service's map output) restored to the device, whole; None for an
    empty one."""
    from auron_tpu.columnar.serde import (deserialize_host_batch,
                                          host_to_batch)
    host, _extras = deserialize_host_batch(frame)
    if not host.num_rows:
        return None
    batch = host_to_batch(host, bucket_rows(host.num_rows))
    return _Slice(batch.columns, 0, host.num_rows, whole=True)


#: split programs: the upstream fused-stage chain (when one folds), the
#: partition-id computation and the sort-by-pid compaction in ONE XLA
#: program — the whole-stage-fusion prologue of the exchange, and with no
#: chain the split of every exchange that folds none (``_Split``)
_SPLIT_PROGRAMS = programs.register(
    programs.ProgramCache("parallel.exchange.fused_split", maxsize=256))


def _fused_split_program(frag_keys: tuple, part_sig: tuple,
                         in_schema: Schema, out_schema: Schema,
                         n_out: int, capacity: int, donate: bool,
                         fragments, part_exprs,
                         combine=None, combine_sig=None):
    """One program per (chain signature, partitioning, schema, capacity):
    runs the member fragments, computes partition ids on the chain
    output, and splits — intermediates never touch HBM. The carry vector
    is the members' carries plus one trailing slot counting rows seen at
    the split (the round-robin start offset, kept on device).

    ``combine`` (ops/agg.AggOp.build_combine_stage) is the map-side
    combine fold: the elided partial agg's per-batch combine runs
    between the chain and the partition-id computation, so
    ``out_schema``/``part_exprs`` see the partial state layout and
    groups merge BEFORE the split. Stateless — no carries —
    and the program grows one extra output: the pre-combine live-row
    count, read by the caller in its existing counts fence (combine
    telemetry never adds a sync point). ``combine_sig`` keys the trace."""

    def build():
        from auron_tpu.ops.fused import thread_fragments
        n_frags = len(fragments)
        kind = part_sig[0]

        def auron_parallel_exchange_fused_split(batch: DeviceBatch,
                                                partition_id, carries,
                                                bounds=None):
            outs, new_carries = thread_fragments(fragments, batch,
                                                 partition_id, carries)
            (b,) = outs   # fan-out chains never take this path
            comb_in = None
            if combine is not None:
                b, comb_in = combine(b)
            if kind == "hash":
                ctx = EvalContext()
                cols = [evaluate(e, b, out_schema, ctx).col
                        for e in part_exprs]
                h = hashing.murmur3_columns(cols, b.capacity,
                                            hashing.SPARK_SHUFFLE_SEED)
                nn = jnp.int32(n_out)
                pids = ((h % nn) + nn) % nn
            elif kind == "round_robin":
                start = carries[n_frags].astype(jnp.int32)
                pids = (jnp.arange(b.capacity, dtype=jnp.int32) + start) \
                    % jnp.int32(n_out)
            elif kind == "range":
                pids = range_partition_ids(b, out_schema, part_sig[1],
                                           n_out, *bounds, part_sig[2])
            else:   # single
                pids = jnp.zeros(b.capacity, jnp.int32)
            sorted_batch, counts = _split_body(b, pids, n_out)
            new_carries.append(carries[n_frags]
                               + jnp.asarray(b.num_rows, jnp.int64))
            if combine is not None:
                return sorted_batch, counts, jnp.stack(new_carries), comb_in
            return sorted_batch, counts, jnp.stack(new_carries)

        # graft: donation-ok -- host split path (the mesh exchange
        # keeps donation OFF by contract for its escalation re-run)
        return programs.jit(auron_parallel_exchange_fused_split,
                            donate_argnums=(0,) if donate else ())

    return _SPLIT_PROGRAMS.get_or_build(
        (frag_keys, part_sig, in_schema, n_out, capacity, donate,
         combine_sig), build)


def _split_signature(partitioning) -> tuple:
    """Hashable partitioning signature for the split program. A range
    partitioning's holds its sort orders and the layout of its bounds,
    never their values (the program's operand)."""
    if isinstance(partitioning, HashPartitioning):
        return ("hash", tuple(partitioning.exprs))
    if isinstance(partitioning, RoundRobinPartitioning):
        return ("round_robin",)
    if isinstance(partitioning, RangePartitioning):
        return ("range", tuple(partitioning.sort_orders),
                tuple(partitioning.bound_layout))
    assert isinstance(partitioning, SinglePartitioning), partitioning
    return ("single",)


def _sample_range_bounds(batches, partitioning: RangePartitioning,
                         schema: Schema) -> tuple:
    """Sample a range partitioning's bounds from the LEADING batches of
    ``batches`` (one sample program and one readback a batch, its row
    count riding along) -> (the partitioning with its bounds, the
    batches drawn, which the caller still owes their split)."""
    drawn, samples, rows = [], [], 0
    for batch in batches:
        drawn.append(batch)
        samples.append(sample_range_words(batch, partitioning.sort_orders,
                                          schema))
        rows += len(samples[-1][0])
        if rows >= _RANGE_SAMPLE_ROWS:
            break
    bounds, layout = range_bounds(samples, partitioning.sort_orders,
                                  partitioning.num_partitions)
    return RangePartitioning(partitioning.sort_orders,
                             partitioning.num_partitions, bounds,
                             layout), drawn


class _Split:
    """The map side's split of ONE exchange, whatever its partitioning
    and whatever folded into it: one launch of the split program a batch
    (the chain and the combine stage when ``fold`` brings them, the
    partition ids, the sort by them) and its ONE sync, the counts
    readback. Every route that splits on the host calls it — the
    device-buffer fill, folded or not, a demoted mesh exchange's
    continuation and the RSS writer."""

    def __init__(self, partitioning, n_out: int, in_schema: Schema,
                 out_schema: Schema, donate: bool, fold=None,
                 kmetrics=None):
        self.fragments, self.frag_keys, _input_op, self.combine, \
            self.combine_sig = fold or ([], (), None, None, None)
        self.part_sig = _split_signature(partitioning)
        self.part_exprs = partitioning.exprs \
            if isinstance(partitioning, HashPartitioning) else ()
        self.n_out = n_out
        self.in_schema, self.out_schema = in_schema, out_schema
        self.donate = donate
        #: a range partitioning's bounds, on the device once an exchange
        self.bounds = jax.device_put(partitioning.bounds_operand()) \
            if isinstance(partitioning, RangePartitioning) else None
        self.built_c = self.hit_c = None
        if kmetrics is not None:
            self.built_c = kmetrics.counter("fused_split_programs_built")
            self.hit_c = kmetrics.counter("fused_split_program_hits")

    def carries(self, members=None, seen=None):
        """The carry vector a map partition starts with: its members'
        (their initial ones unless given) and the trailing slot, the
        rows seen at the split so far (the round-robin start)."""
        if members is None:
            members = [f.init_carry for f in self.fragments]
        # graft: disable=GL001 -- host values: initial carries, or a snapshot a timed_get brought
        members = np.asarray(members, np.int64).reshape(-1)
        if seen is None:
            return np.concatenate([members, np.zeros(1, np.int64)])
        return jnp.concatenate([jnp.asarray(members), seen])

    def __call__(self, batch: DeviceBatch, in_p: int, carries, t):
        """-> (sorted batch, host counts a partition, the carries after
        it, pre-combine live rows or None). ``t`` is the open timer
        frame the launch and the readback's wait are booked in."""
        kern, built = _fused_split_program(
            self.frag_keys, self.part_sig, self.in_schema,
            self.out_schema, self.n_out, batch.capacity, self.donate,
            self.fragments, self.part_exprs, self.combine,
            self.combine_sig)
        if self.built_c is not None:
            (self.built_c if built else self.hit_c).add(1)
        outs = t.track(kern(batch, np.int32(in_p), carries, self.bounds))
        sorted_batch, counts, carries = outs[:3]
        # the counts readback is the split's semantic sync point: read
        # inside the timer frame, so that the wait is booked as device;
        # the pre-combine live rows ride the SAME fence
        fenced = _profile.timed_get((counts,) + tuple(outs[3:]))
        # graft: disable=GL001 -- already host: read via timed_get above
        counts_h = np.asarray(fenced[0])
        comb_in_h = int(fenced[1]) if self.combine is not None else None   # graft: disable=GL001 -- same fenced readback
        if self.combine is not None:
            # a combined batch's row count is traced (the group count):
            # pin the concrete live total so that buffer bookkeeping and
            # spill slicing never sync on it
            sorted_batch = DeviceBatch(sorted_batch.columns,
                                       int(counts_h.sum()))
        return sorted_batch, counts_h, carries, comb_in_h


def _record_route(op, metrics, route: str, reason: str, **attrs) -> None:
    """Record one exchange's routing decision (all_to_all vs
    device_buffer vs rss) on its metric set AND the 'mesh' trace
    category — the per-exchange table tools/mesh_report.py prints, and
    what the mesh battery asserts against (recorded, never inferred)."""
    metrics.counter("exchange_route_" + route).add(1)
    from auron_tpu.obs import trace
    trace.event("mesh", "exchange.route", op=repr(op), route=route,
                reason=reason, partitions=op.num_partitions,
                maps=getattr(op, "input_partitions", 1), **attrs)


class _ExchangeBuffer:
    """MemConsumer owning the sorted shuffle entries of one exchange.

    Each entry is one input batch sorted by partition id plus its host
    offset index. Device entries spill (oldest first) to tiered host
    storage via the columnar serde when the memory manager picks this
    consumer as a victim."""

    def __init__(self, op, mem_manager, metrics, conf=None):
        from auron_tpu import config as cfg
        conf = conf or cfg.get_config()
        self.op = op
        self.mem = mem_manager
        self.metrics = metrics
        self.codec_level = conf.get(cfg.SPILL_CODEC_LEVEL)
        #: the most live rows of a batch handed to a reducer
        self.capacity = conf.get(cfg.BATCH_CAPACITY)
        self.consumer_name = f"exchange-{id(op):x}"
        #: entry = ["dev", DeviceBatch, offsets] | ["dev-spilling", ...] |
        #: ["spill", SpillRef, offsets, num_rows]
        self.entries: list = []
        self._dev_bytes = 0   # running counter, guarded by _lock
        self._lock = threading.RLock()
        if mem_manager is not None:
            mem_manager.register_consumer(self)

    # -- write side ---------------------------------------------------------

    def add(self, sorted_batch: DeviceBatch, offsets: np.ndarray) -> None:
        from auron_tpu.columnar.batch import batch_nbytes
        with self._lock:
            self.entries.append(["dev", sorted_batch, offsets])
            self._dev_bytes += batch_nbytes(sorted_batch)
            used = self._dev_bytes
        if self.mem is not None:
            self.mem.update_mem_used(self, used)

    def mem_used(self) -> int:
        with self._lock:
            return self._dev_bytes

    def spill(self) -> int:
        from auron_tpu.columnar.batch import batch_nbytes
        from auron_tpu.columnar.serde import (batch_to_host,
                                              serialize_host_batch,
                                              slice_host_batch)
        if self.mem is None or getattr(self.mem, "spill_manager", None) is None:
            return 0
        # claim victims under the lock (tag flip) so a concurrent spill()
        # can't serialize the same entries twice
        with self._lock:
            victims = [(i, e) for i, e in enumerate(self.entries)
                       if e[0] == "dev"]
            for _i, e in victims:
                e[0] = "dev-spilling"
            if not victims:
                return 0
        n_out = len(victims[0][1][2]) - 1
        freed = 0
        for i, e in victims:
            _tag, batch, offsets = e
            n = _profile.row_count(batch)
            host = batch_to_host(batch, n)
            # ONE FRAME PER PARTITION (the reference's data file + offset
            # index, sort_repartitioner.rs:151+): a reducer later reads
            # only its own frame via Spill.frame_at — never
            # decompressing other partitions' rows
            spill = self.mem.spill_manager.new_spill()
            for p in range(n_out):
                # graft: disable=GL001 -- offsets is a host ndarray
                lo, hi = int(offsets[p]), int(offsets[p + 1])
                part = slice_host_batch(host, lo, hi)
                spill.write_frame(serialize_host_batch(
                    part, codec_level=self.codec_level))
            done = spill.finish()
            with self._lock:
                if i < len(self.entries) and self.entries[i] is e:
                    self.entries[i] = ["spill", done, offsets, n]
                    self._dev_bytes -= batch_nbytes(batch)
                    freed += batch_nbytes(batch)
                else:
                    # buffer was closed/cleared mid-spill
                    done.release()
        self.metrics.counter("mem_spill_count").add(len(victims))
        self.metrics.counter("mem_spill_size").add(freed)
        return freed

    # -- read side ----------------------------------------------------------

    def entry_slices(self, p: int, indices=None) -> Iterator[_Slice]:
        """Partition ``p``'s rows of the entries (of those at
        ``indices`` only: a demoted exchange's per-source read — a spill
        swaps entries IN PLACE, so indices stay stable across pressure),
        in append order: a device entry's range of its sorted batch,
        empty or not; a spilled entry's own frame restored, unless it is
        empty."""
        with self._lock:
            entries = list(self.entries) if indices is None \
                else [self.entries[i] for i in indices]
        for e in entries:
            offsets = e[2]
            # graft: disable=GL001 -- offsets is a host ndarray
            lo, hi = int(offsets[p]), int(offsets[p + 1])
            if e[0].startswith("dev"):
                # "dev" or "dev-spilling": the device batch in this
                # snapshot's entry list stays valid even if a concurrent
                # spill swaps the entry afterwards
                yield _Slice(e[1].columns, lo, hi - lo)
            elif hi > lo:
                yield _frame_slice(e[1].frame_at(p))

    def partition_batches(self, p: int) -> Iterator[DeviceBatch]:
        """Partition ``p``'s rows, entry by entry in append order, in
        as few batches as hold them (``_merged``): the small batches of
        a gather or of a stage's tail reach their reducer as one."""
        return _merged(self.entry_slices(p), self.capacity)

    def close(self) -> None:
        if self.mem is not None:
            self.mem.unregister_consumer(self)
        with self._lock:
            entries, self.entries = self.entries, []
            self._dev_bytes = 0
        for e in entries:
            if e[0] == "spill":
                e[1].release()

    def __del__(self):
        # backstop for spill files when the memoized buffer is dropped with
        # the query's op tree. Deliberately does NOT call close(): cyclic GC
        # can fire this finalizer on the same thread that currently holds
        # the MemManager lock (op -> buffer -> op cycle), and
        # unregister_consumer would deadlock on it. Registration needs no
        # cleanup — the manager holds consumers weakly.
        try:
            for e in self.entries:
                if e[0] == "spill":
                    e[1].release()
        except Exception:
            pass


class _MeshExchangeBuffer:
    """The SPMD twin of _ExchangeBuffer: received rows of a mesh-routed
    exchange, one entry per all-to-all round.

    Each entry holds the mesh-global output column tree (shard p =
    reducer partition p's rows in ``[src * quota + r]`` layout), the
    host recv-count matrix ``[n_dev, n_dev]`` (dest × source) and the
    round's quota. ``partition_batches(p)`` reads device p's shard
    zero-copy and hands the reducer its rows as ONE batch (as few as
    hold them, past the configured batch capacity), the slices a
    SOURCE and round in it source-major, rounds-minor — so a reducer
    sees exactly the map-major row sequence the host device-buffer path
    yields (the bit-identity contract of the mesh battery). Registered
    with the memory manager for visibility and the per-device footprint
    ledger; entries are device-resident by design
    and do not spill (``spill`` returns 0 — the mesh route is chosen
    only when the whole exchange fits the mesh; RSS remains the
    durable tier)."""

    def __init__(self, op, mesh, axis: str, n_out: int, mem_manager,
                 metrics, conf=None):
        from auron_tpu import config as cfg
        self.mesh = mesh
        self.axis = axis
        self.n_out = n_out
        self.mem = mem_manager
        self.metrics = metrics
        #: the most live rows of a batch handed to a reducer
        self.capacity = (conf or cfg.get_config()).get(cfg.BATCH_CAPACITY)
        self.consumer_name = f"mesh-exchange-{id(op):x}"
        #: [(out_cols tree, counts np[n_dev, n_dev], quota), ...]
        self.entries: list = []
        self._dev_bytes = 0
        self._lock = threading.RLock()
        if mem_manager is not None:
            mem_manager.register_consumer(self)

    def add_round(self, out_cols, counts, quota: int) -> int:
        """Record one round. Returns the LIVE bytes this round moved
        (rows actually received × per-row width — the honest
        data-movement figure; the allocated buffers are zero-padded to
        ``n_dev² × quota`` row slots, which under skew overstates
        movement by an order of magnitude)."""
        leaves = jax.tree_util.tree_leaves(out_cols)
        nbytes = sum(l.nbytes for l in leaves)
        if not self.entries and leaves:
            # where this exchange's output actually sits: the distinct
            # devices of the first round's output sharding (recorded
            # once per exchange — every round shares it)
            self.metrics.counter("mesh_shard_devices").add(
                len(leaves[0].sharding.device_set))
        # graft: disable=GL001 -- quota is a python int (bucket_rows)
        slots = self.n_out * self.n_out * max(int(quota), 1)
        live = int(counts.sum())
        # graft: disable=GL001 -- host arithmetic on python ints
        live_bytes = int(nbytes * live / slots) if slots else 0
        with self._lock:
            self.entries.append((out_cols, counts, quota))
            self._dev_bytes += nbytes
        self.metrics.counter("mesh_bytes_moved").add(live_bytes)
        from auron_tpu.obs import trace
        trace.count("mesh_rounds")
        trace.count("mesh_bytes", live_bytes)
        trace.count("mesh_slot_bytes", nbytes)
        if self.mem is not None:
            # the ledger's unit is ONE device's HBM (the memmgr budget
            # is a fraction of a single chip): account the per-device
            # footprint, not the mesh-global total
            self.mem.update_mem_used(self, self.per_device_bytes())
        return live_bytes

    def mem_used(self) -> int:
        """MemConsumer contract: this buffer's charge against the
        (single-device) budget — the per-chip footprint."""
        return self.per_device_bytes()

    def global_bytes(self) -> int:
        """Allocated bytes summed across every shard of the mesh."""
        with self._lock:
            return self._dev_bytes

    def per_device_bytes(self) -> int:
        """The per-chip footprint the memmgr ledger accounts: global
        bytes divide evenly across the mesh (every leaf is batch-dim
        sharded)."""
        with self._lock:
            return self._dev_bytes // max(self.n_out, 1)

    def spill(self) -> int:
        return 0   # device-resident by design (see class docstring)

    def partition_shards(self, p: int) -> list:
        """Partition ``p``'s received rows on the home device: (the
        shard of device ``p``, its live counts a source, its quota) of
        every round that brought ``p`` anything. A shard on another
        chip crosses to the engine's home device first, in ONE
        ``device_put`` of its tree, and is cut there: downstream
        operators mix these rows with build sides and aggregation state
        committed at home, and one program serves every partition (cut
        where it lies, the program would be loaded a chip). Hoisted
        ONCE a partition by both read paths."""
        from auron_tpu.obs import trace
        from auron_tpu.parallel import mesh as mesh_mod
        with self._lock:
            entries = list(self.entries)
        home = self.mesh.devices.flat[0]
        away = self.mesh.devices.flat[p] != home
        shards = []
        for cols, counts, quota in entries:
            live = counts[p]
            if live.max() <= 0:
                continue
            shard = jax.tree_util.tree_map(
                lambda a: mesh_mod.local_shard(a, p, self.mesh), cols)
            if away:
                # the second crossing: the shard's padded leaves
                trace.count("mesh_home_bytes", sum(
                    l.nbytes for l in jax.tree_util.tree_leaves(shard)))
                shard = jax.device_put(shard, home)
            shards.append((shard, live, quota))
        return shards

    def source_slices(self, shards: list, source: int) -> Iterator[_Slice]:
        """What ONE source map sent the partition of ``shards``, rounds
        in order (an empty slice rides along: a partition's read then
        has one shape whichever sources reached it); the non-empty ones
        are counted here."""
        from auron_tpu.obs import trace
        for shard, live, quota in shards:
            n = int(live[source])   # graft: disable=GL001 -- live is the round's host counts row (numpy)
            if n:
                trace.count("mesh_read_slices")
                trace.count("mesh_read_rows", n)
            yield _Slice(shard, source * quota, n)

    def partition_batches(self, p: int) -> Iterator[DeviceBatch]:
        """Partition ``p``'s received rows as ONE batch where they fit
        the configured batch capacity (else packed, slice by slice, into
        as few as hold them: ``_merged``), SOURCE-major, rounds-minor:
        map s's round-r rows appear where the host path's entry (map s,
        batch r) would, so a reducer's group order is the host route's."""
        shards = self.partition_shards(p)
        return _counted(_merged(
            (piece for s in range(self.n_out)
             for piece in self.source_slices(shards, s)), self.capacity))

    def close(self) -> None:
        if self.mem is not None:
            self.mem.unregister_consumer(self)
        with self._lock:
            self.entries = []
            self._dev_bytes = 0


class _DemotedExchangeBuffer:
    """Read path of a MID-QUERY demoted exchange: the rounds that
    completed on the mesh plus the host-routed remainder.

    A demotion splits one exchange's entries across two tiers — rounds
    0..k-1 live in the mesh buffer (shard-resident received rows), the
    lost round's re-routed inputs and every later batch in a classic
    host ``_ExchangeBuffer`` (``host_sources[i]`` = the map partition
    host entry ``i`` came from). The read path interleaves them
    SOURCE-major: for each map, first its mesh rounds (rounds-minor),
    then its host entries in append order — exactly the map-major
    slice sequence both the pure-mesh and pure-host paths hand to
    ``_merged``, which packs it into the same batches, so the
    bit-identity contract (group order and the order a double sum adds
    up in included) survives the demotion. Both sub-buffers stay
    registered with the memory manager (the host half spills under
    pressure like any classic exchange)."""

    def __init__(self, mesh_buffer: "_MeshExchangeBuffer",
                 host_buffer: "_ExchangeBuffer", host_sources: list,
                 n_out: int):
        self.mesh_buffer = mesh_buffer
        self.host_buffer = host_buffer
        self.host_sources = list(host_sources)
        self.n_out = n_out

    def partition_batches(self, p: int) -> Iterator[DeviceBatch]:
        by_source: dict[int, list[int]] = {}
        for i, s in enumerate(self.host_sources):
            by_source.setdefault(s, []).append(i)
        # the partition's shards come home ONCE, as the pure-mesh read
        # path brings them
        shards = self.mesh_buffer.partition_shards(p)

        def slices():
            for s in range(self.n_out):
                yield from self.mesh_buffer.source_slices(shards, s)
                idxs = by_source.get(s)
                if idxs:
                    yield from self.host_buffer.entry_slices(p, idxs)

        return _counted(_merged(slices(), self.mesh_buffer.capacity))

    def close(self) -> None:
        self.mesh_buffer.close()
        self.host_buffer.close()


def _run_mesh_round(kern, cols, num_rows, carries):
    """One launch of the sharded stage program and its ONE fence at the
    stage's output boundary: the round's only readback, booked as device
    wait (PR 8 discipline — never per shard, never per program step).
    Returns the program's outputs and, from the host, ``(global max
    bucket, recv counts[, pre-combine rows])`` — the last rides the same
    fence when a combine stage is folded."""
    from auron_tpu.obs import trace
    with trace.layer_span("exchange", "mesh_round"):
        outs = kern(cols, num_rows, carries)
        return outs, _profile.timed_get((outs[3], outs[1]) + tuple(outs[5:]))


class ShuffleExchangeOp(PhysicalOp):
    name = "shuffle_exchange"
    #: SPMD layout: exchange entries shard on the batch dim; eligible
    #: hash exchanges are re-stamped "gang" by ir/planner.annotate_mesh
    mesh_buffer_kind = "shuffle_entry"

    def __init__(self, child: PhysicalOp, partitioning,
                 input_partitions: int = 1):
        self.child = child
        self.partitioning = partitioning
        self.input_partitions = input_partitions
        self._lock = threading.Lock()
        self._buffer: Optional[_ExchangeBuffer] = None
        #: map-side combine fold (ir/planner._fold_combine): when the
        #: child is an eligible partial AggOp, the planner stamps
        #: 'combine' here and the agg's combine stage joins the split
        #: program, merging groups per batch/round BEFORE the rows
        #: cross. None = no fold (ineligible child — combine_why says
        #: why — or fusion off); the agg then executes as its own op.
        self.combine_mode: Optional[str] = None
        self.combine_why: str = ""
        #: (live rows in, live rows out) of the last materialization's
        #: combine stage, for the route record (set under _lock)
        self._combine_stats: Optional[tuple] = None

    @property
    def children(self):
        return [self.child]

    def schema(self) -> Schema:
        return self.child.schema()

    @property
    def num_partitions(self) -> int:
        return self.partitioning.num_partitions

    # -- map side -----------------------------------------------------------

    def _input_batches(self, ctx: ExecContext) -> Iterator[DeviceBatch]:
        for in_p in range(self.input_partitions):
            map_ctx = ctx.child(partition_id=in_p,
                                num_partitions=self.input_partitions)
            for b in self.child.execute(in_p, map_ctx):
                map_ctx.checkpoint("shuffle.map")
                yield b

    def _materialize(self, ctx: ExecContext) -> _ExchangeBuffer:
        """Run all map tasks; ONE sort-by-pid compaction per batch."""
        from auron_tpu.obs import trace
        with trace.layer_span("exchange", "materialize", cat="shuffle",
                              name="shuffle.materialize",
                              maps=self.input_partitions,
                              partitions=self.num_partitions):
            return self._materialize_inner(ctx)

    def _materialize_inner(self, ctx: ExecContext):
        from auron_tpu.parallel import mesh as mesh_mod
        metrics = ctx.metrics_for(self)
        write_time = metrics.counter("shuffle_write_total_time")
        # SPMD routing: when source and sink stages share the mesh, the
        # hash repartition lowers to the on-device all-to-all; every
        # other shape keeps the host device-buffer path. The decision is
        # recorded per exchange (metric tree + 'mesh' trace events —
        # tools/mesh_report.py) so a route change is observable, never
        # inferred.
        route, reason = mesh_mod.exchange_route(
            self.partitioning, self.num_partitions, self.input_partitions,
            ctx.mesh_plane)
        if route == "all_to_all":
            return self._materialize_mesh(ctx, metrics, write_time, reason)
        buffer = _ExchangeBuffer(self, ctx.mem_manager, metrics, ctx.conf)
        self._combine_stats = None
        try:
            filled = self._fill_buffer(ctx, buffer, write_time)
        except BaseException:
            # a cancelled/failed materialization must not leave the
            # half-filled buffer registered with the memory manager (or
            # its spill files on disk) until gc finds it — the
            # zero-leaked-consumers contract of the cancel battery
            buffer.close()
            raise
        # recorded AFTER the fill (the mesh route's convention) so the
        # event carries the observed combine figures
        _record_route(self, metrics, route, reason,
                      **self._combine_attrs())
        return filled

    def _combine_attrs(self) -> dict:
        """exchange.route attributes of the fold's observed effect —
        empty when no combine stage ran (tools/mesh_report.py columns)."""
        if self._combine_stats is None:
            return {}
        rows_in, rows_out = self._combine_stats   # host ints (_note_combine)
        return dict(combine_mode=self.combine_mode,
                    combine_rows_in=rows_in,
                    combine_rows_out=rows_out,
                    combine_ratio=round(rows_out / rows_in, 4)
                    if rows_in else 1.0)

    def _note_combine(self, metrics, rows_in: int, rows_out: int) -> None:
        """Book one materialization's combine figures: metric counters
        and the route-event stash."""
        rows_in = int(rows_in)     # graft: disable=GL001 -- summed on host from the fold's fenced counts readback
        rows_out = int(rows_out)   # graft: disable=GL001 -- host int like rows_in
        self._combine_stats = (rows_in, rows_out)
        metrics.counter("combine_rows_in").add(rows_in)
        metrics.counter("combine_rows_out").add(rows_out)

    def _materialize_mesh(self, ctx: ExecContext, metrics, write_time,
                          reason: str) -> "_MeshExchangeBuffer":
        """SPMD materialization: the whole map side — fused chain (when
        one folded), partition ids, sort-by-pid split and the shuffle
        itself — runs as ONE shard_map program per round across the
        mesh, the shuffle riding ``lax.all_to_all`` instead of
        materializing through host buffers.

        Round r stacks batch r of every map partition into one
        batch-dim-sharded global batch (shard i = map i, zero-copy
        empty for exhausted maps); the program fences ONCE at its
        output boundary (the recv-counts/global-max readback — the
        PR 8 sync discipline extended to the sharded stage), and a
        bucket overflowing the row quota re-runs the round once at the
        exact needed pow2 quota. Inputs are NEVER donated into the
        exchange program — the re-run path still needs them, whatever
        ``yields_owned_batches`` says about the child.

        A ROUND occupies the whole mesh (``plane.gang``), from its stack
        to its fence and its quota re-run: the PR 9 scheduler's WRR turn
        orders queries' sharded rounds, and the gang lock keeps two
        sharded programs from ever interleaving inside the mesh. The map
        side — every pull of ``input_op``, a child exchange that
        mesh-routes among them, which takes the door for its own rounds
        — and the bookkeeping after the fence run with the door open:
        they are single-device programs and host work, which stages
        already run concurrently off the mesh. Between two rounds of one
        exchange another stage's round may run."""
        from auron_tpu import config as cfg
        from auron_tpu import errors
        from auron_tpu.obs import trace
        from auron_tpu.parallel import mesh as mesh_mod
        from auron_tpu.parallel.mesh_exchange import stage_exchange_program
        from auron_tpu.runtime import faults
        from jax.sharding import NamedSharding, PartitionSpec as _P

        plane = ctx.mesh_plane
        n_out = self.num_partitions
        mesh = plane.mesh_for(n_out)
        axis = plane.axis
        out_schema = self.child.schema()

        fold = self._fold_spec() \
            if ctx.conf.get(cfg.FUSION_ENABLED) else None
        if fold is not None:
            fragments, frag_keys, input_op, combine, combine_sig = fold
            fmetrics = ctx.metrics_for(self.child)
            fmetrics.counter("split_folded").add(1)
        else:
            fragments, frag_keys = [], ()
            input_op = self.child
            combine = combine_sig = None
            fmetrics = None
        self._combine_stats = None
        comb_in_total = 0
        comb_out_total = 0
        in_schema = input_op.schema()
        part_exprs = self.partitioning.exprs
        part_key = ("hash", part_exprs)
        init = [f.init_carry for f in fragments]

        kmetrics = ctx.metrics_for("kernels")
        built_c = kmetrics.counter("mesh_stage_programs_built")
        hit_c = kmetrics.counter("mesh_stage_program_hits")

        from auron_tpu.parallel import mesh_exchange as mex
        from auron_tpu.runtime import watchdog

        buffer = _MeshExchangeBuffer(self, mesh, axis, n_out,
                                     ctx.mem_manager, metrics, ctx.conf)
        rounds = escalations = 0   # rounds = COMPLETED mesh rounds
        bytes_moved = 0   # LIVE bytes through the all-to-all (unpadded)
        quota: Optional[int] = None   # sticky: escalated once, reused
        dest_rows = np.zeros(n_out, np.int64)
        # graft: disable=GL001 -- a configuration value, host data
        straggler_factor = float(ctx.conf.get(cfg.MESH_STRAGGLER_FACTOR))
        demote_on_straggler = ctx.conf.get(cfg.MESH_DEMOTE_ON_STRAGGLER)
        demote_reason: Optional[str] = None
        pending: list = []         # (map, still-live batch) of a lost round
        carries_h = None           # host carry snapshot for the demoted path
        t_demote = 0.0

        def polled(in_p: int):
            map_ctx = ctx.child(partition_id=in_p,
                                num_partitions=self.input_partitions)
            for b in input_op.execute(in_p, map_ctx):
                map_ctx.checkpoint("shuffle.map")
                yield b

        try:
            iters = [polled(p) if p < self.input_partitions
                     else iter(())
                     for p in range(n_out)]
            carries = jax.device_put(
                jnp.broadcast_to(
                    jnp.asarray(init, jnp.int64), (n_out, len(init))),
                NamedSharding(mesh, _P(axis, None)))
            while True:
                batches = [next(it, None) for it in iters]
                ref = next((b for b in batches if b is not None), None)
                if ref is None:
                    break
                live = [(p, b) for p, b in enumerate(batches)
                        if b is not None]
                n_live = len(live)
                # zero-copy empties for exhausted maps: a live
                # batch's arrays with num_rows=0 (rows past
                # num_rows are dead by the batch contract)
                batches = [b if b is not None else
                           DeviceBatch(ref.columns,
                                       jnp.asarray(0, jnp.int32))
                           for b in batches]
                # the sharded-stage fault site (chaos battery): a
                # device fault mid-exchange must classify cleanly
                faults.maybe_fail("device.compute",
                                  errors.DeviceExecutionError)
                # the door: held from the round's stack to its fence (and
                # its quota re-run) and never across a pull of the map side
                # above — what it keeps apart is two sharded programs
                # interleaving their per-device launch order. Taken BEFORE
                # the guard starts its clock: parking behind another
                # stage's round is not this round's latency
                with plane.gang(ctx.cancel_event, heartbeat=ctx.heartbeat):
                    # gang-aware round guard: flags downgraded to "slow"
                    # when the round completes; a raise below is the
                    # dead-device verdict (watchdog.MeshRoundGuard)
                    guard = watchdog.MeshRoundGuard(ctx.heartbeat)
                    round_built = False   # compile time is not latency
                    try:
                        with guard:
                            # the mesh fault domain's per-round site
                            mex.round_fault_check(ctx)
                            with timer(write_time):
                                with trace.layer_span("exchange",
                                                      "mesh_stack"):
                                    cols, num_rows, cap = \
                                        mesh_mod.stack_global_batch(
                                            batches, mesh, axis)
                                if quota is None:
                                    quota = bucket_rows(
                                        max((2 * cap) // n_out, 1))
                                while True:
                                    kern, built = stage_exchange_program(
                                        mesh, axis, n_out, frag_keys,
                                        part_key, in_schema, out_schema,
                                        cap, quota, fragments, part_exprs,
                                        combine, combine_sig)
                                    round_built |= built
                                    (built_c if built else hit_c).add(1)
                                    outs, fenced = _run_mesh_round(
                                        kern, cols, num_rows, carries)
                                    (out_cols, rc, _nr, gmax,
                                     new_carries) = outs[:5]
                                    gmax_h, rc_h = fenced[:2]
                                    comb_h = fenced[2] \
                                        if combine is not None else None
                                    # graft: disable=GL001 -- gmax_h came to the host in the round's timed_get
                                    needed = int(gmax_h)
                                    if needed <= quota:
                                        break
                                    # one-shot escalation at the exact
                                    # pow2 quota (the
                                    # exchange_device_batches contract);
                                    # the un-donated inputs are still
                                    # live for this re-run
                                    escalations += 1
                                    trace.count("mesh_escalations")
                                    quota = bucket_rows(needed)
                    except BaseException as e:
                        err = mex.classify_collective(e)
                        if not mex.is_mesh_loss(err):
                            if err is e:
                                raise
                            raise err from e
                        # DEVICE LOSS mid-round: quarantine first (even
                        # if in-place demotion fails below, the next
                        # task attempt routes against the shrunken
                        # plane), then capture the still-live inputs of
                        # the lost round (donation-off contract) for
                        # the host re-route
                        t_demote = time.perf_counter()
                        # a stall the monitor flagged while the dying
                        # round blocked must not abort the recovery at
                        # the host continuation's first checkpoint
                        guard.forgive_stall()
                        if ctx.conf.get(cfg.MESH_QUARANTINE):
                            plane.quarantine(
                                getattr(err, "device", None),
                                f"{type(err).__name__} at round "
                                f"{rounds}")
                        try:
                            # the carry readback IS the demotion's sync
                            # point: timed_get books the wait as device
                            carries_h = np.asarray(
                                _profile.timed_get(carries))
                        except Exception:
                            # the carry shards are unreadable too: the
                            # loss reaches past this round — surface
                            # the classified verdict; the task-level
                            # retry (MeshUnavailable is transient)
                            # re-materializes host-side against the
                            # quarantined plane
                            raise err from e
                        pending = live
                        demote_reason = "device_loss"
                        self._emit_demote(metrics, err, rounds, plane)
                        break
                carries = new_carries
                rounds += 1
                # graft: disable=GL001 -- rc_h came to the host in the round's timed_get
                counts = np.asarray(rc_h).reshape(n_out, n_out)
                dest_rows += counts.sum(axis=1)
                bytes_moved += buffer.add_round(out_cols, counts,
                                                quota)
                if comb_h is not None:
                    # per-shard pre-combine rows of the COMPLETED
                    # round (escalation re-runs were discarded)
                    comb_in_total += int(np.asarray(comb_h).sum())   # graft: disable=GL001 -- comb_h rode the round's host counts readback
                    comb_out_total += int(counts.sum())
                if fmetrics is not None:
                    # the folded chain still owns its plan node:
                    # post-chain live rows are what the exchange
                    # moved (the _materialize_fused convention)
                    fmetrics.counter("output_rows").add(
                        int(counts.sum()))
                    fmetrics.counter("output_batches").add(n_live)
                # straggler defense: judge THIS round against the
                # rolling p50 BEFORE it joins the window; a stall
                # flag the guard forgave is a straggler by
                # construction (the round outlived the watchdog
                # timeout and still completed). Rounds that BUILT a
                # program (first shape class, quota escalation) are
                # excluded from verdict AND window — compile time is
                # not chip latency, and billing it would demote a
                # healthy mesh / inflate the baseline
                if round_built:
                    slow = False
                else:
                    slow = guard.forgiven or plane.round_stats \
                        .is_straggler(guard.elapsed_s,
                                      straggler_factor)
                    plane.round_stats.observe(guard.elapsed_s)
                if slow:
                    plane.record_straggler()
                    metrics.counter("mesh_stragglers").add(1)
                    trace.event(
                        "mesh", "mesh.straggler", op=repr(self),
                        round=rounds - 1,
                        elapsed_ms=round(guard.elapsed_s * 1e3, 3),
                        p50_ms=round(
                            (plane.round_stats.p50() or 0.0) * 1e3,
                            3),
                        forgiven_stall=guard.forgiven,
                        demoting=bool(demote_on_straggler))
                    if demote_on_straggler:
                        # the slow round COMPLETED — its received
                        # rows stay valid on the mesh; only the
                        # remaining rounds re-route
                        t_demote = time.perf_counter()
                        carries_h = np.asarray(
                            _profile.timed_get(carries))
                        demote_reason = "straggler"
                        self._emit_demote(metrics, None, rounds,
                                          plane)
                        break
            # the door was released at the round's exit on every path: the
            # demoted host continuation below never holds the mesh, and
            # neighbor queries are never wedged behind a dead one
            if demote_reason is None:
                total = int(dest_rows.sum())
                skew = (float(dest_rows.max()
                              / max(dest_rows.mean(), 1e-9))
                        if total else 1.0)
                metrics.counter("mesh_rounds").add(rounds)
                metrics.counter("mesh_quota_escalations").add(escalations)
                if combine is not None:
                    self._note_combine(metrics, comb_in_total,
                                       comb_out_total)
                _record_route(self, metrics, "all_to_all", reason,
                              rounds=rounds, escalations=escalations,
                              bytes=bytes_moved, rows=total,
                              devices=n_out, skew=round(skew, 3),
                              **self._combine_attrs())
                return buffer
        except BaseException:
            buffer.close()
            raise
        plane.record_demotion(demote_reason)
        return self._demote_to_host(
            ctx, metrics, write_time, buffer, iters, pending, carries_h,
            demote_reason, rounds, escalations, bytes_moved, fragments,
            frag_keys, fmetrics, t_demote, input_op, combine, combine_sig,
            (comb_in_total, comb_out_total))

    def _emit_demote(self, metrics, err, rounds_done: int, plane) -> None:
        """Put the demotion DECISION on the timeline the moment it is
        taken (the chaos correlation links the injected fault to this
        event); the completed continuation's totals follow on the
        ``exchange.route`` record."""
        from auron_tpu.obs import trace
        metrics.counter("mesh_demotions").add(1)
        trace.event("mesh", "exchange.demote", op=repr(self),
                    reason="device_loss" if err is not None
                    else "straggler",
                    error=type(err).__name__ if err is not None else "",
                    rounds_completed=rounds_done,
                    quarantined=plane.quarantined(),
                    usable=plane.usable_width)

    def _demote_to_host(self, ctx: ExecContext, metrics, write_time,
                        mesh_buffer: "_MeshExchangeBuffer", iters,
                        pending, carries_h, demote_reason: str,
                        rounds_done: int, escalations: int,
                        bytes_moved: int, fragments, frag_keys,
                        fmetrics, t_demote: float, input_op=None,
                        combine=None, combine_sig=None,
                        comb_totals=(0, 0)):
        """Host continuation of a demoted exchange: the REMAINING rounds
        re-route down the existing ladder (``all_to_all`` → host
        ``device_buffer``; RSS stays the durable tier below it), run
        OUTSIDE the gang — a demoted exchange never holds the mesh.

        Only the lost round's map inputs are recomputed (``pending`` —
        still live because inputs are never donated into the exchange
        program), and only rounds the mesh never completed are routed
        here: already-consumed rounds stay in the mesh buffer and are
        never re-yielded, the map-by-map streaming contract of the RSS
        recovery path applied to the SPMD tier. When the mesh program
        had a fused chain folded in, the same chain folds into the host
        split program with each map's member carries seeded from the
        last completed round's carry snapshot — the demoted path keeps
        computing the SAME rows."""
        n_out = self.num_partitions
        out_schema = self.child.schema()
        if input_op is None:
            input_op = self.child.input if fragments else self.child
        # the demoted path never donates: one launch of the split
        # program a batch (chain — and the map-side combine, when the
        # mesh program had one folded — rides along)
        split = _Split(self.partitioning, n_out, input_op.schema(),
                       out_schema, False,
                       fold=(fragments, frag_keys, input_op, combine,
                             combine_sig))
        host = _ExchangeBuffer(self, ctx.mem_manager, metrics, ctx.conf)
        sources: list[int] = []
        recompute_rows = 0
        recompute_bytes = 0
        host_rows = 0
        comb_in_total, comb_out_total = comb_totals
        pending_by_map = dict(pending)

        def route_batch(in_p: int, batch: DeviceBatch, carries):
            nonlocal host_rows, comb_in_total, comb_out_total
            # entry tagged with its source map so the combined read
            # path can interleave map-major
            with timer(write_time) as t:
                sorted_batch, counts_h, carries, comb_in_h = split(
                    batch, in_p, carries, t)
            n = int(counts_h.sum())
            if combine is not None:
                comb_in_total += comb_in_h
                comb_out_total += n
            offsets = np.concatenate(
                [np.zeros(1, np.int64), np.cumsum(counts_h)])
            host.add(sorted_batch, offsets)
            sources.append(in_p)
            host_rows += n
            if fmetrics is not None:
                fmetrics.counter("output_rows").add(n)
                fmetrics.counter("output_batches").add(1)
            return carries, n

        try:
            for in_p in range(self.input_partitions):
                # member carries from the last completed mesh round
                # + the trailing split-seen slot (round-robin only —
                # mesh routing is hash-only, the slot is inert)
                carries = split.carries(members=carries_h[in_p])
                pend = pending_by_map.pop(in_p, None)
                if pend is not None:
                    # the lost round's re-route: its rows are the
                    # demotion's recompute cost
                    ctx.checkpoint("exchange.demote")
                    from auron_tpu.columnar.batch import batch_nbytes
                    recompute_bytes += batch_nbytes(pend)
                    carries, n = route_batch(in_p, pend, carries)
                    recompute_rows += n
                for batch in iters[in_p]:
                    # polled() checkpoints per child batch already
                    carries, _n = route_batch(in_p, batch, carries)
        except BaseException:
            # every unwind path releases BOTH halves' consumers (and
            # the host half's spill files) — the zero-leak contract
            host.close()
            mesh_buffer.close()
            raise
        latency_ms = round((time.perf_counter() - t_demote) * 1e3, 3)
        metrics.counter("mesh_rounds").add(rounds_done)
        metrics.counter("mesh_quota_escalations").add(escalations)
        if combine is not None:
            self._note_combine(metrics, comb_in_total, comb_out_total)
        _record_route(self, metrics, "demoted", demote_reason,
                      rounds=rounds_done, escalations=escalations,
                      bytes=bytes_moved, rows=host_rows,
                      recompute_rows=recompute_rows,
                      recompute_bytes=recompute_bytes,
                      latency_ms=latency_ms, devices=n_out,
                      **self._combine_attrs())
        logger.warning(
            "mesh exchange demoted to host (%s): %d mesh round(s) kept, "
            "%d host rows routed, %d rows recomputed from the lost "
            "round, %.1fms demote-to-reroute latency", demote_reason,
            rounds_done, host_rows, recompute_rows, latency_ms)
        return _DemotedExchangeBuffer(mesh_buffer, host, sources, n_out)

    def _fill_buffer(self, ctx: ExecContext, buffer: "_ExchangeBuffer",
                     write_time) -> "_ExchangeBuffer":
        from auron_tpu import config as cfg
        schema = self.child.schema()
        n_out = self.num_partitions

        partitioning = self.partitioning
        # a range partitioning's bounds are sampled from the LEADING
        # batches of this same pass, which the child must hand over
        # unfolded — the child is never executed twice
        unsampled = isinstance(partitioning, RangePartitioning) \
            and not partitioning.bounds
        fold = self._fold_spec() \
            if not unsampled and ctx.conf.get(cfg.FUSION_ENABLED) \
            else None
        if fold is not None:
            self._materialize_fused(ctx, buffer, write_time, fold)
            return buffer

        batches = self._input_batches(ctx)
        pending: list[DeviceBatch] = []
        if unsampled:
            partitioning, pending = _sample_range_bounds(
                batches, partitioning, schema)
            self.partitioning = partitioning

        donate = yields_owned_batches(self.child) \
            and jax.default_backend() != "cpu"
        split = _Split(partitioning, n_out, schema, schema, donate)
        # the rows seen so far (the round-robin start) ride the carry
        carries = split.carries()
        shuffle_bytes = ctx.metrics_for(self).counter("shuffle_bytes_live")
        from auron_tpu.columnar.batch import batch_nbytes
        import itertools
        for batch in itertools.chain(pending, batches):
            with timer(write_time) as t:
                sorted_batch, counts_h, carries, _ = split(
                    batch, 0, carries, t)
            live_rows = int(counts_h.sum())   # graft: disable=GL001 -- counts_h is a host ndarray (the split's timed_get)
            cap = max(int(sorted_batch.capacity), 1)   # graft: disable=GL001 -- capacity is a python int by construction
            shuffle_bytes.add(batch_nbytes(sorted_batch) * live_rows // cap)
            offsets = np.concatenate(
                [np.zeros(1, np.int64), np.cumsum(counts_h)])
            buffer.add(sorted_batch, offsets)
        return buffer

    def _split_fragments(self):
        """The child chain's fragments when they can fold into the split
        program, else None (no chain / fused limit / fan-out members):
        the exchange then splits its child's output with the same
        program and no chain in it."""
        from auron_tpu.ops.fused import FusedStageOp
        if not isinstance(self.child, FusedStageOp) \
                or self.child.has_limit():
            return None
        fragments, frag_keys = self.child.fragment_pipeline()
        if not fragments or any(f.fanout != 1 for f in fragments):
            return None
        return fragments, frag_keys

    def _fold_spec(self):
        """Fold-aware map side: (fragments, frag_keys, input_op,
        combine, combine_sig) or None for the classic per-op path.

        With a planner-stamped ``combine_mode`` the child IS the partial
        AggOp being elided: the exchange executes the agg's OWN child
        (chain fragments when one fused below it) and folds the agg's
        combine stage into the split program. Without one,
        this is exactly the PR 2 chain fold (_split_fragments)."""
        from auron_tpu.ops.fused import FusedStageOp
        if self.combine_mode is not None:
            agg = self.child          # planner guaranteed: eligible AggOp
            inner = agg.child
            fragments, frag_keys, input_op = [], (), inner
            if isinstance(inner, FusedStageOp) and not inner.has_limit():
                frags, keys = inner.fragment_pipeline()
                if frags and all(f.fanout == 1 for f in frags):
                    fragments, frag_keys, input_op = \
                        frags, keys, inner.input
            return (fragments, frag_keys, input_op,
                    agg.build_combine_stage(), agg.combine_signature())
        frag_info = self._split_fragments()
        if frag_info is None:
            return None
        fragments, frag_keys = frag_info
        return fragments, frag_keys, self.child.input, None, None

    def _materialize_fused(self, ctx: ExecContext, buffer: _ExchangeBuffer,
                           write_time, fold: tuple) -> None:
        """Whole-stage split: the child chain's member fragments join the
        exchange's partition-id + sort-by-pid program, so a
        filter→project chain feeding a hash shuffle is ONE XLA launch
        per batch with the intermediates living only in registers/VMEM.
        With a map-side combine folded (``fold`` carries the elided
        partial agg's combine stage) the same launch also merges the
        batch's groups before the split — the bytes entering the buffer
        (and its RSS spill frames) are per-batch GROUPS, not rows."""
        n_out = self.num_partitions
        # the folded chain/agg still OWNS its plan node (see the
        # hash-join probe fold): the sorted batch's live count IS the
        # folded work's output count, and the one-launch program's time
        # lands on the whole-stage node
        fmetrics = ctx.metrics_for(self.child)
        f_elapsed = fmetrics.counter("elapsed_compute")
        f_rows = fmetrics.counter("output_rows")
        f_batches = fmetrics.counter("output_batches")
        fmetrics.counter("split_folded").add(1)
        metrics = ctx.metrics_for(self)

        _fragments, _frag_keys, input_op, combine, _combine_sig = fold
        donate = yields_owned_batches(input_op) \
            and jax.default_backend() != "cpu"
        split = _Split(self.partitioning, n_out, input_op.schema(),
                       self.child.schema(), donate, fold=fold,
                       kmetrics=ctx.metrics_for("kernels"))
        comb_in_total = 0
        comb_out_total = 0
        from auron_tpu.columnar.batch import batch_nbytes

        # the trailing carry slot (rows seen at the split — the
        # round-robin start) persists across input partitions; member
        # carries reset per input partition like a fresh execute() would
        split_seen = None
        for in_p in range(self.input_partitions):
            map_ctx = ctx.child(partition_id=in_p,
                                num_partitions=self.input_partitions)
            carries = split.carries(seen=split_seen)
            for batch in input_op.execute(in_p, map_ctx):
                map_ctx.checkpoint("shuffle.map")
                t0v = f_elapsed.value
                with timer(f_elapsed) as t:
                    sorted_batch, counts_h, carries, comb_in_h = split(
                        batch, in_p, carries, t)
                # the shuffle node keeps its canonical write-time view
                # of the same launch (chain + split are one program)
                write_time.add(f_elapsed.value - t0v)
                live = int(counts_h.sum())
                if combine is not None:
                    comb_in_total += comb_in_h
                    comb_out_total += live
                f_rows.add(live)
                f_batches.add(1)
                # honest data-movement figure for the host route: live
                # rows × per-row width (the mesh buffer's add_round
                # convention; the allocated batch is capacity-padded)
                nbytes = batch_nbytes(sorted_batch)
                cap = max(int(sorted_batch.capacity), 1)   # graft: disable=GL001 -- capacity is a python int by construction
                metrics.counter("shuffle_bytes_live").add(
                    nbytes * live // cap)
                offsets = np.concatenate(
                    [np.zeros(1, np.int64), np.cumsum(counts_h)])
                buffer.add(sorted_batch, offsets)
            split_seen = carries[-1:]
        if combine is not None:
            self._note_combine(metrics, comb_in_total, comb_out_total)

    # -- reduce side --------------------------------------------------------

    def execute(self, partition: int, ctx: ExecContext) -> Iterator[DeviceBatch]:
        with self._lock:
            if self._buffer is None:
                self._buffer = self._materialize(ctx)
        metrics = ctx.metrics_for(self, "_read")
        read_time = metrics.counter("shuffle_read_total_time")

        def polled(buf):
            # lifecycle poll per fetched batch: a cancel mid-fetch lands
            # within one batch, and the stall watchdog sees the reducer
            # making progress
            for b in buf.partition_batches(partition):
                ctx.checkpoint("shuffle.fetch")
                yield b

        # production-segment timing only (obs/trace.stream_spanned): the
        # read timer must not bill the consumer's compute, and the span
        # must not stay open across yields
        from auron_tpu.obs import trace
        stream = trace.stream_spanned(
            "shuffle", "shuffle.fetch", polled(self._buffer),
            time_counter=read_time, partition=partition)
        return count_output(stream, metrics, timed=True)

    def __repr__(self):
        return (f"ShuffleExchangeOp[{type(self.partitioning).__name__} "
                f"{self.input_partitions}->{self.num_partitions}]")


class RssShuffleExchangeOp(PhysicalOp):
    """Shuffle through the host shuffle service (the RSS tier, reference:
    shuffle/rss.rs + rss_shuffle_writer_exec.rs): the map side pushes
    per-partition serialized frames to shared storage instead of keeping
    buckets device-resident, so shuffle size is bounded by storage, not
    HBM, and reducers on OTHER HOSTS read the same shuffle through their
    own service instance (see RssShuffleReadOp)."""

    name = "rss_shuffle_exchange"

    def __init__(self, child: PhysicalOp, partitioning, service,
                 shuffle_id: int, input_partitions: int = 1):
        self.child = child
        self.partitioning = partitioning
        self.service = service
        self.shuffle_id = shuffle_id
        self.input_partitions = input_partitions
        self._lock = threading.Lock()
        self._written = False

    @property
    def children(self):
        return [self.child]

    def schema(self) -> Schema:
        return self.child.schema()

    @property
    def num_partitions(self) -> int:
        return self.partitioning.num_partitions

    def _journal(self, ctx: ExecContext):
        """The driving query's crash-safe journal (runtime/journal),
        resolved through the cancel token; None when journaling is off
        for this query."""
        return getattr(ctx.cancel_event, "journal", None)

    def _materialize(self, ctx: ExecContext) -> None:
        partitioning = self.partitioning
        schema = self.child.schema()
        # the RSS tier is routed by construction (durable / multihost —
        # readers on OTHER hosts cannot reach this host's mesh), but the
        # decision is still recorded so the per-exchange route table is
        # complete
        _record_route(self, ctx.metrics_for(self), "rss", "rss_tier")
        # invalidate any previous attempt's manifest so readers can't mix
        # stale map outputs into this attempt
        self.service.begin_shuffle(self.shuffle_id)
        journal = self._journal(ctx)
        # map-level resume: a resumed query skips exactly the map
        # outputs the journal proves committed AND intact on storage
        # (size + trailer CRC), recomputing only what the durable tier
        # never received. Range partitioning is excluded — its bounds
        # are sampled from map 0's live batches, so a skipped map 0
        # would leave later maps unboundable; a range exchange resumes
        # only at full-satisfied granularity (see execute()).
        map_skips_ok = (journal is not None and journal.resumed
                        and not isinstance(partitioning,
                                           RangePartitioning))
        jmetrics = ctx.metrics_for(self)

        for in_p in range(self.input_partitions):
            if map_skips_ok:
                size = journal.reusable_map(self.shuffle_id, in_p,
                                            self.service)
                if size is not None:
                    journal.note_map_skipped(self.shuffle_id, size)
                    jmetrics.counter("journal_maps_skipped").add(1)
                    jmetrics.counter("journal_bytes_reused").add(size)
                    continue
                journal.note_map_recomputed(self.shuffle_id)
                jmetrics.counter("journal_maps_recomputed").add(1)
            map_ctx = ctx.child(partition_id=in_p,
                                num_partitions=self.input_partitions)
            batches = self.child.execute(in_p, map_ctx)
            pending: list[DeviceBatch] = []
            if in_p == 0 and isinstance(partitioning, RangePartitioning) \
                    and not partitioning.bounds:
                # sample bounds from map 0's leading batches; all maps of
                # this shuffle then share the same bounds (the reference
                # samples once, driver-side)
                partitioning, pending = _sample_range_bounds(
                    batches, partitioning, schema)
                self.partitioning = partitioning
            self._write_map(in_p, ctx, partitioning, pending, batches)
        self.service.commit_shuffle(self.shuffle_id, self.input_partitions)
        if journal is not None:
            # the journal's shuffle-level commit record rides the SAME
            # boundary as the durable tier's manifest (fsync here only)
            journal.record_shuffle_commit(self.shuffle_id,
                                          self.input_partitions)

    def _write_map(self, in_p: int, ctx: ExecContext, partitioning,
                   pending=(), batches=None) -> None:
        """Write ONE map task's output. Also the corruption-recovery
        entry point: a checksum failure on fetch recomputes exactly this
        map (``batches=None`` re-executes the child partition — the
        engine is functional, so the recompute is exact). The writer's
        context manager guarantees no exception path leaves a ``.part``
        file behind."""
        import itertools

        from auron_tpu import config as cfg
        from auron_tpu.columnar.serde import (batch_to_host,
                                              serialize_host_batch,
                                              slice_host_batch)
        from auron_tpu.obs import trace
        metrics = ctx.metrics_for(self)
        write_time = metrics.counter("shuffle_write_total_time")
        n_out = self.num_partitions
        schema = self.child.schema()
        codec_level = ctx.conf.get(cfg.SPILL_CODEC_LEVEL)
        if batches is None:
            map_ctx = ctx.child(partition_id=in_p,
                                num_partitions=self.input_partitions)
            batches = self.child.execute(in_p, map_ctx)
        donate = yields_owned_batches(self.child) \
            and jax.default_backend() != "cpu"
        split = _Split(partitioning, n_out, schema, schema, donate)
        carries = split.carries()
        with trace.layer_span("exchange", "map_write", cat="shuffle",
                              name="rss.map_write",
                              shuffle=self.shuffle_id, map=in_p), \
                self.service.partition_writer(self.shuffle_id, in_p,
                                              n_out) as writer:
            for batch in itertools.chain(pending, batches):
                # lifecycle poll per map batch: a cancel mid-write
                # aborts through the writer's context manager (no .part
                # left behind) and the heartbeat shows write progress
                ctx.checkpoint("rss.map_write")
                with timer(write_time) as t:
                    sorted_batch, counts_h, carries, _ = split(
                        batch, in_p, carries, t)
                offsets = np.concatenate(
                    [np.zeros(1, np.int64), np.cumsum(counts_h)])
                n = int(offsets[-1])   # graft: disable=GL001 -- offsets is a host ndarray
                with timer(write_time, bucket="serde"):
                    host = batch_to_host(sorted_batch, n)
                    for p in range(n_out):
                        # graft: disable=GL001 -- offsets is a host ndarray
                        lo, hi = int(offsets[p]), int(offsets[p + 1])
                        if hi > lo:
                            writer.write(p, serialize_host_batch(
                                slice_host_batch(host, lo, hi),
                                codec_level=codec_level))
            writer.commit()
            journal = self._journal(ctx)
            if journal is not None:
                # recorded AFTER the atomic rename: the journal never
                # claims more than the durable tier holds (async
                # append; made durable by the shuffle-commit fsync)
                journal.record_map(self.shuffle_id, in_p,
                                   writer.committed_size,
                                   writer.trailer_crc)

    #: per-map corruption-recovery bound: recompute + refetch this many
    #: times before surfacing the classified error (a fault plan that
    #: corrupts EVERY write would otherwise loop forever)
    _CORRUPTION_RECOVERY_ATTEMPTS = 3

    def _fetch_map(self, map_id: int, partition: int,
                   ctx: ExecContext) -> list[bytes]:
        """Verified frames of one map output, with corruption recovery:
        a checksum mismatch invalidates that map output and RECOMPUTES
        the map task (the lineage-recompute contract the reference
        inherits from Spark's shuffle-integrity layer) instead of
        blindly retrying the reducer over the same corrupt bytes."""
        from auron_tpu import errors as aerr
        attempt = 0
        while True:
            try:
                return self.service.map_partition_frames(
                    self.shuffle_id, map_id, partition)
            except aerr.ShuffleCorruption:
                if attempt >= self._CORRUPTION_RECOVERY_ATTEMPTS:
                    raise
                attempt += 1
                logger.warning(
                    "shuffle %d map %d corrupt on fetch (partition %d); "
                    "invalidating and recomputing the map task "
                    "(recovery attempt %d/%d)", self.shuffle_id, map_id,
                    partition, attempt, self._CORRUPTION_RECOVERY_ATTEMPTS)
                with self._lock:   # one recovery of a map at a time
                    try:
                        # another reducer may have repaired the map while
                        # we waited for the lock — re-verify before
                        # invalidating, or we would delete its clean file
                        return self.service.map_partition_frames(
                            self.shuffle_id, map_id, partition)
                    except aerr.ShuffleCorruption:
                        from auron_tpu.obs import trace
                        ctx.metrics_for("recovery").counter(
                            "corruption_recomputes").add(1)
                        trace.event(
                            "shuffle", "shuffle.corruption_recompute",
                            shuffle=self.shuffle_id, map=map_id,
                            partition=partition, attempt=attempt)
                        self.service.invalidate_map(self.shuffle_id,
                                                    map_id)
                        self._write_map(map_id, ctx, self.partitioning)

    def execute(self, partition: int, ctx: ExecContext) -> Iterator[DeviceBatch]:
        with self._lock:
            if not self._written:
                journal = self._journal(ctx)
                if journal is not None and journal.satisfied(
                        self.shuffle_id, self.input_partitions,
                        self.service):
                    # SATISFIED exchange (crash-safe journal): every
                    # map output is committed and intact on storage —
                    # the whole map side is skipped and reducers fetch
                    # straight from the journaled RSS files. Recorded
                    # like every other routing decision.
                    metrics = ctx.metrics_for(self)
                    metrics.counter("journal_maps_skipped").add(
                        self.input_partitions)
                    _record_route(self, metrics, "rss",
                                  "journal_satisfied")
                    from auron_tpu.obs import trace
                    trace.event("journal", "journal.satisfied",
                                shuffle=self.shuffle_id,
                                maps=self.input_partitions)
                else:
                    self._materialize(ctx)
                self._written = True
        metrics = ctx.metrics_for(self, "_read")
        read_time = metrics.counter("shuffle_read_total_time")

        def slices():
            # map-by-map fetch: each map's frames are fully verified
            # before any is yielded, so corruption recovery never
            # re-yields data a downstream operator already consumed
            maps = self.service.committed_maps(self.shuffle_id)
            for map_id in range(len(maps)):
                ctx.checkpoint("rss.fetch")
                for frame in self._fetch_map(map_id, partition, ctx):
                    # deserialize INSIDE the timer, yield OUTSIDE it: a
                    # yield under the timer would bill the consumer's
                    # compute to shuffle_read_total_time
                    with timer(read_time, bucket="serde"):
                        piece = _frame_slice(frame)
                    if piece is not None:
                        yield piece

        from auron_tpu import config as cfg
        return count_output(
            _merged(slices(), ctx.conf.get(cfg.BATCH_CAPACITY)), metrics,
            timed=True)

    def __repr__(self):
        return (f"RssShuffleExchangeOp[{type(self.partitioning).__name__} "
                f"{self.input_partitions}->{self.num_partitions} "
                f"shuffle={self.shuffle_id}]")


class RssShuffleReadOp(PhysicalOp):
    """Reducer-side read of a committed RSS shuffle — the entry point for
    a DIFFERENT host than the one that wrote (reference:
    AuronCelebornShuffleReader): needs only the shared service root, the
    shuffle id, and the schema."""

    name = "rss_shuffle_read"

    def __init__(self, service, shuffle_id: int, schema: Schema,
                 num_partitions: int):
        self.service = service
        self.shuffle_id = shuffle_id
        self._schema = schema
        self.num_partitions = num_partitions

    def schema(self) -> Schema:
        return self._schema

    def execute(self, partition: int, ctx: ExecContext) -> Iterator[DeviceBatch]:
        metrics = ctx.metrics_for(self)
        read_time = metrics.counter("shuffle_read_total_time")

        def slices():
            for frame in self.service.partition_frames(self.shuffle_id,
                                                       partition):
                # yield outside the timer (see RssShuffleExchangeOp)
                with timer(read_time, bucket="serde"):
                    piece = _frame_slice(frame)
                if piece is not None:
                    yield piece

        from auron_tpu import config as cfg
        return count_output(
            _merged(slices(), ctx.conf.get(cfg.BATCH_CAPACITY)), metrics,
            timed=True)

    def __repr__(self):
        return f"RssShuffleReadOp[shuffle={self.shuffle_id}]"


class _BroadcastBuffer:
    """MemConsumer owning a broadcast's collected batches.

    The reference registers broadcast hash maps with its memory manager
    (join_hash_map.rs:365-387) so an oversized build side spills instead of
    OOMing; this is the same contract for the collected device batches. Each
    entry is ["dev", DeviceBatch] or ["spill", SpillRef, num_rows]; replay
    rehydrates spilled entries per consumer without pinning them back into
    the buffer (consumers stream them, HBM stays at one batch at a time)."""

    def __init__(self, op, mem_manager, metrics, conf=None):
        from auron_tpu import config as cfg
        conf = conf or cfg.get_config()
        self.mem = mem_manager
        self.metrics = metrics
        self.codec_level = conf.get(cfg.SPILL_CODEC_LEVEL)
        self.consumer_name = f"broadcast-{id(op):x}"
        self.entries: list = []
        self._dev_bytes = 0
        self._lock = threading.RLock()
        if mem_manager is not None:
            mem_manager.register_consumer(self)

    def add(self, batch: DeviceBatch) -> None:
        from auron_tpu.columnar.batch import batch_nbytes
        with self._lock:
            self.entries.append(["dev", batch])
            self._dev_bytes += batch_nbytes(batch)
            used = self._dev_bytes
        if self.mem is not None:
            self.mem.update_mem_used(self, used)

    def mem_used(self) -> int:
        with self._lock:
            return self._dev_bytes

    def spill(self) -> int:
        from auron_tpu.columnar.batch import batch_nbytes
        from auron_tpu.columnar.serde import (batch_to_host,
                                              serialize_host_batch)
        if self.mem is None or getattr(self.mem, "spill_manager", None) is None:
            return 0
        with self._lock:  # tag flip, same protocol as _ExchangeBuffer
            victims = [(i, e) for i, e in enumerate(self.entries)
                       if e[0] == "dev"]
            for _i, e in victims:
                e[0] = "dev-spilling"
            if not victims:
                return 0
        freed = 0
        for i, e in victims:
            batch = e[1]
            n = _profile.row_count(batch)
            spill = self.mem.spill_manager.new_spill()
            spill.write_frame(serialize_host_batch(
                batch_to_host(batch, n), codec_level=self.codec_level))
            done = spill.finish()
            with self._lock:
                if i < len(self.entries) and self.entries[i] is e:
                    self.entries[i] = ["spill", done, n]
                    self._dev_bytes -= batch_nbytes(batch)
                    freed += batch_nbytes(batch)
                else:
                    done.release()
        self.metrics.counter("mem_spill_count").add(len(victims))
        self.metrics.counter("mem_spill_size").add(freed)
        return freed

    def replay(self) -> Iterator[DeviceBatch]:
        from auron_tpu.columnar.serde import (deserialize_host_batch,
                                              host_to_batch)
        with self._lock:
            entries = list(self.entries)
        for e in entries:
            if e[0].startswith("dev"):
                yield e[1]
            else:
                host, _extras = deserialize_host_batch(e[1].frame_at(0))
                yield host_to_batch(host, bucket_rows(e[2]))

    def close(self) -> None:
        if self.mem is not None:
            self.mem.unregister_consumer(self)
        with self._lock:
            entries, self.entries = self.entries, []
            self._dev_bytes = 0
        for e in entries:
            if e[0] == "spill":
                e[1].release()

    def __del__(self):
        # see _ExchangeBuffer.__del__ for why this must not call close()
        try:
            for e in self.entries:
                if e[0] == "spill":
                    e[1].release()
        except Exception:
            pass


class BroadcastExchangeOp(PhysicalOp):
    """Collect the child once, replay to every consumer partition
    (reference: NativeBroadcastExchangeBase collect→IPC→re-expose,
    SURVEY.md §3.4). Device batches are naturally shared on a single host;
    in SPMD execution the same batch is replicated into every shard. The
    collected set is a memmgr consumer (_BroadcastBuffer): a build side
    larger than the budget spills to host tiers and replays from there."""

    name = "broadcast_exchange"
    #: every consumer partition replays the same collected batches
    owns_output = False
    #: SPMD layout: the collected set replicates across the mesh
    #: (parallel/mesh.buffer_spec) — in sharded execution every shard
    #: reads the same broadcast relation
    mesh_buffer_kind = "broadcast"

    def __init__(self, child: PhysicalOp, input_partitions: int = 1,
                 subplan_key=None):
        self.child = child
        self.input_partitions = input_partitions
        self._lock = threading.Lock()
        self._buffer: Optional[_BroadcastBuffer] = None
        #: warm-path subplan identity (ir/planner computes it from the
        #: subtree's plan + source fingerprints; None = caching off or
        #: identity not capturable): a hit replays the cached host-side
        #: relation instead of collecting the child at all
        self._subplan_key = subplan_key
        self._cached_entries = None

    @property
    def children(self):
        return [self.child]

    def schema(self) -> Schema:
        return self.child.schema()

    def execute(self, partition: int, ctx: ExecContext) -> Iterator[DeviceBatch]:
        metrics = ctx.metrics_for(self)
        with self._lock:
            if self._buffer is None and self._cached_entries is None \
                    and self._subplan_key is not None:
                from auron_tpu.cache import result_cache as _rcache
                self._cached_entries = _rcache.get_cache().get_subplan(
                    self._subplan_key)
            if self._buffer is None and self._cached_entries is None:
                from auron_tpu.obs import trace
                with trace.layer_span("exchange", "broadcast_collect",
                                      cat="shuffle",
                                      name="broadcast.collect",
                                      maps=self.input_partitions):
                    buf = _BroadcastBuffer(self, ctx.mem_manager, metrics,
                                           conf=ctx.config)
                    try:
                        for in_p in range(self.input_partitions):
                            map_ctx = ctx.child(
                                partition_id=in_p,
                                num_partitions=self.input_partitions)
                            for b in self.child.execute(in_p, map_ctx):
                                map_ctx.checkpoint("broadcast.collect")
                                buf.add(b)
                    except BaseException:
                        # cancelled/failed collect: release the
                        # half-filled buffer (consumer + spills) now,
                        # not at gc time
                        buf.close()
                        raise
                    self._buffer = buf
                self._store_subplan(buf)
        if self._cached_entries is not None:
            return count_output(self._replay_cached(), metrics,
                                timed=True)
        return count_output(self._buffer.replay(), metrics, timed=True)

    def _store_subplan(self, buf: "_BroadcastBuffer") -> None:
        """Publish the freshly-collected relation to the warm-path
        subplan cache as HOST entries (device buffers must not outlive
        this query's memmgr ledger). Skipped when any entry already
        spilled — the process is under pressure, exactly when adding a
        cache copy would be wrong."""
        if self._subplan_key is None:
            return
        from auron_tpu.columnar.batch import batch_nbytes
        from auron_tpu.columnar.serde import batch_to_host
        with buf._lock:
            entries = list(buf.entries)
        if any(e[0] != "dev" for e in entries):
            return
        host_entries, nbytes = [], 0
        for e in entries:
            # sanctioned readback (GL001): the row-count scalar lives on
            # device; timed_get books the wait at this sync point
            n = int(_profile.timed_get(e[1].num_rows))
            host_entries.append((batch_to_host(e[1], n), n))
            nbytes += batch_nbytes(e[1])
        from auron_tpu.cache import result_cache as _rcache
        _rcache.get_cache().put_subplan(self._subplan_key, host_entries,
                                        nbytes)

    def _replay_cached(self) -> Iterator[DeviceBatch]:
        from auron_tpu.columnar.serde import host_to_batch
        for host, n in self._cached_entries:
            yield host_to_batch(host, bucket_rows(n))
