"""Equi-joins: broadcast hash join and sort-merge join, TPU-style.

The reference implements BHJ as an open-addressing JoinHashMap serialized
into a RecordBatch column for cross-task reuse (reference:
datafusion-ext-plans/src/joins/join_hash_map.rs:44-73,365) and SMJ as
streaming cursors (reference: joins/smj/stream_cursor.rs). Sequential probe
chains and cursor advances don't vectorize, so this engine uses one
primitive for both: the build side is sorted by xxhash64(join keys) once,
and each probe batch binary-searches the sorted hash array (vectorized
searchsorted = log2(B) gathers per probe row, all lanes in parallel).
Candidate ranges are expanded into (probe_idx, build_idx) pairs with a
static output capacity chosen by the host from the exact match count, then
verified by exact key comparison (hash collisions drop out via compaction).

Join types: inner / left / right / full / semi / anti / existence
(reference: auron.proto JoinType + bhj/full_join.rs probe variants).
"""

from __future__ import annotations

from typing import Iterator, Optional

import numpy as np

import jax
import jax.numpy as jnp

from auron_tpu.columnar.batch import (DeviceBatch, PrimitiveColumn, StringColumn,
                                      compact, gather_batch, gather_column)
from auron_tpu.memmgr.consumer import BufferedSpillConsumer
from auron_tpu.columnar.schema import DataType, Field, Schema
from auron_tpu.exprs import ir
from auron_tpu.exprs.eval import EvalContext, evaluate
from auron_tpu.obs import profile as _profile
from auron_tpu.ops import hashing
from auron_tpu.ops.base import ExecContext, PhysicalOp, count_output, timer
from auron_tpu.ops.sort import _concat_all
from auron_tpu.runtime import programs
from auron_tpu.runtime.programs import program_cache
from auron_tpu.utils.shapes import bucket_rows

# sentinel hashes guaranteeing null keys never match (numpy scalars so the
# import doesn't force jax backend init — see ops/hashing.py)
_NULL_PROBE = np.uint64(0xFFFFFFFFFFFFFFFF)
_NULL_BUILD = np.uint64(0xFFFFFFFFFFFFFFFE)


def _key_hashes(cols, cap, live, null_sentinel) -> jax.Array:
    h = hashing.xxhash64_columns(list(cols), cap).view(jnp.uint64)
    any_null = jnp.zeros(cap, bool)
    for c in cols:
        any_null = any_null | ~c.validity
    h = jnp.where(any_null | ~live, null_sentinel, h)
    return h


def _take_cols(cols, idx, valid):
    return tuple(gather_column(c, idx, valid) for c in cols)


def _candidate_lookup(h, index_kind: str, index_args: tuple, rounds: int):
    """(lo, counts) of each probe hash's candidate run in the sorted
    build table — via two binary searches ('sorted'), or one hash-table
    probe of the run index ('ht', auron_tpu/hashtable). Both return the
    EXACT same (lo, counts) for present hashes and counts == 0 for
    absent ones, so downstream expand + exact-key verification make the
    two candidate searches bit-identical end to end."""
    if index_kind == "ht":
        from auron_tpu.hashtable.core import EMPTY, probe_hash_index
        idx_h, idx_lo, idx_cnt = index_args
        live = h != EMPTY       # null/dead probe rows match nothing
        slot, found = probe_hash_index(idx_h, h, live, rounds)
        lo = jnp.where(found, idx_lo[slot], 0).astype(jnp.int32)
        counts = jnp.where(found, idx_cnt[slot], 0).astype(jnp.int32)
        return lo, counts
    (build_hashes,) = index_args
    lo = jnp.searchsorted(build_hashes, h, side="left").astype(jnp.int32)
    hi = jnp.searchsorted(build_hashes, h, side="right").astype(jnp.int32)
    return lo, hi - lo


def _probe_count_body(probe: DeviceBatch, index_kind: str,
                      index_args: tuple, rounds: int, key_exprs: tuple,
                      in_schema: Schema):
    """Traced probe-side candidate search over the build-side index."""
    ctx = EvalContext()
    keys = tuple(evaluate(e, probe, in_schema, ctx).col for e in key_exprs)
    h = _key_hashes(keys, probe.capacity, probe.row_mask(), _NULL_PROBE)
    lo, counts = _candidate_lookup(h, index_kind, index_args, rounds)
    total = jnp.sum(counts)
    return h, lo, counts, total


@program_cache("ops.joins.probe_count", maxsize=256)
def _probe_count_kernel(key_exprs: tuple, in_schema: Schema, capacity: int,
                        build_cap: int, index_kind: str, rounds: int):
    @jax.jit
    def auron_ops_joins_probe_count(probe: DeviceBatch, *index_args):
        return _probe_count_body(probe, index_kind, index_args, rounds,
                                 key_exprs, in_schema)

    return auron_ops_joins_probe_count


#: probe-prologue programs: the probe-side fused-stage chain + key hashing
#: + candidate search in ONE XLA program (the join-side analogue of the
#: exchange's fused split) — the probe chain's intermediate batch goes
#: straight into the hash probe without an extra program boundary
_PROBE_PROGRAMS = programs.register(
    programs.ProgramCache("ops.joins.fused_probe", maxsize=256))

#: match programs: candidate expansion + exact-key verification + pair
#: gather + compaction — the whole match phase of ONE probe batch in ONE
#: XLA program, for every join type (the type is a static key of the
#: program). With a consumer chain (Fusion 2.0's probe-into-consumer fold)
#: the downstream FusedStageOp's fragments run in the same launch, so the
#: inner join's matched output feeds the chain without materializing
#: between two programs (the dual of the probe prologue above)
_MATCH_PROGRAMS = programs.register(
    programs.ProgramCache("ops.joins.match", maxsize=256))


def _expand(lo, counts, out_cap: int, capacity: int):
    """Expand candidate ranges to (probe_idx, build_idx) pairs."""
    starts = jnp.cumsum(counts) - counts  # exclusive prefix
    total = jnp.sum(counts)
    slots = jnp.arange(out_cap, dtype=jnp.int32)
    # probe row owning slot t: last row with starts <= t
    probe_idx = jnp.searchsorted(
        starts, slots, side="right").astype(jnp.int32) - 1
    probe_idx = jnp.clip(probe_idx, 0, capacity - 1)
    offset = slots - starts[probe_idx]
    build_idx = lo[probe_idx] + offset
    in_range = slots < total
    return probe_idx, jnp.where(in_range, build_idx, 0), in_range


def _match_program(join_type: str, frag_keys: tuple, key_exprs: tuple,
                   probe_schema: Schema, build_schema: Schema,
                   out_cap: int, capacity: int, build_cap: int, fragments):
    """One program per (join type, consumer chain or none, join keys,
    schemas, capacities): expand, verify, gather both sides, compact.
    Returns ``(outs, matched, carries)``; ``outs`` by type:

    - inner / right: (pairs,)
    - left / full: (pairs, unmatched probe rows with null build columns)
    - semi / anti: (the kept probe rows,); existence: (probe + flag,)

    ``matched`` is the build side's or-accumulated match mask (right /
    full; None otherwise) — an operand and a result. ``fragments`` is
    the consumer FusedStageOp's member chain (inner joins only, possibly
    empty): the compacted pair batch runs through it with ``carries``
    advancing exactly as the consumer's own stage program would advance
    them, so the fold only removes a program boundary."""

    def build():
        from auron_tpu.ops.fused import thread_fragments

        def auron_ops_joins_match(probe: DeviceBatch,
                                  build_batch: DeviceBatch,
                                  build_keys: tuple, lo, counts, matched,
                                  partition_id, carries):
            ctx = EvalContext()
            probe_key_cols = tuple(
                evaluate(e, probe, probe_schema, ctx).col for e in key_exprs)
            probe_idx, build_idx, in_range = _expand(lo, counts, out_cap,
                                                     capacity)
            ok = _keys_match(probe_key_cols, probe_idx, build_keys,
                             build_idx) & in_range
            if join_type in ("right", "full"):
                matched = matched.at[jnp.where(ok, build_idx, build_cap)] \
                    .set(True, mode="drop") | matched
            if join_type in ("semi", "anti", "existence", "left", "full"):
                matched_probe = jnp.zeros(capacity, bool).at[
                    jnp.where(ok, probe_idx, capacity)].set(True, mode="drop")
            if join_type == "semi":
                return (compact(probe, matched_probe),), matched, carries
            if join_type == "anti":
                keep = ~matched_probe & probe.row_mask()
                return (compact(probe, keep),), matched, carries
            if join_type == "existence":
                cols = probe.columns + (PrimitiveColumn(
                    matched_probe, jnp.ones(capacity, bool)),)
                return (DeviceBatch(cols, probe.num_rows),), matched, carries

            out_probe = _take_cols(probe.columns, probe_idx,
                                   jnp.ones_like(probe_idx, bool))
            out_build = _take_cols(build_batch.columns, build_idx,
                                   jnp.ones_like(build_idx, bool))
            pair = DeviceBatch(tuple(out_probe) + tuple(out_build),
                               jnp.asarray(out_cap, jnp.int32))
            outs = (compact(pair, ok),)
            if fragments:
                outs, new_carries = thread_fragments(
                    fragments, outs[0], partition_id, carries)
                carries = jnp.stack(new_carries)
            if join_type in ("left", "full"):
                # unmatched probe rows with nulls on the build side
                left_out = compact(probe,
                                   ~matched_probe & probe.row_mask())
                null_build = tuple(_null_column_like(c, capacity)
                                   for c in build_batch.columns)
                outs += (DeviceBatch(left_out.columns + null_build,
                                     left_out.num_rows),)
            return outs, matched, carries

        # donation stays off: the probe batch is an output of the
        # semi/anti/existence types and the build side serves every batch
        return programs.jit(auron_ops_joins_match)

    return _MATCH_PROGRAMS.get_or_build(
        (join_type, frag_keys, key_exprs, probe_schema, build_schema,
         out_cap, capacity, build_cap), build)


def _fused_probe_program(frag_keys: tuple, key_exprs: tuple,
                         in_schema: Schema, out_schema: Schema,
                         capacity: int, build_cap: int, fragments,
                         index_kind: str, rounds: int,
                         donate: bool = False):
    """One program per (probe chain, join keys, schema, capacities,
    candidate-search backend): member fragments thread the batch, then
    the probe-count body runs on the chain output. Returns the
    transformed batch too — the join's match/gather phase consumes it,
    and the downstream eager key evaluation (_keys_match) sees exactly
    the batch the standalone chain would have produced, keeping fused
    results bit-identical. ``donate`` hands the raw input batch to XLA
    when the probe child owns it (dead after the chain; no-op on CPU)."""

    def build():
        from auron_tpu.ops.fused import thread_fragments
        from auron_tpu.runtime import programs as _programs

        def auron_ops_joins_fused_probe(batch: DeviceBatch, partition_id,
                                        carries, *index_args):
            outs, new_carries = thread_fragments(fragments, batch,
                                                 partition_id, carries)
            (b,) = outs   # fan-out chains never take this path
            h, lo, counts, total = _probe_count_body(
                b, index_kind, index_args, rounds, key_exprs, out_schema)
            return b, lo, counts, total, jnp.stack(new_carries)

        # graft: donation-ok -- probe chain owns the raw batch
        # (fragment_computes gate); probe programs never re-run
        return _programs.jit(auron_ops_joins_fused_probe,
                             donate_argnums=(0,) if donate else ())

    return _PROBE_PROGRAMS.get_or_build(
        (frag_keys, key_exprs, in_schema, capacity, build_cap,
         index_kind, rounds, donate), build)


@program_cache("ops.joins.build_side", maxsize=256)
def _build_side_program(key_exprs: tuple, schema: Schema, capacity: int):
    """The build side's sort by key hash — key evaluation, hashing, the
    stable argsort and the gathers of the batch, the hashes and the key
    columns — as one program."""

    @jax.jit
    def auron_ops_joins_build_side(batch: DeviceBatch):
        ctx = EvalContext()
        keys = tuple(evaluate(e, batch, schema, ctx).col for e in key_exprs)
        h = _key_hashes(keys, capacity, batch.row_mask(), _NULL_BUILD)
        perm = jnp.argsort(h, stable=True)
        return (gather_batch(batch, perm, batch.num_rows), h[perm],
                _take_cols(keys, perm, jnp.ones(capacity, bool)))

    return auron_ops_joins_build_side


@program_cache("ops.joins.unmatched_build", maxsize=256)
def _unmatched_build_program(probe_schema: Schema, capacity: int):
    """Right / full joins' last batch: the build rows no probe batch
    matched, with nulls on the probe side."""

    @jax.jit
    def auron_ops_joins_unmatched_build(build_batch: DeviceBatch, matched):
        build_out = compact(build_batch, ~matched & build_batch.row_mask())
        null_probe = tuple(_null_column_like_schema(f, capacity)
                           for f in probe_schema)
        return DeviceBatch(null_probe + build_out.columns, build_out.num_rows)

    return auron_ops_joins_unmatched_build


class _BuildSide:
    """Sorted-by-hash build table, plus (when enabled and the build side
    fits) the hash-table candidate index over its hash runs."""

    def __init__(self, batch: DeviceBatch, schema: Schema, key_exprs,
                 metrics, conf=None, track_matched: bool = False):
        self.schema = schema
        self.capacity = cap = batch.capacity
        self.batch, self.hashes, self.keys = _build_side_program(
            tuple(key_exprs), schema, cap)(batch)
        # matched mask for right/full joins, or-accumulated across batches
        # by the match program (a host constant until its first launch)
        self.matched = np.zeros(cap, bool) if track_matched else None
        # hash-run candidate index (auron_tpu/hashtable): probe hash →
        # (run lo, run length) in O(probe rounds) gathers instead of two
        # O(log B) searchsorted passes; None keeps the searchsorted path
        # (disabled, too large, or sentinel-colliding hashes)
        self.index = None
        self.rounds = 64
        if conf is not None:
            from auron_tpu import config as cfg
            if conf.get(cfg.HASHTABLE_ENABLED) \
                    and conf.get(cfg.HASHTABLE_BACKEND) != "sort":
                from auron_tpu.hashtable import build_join_index
                self.rounds = max(1, conf.get(
                    cfg.HASHTABLE_MAX_PROBE_ROUNDS))
                self.index = build_join_index(self.hashes, self.rounds)
        if metrics is not None:
            metrics.counter(
                "dispatch_ht_index" if self.index is not None
                else "dispatch_searchsorted").add(1)

    @property
    def index_kind(self) -> str:
        return "ht" if self.index is not None else "sorted"

    def index_args(self) -> tuple:
        if self.index is not None:
            return (self.index.th, self.index.lo, self.index.cnt)
        return (self.hashes,)


def _keys_match(probe_keys, probe_idx, build_keys, build_idx) -> jax.Array:
    """Exact equality verification per candidate pair: structural value
    equality (NaN == NaN, struct fieldwise) but top-level NULL keys never
    match (SQL equi-join)."""
    from auron_tpu.ops.hashing import pairwise_eq
    ok = jnp.ones(probe_idx.shape[0], bool)
    for pc, bc in zip(probe_keys, build_keys):
        pv = pc.validity[probe_idx]
        bv = bc.validity[build_idx]
        ok = ok & pv & bv & pairwise_eq(pc, probe_idx, bc, build_idx)
    return ok


class _MatchState:
    """What one ``HashJoinOp.execute`` threads through its probe batches'
    match programs: the folded consumer chain (or none) with its carries,
    and the program-cache counters."""

    __slots__ = ("partition", "fragments", "frag_keys", "carries",
                 "built_c", "hit_c", "fold_built_c", "fold_hit_c")

    def __init__(self, partition: int, built_c, hit_c):
        self.partition = partition
        self.fragments = ()
        self.frag_keys = ()
        self.carries = None
        self.built_c, self.hit_c = built_c, hit_c
        self.fold_built_c = self.fold_hit_c = None

    def count(self, built: bool) -> None:
        (self.built_c if built else self.hit_c).add(1)
        if self.fold_built_c is not None:   # the folded case keeps its own
            (self.fold_built_c if built else self.fold_hit_c).add(1)


class HashJoinOp(PhysicalOp):
    """Generic equi-join; build side fully materialized (broadcast pattern).

    join_type: inner | left | right | full | semi | anti | existence
    (probe side is 'left' in naming below).
    """

    name = "hash_join"

    #: SPMD layout contract (ir/planner.annotate_mesh → parallel/mesh
    #: buffer_spec): the build side REPLICATES across the mesh — every
    #: probe shard reads the full relation, so a sharded probe stage
    #: never exchanges build rows; probe batches shard on the batch dim.
    mesh_build_kind = "hash_build"

    def __init__(self, probe: PhysicalOp, build: PhysicalOp,
                 probe_keys: list[ir.Expr], build_keys: list[ir.Expr],
                 join_type: str = "inner"):
        assert join_type in ("inner", "left", "right", "full", "semi",
                             "anti", "existence")
        self.probe = probe
        self.build = build
        self.probe_keys = tuple(probe_keys)
        self.build_keys = tuple(build_keys)
        self.join_type = join_type

        ps, bs = probe.schema(), build.schema()
        if join_type in ("semi", "anti"):
            self._schema = ps
        elif join_type == "existence":
            self._schema = Schema(tuple(ps.fields) + (Field("exists", DataType.BOOL, False),))
        else:
            self._schema = Schema(tuple(ps.fields) + tuple(bs.fields))

    @property
    def children(self):
        return [self.probe, self.build]

    def schema(self) -> Schema:
        return self._schema

    def execute(self, partition: int, ctx: ExecContext,
                _consumer=None) -> Iterator[DeviceBatch]:
        """``_consumer`` is the probe-into-consumer fold handshake
        (ops/fused.FusedStageOp.execute): ``(consumer_op, fragments,
        frag_keys)`` of the downstream fused chain. The inner join's
        match program then runs that chain too — match phase + consumer
        chain in one launch — and every batch this generator yields is
        ALREADY chained; degraded paths (SMJ fallback, empty build)
        chain via the consumer's ordinary stage program instead so the
        contract holds on every route."""
        metrics = ctx.metrics_for(self)
        elapsed = metrics.counter("elapsed_compute")
        build_time = metrics.counter("build_hash_map_time")
        probe_schema = self.probe.schema()
        build_schema = self.build.schema()
        mem = ctx.mem_manager
        spillable = mem is not None and \
            getattr(mem, "spill_manager", None) is not None
        km = ctx.metrics_for("kernels")
        match = _MatchState(partition,
                            km.counter("join_match_programs_built"),
                            km.counter("join_match_program_hits"))
        consumer_op = None
        if _consumer is not None:
            consumer_op, match.fragments, match.frag_keys = _consumer
            # graft: disable=GL001 -- a host list of python ints
            match.carries = np.asarray(
                [f.init_carry for f in match.fragments], np.int64)
            ctx.metrics_for(consumer_op).counter(
                "probe_consumer_folded").add(1)
            match.fold_built_c = km.counter("gather_consumer_programs_built")
            match.fold_hit_c = km.counter("gather_consumer_program_hits")

        def stream():
            consumer = _JoinBuildConsumer(self, mem, metrics, ctx.conf) \
                if spillable else None
            try:
                build_batches = []
                with timer(build_time):
                    for b in self.build.execute(partition, ctx):
                        # the build side materializes fully before any
                        # probe batch streams: without a poll here a
                        # cancel/deadline waits out the whole build
                        ctx.checkpoint("join.build")
                        if consumer is not None:
                            consumer.add(b)
                        else:
                            build_batches.append(b)
                if consumer is not None and consumer.spills:
                    # Build side exceeded its memory share: degrade to an
                    # external sort-merge join over spilled runs (the
                    # reference's smj-fallback knob, conf.rs:53-55, in the
                    # memory-safe direction).
                    metrics.counter("fallback_smj_count").add(1)
                    out = self._smj_fallback(consumer, partition, ctx)
                    if consumer_op is not None:
                        out = consumer_op.run_chain(out, partition, ctx)
                    yield from out
                    return
                if consumer is not None:
                    build_batches = consumer.take_buffered()
                with timer(build_time):
                    merged = None
                    if build_batches:
                        merged = _concat_all(build_batches) \
                            if len(build_batches) > 1 else build_batches[0]
                if merged is None:
                    out = self._empty_build_stream(partition, ctx,
                                                   probe_schema)
                    if consumer_op is not None:
                        out = consumer_op.run_chain(out, partition, ctx)
                    yield from out
                    return
                side = _BuildSide(
                    merged, build_schema, self.build_keys, metrics,
                    conf=ctx.conf,
                    track_matched=self.join_type in ("right", "full"))

                fold = self._probe_fold(ctx)
                if fold is not None:
                    yield from self._probe_fused(fold, side, partition, ctx,
                                                 probe_schema, build_schema,
                                                 elapsed, match)
                else:
                    for probe in self.probe.execute(partition, ctx):
                        yield from self._probe_one(probe, side, probe_schema,
                                                   build_schema, elapsed,
                                                   match)

                if self.join_type in ("right", "full"):
                    yield _unmatched_build_program(
                        probe_schema, side.capacity)(side.batch, side.matched)
            finally:
                if consumer is not None:
                    consumer.close()

        return count_output(stream(), metrics)

    def _smj_fallback(self, consumer: "_JoinBuildConsumer", partition: int,
                      ctx: ExecContext) -> Iterator[DeviceBatch]:
        """Oversized build side: sort both sides externally (SortOp handles
        the spill-backed sorting) and stream an order-preserving merge join
        with a bounded window."""
        from auron_tpu.ops.smj import SortMergeJoinOp
        from auron_tpu.ops.sort import SortOp
        replay = _SpillReplayOp(self.build.schema(), consumer.spills,
                                consumer.take_buffered())
        probe_sorted = SortOp(self.probe,
                              [ir.SortOrder(e) for e in self.probe_keys])
        build_sorted = SortOp(replay,
                              [ir.SortOrder(e) for e in self.build_keys])
        smj = SortMergeJoinOp(probe_sorted, build_sorted,
                              list(self.probe_keys), list(self.build_keys),
                              self.join_type)
        yield from smj.execute(partition, ctx)

    # -- helpers ------------------------------------------------------------
    def _probe_fold(self, ctx: ExecContext):
        """(fragments, frag_keys, input_op) when the probe side is a
        fused chain whose fragments can fold into the probe-count
        program, else None."""
        from auron_tpu import config as cfg
        from auron_tpu.ops.fused import FusedStageOp
        if not ctx.conf.get(cfg.FUSION_ENABLED):
            return None
        if not isinstance(self.probe, FusedStageOp) \
                or self.probe.has_limit():
            return None
        fragments, frag_keys = self.probe.fragment_pipeline()
        if not fragments or any(f.fanout != 1 for f in fragments):
            return None
        return fragments, frag_keys, self.probe.input

    def _probe_fused(self, fold, side: _BuildSide, partition: int,
                     ctx: ExecContext, probe_schema, build_schema, elapsed,
                     match: "_MatchState"):
        """Probe loop with the chain folded into the probe program: one
        XLA launch runs the member fragments AND the candidate search;
        the transformed batch comes back for the match/gather phase."""
        fragments, frag_keys, input_op = fold
        kmetrics = ctx.metrics_for("kernels")
        built_c = kmetrics.counter("fused_probe_programs_built")
        hit_c = kmetrics.counter("fused_probe_program_hits")
        # the folded chain still OWNS its plan node: the probe program
        # runs the member fragments and returns the transformed batch,
        # so the FusedStageOp node gets its real output rows and the
        # program's time (the whole-stage attribution — without this,
        # EXPLAIN ANALYZE would show the elided node as dead)
        fmetrics = ctx.metrics_for(self.probe)
        f_elapsed = fmetrics.counter("elapsed_compute")
        f_rows = fmetrics.counter("output_rows")
        f_batches = fmetrics.counter("output_batches")
        fmetrics.counter("probe_search_folded").add(1)
        in_schema = input_op.schema()
        # donation sweep: the raw probe batch is dead once the chain
        # produced the transformed batch — donate it when owned
        from auron_tpu.ops.base import yields_owned_batches
        donate = (any(getattr(m, "fragment_computes", False)
                      for m in self.probe.members)
                  and yields_owned_batches(input_op))
        # graft: disable=GL001 -- a host list of python ints
        carries = np.asarray([f.init_carry for f in fragments], np.int64)
        for raw in input_op.execute(partition, ctx):
            ctx.check_cancelled()
            kern, built = _fused_probe_program(
                frag_keys, self.probe_keys, in_schema, probe_schema,
                raw.capacity, side.capacity, fragments,
                side.index_kind, side.rounds, donate)
            (built_c if built else hit_c).add(1)
            with timer(f_elapsed) as t:
                probe, lo, counts, total, carries = t.track(
                    kern(raw, np.int32(partition), carries,
                         *side.index_args()))
            # the first read of this launch: it holds the wait for the
            # fused probe program (the candidate total below comes from
            # the same launch and is there by then)
            f_rows.add(_profile.row_count(probe))
            f_batches.add(1)
            yield from self._probe_one(probe, side, probe_schema,
                                       build_schema, elapsed, match,
                                       pre=(lo, counts, total))

    def _probe_one(self, probe: DeviceBatch, side: _BuildSide, probe_schema,
                   build_schema, elapsed, match: "_MatchState",
                   pre=None):
        cap = probe.capacity
        if pre is None:
            kern = _probe_count_kernel(self.probe_keys, probe_schema, cap,
                                       side.capacity, side.index_kind,
                                       side.rounds)
            with timer(elapsed) as t:
                _h, lo, counts, total = t.track(
                    kern(probe, *side.index_args()))
        else:   # the fused probe program already ran the candidate search
            lo, counts, total = pre
        # the one sync a probe batch: the exact candidate count sizes the
        # match program's output capacity
        total_i = int(_profile.timed_get(total))
        if total_i == 0 and self.join_type in ("inner", "right"):
            # no candidates → no pair batch, and a folded consumer chain
            # (with its carries) never sees one
            return

        kern, built = _match_program(
            self.join_type, match.frag_keys, self.probe_keys, probe_schema,
            build_schema, bucket_rows(max(total_i, 1)), cap, side.capacity,
            match.fragments)
        match.count(built)
        with timer(elapsed) as t:
            outs, side.matched, match.carries = t.track(kern(
                probe, side.batch, side.keys, lo, counts, side.matched,
                np.int32(match.partition), match.carries))
        if total_i == 0 and self.join_type in ("left", "full"):
            outs = outs[1:]     # no candidates → no pair batch
        yield from outs

    def _empty_build_stream(self, partition, ctx, probe_schema):
        for probe in self.probe.execute(partition, ctx):
            cap = probe.capacity
            if self.join_type in ("anti",):
                yield probe
            elif self.join_type in ("semi",):
                yield DeviceBatch(probe.columns, jnp.asarray(0, jnp.int32))
            elif self.join_type == "existence":
                cols = probe.columns + (PrimitiveColumn(
                    jnp.zeros(cap, bool), jnp.ones(cap, bool)),)
                yield DeviceBatch(cols, probe.num_rows)
            elif self.join_type in ("left", "full"):
                null_build = tuple(_null_column_like_schema(f, cap)
                                   for f in self.build.schema())
                yield DeviceBatch(probe.columns + null_build, probe.num_rows)
            # inner/right with empty build: no output

    def __repr__(self):
        return f"HashJoinOp[{self.join_type}, {len(self.probe_keys)} keys]"


#: the most output slots one cross-join program fills; a build side too
#: long for it is taken in slices, a program each
_CROSS_MAX_SLOTS = 1 << 22


@program_cache("ops.joins.cross", maxsize=64)
def _cross_program(probe_cap: int, build_cap: int, chunk: int):
    """Every live row of a probe batch beside the (at most ``chunk``)
    build rows from row ``offset`` on, probe row by probe row: with w
    build rows in the slice, slot t holds probe row t // w and build row
    offset + t % w, so the live slots are the first rows x w and nothing
    is compacted. One program a (probe capacity, build capacity, slice)."""
    out_cap = bucket_rows(probe_cap * chunk)

    @jax.jit
    def auron_ops_joins_cross(probe: DeviceBatch, build: DeviceBatch,
                              offset):
        # the operator asks for no slice past the build side's last row
        w = jnp.minimum(jnp.asarray(build.num_rows, jnp.int32) - offset,
                        chunk)
        n_out = jnp.asarray(probe.num_rows, jnp.int32) * w
        slots = jnp.arange(out_cap, dtype=jnp.int32)
        live = slots < n_out
        probe_idx = jnp.minimum(slots // w, probe_cap - 1)
        build_idx = jnp.minimum(offset + slots % w, build_cap - 1)
        return DeviceBatch(_take_cols(probe.columns, probe_idx, live)
                           + _take_cols(build.columns, build_idx, live),
                           n_out)

    return auron_ops_joins_cross


class CrossJoinOp(PhysicalOp):
    """The inner join without keys and without a condition (Spark's
    BroadcastNestedLoopJoinExec BuildRight Inner, CartesianProductExec):
    the build side is collected whole, as a hash join's is, and every
    probe batch leaves beside every row of it, probe row by probe row."""

    name = "cross_join"

    def __init__(self, probe: PhysicalOp, build: PhysicalOp):
        self.probe = probe
        self.build = build
        self._schema = Schema(tuple(probe.schema().fields)
                              + tuple(build.schema().fields))

    @property
    def children(self):
        return [self.probe, self.build]

    def schema(self) -> Schema:
        return self._schema

    def execute(self, partition: int, ctx: ExecContext) -> Iterator[DeviceBatch]:
        metrics = ctx.metrics_for(self)
        elapsed = metrics.counter("elapsed_compute")

        def stream():
            with timer(metrics.counter("build_hash_map_time")):
                batches = []
                for b in self.build.execute(partition, ctx):
                    ctx.checkpoint("join.build")
                    batches.append(b)
                if not batches:
                    return        # no build row, no pair
                build = _concat_all(batches) if len(batches) > 1 \
                    else batches[0]
                n_build = _profile.row_count(build)
            if n_build == 0:
                return
            for probe in self.probe.execute(partition, ctx):
                ctx.check_cancelled()
                chunk = max(1, min(n_build,
                                   _CROSS_MAX_SLOTS // probe.capacity))
                kern = _cross_program(probe.capacity, build.capacity, chunk)
                for offset in range(0, n_build, chunk):
                    with timer(elapsed) as t:
                        out = t.track(kern(probe, build, np.int32(offset)))
                    yield out

        return count_output(stream(), metrics,
                            also=(("cross_join_rows", 1),))

    def __repr__(self):
        return "CrossJoinOp[inner]"


def _null_column_like(col, cap):
    from auron_tpu.columnar.decimal128 import Decimal128Column
    if isinstance(col, StringColumn):
        return StringColumn(jnp.zeros((cap, col.width), jnp.uint8),
                            jnp.zeros(cap, jnp.int32), jnp.zeros(cap, bool))
    if isinstance(col, Decimal128Column):
        return Decimal128Column(jnp.zeros(cap, jnp.int64),
                                jnp.zeros(cap, jnp.int64),
                                jnp.zeros(cap, bool))
    return PrimitiveColumn(jnp.zeros(cap, col.data.dtype), jnp.zeros(cap, bool))


def _null_column_like_schema(field: Field, cap):
    from auron_tpu.exprs.eval import null_column_for_field
    return null_column_for_field(field, cap)


class _JoinBuildConsumer(BufferedSpillConsumer):
    """Build-side buffering registered with the memory manager (the
    MemConsumer role the reference's broadcast-join build plays,
    join_hash_map.rs:365-387). Under pressure, buffered batches spill as
    unsorted runs to tiered storage; their presence switches the join to
    the external sort-merge fallback."""

    def __init__(self, op: "HashJoinOp", mem, metrics, conf):
        super().__init__(f"join-build-{id(op):x}", mem, metrics, conf)


class _SpillReplayOp(PhysicalOp):
    """Replays spilled build-side runs (plus any still-resident batches) as
    a child stream for the sort-merge fallback."""

    name = "spill_replay"

    def __init__(self, schema: Schema, spills, batches: list[DeviceBatch]):
        self._schema = schema
        self.spills = spills
        self.batches = batches

    def schema(self) -> Schema:
        return self._schema

    def execute(self, partition: int, ctx: ExecContext) -> Iterator[DeviceBatch]:
        from auron_tpu.columnar.serde import (deserialize_host_batch,
                                              host_to_batch)
        def stream():
            for s in self.spills:
                for frame in s.frames():
                    host, _ = deserialize_host_batch(frame)
                    if host.num_rows:
                        yield host_to_batch(host, bucket_rows(host.num_rows))
            for b in self.batches:
                yield b
        return stream()

    def __repr__(self):
        return f"_SpillReplayOp[{len(self.spills)} spills]"


# canonical SMJ implementation (order-preserving streaming merge) lives in
# ops/smj.py; re-exported here so plan builders import one joins module
from auron_tpu.ops.smj import SortMergeJoinOp  # noqa: E402
