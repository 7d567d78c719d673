"""Whole-stage fusion: one jit-compiled program per operator chain.

The paper's core bet is lowering the host engine's plan into native
vectorized execution; the per-operator analogue this engine shipped with
jits one program per operator per shape bucket, so every operator
boundary round-trips a materialized DeviceBatch through HBM and the
TPC-DS gate is compile-bound (PERF.md). Whole-stage codegen — Neumann's
"compiling query plans", the HyPer lineage in PAPERS.md — maps directly
onto jit composition: a maximal chain of per-batch, row-local operators
(filter, project, expand, limit-within-batch, rename) becomes ONE
``FusedStageOp`` whose body is one XLA program built from the member
ops' ``KernelFragment``s. XLA then eliminates the intermediates
entirely: a fused filter→project chain keeps the filtered batch in
registers/VMEM instead of writing it back to HBM, and the stage costs
one program build instead of one per member.

Fragment contract (``PhysicalOp.build_kernel_fragment``): a pure
traceable function

    apply(batch, partition_id, carry) -> (out_batches, carry')

where ``carry`` is one int64 scalar of per-member streaming state —
the member's ``row_num_offset`` for expression evaluation (advanced by
input rows per batch, exactly like the unfused operators' host-side
``row_off``), or the remaining-row budget for a fused limit. Carries
live on device between batches (an int64[n_members] vector threaded
through the program), so fusion adds no host synchronization; only a
fused limit reads its slot back per batch — the same per-batch sync the
unfused LimitOp paid via ``int(batch.num_rows)``.

Stage breakers — agg cores, joins, sorts, exchanges, window, generate —
never implement fragments, so the planner's fusion pass
(ir/planner.fuse_stages) cannot cross them by construction.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Iterator

import jax
import jax.numpy as jnp

from auron_tpu.columnar.batch import DeviceBatch
from auron_tpu.columnar.schema import Schema
from auron_tpu.ops.base import (ExecContext, PhysicalOp, count_output,
                                timer)
from auron_tpu.runtime import programs


@dataclass(frozen=True)
class KernelFragment:
    """One operator's contribution to a fused stage program.

    ``key`` is a hashable signature that — together with the stage's
    input schema and batch capacity — fully determines ``apply``'s
    traced behavior; it is the program-cache key component for this
    member. ``fanout`` is the number of output batches per input batch
    (ExpandOp > 1). ``init_carry`` seeds the member's carry slot at
    stream start; ``is_limit`` marks a carry that counts a remaining-row
    budget the host must poll for early exit. ``row_counts`` are the
    member's ((count key, factor), ...) of ``ops/base.count_output``'s
    ``also``: counts of the task's ledger that grow with the rows that
    leave the program the member runs in.
    """

    key: tuple
    apply: Callable
    fanout: int = 1
    init_carry: int = 0
    is_limit: bool = False
    row_counts: tuple = ()


#: the one compile site for fused stage programs, keyed on
#: (member fragment keys, stage input schema, capacity)
_STAGE_PROGRAMS = programs.register(
    programs.ProgramCache("ops.fused.stage", maxsize=512))


def thread_fragments(fragments, batch: DeviceBatch, partition_id, carries):
    """Traced core shared by every fused program (the stage kernel, the
    exchange's split prologue, the join's probe prologue): thread each
    intermediate batch through the member chain — expand fan-out is
    unrolled statically, and each member's carry advances across the
    intermediate batches in exactly the order the unfused generator
    chain would stream them. Returns (out_batches, carry_list)."""
    outs = (batch,)
    new_carries = []
    for i, frag in enumerate(fragments):
        carry = carries[i]
        nxt = []
        for b in outs:
            res, carry = frag.apply(b, partition_id, carry)
            nxt.extend(res)
        outs = tuple(nxt)
        new_carries.append(jnp.asarray(carry, jnp.int64))
    return outs, new_carries


def sharded_fragment_chain(fragments: list[KernelFragment]):
    """The SPMD form of a fused stage body (parallel/mesh_exchange):
    a traced function running the member chain on ONE mesh shard's
    local batch, with the member carries threaded as an
    ``int64[n_members]`` vector (each shard owns its map partition's
    carries — exactly the per-partition streaming state the unfused
    host loop keeps per ``execute(partition)`` call).

    ``apply(batch, partition_id, carry_vec) -> (out_batch, carry_vec')``

    Only straight chains qualify (fan-out members and fused limits are
    rejected by the exchange's eligibility check before tracing):
    a sharded stage yields exactly one output batch per shard."""

    def apply(batch: DeviceBatch, partition_id, carry_vec):
        outs, new_carries = thread_fragments(
            fragments, batch, partition_id,
            [carry_vec[i] for i in range(len(fragments))])
        (b,) = outs   # eligibility rejected fan-out chains
        return b, (jnp.stack(new_carries) if new_carries
                   else jnp.zeros((0,), jnp.int64))

    return apply


def build_stage_kernel(fragments: list[KernelFragment],
                       donate: bool = False):
    """Compose member fragments into one jitted program. ``donate``
    hands the input batch's buffers to XLA — the chain gathers/projects
    into fresh arrays, so an OWNED input batch is dead the moment the
    program runs (programs.jit keeps donation off the advisory CPU
    backend)."""

    def auron_ops_fused_stage(batch: DeviceBatch, partition_id,
                              carries):
        outs, new_carries = thread_fragments(fragments, batch,
                                             partition_id, carries)
        return outs, jnp.stack(new_carries)

    # graft: donation-ok -- donate gated on yields_owned_batches by
    # the caller; fused stages never retry on the same inputs
    return programs.jit(auron_ops_fused_stage,
                        donate_argnums=(0,) if donate else ())


def stage_program(frag_keys: tuple, in_schema: Schema, capacity: int,
                  fragments: list[KernelFragment], donate: bool = False):
    """Central-registry lookup of the stage program. Returns
    (kernel, built) — ``built`` feeds the per-stage counters in the
    ``kernels`` metrics snapshot."""
    return _STAGE_PROGRAMS.get_or_build(
        (frag_keys, in_schema, capacity, donate),
        lambda: build_stage_kernel(fragments, donate))


class FusedStageOp(PhysicalOp):
    """A maximal chain of fusable operators executing as one program.

    ``members`` are ordered upstream→downstream; the stage's input is
    the first member's child. Schema, output batches and row offsets are
    bit-identical to executing the members separately — the fusion pass
    only changes how many XLA programs exist and where the
    intermediates live.
    """

    name = "fused_stage"

    def __init__(self, members: list[PhysicalOp]):
        assert members, "fused stage needs at least one member"
        for m in members:
            assert m.fusable, f"{m!r} is not fusable"
        self.members = list(members)
        self.input = members[0].children[0]
        self._schema = members[-1].schema()

    @property
    def children(self):
        return [self.input]

    @property
    def owns_output(self):
        # a chain with any computing member gathers/projects into fresh
        # arrays; a pure pass-through chain (rename/limit) aliases its
        # input's columns
        if any(m.fragment_computes for m in self.members):
            return True
        return "inherit"

    @property
    def drops_rows(self):
        return any(m.drops_rows for m in self.members)

    def schema(self) -> Schema:
        return self._schema

    def fragment_pipeline(self):
        """(fragments, frag_keys) for this stage — also consumed by
        ShuffleExchangeOp when it folds the chain into its split program
        (the exchange-prologue fusion)."""
        fragments = [m.build_kernel_fragment() for m in self.members]
        assert all(f is not None for f in fragments)
        return fragments, tuple(f.key for f in fragments)

    def row_counts(self, fragments) -> tuple:
        """The members' row counts, summed by key: what this stage's
        output is counted as besides ``output_rows``. (A member's rows
        are counted as they leave the PROGRAM: a division under a later
        filter counts the survivors, one under a later expand each of
        its copies.)"""
        out: dict = {}
        for frag in fragments:
            for key, factor in frag.row_counts:
                out[key] = out.get(key, 0) + factor
        return tuple(out.items())

    def has_limit(self) -> bool:
        from auron_tpu.ops.limit import LimitOp
        return any(isinstance(m, LimitOp) for m in self.members)

    def _consumer_fold(self, ctx: ExecContext):
        """(fragments, frag_keys) when this stage's input is an inner
        hash join whose matched output can run through the join's
        match program (ops/joins._match_program, with this chain) — the
        probe-into-consumer fold. Fan-out members and fused limits keep
        the stage on its own program (the gather program yields exactly
        one batch and never polls a budget)."""
        from auron_tpu.ops.joins import HashJoinOp
        j = self.input
        if not isinstance(j, HashJoinOp) or j.join_type != "inner":
            return None
        if self.has_limit():
            return None
        fragments, frag_keys = self.fragment_pipeline()
        if not fragments or any(f.fanout != 1 for f in fragments):
            return None
        return fragments, frag_keys

    def run_chain(self, source, partition: int,
                  ctx: ExecContext) -> Iterator[DeviceBatch]:
        """Run the member chain over an externally produced batch stream
        — the consumer fold's degraded path (the join fell back to SMJ
        or saw an empty build side): those batches flow through the
        ordinary stage program here, so every batch the join yields is
        chained exactly once on every route."""
        kmetrics = ctx.metrics_for("kernels")
        built_c = kmetrics.counter("fused_stage_programs_built")
        hit_c = kmetrics.counter("fused_stage_program_hits")
        fragments, frag_keys = self.fragment_pipeline()
        in_schema = self.input.schema()
        carries = jnp.asarray([f.init_carry for f in fragments],
                              dtype=jnp.int64)
        for batch in source:
            ctx.check_cancelled()
            kern, built = stage_program(frag_keys, in_schema,
                                        batch.capacity, fragments)
            (built_c if built else hit_c).add(1)
            outs, carries = kern(batch, jnp.int32(partition), carries)
            yield from outs

    def execute(self, partition: int, ctx: ExecContext) -> Iterator[DeviceBatch]:
        metrics = ctx.metrics_for(self)
        from auron_tpu import config as cfg
        if ctx.conf.get(cfg.FUSION_ENABLED):
            fold = self._consumer_fold(ctx)
            if fold is not None:
                # probe-into-consumer: the join runs this stage's
                # fragments inside its gather program and yields
                # already-chained batches — count them as this stage's
                # output (whole-stage attribution, as with the probe
                # prologue fold)
                fragments, frag_keys = fold
                return count_output(
                    self.input.execute(partition, ctx,
                                       _consumer=(self, fragments,
                                                  frag_keys)),
                    metrics, also=self.row_counts(fragments),
                    shrink=self.drops_rows)
        elapsed = metrics.counter("elapsed_compute")
        kmetrics = ctx.metrics_for("kernels")
        built_c = kmetrics.counter("fused_stage_programs_built")
        hit_c = kmetrics.counter("fused_stage_program_hits")
        in_schema = self.input.schema()
        fragments, frag_keys = self.fragment_pipeline()
        limit_slots = [i for i, f in enumerate(fragments) if f.is_limit]
        init = [f.init_carry for f in fragments]
        # donation sweep: an owned input batch is dead once the chain
        # gathered/projected it into fresh arrays — donate it to XLA
        # (no-op on CPU; pass-through chains alias their input in the
        # output, which donation supports — the input buffer BECOMES
        # the output buffer)
        from auron_tpu.ops.base import yields_owned_batches
        donate = (any(m.fragment_computes for m in self.members)
                  and yields_owned_batches(self.input))

        def stream():
            from auron_tpu.obs import profile as _profile
            carries = jnp.asarray(init, dtype=jnp.int64)
            for batch in self.input.execute(partition, ctx):
                ctx.check_cancelled()
                kern, built = stage_program(frag_keys, in_schema,
                                            batch.capacity, fragments,
                                            donate)
                (built_c if built else hit_c).add(1)
                with timer(elapsed) as t:
                    outs, carries = t.track(
                        kern(batch, jnp.int32(partition), carries))
                    if limit_slots:
                        # a fused limit's budget readback is a real
                        # per-batch sync point: time it as device wait
                        budgets = _profile.timed_get(
                            [carries[i] for i in limit_slots])
                yield from outs
                # a fused limit exhausts: stop pulling the child (the
                # slot readback is the same per-batch sync the unfused
                # LimitOp paid on int(batch.num_rows))
                # graft: disable=GL001 -- budgets came to the host in the timed_get above
                if limit_slots and any(int(b) <= 0 for b in budgets):
                    break

        return count_output(stream(), metrics,
                            also=self.row_counts(fragments),
                            shrink=self.drops_rows)

    def __repr__(self):
        inner = " -> ".join(repr(m) for m in self.members)
        return f"FusedStageOp[{inner}]"
