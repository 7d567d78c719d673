"""SPMD shuffle over a jax.sharding.Mesh: the ICI all-to-all exchange.

This is the TPU-native replacement for the reference's file-based shuffle
(SURVEY.md §5.8): instead of compacted spill files fetched through the block
store, each mesh device buckets its rows by target partition *on device* and
one `lax.all_to_all` moves every bucket to its owner across ICI links in a
single collective. Static shapes are preserved by a per-(src,dst) row quota:
send buffers are [n_dev, quota, ...]; overflow (a bucket exceeding quota) is
reported per-device as the observed max bucket size so the host can rerun
the exchange ONCE at exactly the needed quota (rounded up to a power of two
so escalations land on a small reusable set of compiled programs) — same
contract as the engine's other capacity re-bucketing, without the
compile-per-doubling churn of a blind retry loop.

Works identically on a virtual CPU mesh (tests / driver dry-run) and a real
TPU slice; on multi-host deployments the same code spans hosts because jax
global meshes hide DCN vs ICI (collectives ride the fastest available
fabric).
"""

from __future__ import annotations

from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from auron_tpu.obs import profile as _profile
from auron_tpu.runtime.programs import program_cache


def make_mesh(num_devices: int | None = None, axis: str = "data") -> Mesh:
    devs = jax.devices()
    n = num_devices or len(devs)
    return Mesh(np.array(devs[:n]), (axis,))


# ---------------------------------------------------------------------------
# collective boundary: fault site + device-loss classification
# ---------------------------------------------------------------------------

def round_fault_check(cancel=None) -> None:
    """The per-round injection site of the mesh fault domain
    (``mesh.all_to_all``): fired once before every all-to-all round.
    ``io_error`` raises the classified :class:`errors.MeshUnavailable`
    (simulated device loss — the exchange's demotion handler routes the
    remaining rounds host-side), ``fatal`` an InjectedFatalError
    carrying this site (same demotion: a deterministically failing mesh
    is recovered by routing AROUND it), ``hang`` a straggling chip (the
    sleep lands inside the round guard's timer, so the straggler
    defense sees it)."""
    from auron_tpu import errors
    from auron_tpu.runtime import faults
    faults.maybe_fail("mesh.all_to_all", errors.MeshUnavailable,
                      cancel=cancel)


def classify_collective(e: BaseException) -> BaseException:
    """Classification at the collective boundary: a bare RuntimeError
    crossing out of a shard_map program routes through
    ``errors.classify_runtime``, whose device-loss signatures become
    :class:`errors.MeshUnavailable` — the verdict the demotion ladder
    keys on. Already-classified errors pass through unchanged."""
    from auron_tpu import errors
    if isinstance(e, errors.AuronError) or not isinstance(e, RuntimeError):
        return e
    return errors.classify_runtime(e)


def is_mesh_loss(e: BaseException) -> bool:
    """True when ``e`` is the mesh fault domain's DEMOTABLE class: a
    classified device loss (MeshUnavailable, injected or real) or any
    classified error raised AT a mesh fault site (an injected ``fatal``
    at ``mesh.all_to_all`` carries the site — a deterministic failure
    of the mesh plane is recovered by demotion, not by retrying the
    same collective). Errors from the map-side CHILD operators (e.g.
    ``device.compute`` faults inside the drive loop) are NOT mesh
    losses: they keep their own recovery semantics (task retry /
    surfaced verdict)."""
    from auron_tpu import errors
    if isinstance(e, errors.MeshUnavailable):
        return True
    return (isinstance(e, errors.AuronError)
            and (getattr(e, "site", None) or "").startswith("mesh."))


# ---------------------------------------------------------------------------
# sharded stage-exchange program (the SPMD execution plane's workhorse)
# ---------------------------------------------------------------------------

#: central compile site for the sharded stage programs: the fused member
#: chain (when the exchange folded one), the partition-id compute, the
#: sort-by-pid split and the all-to-all collective in ONE shard_map
#: program — the whole map side of a shuffle runs partition-parallel
#: across the mesh with no host round-trip between its steps
from auron_tpu.runtime import programs as _programs

_STAGE_EXCHANGE_PROGRAMS = _programs.register(
    _programs.ProgramCache("parallel.mesh_exchange.stage", maxsize=128))


def stage_exchange_program(mesh: Mesh, axis: str, n_dev: int,
                           frag_keys: tuple, part_key: tuple,
                           in_schema, out_schema, capacity: int,
                           quota: int, fragments, part_exprs,
                           combine=None, combine_sig=None):
    """Central-registry lookup of the sharded stage-exchange program for
    one (chain signature, hash keys, schema, capacity, quota) class.
    Returns ``(kernel, built)``.

    The program NEVER donates its inputs: a bucket overflowing the row
    quota triggers the one-shot host-side re-run at the exact needed
    pow2 quota (the ``exchange_device_batches`` contract), and a donated
    input would be poisoned for that re-run — the donate sweep must
    not reach across the exchange (``yields_owned_batches``
    notwithstanding).

    ``combine`` (ops/agg.AggOp.build_combine_stage, keyed by
    ``combine_sig``) is the map-side combine fold: each shard merges its
    round's groups BETWEEN the chain and the partition-id compute, so
    what crosses
    ``lax.all_to_all`` is per-shard GROUPS — fewer live rows through the
    collective, the cheapest scale-out win available. Stateless, so the
    escalation re-run and the demoted host path replay it exactly.

    Kernel signature (all global, batch-dim sharded on ``axis`` unless
    noted)::

        kernel(columns, num_rows, carries) ->
            (out_columns, recv_counts, out_num_rows, global_max, carries'
             [, combine_rows_in])

    - ``columns``: the stacked input batch's column pytree, every leaf
      ``[n_dev * capacity, ...]`` (shard i = map partition i's rows);
    - ``num_rows``: ``int32[n_dev]`` live rows per shard;
    - ``carries``: ``int64[n_dev, n_frags]`` per-shard member carries;
    - ``out_columns``: received rows, shard p = reducer partition p; row
      layout per shard is ``[src * quota + r]`` (source-major, original
      row order within a source — NOT compacted, so the reducer can
      slice per source and preserve the host path's map-major order);
    - ``recv_counts``: ``int32[n_dev * n_dev]``, shard p's row = rows
      received from each source;
    - ``global_max``: REPLICATED int32 — the global largest bucket, the
      host's one output-boundary readback: rows were dropped iff it
      exceeds ``quota``, and its value is the exact quota the single
      re-run needs;
    - ``combine_rows_in``: ``int32[n_dev]`` pre-combine live rows per
      shard, present only when a combine stage is folded — read in the
      same output-boundary fence (telemetry adds no sync point).
    """
    key = (frag_keys, part_key, in_schema, out_schema, n_dev, capacity,
           quota, axis, combine_sig)

    def build():
        from auron_tpu.columnar.batch import DeviceBatch, gather_batch
        from auron_tpu.exprs.eval import EvalContext, evaluate
        from auron_tpu.ops import hashing
        from auron_tpu.ops.fused import sharded_fragment_chain
        chain = sharded_fragment_chain(fragments) if fragments else None
        n_frags = len(fragments)

        def auron_parallel_mesh_exchange_stage(columns, num_rows, carries):
            nr = num_rows[0]
            batch = DeviceBatch(columns, nr)
            # this device IS its map partition (maps assigned in order)
            pid_dev = lax.axis_index(axis).astype(jnp.int32)
            if chain is not None:
                b, new_carry = chain(batch, pid_dev, carries[0])
            else:
                b, new_carry = batch, jnp.zeros((n_frags,), jnp.int64)
            comb_in = None
            if combine is not None:
                # map-side combine: this shard's round collapses to its
                # groups before any row is offered to the collective
                b, comb_in = combine(b)
            # partition ids on the chain output (Spark-exact pmod
            # murmur3 — the HashPartitioning contract)
            ctx = EvalContext()
            cols = [evaluate(e, b, out_schema, ctx).col
                    for e in part_exprs]
            h = hashing.murmur3_columns(cols, b.capacity,
                                        hashing.SPARK_SHUFFLE_SEED)
            nn = jnp.int32(n_dev)
            pids = ((h % nn) + nn) % nn
            # stable sort-by-pid split (the buffered_data.rs compaction,
            # exactly _split_body's shape — inlined because the bucket
            # scatter below needs the sorted pid column too)
            live = b.row_mask()
            pid_key = jnp.where(live, pids, nn)
            perm = jnp.argsort(pid_key, stable=True)
            sorted_b = gather_batch(b, perm, b.num_rows)
            sorted_pid = pid_key[perm]
            counts = jax.ops.segment_sum(
                live.astype(jnp.int32), jnp.clip(pid_key, 0, n_dev),
                num_segments=n_dev + 1)[:n_dev]
            offsets = jnp.cumsum(counts) - counts   # exclusive
            max_count = jnp.max(counts).astype(jnp.int32)
            cap_b = sorted_b.capacity
            pos = jnp.arange(cap_b, dtype=jnp.int32)
            tgt = jnp.clip(sorted_pid, 0, n_dev - 1)
            slot = pos - offsets[tgt]
            in_quota = (sorted_pid < nn) & (slot < quota)
            flat_slot = jnp.where(in_quota, tgt * quota + slot,
                                  n_dev * quota)
            send_counts = jnp.minimum(counts, quota)

            def send_recv(leaf):
                buf = jnp.zeros((n_dev * quota,) + leaf.shape[1:],
                                leaf.dtype)
                buf = buf.at[flat_slot].set(leaf, mode="drop")
                buf = buf.reshape((n_dev, quota) + leaf.shape[1:])
                recv = lax.all_to_all(buf, axis, split_axis=0,
                                      concat_axis=0, tiled=False)
                return recv.reshape((n_dev * quota,) + leaf.shape[1:])

            out_cols = jax.tree_util.tree_map(send_recv, sorted_b.columns)
            recv_counts = lax.all_to_all(send_counts, axis, split_axis=0,
                                         concat_axis=0, tiled=True)
            out_nr = jnp.sum(recv_counts).astype(jnp.int32)
            gmax = lax.pmax(max_count, axis)
            if comb_in is not None:
                return (out_cols, recv_counts, out_nr[None], gmax,
                        new_carry[None, :], comb_in[None])
            return (out_cols, recv_counts, out_nr[None], gmax,
                    new_carry[None, :])

        in_specs = (P(axis), P(axis), P(axis, None))
        out_specs = (P(axis), P(axis), P(axis), P(), P(axis, None))
        if combine is not None:
            out_specs = out_specs + (P(axis),)
        # donation deliberately OFF (see docstring): programs.jit with
        # no donate_argnums, on every backend
        return _programs.jit(shard_map(auron_parallel_mesh_exchange_stage,
                                       mesh=mesh, in_specs=in_specs,
                                       out_specs=out_specs))

    return _STAGE_EXCHANGE_PROGRAMS.get_or_build(key, build)


@program_cache("parallel.mesh_exchange.exchange", maxsize=64)
def _exchange_fn(mesh: Mesh, n_cols: int, quota: int, axis: str):
    """Builds the jitted SPMD exchange for a given column arity and quota.

    Inputs (global, sharded on axis 0):
      cols:     tuple of arrays [n_dev*cap, ...]
      pids:     int32[n_dev*cap]  target partition per row
      num_rows: int32[n_dev]     live row count per shard
    Outputs:
      out_cols:     tuple of arrays [n_dev * (n_dev*quota), ...]
      out_num_rows: int32[n_dev]
      max_count:    replicated int32 scalar — the GLOBAL largest bucket
                    (pmax over the axis), readable on every controller of
                    a multi-host run; rows were dropped iff it exceeds
                    quota, and the value tells the host the exact quota a
                    single retry needs

    Program builds are countable via ``_exchange_fn.cache_info().misses``;
    tests assert skew escalation stays within a 2-compile budget.
    """
    n_dev = mesh.shape[axis]

    def auron_parallel_mesh_exchange_exchange(cols, pids, num_rows):
        cap = pids.shape[0]
        nr = num_rows[0]
        live = jnp.arange(cap, dtype=jnp.int32) < nr
        pid_key = jnp.where(live, pids, n_dev)
        perm = jnp.argsort(pid_key, stable=True)
        sorted_pid = pid_key[perm]

        ones = live.astype(jnp.int32)
        counts = jax.ops.segment_sum(ones, pid_key, num_segments=n_dev + 1)[:n_dev]
        offsets = jnp.cumsum(counts) - counts  # exclusive
        max_count = jnp.max(counts).astype(jnp.int32)

        pos = jnp.arange(cap, dtype=jnp.int32)
        tgt = jnp.clip(sorted_pid, 0, n_dev - 1)
        slot = pos - offsets[tgt]
        in_quota = (sorted_pid < n_dev) & (slot < quota)
        flat_slot = jnp.where(in_quota, tgt * quota + slot, n_dev * quota)

        send_counts = jnp.minimum(counts, quota)

        out_cols = []
        for c in cols:
            c_sorted = c[perm]
            buf_shape = (n_dev * quota,) + c.shape[1:]
            buf = jnp.zeros(buf_shape, c.dtype)
            buf = buf.at[flat_slot].set(c_sorted, mode="drop")
            buf = buf.reshape((n_dev, quota) + c.shape[1:])
            recv = lax.all_to_all(buf, axis, split_axis=0, concat_axis=0,
                                  tiled=False)
            out_cols.append(recv.reshape((n_dev * quota,) + c.shape[1:]))

        # counts from each source
        recv_counts = lax.all_to_all(send_counts, axis, split_axis=0,
                                     concat_axis=0, tiled=True)
        # compact received rows: row r of source s lives at s*quota + r,
        # valid while r < recv_counts[s]
        rr = jnp.arange(n_dev * quota, dtype=jnp.int32)
        src = rr // quota
        r_in = rr % quota
        valid = r_in < recv_counts[src]
        order = jnp.argsort(jnp.where(valid, 0, 1).astype(jnp.int32),
                            stable=True)
        out_cols = [c[order] for c in out_cols]
        out_nr = jnp.sum(recv_counts).astype(jnp.int32)
        # global (replicated) max bucket: the host-side quota check must
        # read this value on EVERY controller in a multi-host run, and a
        # P(axis)-sharded output is not fully addressable there — a pmax
        # into a replicated output is, and costs one tiny collective
        gmax = lax.pmax(max_count, axis)
        return (tuple(out_cols), out_nr[None], gmax)

    in_specs = (tuple(P(axis) for _ in range(n_cols)), P(axis), P(axis))
    out_specs = (tuple(P(axis) for _ in range(n_cols)), P(axis), P())

    return jax.jit(shard_map(auron_parallel_mesh_exchange_exchange,
                             mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs))


def mesh_all_to_all(mesh: Mesh, cols: tuple, pids, num_rows, quota: int,
                    axis: str = "data"):
    """Run the SPMD exchange; returns (cols, num_rows_per_shard, max_count)
    with max_count the replicated global max bucket size. Rows were
    dropped iff max_count > quota; rerun at that quota."""
    fn = _exchange_fn(mesh, len(cols), quota, axis)
    return fn(tuple(cols), pids, num_rows)


def exchange_device_batches(mesh: Mesh, cols: tuple, pids, num_rows,
                            axis: str = "data", initial_quota: int | None = None):
    """Overflow-safe wrapper, at most TWO compiled programs per shape class.

    Quotas are always powers of two: the first attempt uses a pow2 estimate,
    and if any bucket overflows, the returned max bucket size tells us the
    exact quota needed, so a single retry (at the next pow2 ≥ that size)
    always fits. Blind doubling would compile a fresh SPMD program per step
    (~seconds each on a real TPU slice); this escalates once, to a quota
    value drawn from a log-sized bucket set that future calls reuse.
    """
    from auron_tpu.utils.shapes import bucket_rows
    n_dev = mesh.shape[axis]
    cap = pids.shape[0] // n_dev
    quota = bucket_rows(initial_quota or (2 * cap) // n_dev)
    out_cols, out_nr, max_count = mesh_all_to_all(
        mesh, cols, pids, num_rows, quota, axis)
    needed = int(np.max(_profile.timed_get(max_count)))
    if needed <= quota:
        return out_cols, out_nr, quota
    quota = bucket_rows(needed)
    out_cols, out_nr, _ = mesh_all_to_all(
        mesh, cols, pids, num_rows, quota, axis)
    return out_cols, out_nr, quota
