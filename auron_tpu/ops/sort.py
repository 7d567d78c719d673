"""Sort operator.

The reference's external sort is row-format blocks + loser-tree k-way merge
with key prefixes (reference: datafusion-ext-plans/src/sort_exec.rs). On TPU
the economics invert: one big device sort beats incremental merging, so the
design is: buffer the (bounded) partition, normalize every sort key into
order-preserving uint64 words, and run a chain of stable argsorts
(least-significant key first) that XLA lowers to its parallel sort. Nulls
first/last and asc/desc are encoded into the key words themselves:

  int64     → x XOR sign-bit        (order-preserving unsigned map)
  float     → IEEE trick: flip all bits if negative else flip sign bit
  string    → big-endian byte words (zero padding already sorts prefixes first)
  desc      → bitwise NOT of the word
  null rank → one leading word per key: 0/1 by nulls_first

Spill for over-HBM partitions hooks in at the buffer stage via the memory
manager (sorted-run spill + host merge), added with the memmgr subsystem.
"""

from __future__ import annotations

from typing import Iterator, Optional

import jax
import jax.numpy as jnp

from auron_tpu.columnar.batch import (DeviceBatch, ListColumn,
                                      PrimitiveColumn, StringColumn,
                                      concat_live_rows, gather_batch)
from auron_tpu.columnar.schema import DataType, Schema
from auron_tpu.exprs import ir
from auron_tpu.exprs.eval import EvalContext, evaluate
from auron_tpu.memmgr.consumer import BufferedSpillConsumer
from auron_tpu.obs import profile as _profile
from auron_tpu.ops.base import (ExecContext, PhysicalOp, count_output,
                                timer, yields_owned_batches)
from auron_tpu.runtime import programs
from auron_tpu.runtime.programs import program_cache
from auron_tpu.utils.shapes import bucket_rows


def _sort_donate(batches: list[DeviceBatch], child: PhysicalOp) -> bool:
    """Donate the sort input when it is dead after the kernel: a multi-
    batch merge is always a fresh local concat; a single batch is safe
    only when the child constructs fresh outputs (donating a replayed
    broadcast/device-scan batch would poison later readers). CPU treats
    donation as advisory, so skip it there (programs.jit also guards)."""
    if jax.default_backend() == "cpu":
        return False
    return len(batches) > 1 or yields_owned_batches(child)


def string_be_words(chars: "jax.Array") -> "jax.Array":
    """[n, w] uint8 → [n, ceil(w/8)] big-endian uint64 words whose
    unsigned order equals byte-lexicographic order (zero padding sorts
    prefixes first; SQL strings never contain NUL). THE one definition of
    the order-preserving string encoding — order_words and the
    string-list sort share it."""
    n, w = chars.shape
    pad = (-w) % 8
    if pad:
        chars = jnp.pad(chars, ((0, 0), (0, pad)))
    u = chars.astype(jnp.uint64).reshape(n, -1, 8)
    shifts = jnp.asarray([56, 48, 40, 32, 24, 16, 8, 0], jnp.uint64)
    return jnp.sum(u << shifts[None, None, :], axis=2)


def _f32_order_bits(d: "jax.Array") -> "jax.Array":
    """A (canonicalized) float32 as the uint64 whose low 32 bits order as
    the float does: negatives complemented, the rest sign-flipped."""
    b = d.view(jnp.int32).astype(jnp.int64).astype(jnp.uint64) \
        & jnp.uint64(0xFFFFFFFF)
    sign = (b >> 31) & 1
    return jnp.where(sign == 1, (~b) & jnp.uint64(0xFFFFFFFF),
                     b | jnp.uint64(0x80000000))


def f64_split_order_word(d: "jax.Array") -> "jax.Array":
    """The order word of a float64 on a device that has no float64 bits.
    The TPU carries a double as a pair of float32 (its compiler rewrites
    every 64-bit type away and has no rule for a bitcast of one), so the
    pair is the word: ``hi`` = the double rounded to float32, ``lo`` =
    what is left, exact there. Rounding is monotone, so (hi, lo) orders
    as the doubles do, and equal doubles give equal words."""
    from auron_tpu.ops.hashing import canonicalize_float
    d = canonicalize_float(d)
    hi = d.astype(jnp.float32)
    # inf - inf and NaN - NaN are NaN: one word for every such key
    lo = canonicalize_float((d - hi.astype(jnp.float64))
                            .astype(jnp.float32))
    return (_f32_order_bits(hi) << 32) | _f32_order_bits(lo)


def f64_order_word(d: "jax.Array") -> "jax.Array":
    """One order-preserving uint64 for a float64 key: its IEEE bits where
    the backend has them, the float32 pair on the TPU. The fork exists
    only for the TPU compiler's X64 rewrite, which has no rule for a
    bitcast of a double; the pair cannot serve everywhere, because it
    drops 5 of a CPU double's 53 mantissa bits. Both go (the IEEE branch
    alone stays) once the TPU compiles ``bitcast_convert_type`` of a
    float64."""
    if jax.default_backend() == "tpu":
        return f64_split_order_word(d)
    from jax import lax
    from auron_tpu.ops.hashing import canonicalize_float
    pair = lax.bitcast_convert_type(canonicalize_float(d), jnp.uint32)
    b = pair[..., 0].astype(jnp.uint64) \
        | (pair[..., 1].astype(jnp.uint64) << 32)
    sign = (b >> 63) & 1
    return jnp.where(sign == 1, ~b, b | jnp.uint64(1 << 63))


def order_words(col, ascending: bool, nulls_first: bool) -> list[jax.Array]:
    """Normalize one sort key column into order-preserving uint64 words,
    most significant first (excluding the null-rank word, which the caller
    gets separately)."""
    from auron_tpu.columnar.batch import StructColumn
    from auron_tpu.columnar.decimal128 import Decimal128Column
    words: list[jax.Array] = []
    if isinstance(col, StructColumn):
        # struct ordering is fieldwise; each field contributes its own
        # null-rank word (null fields sort first ascending, like Spark's
        # InterpretedOrdering) then its value words, nulls neutralized
        for ch in col.children:
            cv = ch.validity & col.validity
            words.append(jnp.where(cv, jnp.uint64(1), jnp.uint64(0)))
            words.extend(jnp.where(cv, w, jnp.uint64(0))
                         for w in order_words(ch, True, True))
        if not ascending:
            words = [~w for w in words]
        return words
    if isinstance(col, Decimal128Column):
        # signed 128-bit order: sign-flipped hi limb, then unsigned lo
        hi_w = col.hi.astype(jnp.uint64) ^ jnp.uint64(1 << 63)
        lo_w = col.lo.astype(jnp.uint64)
        words = [hi_w, lo_w]
        if not ascending:
            words = [~w for w in words]
        return words
    if isinstance(col, StringColumn):
        be = string_be_words(col.chars)
        words.extend(be[:, i] for i in range(be.shape[1]))
    else:
        d = col.data
        if d.dtype == jnp.bool_:
            u = d.astype(jnp.uint64)
        elif jnp.issubdtype(d.dtype, jnp.signedinteger):
            u = d.astype(jnp.int64).astype(jnp.uint64) ^ jnp.uint64(1 << 63)
        elif d.dtype == jnp.dtype(jnp.float32):
            # Spark ordering: -0.0 == 0.0 and every NaN is the same
            # (greatest) value — canonicalize before bit-twiddling so
            # equal-under-Spark keys produce identical order words (SMJ
            # and window group detection compare words for equality)
            from auron_tpu.ops.hashing import canonicalize_float
            u = _f32_order_bits(canonicalize_float(d))
        elif d.dtype == jnp.dtype(jnp.float64):
            u = f64_order_word(d)
        else:
            u = d.astype(jnp.uint64)
        words.append(u)
    if not ascending:
        words = [~w for w in words]
    return words


def sort_key_words(key_cols, orders) -> list[jax.Array]:
    """All order words for a composite key, most-significant first: per key,
    one null-rank word then the value words (nulls neutralized to 0)."""
    all_words: list[jax.Array] = []
    for col, (asc, nf) in zip(key_cols, orders):
        null_word = jnp.where(col.validity,
                              jnp.uint64(1 if nf else 0),
                              jnp.uint64(0 if nf else 1))
        words = order_words(col, asc, nf)
        # null rows: neutralize value words so they compare equal
        words = [jnp.where(col.validity, w, 0) for w in words]
        all_words.append(null_word)
        all_words.extend(words)
    return all_words


def sort_permutation(batch: DeviceBatch, key_cols, orders) -> jax.Array:
    """Stable multi-key sort permutation. orders: list[(ascending,
    nulls_first)] aligned with key_cols. Padding rows sort to the end."""
    cap = batch.capacity
    live = batch.row_mask()
    all_words = sort_key_words(key_cols, orders)
    # dead rows to the very end: leading liveness word
    lead = jnp.where(live, jnp.uint64(0), jnp.uint64(1))
    perm = jnp.arange(cap, dtype=jnp.int32)
    for w in reversed(all_words):
        perm = perm[jnp.argsort(w[perm], stable=True)]
    perm = perm[jnp.argsort(lead[perm], stable=True)]
    return perm


@program_cache("ops.sort.sort", maxsize=256)
def _sort_kernel(sort_exprs: tuple, in_schema: Schema, capacity: int,
                 donate: bool):
    def auron_ops_sort_sort(batch: DeviceBatch):
        ctx = EvalContext()
        key_cols = [evaluate(s.expr, batch, in_schema, ctx).col
                    for s in sort_exprs]
        orders = [(s.ascending, s.nulls_first) for s in sort_exprs]
        perm = sort_permutation(batch, key_cols, orders)
        return gather_batch(batch, perm, batch.num_rows)

    # the un-sorted input is dead after the gather — donating it halves
    # peak HBM for the sort step (callers gate on ownership + platform)
    # graft: donation-ok -- _sort_donate gate (owned batches only)
    return programs.jit(auron_ops_sort_sort,
                        donate_argnums=(0,) if donate else ())


def pad_word(ascending: bool) -> int:
    """What a string key's missing trailing words read in a narrower
    width bucket: the chars past a string's end are 0, complemented when
    the key descends — what the kernel itself would emit for them."""
    return 0 if ascending else (1 << 64) - 1


def key_word_layout(sort_exprs: tuple, in_schema: Schema,
                    batch: DeviceBatch) -> list[tuple[int, int]]:
    """Per sort key: (word count incl. null word, pad word). Word counts
    depend on evaluated string widths, which are static per batch structure
    — jax.eval_shape gets them without compute. The pad word is what the
    kernel itself would emit for the missing trailing chars of a narrower
    width bucket (0 for ascending, ~0 for descending), letting the spill
    merge align runs whose strings landed in different buckets."""
    ectx = EvalContext()
    shapes = jax.eval_shape(
        lambda b: tuple(evaluate(s.expr, b, in_schema, ectx).col
                        for s in sort_exprs), batch)
    layout = []
    for s, col in zip(sort_exprs, shapes):
        if isinstance(col, StringColumn):
            n_value_words = (col.chars.shape[1] + 7) // 8
        else:
            n_value_words = 1
        layout.append((1 + n_value_words, pad_word(s.ascending)))
    return layout


@program_cache("ops.sort.sort_with_words", maxsize=256)
def _sort_with_words_kernel(sort_exprs: tuple, in_schema: Schema,
                            capacity: int, donate: bool):
    """Sorted batch + its order-word matrix [capacity, W] — the words ride
    into the spill so the host k-way merge (memmgr.merge) compares exactly
    what the device sorted."""

    def auron_ops_sort_sort_with_words(batch: DeviceBatch):
        ctx = EvalContext()
        key_cols = [evaluate(s.expr, batch, in_schema, ctx).col
                    for s in sort_exprs]
        orders = [(s.ascending, s.nulls_first) for s in sort_exprs]
        perm = sort_permutation(batch, key_cols, orders)
        words = jnp.stack(sort_key_words(key_cols, orders), axis=1)
        return gather_batch(batch, perm, batch.num_rows), words[perm]

    # graft: donation-ok -- _sort_donate gate (owned batches only);
    # the k-way merge consumes each gathered run exactly once
    return programs.jit(auron_ops_sort_sort_with_words,
                        donate_argnums=(0,) if donate else ())


@program_cache("ops.sort.concat", maxsize=256)
def _concat_kernel(capacities: tuple, widths: tuple):
    """Buffered batches into ONE capacity-bucketed batch
    (``columnar/batch.concat_live_rows``): the live rows of each batch
    gathered to the front in batch order, the rest padding. The row
    counts are operands and their sum is computed there, so no count
    comes to the host; the key holds the input capacities and widths,
    never a row count."""
    total_cap = bucket_rows(sum(capacities))

    def auron_ops_sort_concat(batches: tuple):
        return concat_live_rows(batches, total_cap)

    return programs.jit(auron_ops_sort_concat)


def _concat_all(batches: list[DeviceBatch]) -> DeviceBatch:
    """Concatenate buffered batches into one capacity-bucketed batch:
    one launch of ``ops.sort.concat``, no readback (the result's row
    count stays on the device). How many batches a caller collected
    follows its data, so the arity is rounded up to a power of two with
    zero-row views of the smallest batch (its arrays, ``num_rows`` 0:
    rows past ``num_rows`` are dead by the batch contract) as far as
    that leaves the result in its capacity bucket — batches of one
    capacity, as an exchange's reducer is handed, then compile for as
    few shapes as the buckets the eager concatenation ended in."""
    batches = list(batches)
    small = min(batches, key=lambda b: b.capacity)
    stacked_cap = sum(b.capacity for b in batches)
    spare = (bucket_rows(stacked_cap) - stacked_cap) // small.capacity
    while len(batches) & (len(batches) - 1) and spare > 0:
        batches.append(DeviceBatch(small.columns, 0))
        spare -= 1
    widths = tuple(tuple(leaf.shape[1:] for leaf in
                         jax.tree_util.tree_leaves(b.columns))
                   for b in batches)
    kern = _concat_kernel(tuple(b.capacity for b in batches), widths)
    return kern(tuple(batches))


class _SortSpillConsumer(BufferedSpillConsumer):
    """Per-execution buffering state registered with the memory manager
    (the MemConsumer role SortExec plays in the reference,
    sort_exec.rs:375). A spill sorts the buffer into one run and writes it
    with its order words so the host k-way merge compares exactly what the
    device sorted."""

    def __init__(self, op: "SortOp", in_schema: Schema, mem_manager,
                 metrics, frame_rows: Optional[int] = None, conf=None):
        from auron_tpu import config as cfg
        conf = conf or cfg.get_config()
        self.op = op
        self.in_schema = in_schema
        super().__init__(f"sort-{id(op):x}", mem_manager, metrics, conf,
                         frame_rows=frame_rows)

    def _write_run(self, spill, batches: list[DeviceBatch]) -> None:
        import numpy as np
        from auron_tpu.columnar.serde import (batch_to_host,
                                              serialize_host_batch,
                                              slice_host_batch)
        from auron_tpu.memmgr.merge import (ORDER_WORDS_EXTRA,
                                            WORD_LAYOUT_EXTRA)
        merged = _concat_all(batches) if len(batches) > 1 else batches[0]
        # graft: disable=GL001 -- a host list of python ints
        layout = np.asarray(
            key_word_layout(self.op.sort_exprs, self.in_schema, merged),
            dtype=np.uint64)
        kern = _sort_with_words_kernel(self.op.sort_exprs, self.in_schema,
                                       merged.capacity,
                                       _sort_donate(batches, self.op.child))
        run, words = kern(merged)
        # the sort-collect spill's semantic sync point: this readback
        # carries the device wait (booked as device when a timer frame
        # is open, obs/profile.timed_get)
        n = int(_profile.timed_get(run.num_rows))
        host = batch_to_host(run, n)
        host_words = _profile.timed_get(words[:n])
        for lo in range(0, max(n, 1), self.frame_rows):
            hi = min(lo + self.frame_rows, n)
            spill.write_frame(serialize_host_batch(
                slice_host_batch(host, lo, hi),
                extras={ORDER_WORDS_EXTRA: host_words[lo:hi],
                        WORD_LAYOUT_EXTRA: layout},
                codec_level=self.codec_level))


class SortOp(PhysicalOp):
    name = "sort"

    def __init__(self, child: PhysicalOp, sort_exprs: list[ir.SortOrder],
                 fetch: Optional[int] = None):
        self.child = child
        self.sort_exprs = tuple(sort_exprs)
        self.fetch = fetch

    @property
    def children(self):
        return [self.child]

    def schema(self) -> Schema:
        return self.child.schema()

    def _limit(self, stream):
        remaining = self.fetch
        for out in stream:
            if remaining is None:
                yield out
                continue
            if remaining <= 0:
                return
            n = _profile.row_count(out)
            if n > remaining:
                out = DeviceBatch(out.columns, jnp.asarray(remaining, jnp.int32))
            remaining -= n
            yield out
            if remaining <= 0:
                return

    def execute(self, partition: int, ctx: ExecContext) -> Iterator[DeviceBatch]:
        metrics = ctx.metrics_for(self)
        elapsed = metrics.counter("elapsed_compute")
        in_schema = self.child.schema()
        mem = ctx.mem_manager
        spillable = mem is not None and getattr(mem, "spill_manager", None) is not None

        def in_mem_stream(batches):
            if not batches:
                return
            donate = _sort_donate(batches, self.child)
            with timer(elapsed) as t:
                merged = _concat_all(batches) if len(batches) > 1 else batches[0]
                kern = _sort_kernel(self.sort_exprs, in_schema,
                                    merged.capacity, donate)
                out = t.track(kern(merged))
            yield out

        def external_stream(consumer):
            """Runs on tiered storage + final host k-way merge."""
            from auron_tpu.columnar.serde import host_to_batch
            from auron_tpu.memmgr.merge import merge_sorted_runs
            if consumer.buffered:
                consumer.spill()   # final in-mem run joins the merge
            for host in merge_sorted_runs(
                    [s.frames() for s in consumer.spills]):
                # lifecycle poll per merged run batch: cancels land
                # mid-merge and the stall watchdog sees spill progress
                ctx.checkpoint("spill.merge")
                yield host_to_batch(host, bucket_rows(host.num_rows))

        def stream():
            if not spillable:
                collected = []
                for b in self.child.execute(partition, ctx):
                    ctx.checkpoint("sort.collect")   # cancel lands mid-collect too
                    collected.append(b)
                yield from self._limit(in_mem_stream(collected))
                return
            consumer = _SortSpillConsumer(self, in_schema, mem, metrics,
                                          conf=ctx.conf)
            try:
                for batch in self.child.execute(partition, ctx):
                    ctx.checkpoint("sort.collect")
                    consumer.add(batch)
                # claim the buffer FIRST (take_buffered) so a concurrent
                # victim spill can't serialize batches the in-mem sort
                # may have donated to XLA; wait out any in-flight spill
                # so the (buffer, spills) view below is consistent — an
                # unpublished run would otherwise vanish silently
                taken = consumer.take_buffered()
                consumer.wait_spills_published()
                if not consumer.spills:
                    yield from self._limit(in_mem_stream(taken))
                else:
                    # a victim spill raced in: hand the claimed batches
                    # back so external_stream's final spill includes them
                    for b in taken:
                        consumer.add(b)
                    yield from self._limit(external_stream(consumer))
            finally:
                consumer.close()

        return count_output(stream(), metrics)

    def __repr__(self):
        return f"SortOp[{len(self.sort_exprs)} keys, fetch={self.fetch}]"
