"""Output partitioners.

Spark-exact row→partition assignment (reference: datafusion-ext-plans/src/
shuffle/mod.rs:111-279): hash (murmur3 seed 42, pmod), round-robin, range
(binary search over sampled bounds), single. Producing the partition-id
column is a device kernel; what happens with it (host split vs ICI
all-to-all) is the exchange's business.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp

from auron_tpu.columnar.batch import DeviceBatch, StringColumn
from auron_tpu.columnar.schema import Schema
from auron_tpu.exprs import ir
from auron_tpu.exprs.eval import EvalContext, evaluate
from auron_tpu.obs import profile as _profile
from auron_tpu.ops import hashing


@dataclass(frozen=True)
class HashPartitioning:
    exprs: tuple
    num_partitions: int

    def partition_ids(self, batch: DeviceBatch, schema: Schema) -> jax.Array:
        ctx = EvalContext()
        cols = [evaluate(e, batch, schema, ctx).col for e in self.exprs]
        h = hashing.murmur3_columns(cols, batch.capacity,
                                    hashing.SPARK_SHUFFLE_SEED)
        n = jnp.int32(self.num_partitions)
        return ((h % n) + n) % n  # pmod: Spark keeps sign-safe modulo


@dataclass(frozen=True)
class RoundRobinPartitioning:
    num_partitions: int
    start: int = 0

    def partition_ids(self, batch: DeviceBatch, schema: Schema) -> jax.Array:
        idx = jnp.arange(batch.capacity, dtype=jnp.int32) + self.start
        return idx % jnp.int32(self.num_partitions)


@dataclass(frozen=True)
class SinglePartitioning:
    num_partitions: int = 1

    def partition_ids(self, batch: DeviceBatch, schema: Schema) -> jax.Array:
        return jnp.zeros(batch.capacity, jnp.int32)


@dataclass(frozen=True)
class RangePartitioning:
    """Range partitioning over sampled bounds. ``bounds`` is a host-side
    tuple of row tuples (one per boundary) computed by sampling the input —
    the reference samples on the JVM side too (reference:
    NativeShuffleExchangeBase.scala:313+)."""

    sort_orders: tuple     # tuple[ir.SortOrder]
    num_partitions: int
    bounds: tuple          # tuple of key tuples, len == num_partitions - 1

    def partition_ids(self, batch: DeviceBatch, schema: Schema) -> jax.Array:
        from auron_tpu.ops.sort import order_words
        ctx = EvalContext()
        cap = batch.capacity
        if not self.bounds:
            return jnp.zeros(cap, jnp.int32)

        # Normalize both rows and bounds into uint64 word tuples, then
        # lexicographic searchsorted implemented as vectorized compares
        # against each bound (num_partitions is small).
        row_words = []
        for so, key_idx in zip(self.sort_orders, range(len(self.sort_orders))):
            col = evaluate(so.expr, batch, schema, ctx).col
            null_word = jnp.where(col.validity,
                                  jnp.uint64(1 if so.nulls_first else 0),
                                  jnp.uint64(0 if so.nulls_first else 1))
            words = [jnp.where(col.validity, w, 0)
                     for w in order_words(col, so.ascending, so.nulls_first)]
            row_words.append(null_word)
            row_words.extend(words)

        pid = jnp.zeros(cap, jnp.int32)
        for bound in self.bounds:
            # bound is already normalized to matching uint64 words
            ge = jnp.zeros(cap, bool)
            eq = jnp.ones(cap, bool)
            for w, bw in zip(row_words, bound):
                bw = jnp.uint64(bw)
                ge = ge | (eq & (w > bw))
                eq = eq & (w == bw)
            pid = pid + (ge | eq).astype(jnp.int32)
        return jnp.minimum(pid, self.num_partitions - 1)


def compute_range_bounds(sample_batches, sort_orders, schema: Schema,
                         num_partitions: int) -> tuple:
    """Host-side bound computation from sampled batches: normalize sample
    keys to uint64 words, sort lexicographically, take evenly spaced
    boundaries. Returns tuple of word tuples aligned with
    RangePartitioning.partition_ids."""
    from auron_tpu.ops.sort import order_words
    ctx = EvalContext()
    rows = []
    for batch in sample_batches:
        words_dev = []
        for so in sort_orders:
            col = evaluate(so.expr, batch, schema, ctx).col
            null_word = jnp.where(col.validity,
                                  jnp.uint64(1 if so.nulls_first else 0),
                                  jnp.uint64(0 if so.nulls_first else 1))
            words = [jnp.where(col.validity, w, 0)
                     for w in order_words(col, so.ascending, so.nulls_first)]
            words_dev.append(null_word)
            words_dev.extend(words)
        # the sample's one readback: every key word of the batch at once
        words_cols = _profile.timed_get(words_dev)
        n = _profile.row_count(batch)
        mat = np.stack(words_cols, axis=1)[:n]  # [n, n_words]
        rows.append(mat)
    if not rows:
        return ()
    allrows = np.concatenate(rows, axis=0)
    if allrows.shape[0] == 0:
        return ()
    # lexicographic sort by word tuple
    order = np.lexsort(tuple(allrows[:, i] for i in range(allrows.shape[1] - 1, -1, -1)))
    allrows = allrows[order]
    n = allrows.shape[0]
    bounds = []
    for k in range(1, num_partitions):
        idx = min(n - 1, (k * n) // num_partitions)
        # graft: disable=GL001 -- allrows is the host matrix of the sample
        bounds.append(tuple(int(x) for x in allrows[idx]))
    # dedupe equal bounds (degenerate distributions)
    out = []
    for b in bounds:
        if not out or b != out[-1]:
            out.append(b)
    return tuple(out)
