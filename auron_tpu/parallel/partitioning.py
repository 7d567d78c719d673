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
from auron_tpu.runtime import programs
from auron_tpu.runtime.programs import program_cache


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


def _range_key_words(batch: DeviceBatch, schema: Schema,
                     sort_orders) -> list[list[jax.Array]]:
    """Traced: per sort key, its null-rank word then its value words
    (nulls neutralized to 0) — ops/sort.sort_key_words a key, so the
    words a range exchange compares are the words the sort below it
    sorts by."""
    from auron_tpu.ops.sort import sort_key_words
    ctx = EvalContext()
    return [sort_key_words([evaluate(so.expr, batch, schema, ctx).col],
                           [(so.ascending, so.nulls_first)])
            for so in sort_orders]


def _valid_marker(so) -> int:
    """The null-rank word of a row whose key is NOT null."""
    return 1 if so.nulls_first else 0


def range_partition_ids(batch: DeviceBatch, schema: Schema, sort_orders,
                        num_partitions: int, bounds, n_bounds,
                        bound_layout: tuple = ()) -> jax.Array:
    """Traced: a row's partition is the number of live bounds it is not
    below, rows and bounds compared word by word (lexicographic
    searchsorted, side right). ``bounds`` is ``uint64[B, W]`` — an
    operand of the split program, so neither a bound's value nor how
    many there are compiles anything — of which the first ``n_bounds``
    rows count. ``bound_layout`` gives the words a key of a BOUND has
    (null word included) where the sample's string keys may have landed
    in another width bucket than this batch's: the shorter side of a key
    is extended by the pad word, 0 where the key is null (a null's value
    words are all 0, whatever its batch's width). Empty = the batch's
    own layout."""
    from auron_tpu.ops.sort import pad_word
    cap = batch.capacity
    pid = jnp.zeros(cap, jnp.int32)
    if not sort_orders or 0 in bounds.shape:
        return pid
    per_key = _range_key_words(batch, schema, sort_orders)
    layout = tuple(bound_layout) or tuple(len(k) for k in per_key)
    for i in range(bounds.shape[0]):
        gt = jnp.zeros(cap, bool)
        eq = jnp.ones(cap, bool)
        col = 0
        for so, words, n_b in zip(sort_orders, per_key, layout):
            marker = jnp.uint64(_valid_marker(so))
            pad = jnp.uint64(pad_word(so.ascending))
            row_pad = jnp.where(words[0] == marker, pad, jnp.uint64(0))
            bound_pad = jnp.where(bounds[i, col] == marker, pad,
                                  jnp.uint64(0))
            for j in range(max(len(words), n_b)):
                w = words[j] if j < len(words) else row_pad
                bw = bounds[i, col + j] if j < n_b else bound_pad
                gt = gt | (eq & (w > bw))
                eq = eq & (w == bw)
            col += n_b
        pid = pid + ((gt | eq) & (i < n_bounds)).astype(jnp.int32)
    return jnp.minimum(pid, num_partitions - 1)


@dataclass(frozen=True)
class RangePartitioning:
    """Range partitioning over sampled bounds. ``bounds`` is a host-side
    tuple of row tuples (one per boundary) computed by sampling the input —
    the reference samples on the JVM side too (reference:
    NativeShuffleExchangeBase.scala:313+). ``bound_layout`` is the words
    a key of a bound has (``range_bounds`` fills it)."""

    sort_orders: tuple     # tuple[ir.SortOrder]
    num_partitions: int
    bounds: tuple          # tuple of key tuples, len <= num_partitions - 1
    bound_layout: tuple = ()

    def bounds_operand(self):
        """The bounds as the split program reads them: ``uint64
        [num_partitions - 1, W]`` on the host, zero rows past the live
        ones, and how many are live — a deduplicated bound changes the
        count, never the shape."""
        n = len(self.bounds)
        mat = np.zeros((max(self.num_partitions - 1, n),
                        len(self.bounds[0]) if n else 0), np.uint64)
        if n:
            mat[:n] = np.array(self.bounds, np.uint64)
        return mat, np.int32(n)

    def partition_ids(self, batch: DeviceBatch, schema: Schema,
                      bounds=None) -> jax.Array:
        """Traced inside the split program, which hands the bounds in
        as its operand ``(matrix, live count)``; without one they are
        this partitioning's own."""
        mat, n = bounds if bounds is not None else self.bounds_operand()
        return range_partition_ids(batch, schema, self.sort_orders,
                                   self.num_partitions, mat, n,
                                   self.bound_layout)


@program_cache("parallel.partitioning.range_sample", maxsize=256)
def _range_sample_kernel(sort_orders: tuple, schema: Schema, capacity: int):
    """The range sample of ONE batch: its ``[capacity, W]`` order-word
    matrix, the words a key has in this batch (a constant of the trace:
    a string key's width bucket) and the batch's row count, so that one
    readback brings all three."""

    def auron_parallel_partitioning_range_sample(batch: DeviceBatch):
        per_key = _range_key_words(batch, schema, sort_orders)
        flat = [w for k in per_key for w in k]
        words = jnp.stack(flat, axis=1) if flat \
            else jnp.zeros((batch.capacity, 0), jnp.uint64)
        layout = jnp.asarray([len(k) for k in per_key], jnp.int32)
        return words, layout, jnp.asarray(batch.num_rows, jnp.int32)

    return programs.jit(auron_parallel_partitioning_range_sample)


def sample_range_words(batch: DeviceBatch, sort_orders,
                       schema: Schema) -> tuple:
    """One program and the sample's ONE readback a batch: ``(the live
    rows' word matrix [n, W] on the host, the words a key)``."""
    kern = _range_sample_kernel(tuple(sort_orders), schema, batch.capacity)
    words, layout, n = _profile.timed_get(kern(batch))
    # graft: disable=GL001 -- host values: read by the timed_get above
    return np.asarray(words)[:int(n)], tuple(int(x) for x in layout)


def _align_words(mat: np.ndarray, layout: tuple, target: tuple,
                 sort_orders) -> np.ndarray:
    """``mat`` with every key widened to ``target`` words by its pad
    word (0 where the key is null) — what its batch would have read in
    the wider string width bucket."""
    from auron_tpu.ops.sort import pad_word
    if layout == target:
        return mat
    out, col = [], 0
    for so, have, want in zip(sort_orders, layout, target):
        out.append(mat[:, col:col + have])
        if want > have:
            pad = np.where(mat[:, col] == np.uint64(_valid_marker(so)),
                           np.uint64(pad_word(so.ascending)), np.uint64(0))
            out.append(np.repeat(pad[:, None], want - have, axis=1))
        col += have
    return np.concatenate(out, axis=1)


def range_bounds(samples, sort_orders, num_partitions: int) -> tuple:
    """Host-side bound choice from sampled word matrices
    (``sample_range_words``): align them to one layout, sort
    lexicographically, take evenly spaced boundaries, drop repeats.
    Returns ``(bounds, bound_layout)`` for ``RangePartitioning``."""
    samples = [(m, lay) for m, lay in samples if m.shape[0]]
    if not samples or not sort_orders:
        return (), ()
    target = tuple(max(lay[k] for _m, lay in samples)
                   for k in range(len(sort_orders)))
    allrows = np.concatenate(
        [_align_words(m, lay, target, sort_orders) for m, lay in samples],
        axis=0)
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
    return tuple(out), target
