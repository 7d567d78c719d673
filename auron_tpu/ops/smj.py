"""Sort-merge join: order-preserving streaming merge over sorted children.

The reference SMJ advances row cursors over two sorted streams (reference:
datafusion-ext-plans/src/sort_merge_join_exec.rs, joins/smj/stream_cursor.rs)
— a sequential pattern that doesn't vectorize. The TPU design keeps the
*streaming window* idea but replaces cursor advancement with vectorized
binary search:

  - every join key is normalized into order-preserving uint64 words (the
    same encoding the sort operator uses, ops/sort.py:order_words), so a
    multi-column key compares as a fixed-width word vector;
  - the right ("build") side is buffered in a sliding window that covers
    exactly the key range of the current left batch — batches ahead of the
    range stay unpulled, batches behind it are evicted as the left stream
    advances (the streaming bound the reference gets from its cursors);
  - each left batch binary-searches the window's word matrix for its
    match range (lo/hi per row, all lanes parallel), then expands ranges to
    (left_row, window_row) pairs in slot order — ascending left row, then
    ascending window row — so output order is exactly the children's sort
    order. Left-outer rows that match nothing emit one synthesized
    null-extended slot inline, preserving interleaved order.

Join types: inner / left / right / full / semi / anti / existence, with
"left" = the streaming probe side (reference: auron.proto JoinType).
Right/full track a per-window-row matched mask; unmatched window rows are
emitted (null-extended) when their batch slides out of the window, i.e. in
key order.

Memory: the window registers with the memory manager; under pressure it
offloads its device arrays to host DRAM (re-uploaded lazily at next probe)
— the analogue of the reference's build-side spill consumer
(join_hash_map.rs:365-387 + MemConsumer).
"""

from __future__ import annotations

from typing import Iterator, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from auron_tpu.columnar.batch import (DeviceBatch, PrimitiveColumn,
                                      StringColumn, batch_nbytes, compact,
                                      gather_batch, gather_column)
from auron_tpu.columnar.schema import DataType, Field, Schema
from auron_tpu.exprs import ir
from auron_tpu.exprs.eval import EvalContext, evaluate
from auron_tpu.obs import profile as _profile
from auron_tpu.ops.base import ExecContext, PhysicalOp, count_output, timer
from auron_tpu.ops.sort import _concat_all, sort_key_words
from auron_tpu.utils.shapes import bucket_rows
from auron_tpu.runtime.programs import program_cache

__all__ = ["SortMergeJoinOp"]


# ---------------------------------------------------------------------------
# key words
# ---------------------------------------------------------------------------

@program_cache("ops.smj.key_words", maxsize=256)
def _key_words_kernel(key_exprs: tuple, in_schema: Schema, capacity: int):
    """Per-key order-word matrices [capacity, nw_k] (null word included, so
    word order == the child's (asc, nulls_first) sort order) + a per-row
    "never matches" mask (null key or dead row)."""

    @jax.jit
    def auron_ops_smj_key_words(batch: DeviceBatch):
        ctx = EvalContext()
        cols = [evaluate(e, batch, in_schema, ctx).col for e in key_exprs]
        dead = ~batch.row_mask()
        per_key = []
        for c in cols:
            words = sort_key_words([c], [(True, True)])
            per_key.append(jnp.stack(words, axis=1))
            dead = dead | ~c.validity
        return tuple(per_key), dead

    return auron_ops_smj_key_words


def _pad_and_join(per_key, widths: tuple[int, ...]) -> jax.Array:
    """Zero-pad each key's word matrix to the target width and hstack.
    Zero is exactly the word the encoder emits for missing trailing string
    bytes at a wider bucket (ascending keys), so padding is order-exact."""
    parts = []
    for w, t in zip(per_key, widths):
        if w.shape[1] < t:
            w = jnp.pad(w, ((0, 0), (0, t - w.shape[1])))
        parts.append(w)
    return jnp.concatenate(parts, axis=1) if len(parts) > 1 else parts[0]


def _host_row(per_key, row: int) -> tuple[np.ndarray, ...]:
    """One row's key words per key, on host (for window advance/evict
    decisions)."""
    return tuple(_profile.timed_get([w[row] for w in per_key]))


def _host_lex_le(a: tuple[np.ndarray, ...], b: tuple[np.ndarray, ...]) -> bool:
    """a <= b under the padded word order."""
    for aw, bw in zip(a, b):
        t = max(aw.shape[0], bw.shape[0])
        ap = np.zeros(t, np.uint64); ap[:aw.shape[0]] = aw
        bp = np.zeros(t, np.uint64); bp[:bw.shape[0]] = bw
        for x, y in zip(ap.tolist(), bp.tolist()):
            if x < y:
                return True
            if x > y:
                return False
    return True  # equal


# ---------------------------------------------------------------------------
# device kernels
# ---------------------------------------------------------------------------

@program_cache("ops.smj.probe", maxsize=256)
def _probe_kernel(n_words: int, win_cap: int, cap: int, left_outer: bool):
    """Vectorized lexicographic binary search of every left row's key into
    the window's sorted word matrix. Returns per-left-row lower bound,
    match count, emit count (left-outer adds a synthesized slot for
    matchless live rows) and total emit."""
    steps = max(win_cap, 1).bit_length() + 1

    @jax.jit
    def auron_ops_smj_probe(win_words, win_n, q_words, q_dead, live_n):
        def lex(mid):
            lt = jnp.zeros(cap, bool)
            eq = jnp.ones(cap, bool)
            for w in range(n_words):
                aw = win_words[mid, w]
                qw = q_words[:, w]
                lt = lt | (eq & (aw < qw))
                eq = eq & (aw == qw)
            return lt, lt | eq

        def search(le_mode):
            lo = jnp.zeros(cap, jnp.int32)
            hi = jnp.full(cap, win_n, jnp.int32)

            def body(_, carry):
                lo, hi = carry
                mid = (lo + hi) // 2
                lt, le = lex(mid)
                go = le if le_mode else lt
                active = lo < hi
                lo2 = jnp.where(active & go, mid + 1, lo)
                hi2 = jnp.where(active & ~go, mid, hi)
                return lo2, hi2

            lo, hi = lax.fori_loop(0, steps, body, (lo, hi))
            return lo

        lo = search(False)
        hi = search(True)
        counts = jnp.where(q_dead, 0, hi - lo)
        live = jnp.arange(cap, dtype=jnp.int32) < live_n
        if left_outer:
            emit = jnp.where(live, jnp.maximum(counts, 1), 0)
        else:
            emit = counts
        return lo, counts, emit, jnp.sum(emit)

    return auron_ops_smj_probe


@program_cache("ops.smj.expand", maxsize=256)
def _expand_kernel(out_cap: int, cap: int):
    """Expand per-left-row emit ranges into slot-ordered
    (left_idx, window_idx, is_real_match) triples. Slot order = ascending
    left row, then ascending window row: the order-preservation invariant."""

    @jax.jit
    def auron_ops_smj_expand(lo, counts, emit):
        starts = jnp.cumsum(emit) - emit
        total = jnp.sum(emit)
        slots = jnp.arange(out_cap, dtype=jnp.int32)
        left_idx = jnp.clip(
            jnp.searchsorted(starts, slots, side="right").astype(jnp.int32) - 1,
            0, cap - 1)
        offset = slots - starts[left_idx]
        in_range = slots < total
        real = in_range & (offset < counts[left_idx])
        win_idx = jnp.where(real, lo[left_idx] + offset, 0)
        return left_idx, win_idx, real, total

    return auron_ops_smj_expand


def _gather_pairs(left: DeviceBatch, win: Optional[DeviceBatch], left_idx,
                  win_idx, real, total) -> DeviceBatch:
    ones = jnp.ones_like(real)
    lcols = tuple(gather_column(c, left_idx, ones) for c in left.columns)
    if win is None:
        return DeviceBatch(lcols, total)
    rcols = tuple(gather_column(c, win_idx, real) for c in win.columns)
    return DeviceBatch(lcols + rcols, total)


# ---------------------------------------------------------------------------
# sliding window over the right stream
# ---------------------------------------------------------------------------

class _MergeWindow:
    """Buffered suffix of the right stream covering the live key range.

    Device state (concatenated batch + word matrix) is rebuilt lazily when
    batches are appended/evicted and can be offloaded to host DRAM by the
    memory manager (the MemConsumer role)."""

    consumer_name = "smj-window"

    def __init__(self, key_exprs, schema: Schema, mem, metrics):
        self.key_exprs = key_exprs
        self.schema = schema
        self.mem = mem
        self.metrics = metrics
        #: (batch, per-key word matrices) pairs not yet merged in
        self.pending: list[tuple[DeviceBatch, tuple]] = []
        self.batch: Optional[DeviceBatch] = None     # live-prefix concat
        self.per_key: Optional[tuple] = None          # per-key word matrices
        self.n = 0                                    # live rows
        self.matched: Optional[np.ndarray] = None     # host bool [cap]
        self._host_batch = None                       # offloaded form
        self._bytes = 0
        self._pinned = False
        if mem is not None:
            mem.register_consumer(self)
            self.consumer_name = f"smj-window-{id(self):x}"

    # -- MemConsumer --------------------------------------------------------
    def mem_used(self) -> int:
        return self._bytes

    def pin(self) -> None:
        """Block offload while a probe is reading the device state (the
        refuse-while-merging protocol, same as ops/agg.py's merge guard)."""
        self._pinned = True

    def unpin(self) -> None:
        self._pinned = False

    def spill(self) -> int:
        """Offload device state to host DRAM; next probe re-uploads."""
        if self._pinned or self.batch is None or self._host_batch is not None:
            return 0
        from auron_tpu.columnar.serde import batch_to_host
        freed = self._bytes
        self._host_batch = batch_to_host(self.batch, self.n)
        self.batch = None
        self.per_key = None
        self._bytes = 0
        self.metrics.counter("mem_spill_count").add(1)
        self.metrics.counter("mem_spill_size").add(freed)
        return freed

    # -- window ops ---------------------------------------------------------
    def append(self, batch: DeviceBatch, per_key: tuple) -> None:
        """Queue a right batch with its already-computed key words (the pull
        loop encodes them anyway to read the batch's max key — reusing them
        keeps window maintenance O(total rows), not O(rows × appends)."""
        self.pending.append((batch, per_key))

    def _account(self):
        self._bytes = batch_nbytes(self.batch) if self.batch is not None else 0
        if self.per_key is not None:
            self._bytes += sum(w.size * 8 for w in self.per_key)
        if self.mem is not None:
            self.mem.update_mem_used(self, self._bytes)

    def ensure_built(self) -> None:
        """Materialize device state from pending appends / host offload."""
        parts: list[tuple[DeviceBatch, Optional[tuple], int]] = []
        old_n = self.n
        if self._host_batch is not None:
            from auron_tpu.columnar.serde import host_to_batch
            b = host_to_batch(self._host_batch,
                              bucket_rows(max(self._host_batch.num_rows, 1)))
            parts.append((b, None, _profile.row_count(b)))
            self._host_batch = None
        elif self.batch is not None:
            parts.append((self.batch, self.per_key, self.n))
        for b, pk in self.pending:
            parts.append((b, pk, _profile.row_count(b)))
        self.pending = []
        if not parts:
            return
        if len(parts) == 1 and parts[0][0] is self.batch \
                and self.per_key is not None:
            return  # unchanged
        batches = [p[0] for p in parts]
        merged = _concat_all(batches) if len(batches) > 1 else batches[0]
        self.batch = merged
        self.n = _profile.row_count(merged)
        cap = merged.capacity
        if any(pk is None for _b, pk, _n in parts):
            # reload after host offload: words must be re-encoded
            kern = _key_words_kernel(self.key_exprs, self.schema, cap)
            self.per_key, _ = kern(merged)
        else:
            # splice the per-batch word matrices (live prefixes, widths
            # zero-padded to the window-wide max — order-exact)
            spliced = []
            for ki in range(len(parts[0][1])):
                ws = [pk[ki][:n] for _b, pk, n in parts]
                tw = max(w.shape[1] for w in ws)
                ws = [jnp.pad(w, ((0, 0), (0, tw - w.shape[1])))
                      if w.shape[1] < tw else w for w in ws]
                w = jnp.concatenate(ws, axis=0) if len(ws) > 1 else ws[0]
                if w.shape[0] < cap:
                    w = jnp.pad(w, ((0, cap - w.shape[0]), (0, 0)))
                spliced.append(w)
            self.per_key = tuple(spliced)
        m = np.zeros(cap, bool)
        if self.matched is not None and old_n:
            m[:old_n] = self.matched[:old_n]
        self.matched = m
        self._account()

    def word_widths(self) -> tuple[int, ...]:
        return tuple(w.shape[1] for w in self.per_key)

    def words(self, widths: tuple[int, ...]) -> jax.Array:
        return _pad_and_join(self.per_key, widths)

    def evict_below(self, k: int,
                    want_unmatched: bool = True) -> Optional[DeviceBatch]:
        """Drop the first ``k`` window rows; when ``want_unmatched`` (the
        right/full tracking path) also returns the compacted unmatched
        prefix for null-extension — skipped for join types that discard
        it (one device compact saved per left batch)."""
        if k <= 0 or self.batch is None:
            return None
        k = min(k, self.n)
        cap = self.batch.capacity
        idxs = jnp.arange(cap, dtype=jnp.int32)
        unmatched = None
        if want_unmatched:
            keep_mask = (idxs < k) & (idxs < self.n) & \
                ~jnp.asarray(self.matched[:cap])
            unmatched = compact(self.batch, keep_mask)
        shift = jnp.clip(idxs + k, 0, cap - 1)
        self.batch = gather_batch(self.batch, shift,
                                  jnp.asarray(self.n - k, jnp.int32))
        self.per_key = tuple(w[shift] for w in self.per_key)
        self.matched = np.concatenate(
            [self.matched[k:], np.zeros(k, bool)])
        self.n -= k
        self._account()
        if unmatched is not None and _profile.row_count(unmatched) == 0:
            unmatched = None
        return unmatched

    def unmatched_rest(self) -> Optional[DeviceBatch]:
        if self.batch is None or self.n == 0:
            return None
        cap = self.batch.capacity
        keep = self.batch.row_mask() & ~jnp.asarray(self.matched[:cap])
        out = compact(self.batch, keep)
        return out if _profile.row_count(out) > 0 else None

    def mark_matched(self, matched_dev) -> None:
        self.matched |= _profile.timed_get(matched_dev)

    def close(self) -> None:
        if self.mem is not None:
            self.mem.unregister_consumer(self)


@program_cache("ops.smj.mark", maxsize=256)
def _mark_kernel(win_cap: int):
    """Matched window rows = union of the per-left-row match intervals
    [lo, lo+count): one +1/-1 scatter and a prefix sum — O(win_cap), no
    pair expansion."""

    @jax.jit
    def auron_ops_smj_mark(lo, counts):
        has = counts > 0
        starts = jnp.where(has, lo, win_cap)
        ends = jnp.where(has, lo + counts, win_cap)
        delta = jnp.zeros(win_cap + 1, jnp.int32)
        delta = delta.at[starts].add(1, mode="drop")
        delta = delta.at[ends].add(-1, mode="drop")
        return jnp.cumsum(delta[:win_cap]) > 0

    return auron_ops_smj_mark


# ---------------------------------------------------------------------------
# the operator
# ---------------------------------------------------------------------------

class SortMergeJoinOp(PhysicalOp):
    """Order-preserving merge join; children must be sorted ascending
    (nulls first) on the join keys — the contract the planner establishes,
    as Spark's EnsureRequirements does for the reference
    (sort_merge_join_exec.rs)."""

    name = "sort_merge_join"

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
            self._schema = Schema(tuple(ps.fields) +
                                  (Field("exists", DataType.BOOL, False),))
        else:
            self._schema = Schema(tuple(ps.fields) + tuple(bs.fields))

    @property
    def children(self):
        return [self.probe, self.build]

    def schema(self) -> Schema:
        return self._schema

    # -- execution ----------------------------------------------------------
    def execute(self, partition: int, ctx: ExecContext) -> Iterator[DeviceBatch]:
        metrics = ctx.metrics_for(self)
        elapsed = metrics.counter("elapsed_compute")
        left_schema = self.probe.schema()
        right_schema = self.build.schema()
        jt = self.join_type
        track = jt in ("right", "full")
        left_outer = jt in ("left", "full")

        def null_extended_right(rows: DeviceBatch) -> DeviceBatch:
            cap = rows.capacity
            null_left = tuple(_null_column(f, cap) for f in left_schema)
            return DeviceBatch(null_left + rows.columns, rows.num_rows)


        def stream():
            right_iter = self.build.execute(partition, ctx)
            win = _MergeWindow(self.build_keys, right_schema,
                               ctx.mem_manager, metrics)
            right_done = False
            last_right_max = None
            try:
                for left in self.probe.execute(partition, ctx):
                    nL = _profile.row_count(left)
                    if nL == 0:
                        continue
                    kern = _key_words_kernel(self.probe_keys, left_schema,
                                             left.capacity)
                    with timer(elapsed) as t:
                        q_per_key, q_dead = t.track(kern(left))
                    lmax = _host_row(q_per_key, nL - 1)
                    # pull right batches until the window covers lmax
                    while not right_done and (
                            last_right_max is None
                            or _host_lex_le(last_right_max, lmax)):
                        rb = next(right_iter, None)
                        if rb is None:
                            right_done = True
                            break
                        nR = _profile.row_count(rb)
                        if nR == 0:
                            continue
                        rkern = _key_words_kernel(self.build_keys,
                                                  right_schema, rb.capacity)
                        with timer(elapsed) as t:
                            r_per_key, _ = t.track(rkern(rb))
                        last_right_max = _host_row(r_per_key, nR - 1)
                        win.append(rb, r_per_key)
                    win.pin()
                    try:
                        win.ensure_built()
                        for out in self._probe_one(left, nL, q_per_key,
                                                   q_dead, win, elapsed,
                                                   track, left_outer,
                                                   null_extended_right):
                            yield out
                    finally:
                        win.unpin()
                # tail: flush unmatched window + remaining right stream
                if track:
                    win.pin()
                    try:
                        win.ensure_built()
                        rest = win.unmatched_rest()
                    finally:
                        win.unpin()
                    if rest is not None:
                        yield null_extended_right(rest)
                    for rb in right_iter:
                        if _profile.row_count(rb) > 0:
                            yield null_extended_right(rb)
            finally:
                win.close()

        return count_output(stream(), metrics)

    def _probe_one(self, left: DeviceBatch, nL: int, q_per_key, q_dead,
                   win: _MergeWindow, elapsed, track: bool, left_outer: bool,
                   null_extended_right):
        jt = self.join_type
        cap = left.capacity

        if win.batch is None or win.n == 0:
            # empty window: no matches possible for this batch
            yield from self._emit_no_window(left, cap)
            return

        widths = tuple(
            max(a, b) for a, b in zip(
                tuple(w.shape[1] for w in q_per_key), win.word_widths()))
        # per-key word-count mismatch across sides can only differ on
        # string keys; unify by zero-padding (order-exact)
        win_words = win.words(widths)
        q_words = _pad_and_join(q_per_key, widths)
        win_cap = win.batch.capacity

        pkern = _probe_kernel(win_words.shape[1], win_cap, cap, left_outer)
        with timer(elapsed) as t:
            lo, counts, emit, total = t.track(pkern(win_words, win.n, q_words,
                                                    q_dead, left.num_rows))
        total_i = int(_profile.timed_get(total))

        if jt in ("semi", "anti", "existence"):
            has = counts > 0
            with timer(elapsed) as t:
                if jt == "semi":
                    out = compact(left, has)
                elif jt == "anti":
                    out = compact(left, left.row_mask() & ~has)
                else:
                    col = PrimitiveColumn(has, jnp.ones(cap, bool))
                    out = DeviceBatch(left.columns + (col,), left.num_rows)
                t.track(out)
            if _profile.row_count(out) > 0 or jt == "existence":
                yield out
        elif total_i > 0:
            out_cap = bucket_rows(total_i)
            expand = _expand_kernel(out_cap, cap)
            with timer(elapsed) as t:
                left_idx, win_idx, real, tot = expand(lo, counts, emit)
                out = t.track(_gather_pairs(left, win.batch, left_idx,
                                            win_idx, real, tot))
            if track:
                mark = _mark_kernel(win_cap)
                with timer(elapsed):
                    win.mark_matched(mark(lo, counts))
            yield out

        # advance: window rows strictly below this batch's max key can
        # never match future (ascending) left rows
        k = int(_profile.timed_get(lo[nL - 1]))
        evicted = win.evict_below(k, want_unmatched=track)
        if track and evicted is not None:
            yield null_extended_right(evicted)

    def _emit_no_window(self, left: DeviceBatch, cap: int):
        jt = self.join_type
        if jt == "anti":
            yield left
        elif jt == "semi":
            yield DeviceBatch(left.columns, jnp.asarray(0, jnp.int32))
        elif jt == "existence":
            col = PrimitiveColumn(jnp.zeros(cap, bool), jnp.ones(cap, bool))
            yield DeviceBatch(left.columns + (col,), left.num_rows)
        elif jt in ("left", "full"):
            null_right = tuple(_null_column(f, cap)
                               for f in self.build.schema())
            yield DeviceBatch(left.columns + null_right, left.num_rows)
        # inner/right: nothing

    def __repr__(self):
        return (f"SortMergeJoinOp[{self.join_type}, "
                f"{len(self.probe_keys)} keys]")


def _null_column(field: Field, cap: int):
    from auron_tpu.exprs.eval import null_column_for_field
    return null_column_for_field(field, cap)
