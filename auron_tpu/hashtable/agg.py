"""Hash-table-backed group state for the general aggregation path.

``HashAggState`` is the open-addressing replacement for the sort path's
incremental (batch-sort + searchsorted-merge) state: every batch runs ONE
fused program — the caller's evaluation of the batch's group keys and
contributions (its ``front``, traced here), hash keys, insert (vectorized
probe rounds), scatter the contributions into the owning slots — and the
O(S) state pass disappears entirely (the table IS the state; nothing
re-sorts per batch). The table's set-up rides its first step (the
``fresh`` form of ``hashtable.agg_step`` builds the empty table inside the
program); nothing here touches a device array outside a program of
runtime/programs.py. This is the reference AggTable's update loop
(datafusion-ext-plans/src/agg/agg_table.rs:68-356) with the row-at-a-time
probe replaced by ``hashtable.core``'s lock-step rounds.

Growth keeps the ``auron.agg.initial_capacity`` power-of-two re-bucketing
discipline: when an insert overflows its probe-round budget or occupancy
crosses ``auron.hashtable.load_factor``, the table doubles and re-inserts
itself (one program; keys re-place positionally, accumulators follow
their slots). Pathological repeat overflow — adversarial hash collisions,
not load — raises ``HashTableOverflow``, which the operator catches to
fall back to the sort path mid-stream without losing state.

``export_slots`` hands the slots on as the agg path's canonical
hash-sorted ``(keys, accs, num_groups, hashes)`` — occupied slots sorted
by hash ascending, dead slots carrying the shared sentinel last — so
emit, spill (``memmgr`` bucket spills rely on the hash-sorted run
invariant), and the partial-skip decision reuse the existing machinery
unchanged, and hash-vs-sort results stay bit-identical down to group
output order: traced inside the operator's emit program, or as the
program of its own behind ``to_sorted_table()``.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from auron_tpu.hashtable import core
from auron_tpu.obs import profile as _profile
from auron_tpu.obs import trace as _trace
from auron_tpu.runtime.programs import program_cache
from auron_tpu.utils.shapes import next_pow2

#: absolute slot-capacity ceiling: growth genuinely fixes load-bound and
#: tail-bound overflow (doubling halves chain lengths), so only
#: collision-pathological inputs keep overflowing — they hit this wall
#: and fall back to the sort path
_MAX_CAPACITY = 1 << 26


class HashTableOverflow(Exception):
    """Insert could not place every key within the probe-round budget at
    any sane capacity; the caller falls back to the sort path."""


def _hashes(keys, cap: int) -> jax.Array:
    from auron_tpu.ops import hashing
    h = hashing.xxhash64_columns(list(keys), cap).view(jnp.uint64)
    return core.remap_hashes(h)


@program_cache("hashtable.agg_step", maxsize=256)
def _agg_step_kernel(front, layout: tuple, n: int, table_meta: tuple,
                     key_meta: tuple, acc_meta: tuple, cap: int,
                     rounds: int):
    """One fused program per (front, input layout and capacity, key
    codec, acc layout, table shape): the front's evaluation of the
    batch's group keys and contributions, hash + insert + store winners
    + scatter accumulator contributions. ``front`` is plan data — a
    hashable traceable ``front(*operands) -> (keys, contribs, live)``;
    ``layout`` stands for its operands' shapes. ``table_meta`` is the
    key codec the table comes in with, ``key_meta`` the one it leaves
    with: a batch whose strings are wider than the store widens it here,
    a narrower batch is padded here. ``table_meta`` None is the FRESH
    form, an operator's first step: no table comes in — the empty one
    (hashes, equality words, key store, every accumulator at its neutral)
    is built here, at ``key_meta`` and ``cap``, so a table's set-up is no
    launch of its own. The program takes ``(table, ord_base,
    *operands)``: ``table`` is ``(th, tw, store, accs, auxs)``, or ``()``
    in the fresh form."""
    fresh = table_meta is None
    widen = () if fresh else core.string_width_drift(key_meta, table_meta)

    @jax.jit
    def auron_hashtable_agg_step(table, ord_base, *operands):
        if fresh:
            accs, auxs = core.init_accs(acc_meta, cap)
            th = jnp.full(cap, core.EMPTY, jnp.uint64)
            tw = jnp.zeros((cap, core.total_words(key_meta)), jnp.uint64)
            store = core.empty_store(key_meta, cap)
        else:
            th, tw, store, accs, auxs = table
        keys, contribs, live = front(*operands)
        if widen:
            tw, store, _meta = core.widen_string_store(tw, store,
                                                       table_meta, widen)
        keys = _pad_string_keys(keys, key_meta)
        h = _hashes(keys, n)
        w = core.key_words(keys, key_meta)
        claims, slot, resolved = core.insert_loop(th, tw, h, w, live,
                                                  rounds)
        th2, tw2 = core.table_install(th, tw, h, w, claims)
        store2 = core.store_install(store, keys, key_meta, claims)
        accs2, auxs2 = core.agg_update(accs, auxs, acc_meta, slot,
                                       resolved, tuple(contribs), ord_base)
        n_new = jnp.sum(core.batch_owned(claims).astype(jnp.int32))
        overflow = jnp.any(live & ~resolved)
        return th2, tw2, store2, accs2, auxs2, n_new, overflow

    return auron_hashtable_agg_step


@program_cache("hashtable.agg_grow", maxsize=64)
def _grow_kernel(key_meta: tuple, acc_meta: tuple, old_cap: int,
                 new_cap: int, rounds: int):
    """Re-bucket: re-insert every occupied slot into an empty table of
    ``new_cap`` (stored hashes reused; equality words recomputed from the
    stored original values) and move accumulators to their new slots."""
    W = core.total_words(key_meta)

    @jax.jit
    def auron_hashtable_agg_grow(th, store, accs, auxs):
        occupied = th != core.EMPTY
        cols = core.store_columns(store, key_meta)
        w = core.key_words(cols, key_meta)
        nth = jnp.full(new_cap, core.EMPTY, jnp.uint64)
        ntw = jnp.zeros((new_cap, W), jnp.uint64)
        claims, _slot, resolved = core.insert_loop(nth, ntw, th, w,
                                                   occupied, rounds)
        nth, ntw = core.table_install(nth, ntw, th, w, claims)
        nstore = core.store_install(
            core.empty_store(key_meta, new_cap), cols, key_meta, claims)
        # accumulators follow their keys: each batch-won new slot gathers
        # the old slot's acc through claims (claims[new] = old slot id)
        won = core.batch_owned(claims)
        cw = jnp.clip(claims, 0, old_cap - 1)
        naccs, nauxs = [], []
        for (kind, dt), acc, aux in zip(acc_meta, accs, auxs):
            neutral = core.neutral_like(kind, jnp.dtype(dt))
            naccs.append(jnp.where(won, acc[cw], neutral))
            nauxs.append(jnp.where(won, aux[cw], core.ORD_NONE)
                         if kind == "first" else None)
        return (nth, ntw, nstore, tuple(naccs), tuple(nauxs),
                jnp.any(occupied & ~resolved))

    return auron_hashtable_agg_grow


def export_slots(th, store, accs, key_meta: tuple):
    """Slots -> the hash-sorted group-table layout (dead slots last under
    the shared sentinel), traced: the handoff that keeps emit / spill /
    merge invariants — and output group order — identical to the sort
    path. Returns (key columns, accs, num_groups, hashes)."""
    from auron_tpu.columnar.batch import gather_column
    cap = th.shape[0]
    occupied = th != core.EMPTY
    ng = jnp.sum(occupied.astype(jnp.int32))
    perm = jnp.argsort(th, stable=True)     # EMPTY is max: dead last
    out_valid = jnp.arange(cap, dtype=jnp.int32) < ng
    cols = tuple(gather_column(c, perm, out_valid)
                 for c in core.store_columns(store, key_meta))
    return cols, tuple(a[perm] for a in accs), ng, th[perm]


@program_cache("hashtable.agg_export", maxsize=64)
def _export_kernel(key_meta: tuple, acc_meta: tuple, cap: int):
    """``export_slots`` as a program of its own: the table handed on as a
    sorted STATE (the overflow fall-back's salvage, the partial-skip
    decision). An operator's emit folds the export into
    ``ops.agg.emit``."""

    @jax.jit
    def auron_hashtable_agg_export(th, store, accs):
        return export_slots(th, store, accs, key_meta)

    return auron_hashtable_agg_export


def _pad_string_keys(keys, target_meta: tuple):
    """Pad narrower batch string columns up to the store's width bucket
    (zero padding keeps words and hashes unchanged)."""
    from auron_tpu.columnar.batch import StringColumn
    out = []
    for c, m in zip(keys, target_meta):
        if m[0] == "str" and c.width < m[1]:
            c = StringColumn(
                jnp.pad(c.chars, ((0, 0), (0, m[1] - c.width))),
                c.lens, c.validity)
        out.append(c)
    return tuple(out)


class HashAggState:
    """Mutable per-execution group state: the device table + slot-indexed
    accumulators, with host-driven growth. ``kinds`` is the flat
    device-reduce-kind list (ops/agg._device_kinds order)."""

    def __init__(self, kinds, initial_capacity: int = 4096,
                 load_factor: float = 0.5, max_probe_rounds: int = 64):
        self.kinds = tuple(kinds)
        self.cap = max(16, next_pow2(initial_capacity))
        self.load_factor = float(load_factor)
        self.rounds = int(max_probe_rounds)
        self.count = 0          # occupied slots (host mirror)
        self.rows_seen = 0      # global row ordinal base for 'first'
        self.key_meta = None    # set by the first step that commits
        self.acc_meta = None
        self.th = self.tw = self.store = self.accs = self.auxs = None

    # -- sizing --------------------------------------------------------------

    @property
    def built(self) -> bool:
        return self.key_meta is not None

    def nbytes(self) -> int:
        if not self.built:
            return 0
        total = self.th.nbytes + self.tw.nbytes
        for s in self.store:
            total += sum(a.nbytes for a in s)
        total += sum(a.nbytes for a in self.accs)
        total += sum(a.nbytes for a in self.auxs if a is not None)
        return total

    # -- state transitions ---------------------------------------------------

    def _grow(self) -> None:
        new_cap = self.cap * 2
        while True:
            if new_cap > _MAX_CAPACITY:
                raise HashTableOverflow(
                    f"hash table stuck at {self.count} keys despite "
                    f"capacity {new_cap} (probe rounds {self.rounds})")
            # a first step that overflowed committed nothing: there is
            # nothing to re-bucket, the retry builds its empty table at
            # the doubled capacity
            if self.built:
                kern = _grow_kernel(self.key_meta, self.acc_meta, self.cap,
                                    new_cap, self.rounds)
                nth, ntw, nstore, naccs, nauxs, ovf = kern(
                    self.th, self.store, self.accs, self.auxs)
                if bool(_profile.timed_get(ovf)):
                    new_cap *= 2
                    continue
                self.th, self.tw, self.store = nth, ntw, nstore
                self.accs, self.auxs = naccs, nauxs
            # one a doubling: a re-bucket that overflowed doubled again
            _trace.count("agg_state_grows",
                         (new_cap // self.cap).bit_length() - 1)
            self.cap = new_cap
            return

    def update(self, front, operands: tuple, layout: tuple,
               shapes) -> None:
        """Fold one batch into the table: ``front(*operands)`` — the
        group-key columns, the per-row accumulator contributions and the
        live mask — is evaluated INSIDE the step's program; ``shapes``
        is that triple in the abstract (what the host needs of it: the
        key codec, the contributions' dtypes, the batch's capacity) and
        ``layout`` the operands' part of the program's key. One fused
        program plus one batched scalar readback — the same per-batch
        host-RTT budget as the sort path's group-count readback."""
        keys, contribs, live = shapes
        batch_meta = core.key_meta(keys)
        if self.acc_meta is None:
            self.acc_meta = tuple(
                (kind, str(np.dtype(v.dtype)))
                for kind, v in zip(self.kinds, contribs))
        # reconcile per-batch string width buckets with the store's: a
        # wider batch widens the store, a narrower one is padded — both
        # inside the step. Until a step has committed there is no table:
        # the step is the fresh form, which builds it at the batch's codec
        key_meta = (core.widest_meta(batch_meta, self.key_meta)
                    if self.built else batch_meta)
        n = int(live.shape[0])
        ord_base = np.int64(self.rows_seen)
        while True:
            kern = _agg_step_kernel(front, layout, n, self.key_meta,
                                    key_meta, self.acc_meta, self.cap,
                                    self.rounds)
            # read afresh each attempt: a retry steps into the table
            # ``_grow`` re-bucketed, not the one that overflowed
            table = ((self.th, self.tw, self.store, self.accs, self.auxs)
                     if self.built else ())
            th, tw, store, accs, auxs, n_new, overflow = kern(
                table, ord_base, *operands)
            # this readback is the per-batch sync point (the wait is
            # attributed as device time). NOTE the donation
            # sweep deliberately skips the step/grow kernels: the
            # overflow-retry protocol re-runs them with the SAME state
            # and batch inputs, which donation would have invalidated.
            n_new_h, ovf = _profile.timed_get([n_new, overflow])
            if not bool(ovf):
                self.th, self.tw, self.store = th, tw, store
                self.accs, self.auxs = accs, auxs
                self.key_meta = key_meta
                self.count += int(n_new_h)
                self.rows_seen += n
                _trace.count("agg_hash_batches")
                if self.count > self.load_factor * self.cap:
                    try:
                        self._grow()
                    except HashTableOverflow:
                        # the batch is already committed — raising here
                        # would double-count it when the caller falls
                        # back and re-merges. Results stay correct at
                        # high load; a later insert that genuinely
                        # cannot place surfaces the overflow PRE-commit.
                        pass
                return
            # round budget exhausted: discard this attempt (the committed
            # state is untouched), re-bucket, retry the whole batch
            self._grow()

    def to_sorted_table(self):
        """The canonical hash-sorted 5-tuple (keys, accs, num_groups,
        cap, hashes) — or None when nothing was ever inserted."""
        if not self.built:
            return None
        kern = _export_kernel(self.key_meta, self.acc_meta, self.cap)
        cols, accs, ng, h = kern(self.th, self.store, self.accs)
        return (cols, accs, ng, self.cap, h)


# ---------------------------------------------------------------------------
# single-shot traced form (flagship kernel / microbench)
# ---------------------------------------------------------------------------

def grouped_agg_once(keys, contribs, kinds, live, capacity: int,
                     max_rounds: int = 128, full_rounds: int = 1):
    """Fully traced one-batch hash aggregation: build + update + export
    in one program (no host growth loop — callers size ``capacity`` at
    >= 2x the possible distinct-key count). Returns (key_cols, accs,
    num_groups, group_valid) in SLOT order (no export sort — this is the
    cheap single-program form the bench and microbench measure); rows
    the round budget could not place are dropped (callers pick a budget
    that makes this impossible for their key distribution)."""
    keys = tuple(keys)
    meta = core.key_meta(keys)
    n = live.shape[0]
    W = core.total_words(meta)
    h = _hashes(keys, n)
    w = core.key_words(keys, meta)
    th = jnp.full(capacity, core.EMPTY, jnp.uint64)
    tw = jnp.zeros((capacity, W), jnp.uint64)
    claims, slot, resolved = core.insert_loop(th, tw, h, w, live,
                                              max_rounds, full_rounds,
                                              tail_frac=8)
    store = core.store_install(core.empty_store(meta, capacity), keys,
                               meta, claims)
    acc_meta = tuple((k, str(np.dtype(v.dtype)))
                     for k, v in zip(kinds, contribs))
    accs, auxs = core.init_accs(acc_meta, capacity)
    accs, _auxs = core.agg_update(accs, auxs, acc_meta, slot, resolved,
                                  contribs, jnp.int64(0))
    won = core.batch_owned(claims)
    ng = jnp.sum(won.astype(jnp.int32))
    return core.store_columns(store, meta), accs, ng, won
