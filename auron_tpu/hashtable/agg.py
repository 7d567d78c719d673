"""Hash-table-backed group state for the general aggregation path.

``HashAggState`` is the open-addressing replacement for the sort path's
incremental (batch-sort + searchsorted-merge) state: every batch runs ONE
fused program — hash keys, insert (vectorized probe rounds), scatter the
batch's accumulator contributions into the owning slots — and the O(S)
state pass disappears entirely (the table IS the state; nothing re-sorts
per batch). This is the reference AggTable's update loop
(datafusion-ext-plans/src/agg/agg_table.rs:68-356) with the row-at-a-time
probe replaced by ``hashtable.core``'s lock-step rounds.

Growth keeps the ``auron.agg.initial_capacity`` power-of-two re-bucketing
discipline: when an insert overflows its probe-round budget or occupancy
crosses ``auron.hashtable.load_factor``, the table doubles and re-inserts
itself (one program; keys re-place positionally, accumulators follow
their slots). Pathological repeat overflow — adversarial hash collisions,
not load — raises ``HashTableOverflow``, which the operator catches to
fall back to the sort path mid-stream without losing state.

``to_sorted_table()`` exports the slots as the agg path's canonical
hash-sorted 5-tuple ``(keys, accs, num_groups, cap, hashes)`` — occupied
slots sorted by hash ascending, dead slots carrying the shared sentinel
last — so emit, spill (``memmgr`` bucket spills rely on the hash-sorted
run invariant), and the partial-skip decision reuse the existing
machinery unchanged, and hash-vs-sort results stay bit-identical down to
group output order.
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


@program_cache("hashtable.agg_step", maxsize=128)
def _agg_step_kernel(key_meta: tuple, acc_meta: tuple, n: int, cap: int,
                     rounds: int):
    """One fused program per (key codec, acc layout, batch/table shape):
    hash + insert + store winners + scatter accumulator contributions."""

    @jax.jit
    def auron_hashtable_agg_step(th, tw, store, accs, auxs, keys, contribs, live, ord_base):
        h = _hashes(keys, n)
        w = core.key_words(keys, key_meta)
        claims, slot, resolved = core.insert_loop(th, tw, h, w, live,
                                                  rounds)
        th2, tw2 = core.table_install(th, tw, h, w, claims)
        store2 = core.store_install(store, keys, key_meta, claims)
        accs2, auxs2 = core.agg_update(accs, auxs, acc_meta, slot,
                                       resolved, contribs, ord_base)
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


@program_cache("hashtable.agg_export", maxsize=64)
def _export_kernel(key_meta: tuple, acc_meta: tuple, cap: int):
    """Slots → the hash-sorted group-table layout (dead slots last under
    the shared sentinel): the handoff that keeps emit/spill/merge
    invariants — and output group order — identical to the sort path."""
    from auron_tpu.columnar.batch import gather_column

    @jax.jit
    def auron_hashtable_agg_export(th, store, accs):
        occupied = th != core.EMPTY
        ng = jnp.sum(occupied.astype(jnp.int32))
        perm = jnp.argsort(th, stable=True)     # EMPTY is max: dead last
        out_valid = jnp.arange(cap, dtype=jnp.int32) < ng
        cols = tuple(gather_column(c, perm, out_valid)
                     for c in core.store_columns(store, key_meta))
        accs_out = tuple(a[perm] for a in accs)
        return cols, accs_out, ng, th[perm]

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
        self.key_meta = None    # set lazily on the first update
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

    def _init_arrays(self, keys, contribs) -> None:
        self.key_meta = core.key_meta(keys)
        self.acc_meta = tuple(
            (kind, str(np.dtype(v.dtype)))
            for kind, v in zip(self.kinds, contribs))
        W = core.total_words(self.key_meta)
        self.th = jnp.full(self.cap, core.EMPTY, jnp.uint64)
        self.tw = jnp.zeros((self.cap, W), jnp.uint64)
        self.store = core.empty_store(self.key_meta, self.cap)
        self.accs, self.auxs = core.init_accs(self.acc_meta, self.cap)

    def _unify_widths(self, keys):
        """Reconcile per-batch string width buckets with the store's: pad
        the narrower side (a wider batch widens the store, rebuilding the
        word matrix with zero blocks in the new char-word positions)."""
        meta = core.key_meta(keys)
        if meta == self.key_meta:
            return keys
        widen = core.string_width_drift(meta, self.key_meta)
        if widen:
            self.tw, self.store, self.key_meta = core.widen_string_store(
                self.tw, self.store, self.key_meta, widen)
        return _pad_string_keys(keys, self.key_meta)

    def _grow(self) -> None:
        new_cap = self.cap * 2
        while True:
            if new_cap > _MAX_CAPACITY:
                raise HashTableOverflow(
                    f"hash table stuck at {self.count} keys despite "
                    f"capacity {new_cap} (probe rounds {self.rounds})")
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

    def update(self, keys, contribs, live) -> None:
        """Fold one batch (group-key columns + per-row accumulator
        contributions + live mask) into the table. One fused program plus
        one batched scalar readback — the same per-batch host-RTT budget
        as the sort path's group-count readback."""
        keys = tuple(keys)
        contribs = tuple(contribs)
        if not self.built:
            self._init_arrays(keys, contribs)
        keys = self._unify_widths(keys)
        n = int(live.shape[0])
        ord_base = jnp.asarray(self.rows_seen, jnp.int64)
        while True:
            kern = _agg_step_kernel(self.key_meta, self.acc_meta, n,
                                    self.cap, self.rounds)
            th, tw, store, accs, auxs, n_new, overflow = kern(
                self.th, self.tw, self.store, self.accs, self.auxs,
                keys, contribs, live, ord_base)
            # this readback is the per-batch sync point (the wait is
            # attributed as device time). NOTE the donation
            # sweep deliberately skips the step/grow kernels: the
            # overflow-retry protocol re-runs them with the SAME state
            # and batch inputs, which donation would have invalidated.
            n_new_h, ovf = _profile.timed_get([n_new, overflow])
            if not bool(ovf):
                self.th, self.tw, self.store = th, tw, store
                self.accs, self.auxs = accs, auxs
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
