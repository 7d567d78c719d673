"""DeviceHashTable — the stateful build/probe surface, plus the
hash-join candidate index.

``DeviceHashTable`` is the key→slot map alone (no accumulators): build
inserts key columns and returns stable slot ids, probe is lookup-only.
Distinct/dedup and join-membership shapes use it directly; the general
aggregation path uses the fused ``HashAggState`` instead (one program
per batch including the accumulator scatters).

``build_join_index`` packages the hash-join specialization: the build
side is already sorted by 64-bit key hash (ops/joins._BuildSide), so
candidate lookup only needs ``probe hash → (run start, run length)``.
The index keys slots on the hash value itself (equality = one compare,
no words) and stores the run bounds as slot payloads; a probe becomes
O(probe rounds) gathers instead of the two O(log B) searchsorted
passes, and returns the EXACT (lo, count) pairs searchsorted would —
downstream expand + exact-key verification consume them unchanged, so
join results are bit-identical with the index on or off.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from auron_tpu.hashtable import core
from auron_tpu.obs import profile as _profile
from auron_tpu.runtime.programs import program_cache
from auron_tpu.utils.shapes import next_pow2


@program_cache("hashtable.build", maxsize=128)
def _build_kernel(key_meta: tuple, n: int, cap: int, rounds: int):
    @jax.jit
    def auron_hashtable_build(th, tw, store, keys, live):
        from auron_tpu.hashtable.agg import _hashes
        h = _hashes(keys, n)
        w = core.key_words(keys, key_meta)
        claims, slot, resolved = core.insert_loop(th, tw, h, w, live,
                                                  rounds)
        th2, tw2 = core.table_install(th, tw, h, w, claims)
        store2 = core.store_install(store, keys, key_meta, claims)
        rid = jnp.arange(n, dtype=jnp.int32)
        is_new = resolved & (claims[slot] == rid)
        n_new = jnp.sum(core.batch_owned(claims).astype(jnp.int32))
        return (th2, tw2, store2, slot, is_new, n_new,
                jnp.any(live & ~resolved))

    return auron_hashtable_build


@program_cache("hashtable.probe", maxsize=128)
def _probe_kernel(key_meta: tuple, n: int, cap: int, rounds: int):
    @jax.jit
    def auron_hashtable_probe(th, tw, keys, live):
        from auron_tpu.hashtable.agg import _hashes
        h = _hashes(keys, n)
        w = core.key_words(keys, key_meta)
        return core.probe_loop(th, tw, h, w, live, rounds)

    return auron_hashtable_probe


@program_cache("hashtable.grow", maxsize=64)
def _table_grow_kernel(key_meta: tuple, old_cap: int, new_cap: int,
                       rounds: int):
    W = core.total_words(key_meta)

    @jax.jit
    def auron_hashtable_grow(th, store):
        occupied = th != core.EMPTY
        cols = core.store_columns(store, key_meta)
        w = core.key_words(cols, key_meta)
        nth = jnp.full(new_cap, core.EMPTY, jnp.uint64)
        ntw = jnp.zeros((new_cap, W), jnp.uint64)
        claims, slot, resolved = core.insert_loop(nth, ntw, th, w,
                                                  occupied, rounds)
        nth, ntw = core.table_install(nth, ntw, th, w, claims)
        nstore = core.store_install(
            core.empty_store(key_meta, new_cap), cols, key_meta, claims)
        return nth, ntw, nstore, slot, jnp.any(occupied & ~resolved)

    return auron_hashtable_grow


class DeviceHashTable:
    """Key → slot-id map over canonical-word key equality (null == null,
    NaN == NaN, -0.0 == 0.0). ``insert`` returns per-row slot ids and an
    is-new mask; slot ids are stable until a growth re-bucket, which
    reports the old→new slot remap to the caller."""

    def __init__(self, initial_capacity: int = 4096,
                 load_factor: float = 0.5, max_probe_rounds: int = 64):
        self.cap = max(16, next_pow2(initial_capacity))
        self.load_factor = float(load_factor)
        self.rounds = int(max_probe_rounds)
        self.count = 0
        self.key_meta = None
        self.th = self.tw = self.store = None
        #: (old_cap, new_slot_of_old[old_cap], occupied[old_cap]) of the
        #: most recent growth — callers with slot-indexed side state
        #: consume and clear it
        self.last_remap = None

    def _init_arrays(self, keys) -> None:
        self.key_meta = core.key_meta(keys)
        W = core.total_words(self.key_meta)
        self.th = jnp.full(self.cap, core.EMPTY, jnp.uint64)
        self.tw = jnp.zeros((self.cap, W), jnp.uint64)
        self.store = core.empty_store(self.key_meta, self.cap)

    def _grow(self) -> None:
        from auron_tpu.hashtable.agg import (_MAX_CAPACITY,
                                             HashTableOverflow)
        new_cap = self.cap * 2
        while True:
            if new_cap > _MAX_CAPACITY:
                raise HashTableOverflow(
                    f"hash table stuck at {self.count} keys at capacity "
                    f"{new_cap}")
            kern = _table_grow_kernel(self.key_meta, self.cap, new_cap,
                                      self.rounds)
            nth, ntw, nstore, slot, ovf = kern(self.th, self.store)
            if bool(_profile.timed_get(ovf)):
                new_cap *= 2
                continue
            self.last_remap = (self.cap, slot, self.th != core.EMPTY)
            self.th, self.tw, self.store = nth, ntw, nstore
            self.cap = new_cap
            return

    def _unify_widths(self, keys):
        from auron_tpu.hashtable.agg import _pad_string_keys
        meta = core.key_meta(keys)
        if meta != self.key_meta:
            widen = core.string_width_drift(meta, self.key_meta)
            if widen:
                self.tw, self.store, self.key_meta = \
                    core.widen_string_store(self.tw, self.store,
                                            self.key_meta, widen)
        return _pad_string_keys(keys, self.key_meta)

    def insert(self, keys, live):
        """Insert live rows' keys; returns (slot[n], is_new[n])."""
        keys = tuple(keys)
        if self.key_meta is None:
            self._init_arrays(keys)
        keys = self._unify_widths(keys)
        n = int(live.shape[0])
        while True:
            kern = _build_kernel(self.key_meta, n, self.cap, self.rounds)
            th, tw, store, slot, is_new, n_new, ovf = kern(
                self.th, self.tw, self.store, keys, live)
            n_new_h, ovf_h = _profile.timed_get([n_new, ovf])
            if not bool(ovf_h):
                self.th, self.tw, self.store = th, tw, store
                self.count += int(n_new_h)
                if self.count > self.load_factor * self.cap:
                    self._grow()
                return slot, is_new
            self._grow()

    def probe(self, keys, live):
        """Lookup-only: (slot[n], found[n]); probe keys WIDER than the
        store's width bucket widen it first (a wider probe key can still
        equal a stored narrower one)."""
        if self.key_meta is None:
            n = int(live.shape[0])
            return jnp.zeros(n, jnp.int32), jnp.zeros(n, bool)
        keys = self._unify_widths(tuple(keys))
        n = int(live.shape[0])
        kern = _probe_kernel(self.key_meta, n, self.cap, self.rounds)
        return kern(self.th, self.tw, keys, live)

    def keys_columns(self) -> tuple:
        """Slot-indexed original key values (emit side)."""
        return core.store_columns(self.store, self.key_meta)


# ---------------------------------------------------------------------------
# hash-join candidate index
# ---------------------------------------------------------------------------

@program_cache("hashtable.join_index", maxsize=128)
def _join_index_kernel(cap: int, table_cap: int, rounds: int):
    """Hash-run index over a hash-SORTED build column: one slot per
    distinct 64-bit hash, payload = (run start, run length)."""

    @jax.jit
    def auron_hashtable_join_index(h_sorted):
        idx = jnp.arange(cap, dtype=jnp.int32)
        first = jnp.concatenate(
            [jnp.ones(1, bool), h_sorted[1:] != h_sorted[:-1]])
        run_id = jnp.cumsum(first.astype(jnp.int32)) - 1
        run_lo = jax.ops.segment_min(idx, run_id, num_segments=cap)
        run_hi = jax.ops.segment_max(idx, run_id, num_segments=cap)
        lo_row = run_lo[run_id]
        cnt_row = (run_hi - run_lo + 1)[run_id]
        th = jnp.full(table_cap, core.EMPTY, jnp.uint64)
        tw = jnp.zeros((table_cap, 0), jnp.uint64)
        w = jnp.zeros((cap, 0), jnp.uint64)     # hash IS the key
        claims, _slot, resolved = core.insert_loop(th, tw, h_sorted, w,
                                                   first, rounds)
        won = core.batch_owned(claims)
        cw = jnp.clip(claims, 0, cap - 1)
        th = jnp.where(won, h_sorted[cw], th)
        lo_arr = jnp.where(won, lo_row[cw], 0)
        cnt_arr = jnp.where(won, cnt_row[cw], 0)
        # a real build hash equal to the empty sentinel would be
        # indistinguishable from an empty slot — the host disables the
        # index for that build side (searchsorted handles it exactly)
        bad = jnp.any(h_sorted == core.EMPTY) | \
            jnp.any(first & ~resolved)
        return th, lo_arr, cnt_arr, bad

    return auron_hashtable_join_index


#: build sides larger than this keep the searchsorted candidate search
#: (the index would double their device footprint for a log-factor win
#: that large builds don't feel)
MAX_INDEX_BUILD_ROWS = 1 << 22


class JoinHashIndex:
    """Immutable probe-side index: hash → (lo, count) into the sorted
    build table. ``lookup`` is traced (usable inside fused probe
    programs)."""

    __slots__ = ("th", "lo", "cnt", "rounds", "capacity")

    def __init__(self, th, lo, cnt, rounds: int):
        self.th = th
        self.lo = lo
        self.cnt = cnt
        self.rounds = rounds
        self.capacity = int(th.shape[0])

    def lookup(self, h: jax.Array):
        """(lo[n], counts[n]) for probe hashes — the searchsorted
        contract: count 0 (lo 0) where the hash is absent."""
        live = h != core.EMPTY     # null/dead probe rows never match
        slot, found = core.probe_hash_index(self.th, h, live,
                                            self.rounds)
        lo = jnp.where(found, self.lo[slot], 0)
        counts = jnp.where(found, self.cnt[slot], 0)
        return lo, counts


def build_join_index(h_sorted: jax.Array,
                     max_probe_rounds: int = 64):
    """Index a hash-sorted build column; returns a JoinHashIndex, or
    None when the build side is too large or its hashes collide with the
    empty sentinel (callers keep the exact searchsorted path)."""
    cap = int(h_sorted.shape[0])
    if cap > MAX_INDEX_BUILD_ROWS:
        return None
    table_cap = max(16, next_pow2(cap) * 2)
    kern = _join_index_kernel(cap, table_cap, max_probe_rounds)
    th, lo, cnt, bad = kern(h_sorted)
    if bool(_profile.timed_get(bad)):
        return None
    return JoinHashIndex(th, lo, cnt, max_probe_rounds)
