"""Device-resident open-addressing hash table — traced building blocks.

The reference's AggExec and JoinHashMap are open-addressing tables probed
row-at-a-time (reference: datafusion-ext-plans/src/agg/agg_table.rs:68-356,
joins/join_hash_map.rs:44-365). A sequential probe chain is hostile to a
vector machine, but the probe LOOP itself vectorizes — with one twist that
makes it fast on an XLA backend: random scatters are the expensive
primitive (two orders of magnitude over gathers on the CPU mesh), so the
insert is shaped to spend exactly ONE scatter per round and none on
installs.

**Claim-owner rounds (scatter-claim + gather-verify).** Every unresolved
row probes its cursor slot in lock-step. Rows at unowned slots race
through a single scatter-min of their row id (the claim); then EVERY row
gathers the slot's owner and verifies key equality against the owner's
words — so duplicates resolve in the same round their winner claims, and
rows that hit a different key advance their cursor (double hashing: an
odd, hash-derived step keeps probe chains logarithmic). The claims array
itself becomes the table update: after the loop, slot contents (hash,
words, stored key values) are pure GATHERS of each slot's winning row.

**Compacted tail.** Round one resolves the overwhelming mass of rows;
survivors are collision chains. Rather than paying full-width rounds for
a shrinking set, the loop compacts unresolved rows once — a packed
``jnp.sort`` of (resolved-bit | row-id), ~7x cheaper than argsort — and
finishes them in narrow rounds over a bounded tail buffer. Rows the tail
cannot hold (or that exhaust the round budget) report as unresolved and
the caller grows the table and retries, the same power-of-two
re-bucketing discipline as the sort path's capacity growth.

The **key codec** encodes group/join keys of primitive, string, and
decimal128 columns into canonical uint64 words — NULL rows as a zeroed
word vector under a 0 validity word (null == null, as group keys
require), floats through ``hashing.canonicalize_float`` (-0.0 == 0.0,
one NaN) — so equality is an exact word compare, while the slot-indexed
**store** keeps each key's ORIGINAL column values (first-occurrence
bits, because claim winners are minimum row ids and duplicates probe in
lock-step) for emit: the same representative the sort path's stable
sort picks, bit-for-bit.

``agg_update`` scatters accumulator contributions into their owning
slots for the reassociation-exact reduce kinds (sum/min/max/or/first) —
the replacement for sort + segment-reduce on the general-agg hot path.

Sentinel discipline: an empty slot holds ``EMPTY`` (the sort path's
``_HASH_SENTINEL``); real hashes equal to it are remapped to
``EMPTY - 1`` before insert AND probe, so occupancy stays decidable and
exported tables keep dead slots sorted last, preserving the hash-sorted
state invariant the agg spill/merge machinery relies on.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from auron_tpu.columnar.batch import PrimitiveColumn, StringColumn

#: empty-slot sentinel — deliberately the agg path's _HASH_SENTINEL so
#: exported tables drop into the existing hash-sorted state contract.
#: numpy scalar: a module-level jnp constant would force jax backend init
#: at import time (see ops/hashing.py).
EMPTY = np.uint64(0xFFFFFFFFFFFFFFFF)

#: claims-array sentinels: a slot is unowned, owned by a pre-existing
#: table entry, or owned by batch row id >= 0
UNOWNED = np.int32(0x7FFFFFFF)
PREOWNED = np.int32(-1)

#: reduce kinds ``agg_update`` scatters exactly (bit-identical to the
#: sort path's segment reduction for any update order): integer adds are
#: associative, min/max/or are order-free, and ``first`` resolves through
#: a deterministic global row ordinal. Float sums are structurally
#: supported but reassociate — the dispatch policy keeps them off the
#: hash path unless auron.hashtable.backend=hash forces them.
SUPPORTED_KINDS = frozenset({"sum", "min", "max", "or", "first"})


def remap_hashes(h: jax.Array) -> jax.Array:
    """uint64 hashes with the (astronomically unlikely) EMPTY value moved
    to EMPTY-1, so it can never masquerade as an empty slot."""
    return jnp.where(h == EMPTY, jnp.uint64(EMPTY - np.uint64(1)), h)


# ---------------------------------------------------------------------------
# key codec
# ---------------------------------------------------------------------------

def key_meta(cols) -> tuple:
    """Static per-column codec descriptor — part of every program-cache
    key, and enough to rebuild an empty store. Raises NotImplementedError
    for column shapes without a word encoding (nested types); the
    dispatch policy routes those to the sort path before kernels build.
    """
    from auron_tpu.columnar.decimal128 import Decimal128Column
    meta = []
    for c in cols:
        if isinstance(c, StringColumn):
            meta.append(("str", int(c.width)))
        elif isinstance(c, Decimal128Column):
            meta.append(("dec",))
        elif isinstance(c, PrimitiveColumn):
            meta.append(("prim", str(np.dtype(c.data.dtype))))
        else:
            raise NotImplementedError(
                f"hashtable keys of {type(c).__name__} are not supported")
    return tuple(meta)


def words_per_column(meta_entry) -> int:
    kind = meta_entry[0]
    if kind == "prim":
        return 2                        # validity, canonical value
    if kind == "dec":
        return 3                        # validity, hi, lo
    # string: validity, length, ceil(width / 8) char words (widths are
    # bucketed to multiples of 8 — utils/shapes.bucket_string_width)
    return 2 + (meta_entry[1] + 7) // 8


def total_words(meta: tuple) -> int:
    return sum(words_per_column(m) for m in meta)


def _prim_word(col: PrimitiveColumn) -> jax.Array:
    """One canonical uint64 word per row for a primitive column."""
    from auron_tpu.ops.hashing import _f64_bits, canonicalize_float
    d = col.data
    if d.dtype == jnp.dtype(jnp.float64):
        lo, hi = _f64_bits(d)           # canonicalizes; TPU-safe bitcast
        return lo.astype(jnp.uint64) | (hi.astype(jnp.uint64) << 32)
    if d.dtype == jnp.dtype(jnp.float32):
        return canonicalize_float(d).view(jnp.uint32).astype(jnp.uint64)
    if d.dtype == jnp.bool_:
        return d.astype(jnp.uint64)
    return d.astype(jnp.int64).view(jnp.uint64)


def key_words(cols, meta: tuple) -> jax.Array:
    """uint64[n, W] canonical equality words (zeroed where invalid, so
    null keys equal each other and nothing else)."""
    ws = []
    for c, m in zip(cols, meta):
        valid = c.validity
        ws.append(valid.astype(jnp.uint64))
        zero = jnp.uint64(0)
        if m[0] == "prim":
            ws.append(jnp.where(valid, _prim_word(c), zero))
        elif m[0] == "dec":
            ws.append(jnp.where(valid, c.hi.view(jnp.uint64), zero))
            ws.append(jnp.where(valid, c.lo.view(jnp.uint64), zero))
        else:
            width = m[1]
            ws.append(jnp.where(valid, c.lens.astype(jnp.uint64), zero))
            n = c.chars.shape[0]
            padded = c.chars if width % 8 == 0 else jnp.pad(
                c.chars, ((0, 0), (0, 8 - width % 8)))
            # bytes at/after lens must not contribute (producers pad with
            # zeros, but masking here makes equality contractual)
            in_len = (jnp.arange(padded.shape[1], dtype=jnp.int32)[None, :]
                      < c.lens[:, None]) & valid[:, None]
            b = jnp.where(in_len, padded, 0).astype(jnp.uint64)
            b = b.reshape(n, -1, 8)
            shifts = (jnp.arange(8, dtype=jnp.uint64) * 8)[None, None, :]
            w64 = jnp.sum(b << shifts, axis=2)          # [n, width/8] LE
            ws.extend(w64[:, i] for i in range(w64.shape[1]))
    return jnp.stack(ws, axis=1)


def empty_store(meta: tuple, cap: int) -> tuple:
    """Slot-indexed original-value storage: one tuple of arrays per key
    column (the emit-side complement of the equality words)."""
    store = []
    for m in meta:
        if m[0] == "prim":
            store.append((jnp.zeros(cap, jnp.dtype(m[1])),
                          jnp.zeros(cap, bool)))
        elif m[0] == "dec":
            store.append((jnp.zeros(cap, jnp.int64),
                          jnp.zeros(cap, jnp.int64),
                          jnp.zeros(cap, bool)))
        else:
            store.append((jnp.zeros((cap, m[1]), jnp.uint8),
                          jnp.zeros(cap, jnp.int32),
                          jnp.zeros(cap, bool)))
    return tuple(store)


def _col_arrays(col, m) -> tuple:
    if m[0] == "prim":
        return (col.data, col.validity)
    if m[0] == "dec":
        return (col.hi, col.lo, col.validity)
    return (col.chars, col.lens, col.validity)


def store_columns(store: tuple, meta: tuple) -> tuple:
    """Rebuild key Column objects from a store (slot-indexed)."""
    from auron_tpu.columnar.decimal128 import Decimal128Column
    cols = []
    for s, m in zip(store, meta):
        if m[0] == "prim":
            cols.append(PrimitiveColumn(s[0], s[1]))
        elif m[0] == "dec":
            cols.append(Decimal128Column(s[0], s[1], s[2]))
        else:
            cols.append(StringColumn(s[0], s[1], s[2]))
    return tuple(cols)


def widen_string_store(tw, store: tuple, meta: tuple,
                       new_widths: dict) -> tuple:
    """Grow string columns' width buckets in place: pad stored chars and
    splice zero char-words into the word matrix at each widened column's
    segment (zero padding leaves hashes and the words of every stored
    key unchanged). Returns (tw, store, meta)."""
    cap = tw.shape[0]
    blocks, out_meta, out_store = [], [], []
    off = 0
    for i, m in enumerate(meta):
        w = words_per_column(m)
        seg = tw[:, off:off + w]
        s = store[i]
        if i in new_widths:
            nw = new_widths[i]
            pad_words = (nw - m[1]) // 8
            seg = jnp.concatenate(
                [seg, jnp.zeros((cap, pad_words), jnp.uint64)], axis=1)
            s = (jnp.pad(s[0], ((0, 0), (0, nw - m[1]))), s[1], s[2])
            m = ("str", nw)
        blocks.append(seg)
        out_meta.append(m)
        out_store.append(s)
        off += w
    return (jnp.concatenate(blocks, axis=1), tuple(out_store),
            tuple(out_meta))


def string_width_drift(batch_meta: tuple, table_meta: tuple) -> dict:
    """{column index: new width} for batch string columns wider than the
    table's store; asserts every other shape aspect is stable."""
    widen = {}
    for i, (bm, sm) in enumerate(zip(batch_meta, table_meta)):
        if bm[0] != sm[0] or (bm[0] != "str" and bm != sm):
            raise AssertionError(
                f"hashtable key column {i} changed shape mid-stream: "
                f"{sm} -> {bm}")
        if bm[0] == "str" and bm[1] > sm[1]:
            widen[i] = bm[1]
    return widen


def widest_meta(batch_meta: tuple, table_meta: tuple) -> tuple:
    """The codec a table of ``table_meta`` holds after a batch of
    ``batch_meta``: every string column at the wider of the two width
    buckets."""
    widen = string_width_drift(batch_meta, table_meta)
    return tuple(("str", widen[i]) if i in widen else m
                 for i, m in enumerate(table_meta))


# ---------------------------------------------------------------------------
# install-by-gather (the claims array IS the update)
# ---------------------------------------------------------------------------

def batch_owned(claims: jax.Array) -> jax.Array:
    """bool[cap]: slots claimed by this batch (vs empty / pre-existing)."""
    return (claims != UNOWNED) & (claims != PREOWNED)


def table_install(table_h, table_w, h, w, claims):
    """Fold a finished claims map into (hashes, words): batch-won slots
    gather their winner's hash/words — no scatter touches the table."""
    won = batch_owned(claims)
    cw = jnp.clip(claims, 0, h.shape[0] - 1)
    th = jnp.where(won, h[cw], table_h)
    tw = jnp.where(won[:, None], w[cw], table_w)
    return th, tw


def store_install(store: tuple, cols, meta: tuple, claims) -> tuple:
    """Gather winners' ORIGINAL key values into batch-won slots."""
    won = batch_owned(claims)
    cw = jnp.clip(claims, 0, cols[0].validity.shape[0] - 1)
    out = []
    for s, c, m in zip(store, cols, meta):
        arrs = []
        for old, val in zip(s, _col_arrays(c, m)):
            sel = won if old.ndim == 1 else won[:, None]
            arrs.append(jnp.where(sel, val[cw], old))
        out.append(tuple(arrs))
    return tuple(out)


# ---------------------------------------------------------------------------
# probe loops
# ---------------------------------------------------------------------------

def _probe_base_step(h: jax.Array, cap: int):
    """(base slot, odd step) per row — double hashing over a power-of-two
    table: an odd step is coprime with 2^k, so every row's probe sequence
    visits all slots."""
    mask = jnp.uint64(cap - 1)
    base = (h & mask).astype(jnp.int32)
    step = (((h >> 32) & mask) | jnp.uint64(1)).astype(jnp.int32)
    return base, step


def _claim_round(claims, unresolved, pos, slot, rids, hh, ww, step,
                 table_h, table_w, h_all, w_all, cap: int):
    """One scatter-claim + gather-verify round over an arbitrary row
    subset (full batch or compacted tail). ``rids`` index into the full
    batch arrays ``h_all``/``w_all`` (owner equality gathers)."""
    n = h_all.shape[0]
    owner_pre = claims[pos]
    claimant = unresolved & (owner_pre == UNOWNED)
    cpos = jnp.where(claimant, pos, cap)
    claims = claims.at[cpos].min(rids, mode="drop")
    owner = claims[pos]
    ow = jnp.clip(owner, 0, n - 1)
    by_batch = batch_owned(owner)
    own_h = jnp.where(by_batch, h_all[ow], table_h[pos])
    own_w = jnp.where(by_batch[:, None], w_all[ow], table_w[pos])
    match = (owner != UNOWNED) & (own_h == hh) & \
        jnp.all(own_w == ww, axis=1)
    resolved = unresolved & match
    slot = jnp.where(resolved, pos, slot)
    unresolved = unresolved & ~resolved
    pos = jnp.where(unresolved, (pos + step) & jnp.int32(cap - 1), pos)
    return claims, unresolved, pos, slot


def _tail_capacity(n: int, tail_frac: int) -> int:
    """Static tail-buffer size: generous enough that only genuinely
    pathological chains overflow it (caller grows and retries)."""
    return n if n <= 4096 else max(4096, n // tail_frac)


def insert_loop(table_h: jax.Array, table_w: jax.Array, h: jax.Array,
                w: jax.Array, live: jax.Array, max_rounds: int,
                full_rounds: int = 2, tail_frac: int = 4):
    """Vectorized open-addressing insert.

    ``full_rounds`` claim rounds run at batch width (round one resolves
    the bulk: winners claim, duplicates verify against the winner in the
    same round); survivors compact once via a packed sort and finish in
    narrow rounds over a ``n/4`` tail buffer, early-exiting as soon as
    every row is resolved.

    Returns (claims[cap] int32, slot[n] int32, resolved[n] bool). Slot
    contents derive from ``claims`` by gather (``table_install`` /
    ``store_install``). ``live & ~resolved`` rows exhausted the round
    budget or overflowed the tail buffer — the caller re-buckets and
    retries (or falls back).
    """
    n = h.shape[0]
    cap = table_h.shape[0]
    # never place a key deeper than lookups are allowed to walk: a probe
    # with the same max_rounds must always be able to find it
    full_rounds = max(1, min(full_rounds, max_rounds))
    base, step = _probe_base_step(h, cap)
    rid = jnp.arange(n, dtype=jnp.int32)
    # pre-existing entries own their slots before the batch arrives
    claims = jnp.where(table_h != EMPTY, PREOWNED, UNOWNED)

    unresolved, pos, slot = live, base, jnp.zeros(n, jnp.int32)
    for _ in range(full_rounds):
        claims, unresolved, pos, slot = _claim_round(
            claims, unresolved, pos, slot, rid, h, w, step,
            table_h, table_w, h, w, cap)

    T = _tail_capacity(n, tail_frac)
    # compact survivors: resolved/dead rows sort behind the live
    # unresolved ones (packed sort ~7x cheaper than argsort)
    packed = (jnp.where(unresolved, jnp.uint64(0), jnp.uint64(1)) << 32) \
        | rid.astype(jnp.uint64)
    srt = jnp.sort(packed)[:T]
    t_rid = (srt & jnp.uint64(0xFFFFFFFF)).astype(jnp.int32)
    t_live = (srt >> 32) == 0
    t_h, t_w = h[t_rid], w[t_rid]
    t_pos, t_step = pos[t_rid], step[t_rid]

    def cond(st):
        return (st[0] < max_rounds) & jnp.any(st[1])

    def body(st):
        r, t_unres, t_pos, t_slot, claims = st
        claims, t_unres, t_pos, t_slot = _claim_round(
            claims, t_unres, t_pos, t_slot, t_rid, t_h, t_w, t_step,
            table_h, table_w, h, w, cap)
        return r + 1, t_unres, t_pos, t_slot, claims

    init = (jnp.int32(full_rounds), t_live, t_pos,
            jnp.zeros(T, jnp.int32), claims)
    _r, t_unres, _tp, t_slot, claims = lax.while_loop(cond, body, init)

    done = t_live & ~t_unres
    wb = jnp.where(done, t_rid, n)
    slot = slot.at[wb].set(t_slot, mode="drop")
    resolved = (~unresolved & live).at[wb].set(True, mode="drop") & live
    # rows that did not fit the tail buffer stay unresolved
    return claims, slot, resolved


def probe_loop(table_h: jax.Array, table_w: jax.Array, h: jax.Array,
               w: jax.Array, live: jax.Array, max_rounds: int):
    """Lookup-only probe (joins, distinct-membership): walks the same
    double-hashed sequence as ``insert_loop``; an empty slot proves
    absence (open addressing never deletes). Scatter-free — every round
    is gathers and compares. Returns (slot, found)."""
    cap = table_h.shape[0]
    base, step = _probe_base_step(h, cap)
    cmask = jnp.int32(cap - 1)

    def cond(st):
        return (st[0] < max_rounds) & jnp.any(st[1])

    def body(st):
        r, unresolved, pos, slot, found = st
        slot_h = table_h[pos]
        occupied = slot_h != EMPTY
        match = occupied & (slot_h == h) & \
            jnp.all(table_w[pos] == w, axis=1)
        hit = unresolved & match
        slot = jnp.where(hit, pos, slot)
        # keep walking only past occupied non-matching slots
        unresolved = unresolved & occupied & ~match
        pos = jnp.where(unresolved, (pos + step) & cmask, pos)
        return r + 1, unresolved, pos, slot, found | hit

    init = (jnp.int32(0), live, base, jnp.zeros(h.shape[0], jnp.int32),
            jnp.zeros(h.shape[0], bool))
    _r, _u, _p, slot, found = lax.while_loop(cond, body, init)
    return slot, found


def probe_hash_index(table_h: jax.Array, h: jax.Array, live: jax.Array,
                     max_rounds: int):
    """Degenerate probe for tables keyed on the 64-bit hash alone (the
    join candidate index): equality IS the hash compare, no words."""
    w = jnp.zeros((h.shape[0], 0), jnp.uint64)
    return probe_loop(table_h, jnp.zeros((table_h.shape[0], 0),
                                         jnp.uint64), h, w, live,
                      max_rounds)


# ---------------------------------------------------------------------------
# slot-indexed accumulator update
# ---------------------------------------------------------------------------

def neutral_like(kind: str, dtype):
    """Neutral element of a reduce kind for acc-array initialization."""
    if kind == "sum":
        return jnp.zeros((), dtype)
    if kind == "min":
        if jnp.issubdtype(dtype, jnp.floating):
            return jnp.asarray(jnp.inf, dtype)
        return jnp.asarray(jnp.iinfo(dtype).max, dtype)
    if kind == "max":
        if jnp.issubdtype(dtype, jnp.floating):
            return jnp.asarray(-jnp.inf, dtype)
        return jnp.asarray(jnp.iinfo(dtype).min, dtype)
    if kind == "or":
        return jnp.zeros((), jnp.bool_)
    if kind == "first":
        return jnp.zeros((), dtype)
    raise ValueError(kind)


#: ordinal sentinel for first-kind aux arrays (no row yet)
ORD_NONE = np.int64(0x7FFFFFFFFFFFFFFF)


def init_accs(acc_meta: tuple, cap: int):
    """(accs, auxs): neutral acc array per (kind, dtype); first-kind accs
    get a parallel int64 ordinal array (global first-row tracking)."""
    accs, auxs = [], []
    for kind, dt in acc_meta:
        accs.append(jnp.full(cap, neutral_like(kind, jnp.dtype(dt))))
        auxs.append(jnp.full(cap, ORD_NONE, jnp.int64)
                    if kind == "first" else None)
    return tuple(accs), tuple(auxs)


def agg_update(accs: tuple, auxs: tuple, acc_meta: tuple,
               slot: jax.Array, mask: jax.Array, contribs: tuple,
               ord_base) -> tuple:
    """Fold one batch's per-row contributions into slot-indexed
    accumulators. ``mask`` selects resolved live rows; ``ord_base`` is
    the global row ordinal of this batch's first row (device scalar),
    which makes ``first`` deterministic across batches: the accumulator
    keeps the value at the minimum ordinal — first batch, first row —
    matching the sort path's merge preference for earlier state."""
    cap = accs[0].shape[0] if accs else 0
    pos = jnp.where(mask, slot, cap)
    n = slot.shape[0]
    out_accs, out_auxs = [], []
    for (kind, _dt), acc, aux, v in zip(acc_meta, accs, auxs, contribs):
        if kind == "sum":
            out_accs.append(acc.at[pos].add(
                jnp.where(mask, v, jnp.zeros((), v.dtype)), mode="drop"))
            out_auxs.append(None)
        elif kind in ("min", "max"):
            # contributions already carry the reduce neutral where the
            # row's value is invalid (ops/agg._contributions)
            upd = acc.at[pos]
            out_accs.append((upd.min if kind == "min" else upd.max)(
                v, mode="drop"))
            out_auxs.append(None)
        elif kind == "or":
            hits = jnp.zeros(cap, jnp.int32).at[pos].add(
                v.astype(jnp.int32), mode="drop")
            out_accs.append(acc | (hits > 0))
            out_auxs.append(None)
        elif kind == "first":
            ordinal = ord_base + jnp.arange(n, dtype=jnp.int64)
            ordinal = jnp.where(mask, ordinal, ORD_NONE)
            new_aux = aux.at[pos].min(ordinal, mode="drop")
            # row ordinals are unique, so exactly one row writes per slot
            setter = mask & (ordinal == new_aux[slot])
            out_accs.append(acc.at[jnp.where(setter, slot, cap)].set(
                v, mode="drop"))
            out_auxs.append(new_aux)
        else:
            raise ValueError(kind)
    return tuple(out_accs), tuple(out_auxs)
