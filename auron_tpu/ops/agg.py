"""Hash aggregation, TPU-style.

The reference's AggExec is an open-addressing hash table with sorted-bucket
spills (reference: datafusion-ext-plans/src/agg/agg_table.rs:68-356). Open
addressing is sequential probing — hostile to a vector machine — so this
engine keeps the same *contract* (streaming partial/final agg with a bounded
in-memory group state) but replaces the probe loop with sort-based grouping,
which XLA lowers to parallel bitonic-class sorts on the VPU:

  per input batch:
    state_rows ++ input_rows → xxhash64(group keys)
    → stable sort by hash → null-aware neighbor-equality boundaries
    → segment-reduce accumulators → new state (groups sorted by hash)

Group count exceeding the state capacity triggers a host-side capacity
re-bucket (rerun of the pure merge kernel at the next power of two), the
shape-static analogue of the reference's table growth; hash-ordered state
also gives the sorted-run invariant its bucket spills rely on.

Aggregate set: sum/count/avg/min/max/first/first_ignores_null (reference:
datafusion-ext-plans/src/agg/*.rs). Accumulators are flat device columns —
the AccColumn idea (reference: agg/acc.rs) without the row-format detour.

Everything the operator does to a device array happens inside a program
handed out by runtime/programs.py (the rule ``hash_join`` and ``sort``
keep): a batch's group keys and contributions are evaluated inside the
program that folds them into the state (``hashtable.agg_step``,
``ops.agg.batch_reduce``; the operator hands it its *front*, plan data),
a table cut to its occupancy bucket is cut by the program that reads it
next, and an operator's output — the hash table's export, the cut, every
function's finalisation — is ONE ``ops.agg.emit``. The host keeps the
control flow (growth retries, the path's choice, spills) and the
host-side aggregates (bloom, UDAF). tests/test_agg_launches.py holds the
budget: no eager launch, B + 2 programs an operator over B batches.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import lru_cache
from typing import Iterator, NamedTuple, Optional

import numpy as np

import jax
import jax.numpy as jnp

from auron_tpu.columnar.batch import (DeviceBatch, PrimitiveColumn, StringColumn,
                                      gather_column, leaf_layout,
                                      unify_column_widths)
from auron_tpu.columnar.schema import DataType, Field, Schema
from auron_tpu.exprs import ir
from auron_tpu.exprs.eval import EvalContext, TypedValue, evaluate, infer_dtype
from auron_tpu.obs import profile as _profile
from auron_tpu.obs import trace as _trace
from auron_tpu.ops import hashing
from auron_tpu.ops.base import ExecContext, PhysicalOp, count_output, timer
from auron_tpu.utils.shapes import bucket_rows
from auron_tpu.runtime.programs import program_cache

# ---------------------------------------------------------------------------
# accumulator specs
# ---------------------------------------------------------------------------

_SUM_DTYPE = {
    DataType.INT8: DataType.INT64, DataType.INT16: DataType.INT64,
    DataType.INT32: DataType.INT64, DataType.INT64: DataType.INT64,
    DataType.FLOAT32: DataType.FLOAT64, DataType.FLOAT64: DataType.FLOAT64,
    DataType.DECIMAL: DataType.DECIMAL,
}

_JNPT = {
    DataType.INT64: jnp.int64, DataType.FLOAT64: jnp.float64,
    DataType.DECIMAL: jnp.int64, DataType.INT32: jnp.int32,
    DataType.FLOAT32: jnp.float32, DataType.BOOL: jnp.bool_,
    DataType.INT8: jnp.int8, DataType.INT16: jnp.int16,
    DataType.DATE32: jnp.int32, DataType.TIMESTAMP_US: jnp.int64,
}


@dataclass(frozen=True)
class AccSpec:
    """How one aggregate maps to flat state columns.

    state_fields: (name, dtype, reduce_kind) per state column.
    reduce kinds: sum | min | max | or | first (first = value at the
    first-ordered valid row of the group) run on device inside the merge
    kernel; collect_list/collect_set carry a padded list accumulator
    (values[cap, E], lens[cap]) through the same kernel; bloom / udaf are
    host-side states (kind marks the field, no device accumulator).
    """
    fn: str
    state_fields: tuple
    result: tuple  # (dtype, precision, scale)
    elem: Optional[DataType] = None  # list element dtype (collect_*)
    #: per-state-field (precision, scale) for DECIMAL state columns whose
    #: type differs from the result type (avg's sum accumulates at the
    #: INPUT scale; the result-scale shift happens inside the finalizing
    #: division); None = use the result's (p, s)
    state_ps: Optional[tuple] = None


#: reduce kinds whose state is accumulated host-side, not in the kernel
HOST_KINDS = ("bloom", "udaf")

#: reduce kinds over string values; their accumulator is a 3-tuple
#: (chars[cap, W] uint8, lens[cap] int32, valid[cap] bool) and reduction
#: runs on order-preserving uint64 words (the sort operator's order-word
#: normalization, ops/sort.py order_words) instead of segment min/max
_STR_KINDS = ("smin", "smax", "sfirst", "sfirst_ign")


#: reduce kinds over two-limb decimal(p>18) values; their accumulator is a
#: pair (hi[cap], lo[cap]) of int64 limb arrays reduced with carry-exact
#: 128-bit arithmetic inside the merge kernel (reference handles these as
#: Arrow Decimal128 in its AccColumn: datafusion-ext-plans/src/agg/acc.rs +
#: sum.rs; here the i128 is two int64 limbs, columnar/decimal128.py)
_DEC_KINDS = ("dsum", "dmin", "dmax", "dfirst")

#: collect kinds over two-limb decimal(p>18) values; their accumulator is
#: (hi[cap, E], lo[cap, E], lens[cap]) — the padded-list accumulator with
#: limb-pair payloads. State/wire columns ride the MapColumn carrier
#: (hi→keys, lo→values), the same offsets-over-pairs reuse as entry lists
_DCOLLECT = ("dcollect_list", "dcollect_set")

#: limb-pair neutral elements as plain python ints (module-level jnp
#: constants would force backend init at import time — see ops/hashing.py).
#: dmin's neutral is +2^127-1 (hi=INT64_MAX, lo=all-ones), dmax's is
#: -2^127; real decimals are bounded by 10^38 < 2^127 so neither collides
_DEC_NEUTRAL = {"dmin": (0x7FFFFFFFFFFFFFFF, -1),
                "dmax": (-0x8000000000000000, 0)}


def decimal_avg_result(p: int, s: int) -> tuple[int, int]:
    """Spark avg(decimal(p,s)) → DecimalType.bounded(p+4, s+4): each bound
    clamps at 38 independently (avg(decimal(38,18)) is decimal(38,22)) —
    NOT the adjustPrecisionScale scale-reduction binary arithmetic uses."""
    return min(p + 4, 38), min(s + 4, 38)


def make_acc_spec(agg: ir.AggFunction, in_schema: Schema, mode: str) -> AccSpec:
    fn = agg.fn
    if agg.distinct:
        # DISTINCT state rides the collect_set accumulator: the merge
        # kernel already dedupes per group, so count/sum/avg finalize
        # straight off the set (reference models distinct the same
        # "expand to set then aggregate" way); min/max/first are
        # distinct-invariant and keep their plain state
        if fn in ("count", "sum", "avg"):
            dt, p, s = infer_dtype(agg.arg, in_schema)
            if dt in (DataType.STRING, DataType.LIST):
                raise NotImplementedError(f"{fn} DISTINCT over {dt.value}")
            if dt == DataType.DECIMAL and p > 18:
                raise NotImplementedError(
                    f"{fn} DISTINCT over decimal(p={p}>18): the set "
                    "accumulator is single-word; cast the arg first")
            res = {"count": (DataType.INT64, 0, 0),
                   "sum": (_SUM_DTYPE[dt], 0, 0),
                   "avg": (DataType.FLOAT64, 0, 0)}[fn]
            return AccSpec(f"{fn}_distinct",
                           (("set", dt, "collect_set"),), res, elem=dt)
        if fn not in ("min", "max", "first", "first_ignores_null",
                      "collect_set"):
            raise NotImplementedError(f"{fn} DISTINCT")
    if fn in ("count", "count_star"):
        return AccSpec(fn, (("count", DataType.INT64, "sum"),),
                       (DataType.INT64, 0, 0))
    if fn in ("bloom_filter",) or fn.startswith("udaf:"):
        # host-side accumulators read single-word device columns; keep the
        # plan-time fail-fast for two-limb args (the old all-fn guard)
        if agg.arg is not None:
            _dt, _p, _s = infer_dtype(agg.arg, in_schema)
            if _dt == DataType.DECIMAL and _p > 18:
                raise NotImplementedError(
                    f"{fn} over decimal(p={_p}>18): cast the arg to "
                    "decimal(<=18) or double first")
    if fn == "bloom_filter":
        # host-built runtime filter (reference: agg/bloom_filter.rs);
        # result/state travel as base64 of the Spark wire format
        return AccSpec(fn, (("bloom", DataType.STRING, "bloom"),),
                       (DataType.STRING, 0, 0))
    if fn.startswith("udaf:"):
        from auron_tpu.exprs.udf import lookup_udaf
        udaf = lookup_udaf(fn[5:])
        rdt = getattr(udaf, "dtype", DataType.FLOAT64)
        rp = getattr(udaf, "precision", 0)
        rs = getattr(udaf, "scale", 0)
        return AccSpec(fn, (("udaf", DataType.STRING, "udaf"),), (rdt, rp, rs))
    dt, p, s = infer_dtype(agg.arg, in_schema)
    wide = dt == DataType.DECIMAL and p > 18
    if fn == "sum":
        if dt == DataType.DECIMAL and p + 10 > 18:
            # Spark: sum(decimal(p,s)) → decimal(min(p+10,38), s). Narrow
            # inputs with p in 9..18 promote to the two-limb
            # representation with the Spark type (DecimalType.bounded, as
            # the avg branch); wide sums past 2^127 wrap before the 10^38
            # fits-check can see them — same accepted limitation as the
            # narrow path's int64 sums
            return AccSpec(fn, (("sum", DataType.DECIMAL, "dsum"),
                                ("has", DataType.BOOL, "or")),
                           (DataType.DECIMAL, min(p + 10, 38), s))
        sdt = _SUM_DTYPE[dt]
        sp, ss = (min(p + 10, 18), s) if sdt == DataType.DECIMAL else (0, 0)
        return AccSpec(fn, (("sum", sdt, "sum"), ("has", DataType.BOOL, "or")),
                       (sdt, sp, ss))
    if fn == "avg":
        if dt == DataType.DECIMAL:
            # Spark: avg(decimal(p,s)) → decimal(p+4, s+4) (precision cap
            # 38 wide / 18 narrow). The sum accumulates at the INPUT
            # scale; the finalizer shifts to the result scale inside the
            # division (q*10^k + round(r*10^k/count)) so only genuinely
            # overflowing totals wrap the representation
            if wide or p + 4 > 18:
                # Spark promotes past 18 digits: avg(decimal(16,2)) is
                # decimal(20,6) — narrow inputs with p in 15..18 route
                # through the two-limb representation for the result
                rp, rs = decimal_avg_result(p, s)
                sp, kind = min(p + 10, 38), "dsum"
            else:
                rp = p + 4
                rs = min(s + 4, rp)
                sp, kind = min(p + 10, 18), "sum"
            # the count field's (otherwise unused) precision/scale slots
            # carry the RESULT (p, s) so a final-mode op rebuilt from the
            # partial schema recovers the exact Spark avg type — the
            # capped sum-state type alone is not invertible
            return AccSpec(fn, (("sum", DataType.DECIMAL, kind),
                                ("count", DataType.INT64, "sum")),
                           (DataType.DECIMAL, rp, rs),
                           state_ps=((sp, s), (rp, rs)))
        sdt = _SUM_DTYPE[dt]
        res = (DataType.FLOAT64, 0, 0)
        return AccSpec(fn, (("sum", sdt, "sum"), ("count", DataType.INT64, "sum")),
                       res)
    if fn in ("min", "max"):
        if dt == DataType.STRING:
            # single state field; validity rides inside the string acc
            # tuple (chars, lens, valid) — see _reduce_sorted's _STR_KINDS
            return AccSpec(fn, (("val", DataType.STRING, f"s{fn}"),),
                           (dt, p, s))
        if wide:
            return AccSpec(fn, (("val", DataType.DECIMAL, f"d{fn}"),
                                ("has", DataType.BOOL, "or")), (dt, p, s))
        return AccSpec(fn, (("val", dt, fn), ("has", DataType.BOOL, "or")),
                       (dt, p, s))
    if fn in ("first", "first_ignores_null"):
        if dt == DataType.STRING:
            kind = "sfirst_ign" if fn == "first_ignores_null" else "sfirst"
            return AccSpec(fn, (("val", DataType.STRING, kind),), (dt, p, s))
        kind = "dfirst" if wide else "first"
        return AccSpec(fn, (("val", dt, kind), ("has", DataType.BOOL, "or")),
                       (dt, p, s))
    if fn in ("collect_list", "collect_set"):
        if dt in (DataType.STRING, DataType.LIST):
            raise NotImplementedError(f"{fn} over {dt.value}")
        if wide:
            # two-limb elements: the (p, s) of the ELEMENT type rides the
            # result's precision/scale slots (a LIST result has no other
            # use for them) so serde/arrow can rebuild decimal128 values
            return AccSpec(fn, (("list", dt, f"d{fn}"),),
                           (DataType.LIST, p, s), elem=dt)
        # narrow decimal elements carry their (p, s) the same way so the
        # arrow boundary renders list<decimal(p,s)>, not raw scaled ints
        return AccSpec(fn, (("list", dt, fn),), (DataType.LIST, p, s),
                       elem=dt)
    raise NotImplementedError(f"aggregate function {fn}")


def _device_fields(spec: AccSpec) -> tuple:
    """State fields accumulated on device (everything but bloom/udaf)."""
    return tuple(f for f in spec.state_fields if f[2] not in HOST_KINDS)


def _list_column_from_acc(acc, validity):
    """(values[cap, E], lens[cap]) list accumulator → ListColumn (all
    elements below lens are valid: collect_* skip nulls on input)."""
    from auron_tpu.columnar.batch import ListColumn
    vals, lens = acc
    ev = (jnp.arange(vals.shape[1], dtype=jnp.int32)[None, :]
          < lens[:, None])
    return ListColumn(vals, ev, lens, validity)


def _map_carrier_from_dacc(acc, validity):
    """(hi[cap, E], lo[cap, E], lens[cap]) dcollect accumulator → the
    MapColumn carrier used for list<decimal128> state/output columns
    (hi→keys, lo→values; all in-range elements valid — collect skips
    nulls on input)."""
    from auron_tpu.columnar.batch import MapColumn
    hi, lo, lens = acc
    ev = (jnp.arange(hi.shape[1], dtype=jnp.int32)[None, :]
          < lens[:, None])
    return MapColumn(hi, lo, ev, lens, validity)


def _unify_acc_pair(accs_a: tuple, accs_b: tuple) -> tuple[tuple, tuple]:
    """Pad the trailing (element-count / char-width) dimension of paired
    tuple accumulators so state and batch sides can merge shape-to-shape."""
    def _pad2d(t, e):
        # every 2-D member widens (limb-pair lists carry TWO matrices;
        # strings carry one char matrix); 1-D lens/validity stay as-is
        return tuple(jnp.pad(x, ((0, 0), (0, e - x.shape[1])))
                     if x.ndim == 2 and x.shape[1] < e else x for x in t)

    out_a, out_b = [], []
    for a, b in zip(accs_a, accs_b):
        if isinstance(a, tuple) and a[0].ndim == 2:   # list/string accs;
            # decimal limb pairs are 1-D and width-free
            e = max(a[0].shape[1], b[0].shape[1])
            a = _pad2d(a, e)
            b = _pad2d(b, e)
        out_a.append(a)
        out_b.append(b)
    return tuple(out_a), tuple(out_b)


# neutral elements per reduce kind
def _neutral(kind: str, dtype):
    if kind == "sum":
        return jnp.asarray(0, dtype)
    if kind == "min":
        if jnp.issubdtype(dtype, jnp.floating):
            return jnp.asarray(jnp.inf, dtype)
        return jnp.asarray(jnp.iinfo(dtype).max, dtype)
    if kind == "max":
        if jnp.issubdtype(dtype, jnp.floating):
            return jnp.asarray(-jnp.inf, dtype)
        return jnp.asarray(jnp.iinfo(dtype).min, dtype)
    if kind == "or":
        return jnp.asarray(False, jnp.bool_)
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# merge kernel
# ---------------------------------------------------------------------------

def _keys_equal_prev(sorted_keys, live):
    """eq[i] = keys[i] == keys[i-1] (null == null true, NaN == NaN,
    struct fieldwise; eq[0] = False)."""
    from auron_tpu.ops.hashing import adjacent_eq
    eq = jnp.ones_like(live)
    for col in sorted_keys:
        eq = eq & jnp.concatenate([jnp.zeros(1, bool), adjacent_eq(col)])
    return eq


#: dead rows / invalid state slots carry this hash so they sort last; the
#: (astronomically unlikely) real hash equal to it is still correct — such
#: rows group among themselves via the exact key compare
_HASH_SENTINEL = np.uint64(0xFFFFFFFFFFFFFFFF)


def _gather_acc(acc, perm):
    if isinstance(acc, tuple):
        return tuple(x[perm] for x in acc)
    return acc[perm]


def _reduce_sorted(keys_s, accs_s, live_s, h_s, acc_meta, out_cap):
    """Group + reduce rows that are ALREADY sorted by (dead-last, hash
    asc). Shared by the batch-reduce and state-merge kernels. Returns
    (new_keys, new_accs, h_out, num_groups, needed_elems); outputs stay
    hash-sorted (reps are increasing), which is the state invariant the
    merge-by-searchsorted path relies on."""
    cap = live_s.shape[0]
    same_hash = jnp.concatenate(
        [jnp.zeros(1, bool), h_s[1:] == h_s[:-1]])
    same_keys = _keys_equal_prev(keys_s, live_s)
    boundary = live_s & ~(same_hash & same_keys)
    gid = jnp.cumsum(boundary.astype(jnp.int32)) - 1
    gid = jnp.maximum(gid, 0)
    num_groups = jnp.sum(boundary.astype(jnp.int32))

    # first sorted row of each group → representative for keys
    rep = jax.ops.segment_min(
        jnp.where(live_s, jnp.arange(cap, dtype=jnp.int32), cap),
        gid, num_segments=out_cap)
    rep = jnp.clip(rep, 0, cap - 1)
    out_valid = jnp.arange(out_cap, dtype=jnp.int32) < num_groups
    new_keys = tuple(gather_column(c, rep, out_valid) for c in keys_s)
    h_out = jnp.where(out_valid, h_s[rep], _HASH_SENTINEL)

    new_accs = []
    needed_elems = []
    for (kind, out_elems), acc in zip(acc_meta, accs_s):
        if kind in ("collect_list", "collect_set") or kind in _DCOLLECT:
            # acc = (vals[cap, in_E], lens) — or limb pairs
            # (hi[cap, in_E], lo[cap, in_E], lens) for the dcollect kinds;
            # the offsets/scatter logic is per-matrix and shared
            *mats, lens_in = acc
            in_e = mats[0].shape[1]
            lens_s = jnp.where(live_s, lens_in, 0)
            # within-group exclusive element offset: global exclusive
            # cumsum minus the group's base (cumsum at its first row)
            cum = jnp.cumsum(lens_s)
            excl = cum - lens_s
            base = excl[rep]          # [out_cap]
            start = excl - base[gid]
            j = jnp.arange(in_e, dtype=jnp.int32)[None, :]
            flat = gid[:, None] * out_elems + start[:, None] + j
            ok = (live_s[:, None] & (j < lens_s[:, None])
                  & ((start[:, None] + j) < out_elems))
            flat = jnp.where(ok, flat, out_cap * out_elems)

            def scatter(m_s, _flat=flat):
                buf = jnp.zeros((out_cap * out_elems,), m_s.dtype).at[
                    _flat.reshape(-1)].set(m_s.reshape(-1), mode="drop")
                return buf.reshape(out_cap, out_elems)

            out_mats = [scatter(m) for m in mats]
            glens_raw = jax.ops.segment_sum(lens_s, gid,
                                            num_segments=out_cap)
            needed_elems.append(jnp.max(glens_raw))
            glens = jnp.minimum(glens_raw, out_elems)
            if kind in ("collect_set", "dcollect_set"):
                # per-group dedupe, sort-based so memory stays
                # O(cap * E): row-wise lexsort by (is_pad, value...) pushes
                # padding last and groups equal values adjacently; keep
                # first-of-run, compact left. Set order is unspecified
                # (as in Spark), so reordering is free. Limb pairs sort
                # and compare on (hi, lo) jointly.
                jj = jnp.arange(out_elems, dtype=jnp.int32)
                pad = jj[None, :] >= glens[:, None]
                sorted_ops = jax.lax.sort(
                    (pad, *out_mats), dimension=1,
                    num_keys=1 + len(out_mats))
                s_pad, *s_mats = sorted_ops
                neq = s_mats[0][:, 1:] != s_mats[0][:, :-1]
                for m in s_mats[1:]:
                    neq = neq | (m[:, 1:] != m[:, :-1])
                keep = ~s_pad & jnp.concatenate(
                    [jnp.ones((out_cap, 1), bool), neq], axis=1)
                pos = jnp.cumsum(keep, axis=1) - 1
                row = jnp.arange(out_cap, dtype=jnp.int32)[:, None]
                flat2 = jnp.where(keep, row * out_elems + pos,
                                  out_cap * out_elems)
                out_mats = [scatter(m, flat2) for m in s_mats]
                glens = jnp.sum(keep, axis=1).astype(jnp.int32)
            new_accs.append((*out_mats, glens))
            continue
        if kind in _STR_KINDS:
            chars_s, lens_s, v = acc   # already sorted components
            v_s = v & live_s
            idx = jnp.arange(cap, dtype=jnp.int32)
            if kind in ("sfirst", "sfirst_ign"):
                # representative row per group: first sorted live row
                # (sfirst) or first sorted VALID row (sfirst_ign)
                cand = jnp.where(
                    v_s if kind == "sfirst_ign" else live_s, idx, cap)
                raw = jax.ops.segment_min(cand, gid,
                                          num_segments=out_cap)
                fi = jnp.clip(raw, 0, cap - 1)
                # raw == cap means NO qualifying row (all-null group in
                # sfirst_ign): the clipped index then points at an
                # unrelated row whose validity must not leak through
                res_valid = v_s[fi] & (raw < cap) & out_valid
                new_accs.append((chars_s[fi], lens_s[fi], res_valid))
                continue
            # smin/smax: string order reduces on the sort operator's
            # order-preserving words — rank every row by value with one
            # multi-word argsort, then segment_min of ranks picks each
            # group's winner (reference handles all Arrow types in its
            # AccColumn instead: datafusion-ext-plans/src/agg/acc.rs)
            from auron_tpu.ops.sort import order_words
            col_s = StringColumn(chars_s, lens_s, v_s)
            words = order_words(col_s, ascending=(kind == "smin"),
                                nulls_first=False)
            lw = lens_s.astype(jnp.uint64)  # tiebreak embedded NULs
            words.append(lw if kind == "smin" else ~lw)
            lead = jnp.where(v_s, jnp.uint64(0), jnp.uint64(1))
            vperm = idx
            for w in reversed([lead] + words):
                vperm = vperm[jnp.argsort(w[vperm], stable=True)]
            rank = jnp.zeros(cap, jnp.int32).at[vperm].set(idx)
            winner_rank = jax.ops.segment_min(
                jnp.where(v_s, rank, cap), gid, num_segments=out_cap)
            win = vperm[jnp.clip(winner_rank, 0, cap - 1)]
            has = jax.ops.segment_max(
                v_s.astype(jnp.int8), gid,
                num_segments=out_cap).astype(jnp.bool_)
            new_accs.append((chars_s[win], lens_s[win],
                             has & out_valid))
            continue
        if kind in _DEC_KINDS:
            h_acc, l_acc = acc     # int64 limb pair, already sorted
            if kind == "dsum":
                # carry-exact segmented 128-bit sum: split the unsigned low
                # limb into 32-bit halves, segment-sum each as int64 (a
                # half-sum of cap<=2^31 rows stays < 2^63), recombine with
                # explicit carries. Two's-complement makes the signed total
                # exact mod 2^128 (columnar/decimal128.py add128 contract)
                m32 = 0xFFFFFFFF
                lo_lo = jnp.where(live_s, l_acc & m32, 0)
                lo_hi = jnp.where(live_s, (l_acc >> 32) & m32, 0)
                hi_m = jnp.where(live_s, h_acc, 0)
                s_ll = jax.ops.segment_sum(lo_lo, gid, num_segments=out_cap)
                s_lh = jax.ops.segment_sum(lo_hi, gid, num_segments=out_cap)
                s_h = jax.ops.segment_sum(hi_m, gid, num_segments=out_cap)
                mid = (s_ll >> 32) + s_lh          # both non-negative
                out_lo = (s_ll & m32) | (mid << 32)
                out_hi = s_h + (mid >> 32)
                new_accs.append((out_hi, out_lo))
            elif kind in ("dmin", "dmax"):
                # lexicographic two-pass: signed compare on the high limb,
                # then unsigned compare (sign-flip trick) on the low limb
                # among rows tied at the group's winning high limb
                nh, nl = _DEC_NEUTRAL[kind]
                seg = jax.ops.segment_min if kind == "dmin" \
                    else jax.ops.segment_max
                mh = seg(jnp.where(live_s, h_acc, nh), gid,
                         num_segments=out_cap)
                tied = live_s & (h_acc == mh[gid])
                sign = -0x8000000000000000
                lx = jnp.where(tied, l_acc ^ sign,
                               0x7FFFFFFFFFFFFFFF if kind == "dmin"
                               else sign)
                ml = seg(lx, gid, num_segments=out_cap) ^ sign
                new_accs.append((mh, ml))
            else:   # dfirst: limb pair at the first sorted live row
                fi = jax.ops.segment_min(
                    jnp.where(live_s, jnp.arange(cap, dtype=jnp.int32),
                              cap), gid, num_segments=out_cap)
                fi = jnp.clip(fi, 0, cap - 1)
                new_accs.append((h_acc[fi], l_acc[fi]))
            continue
        acc_s = acc
        if kind == "first":
            # value at first sorted valid row; pair-reduce via segment_min
            # over (order, value-index)
            first_idx = jax.ops.segment_min(
                jnp.where(live_s, jnp.arange(cap, dtype=jnp.int32), cap),
                gid, num_segments=out_cap)
            first_idx = jnp.clip(first_idx, 0, cap - 1)
            new_accs.append(acc_s[first_idx])
            continue
        neutral = _neutral(kind, acc.dtype)
        masked = jnp.where(live_s, acc_s, neutral)
        if kind == "sum":
            red = jax.ops.segment_sum(masked, gid, num_segments=out_cap)
        elif kind == "min":
            red = jax.ops.segment_min(masked, gid, num_segments=out_cap)
        elif kind == "max":
            red = jax.ops.segment_max(masked, gid, num_segments=out_cap)
        elif kind == "or":
            red = jax.ops.segment_max(masked.astype(jnp.int8), gid,
                                      num_segments=out_cap).astype(jnp.bool_)
        else:
            raise ValueError(kind)
        new_accs.append(red)
    return new_keys, tuple(new_accs), h_out, num_groups, tuple(needed_elems)


def _cut(tree, cap: int):
    """The prefix ``[:cap]`` of every leaf of a group table's arrays
    (every leaf leads with the capacity): a table cut to its occupancy
    bucket. Live groups are a hash-sorted prefix, so the cut is a plain
    slice — taken inside the program that reads the table next."""
    return jax.tree_util.tree_map(lambda x: x[:cap], tree)


@program_cache("ops.agg.batch_reduce", maxsize=256)
def _batch_reduce_kernel(front, layout: tuple, acc_meta: tuple, cap: int,
                         donate: bool = False):
    """One batch → its own group table, hash-sorted: the front's
    evaluation of the batch's group keys and contributions
    (``front(columns, num_rows, partition_id) -> (keys, accs, live)``:
    plan data, traced here; ``layout`` stands for the columns' shapes),
    then one O(B log B) sort of the BATCH only — the state is never
    re-sorted (it merges by binary search in _state_merge_kernel).
    acc_meta: tuple of (kind, out_elems) per state column. Returns
    (keys, accs, hashes, num_groups, needed_elems). ``donate`` hands the
    batch's columns to XLA — they are dead after the reduce when the
    child owns its batches and no collect kind can force the caller's
    growth retry (callers gate on exactly that; programs.jit keeps
    donation off the advisory CPU backend). The row count is an operand
    of its own and never donated: the caller may still read it."""
    from auron_tpu.runtime import programs

    def auron_ops_agg_batch_reduce(columns, num_rows, partition_id):
        keys, accs, live = front(columns, num_rows, partition_id)
        h = hashing.xxhash64_columns(list(keys), cap).view(jnp.uint64)
        h = jnp.where(live, h, _HASH_SENTINEL)  # dead rows to the end
        perm = jnp.argsort(h, stable=True)
        live_s = live[perm]
        keys_s = tuple(gather_column(c, perm, jnp.ones(cap, bool))
                       for c in keys)
        accs_s = tuple(_gather_acc(a, perm) for a in accs)
        return _reduce_sorted(keys_s, accs_s, live_s, h[perm], acc_meta, cap)

    # graft: donation-ok -- the owned batch's columns; collect
    # kinds/aliased leaves force donate=False upstream
    return programs.jit(auron_ops_agg_batch_reduce,
                        donate_argnums=(0,) if donate else ())


def _scatter_acc(a_s, a_b, pos_s, pos_b, m: int):
    """Merge two acc entries (state + batch groups) by scattering both to
    their merged positions."""
    if isinstance(a_s, tuple):
        out = []
        for xs, xb in zip(a_s, a_b):
            buf = jnp.zeros((m,) + xs.shape[1:], xs.dtype)
            buf = buf.at[pos_s].set(xs).at[pos_b].set(xb)
            out.append(buf)
        return tuple(out)
    buf = jnp.zeros((m,) + a_s.shape[1:], a_s.dtype)
    return buf.at[pos_s].set(a_s).at[pos_b].set(a_b)


@program_cache("ops.agg.state_merge", maxsize=256)
def _state_merge_kernel(n_keys: int, acc_meta: tuple, cap_s: int,
                        cap_b: int, out_cap: int):
    """Fold a hash-sorted batch group table into the hash-sorted state
    WITHOUT re-sorting the state: merge positions come from two
    searchsorted calls (O(B log S + S)), then one scatter interleaves both
    sides and the shared reduce folds duplicate groups. This is the
    incremental-update contract of the reference's AggTable (reference:
    datafusion-ext-plans/src/agg/agg_table.rs:68-356) with the
    open-addressing probe replaced by the sorted-merge primitive.
    ``cap_s`` / ``cap_b`` are the tables' occupancy buckets: a table
    that comes at the capacity of the program that made it is cut to
    its bucket here."""

    @jax.jit
    def auron_ops_agg_state_merge(keys_s, accs_s, h_s, n_s,
                                  keys_b, accs_b, h_b, n_b):
        keys_s, accs_s, h_s = _cut((keys_s, accs_s, h_s), cap_s)
        keys_b, accs_b, h_b = _cut((keys_b, accs_b, h_b), cap_b)
        unified = [unify_column_widths([a, c])
                   for a, c in zip(keys_s, keys_b)]
        keys_s = tuple(p[0] for p in unified)
        keys_b = tuple(p[1] for p in unified)
        accs_s, accs_b = _unify_acc_pair(accs_s, accs_b)
        live_s = jnp.arange(cap_s, dtype=jnp.int32) < n_s
        live_b = jnp.arange(cap_b, dtype=jnp.int32) < n_b
        # dead slots on both sides hold _HASH_SENTINEL (state invariant +
        # batch-reduce output), so they merge to the tail; side='left' for
        # state vs 'right' for batch keeps state rows first on hash ties
        # (so 'first' semantics prefer earlier batches) and makes the
        # combined position map a permutation of [0, cap_s + cap_b)
        pos_s = (jnp.arange(cap_s, dtype=jnp.int32)
                 + jnp.searchsorted(h_b, h_s, side="left").astype(jnp.int32))
        pos_b = (jnp.arange(cap_b, dtype=jnp.int32)
                 + jnp.searchsorted(h_s, h_b, side="right").astype(jnp.int32))
        m = cap_s + cap_b

        def scatter2(xs, xb):
            buf = jnp.zeros((m,) + xs.shape[1:], xs.dtype)
            return buf.at[pos_s].set(xs).at[pos_b].set(xb)

        def scatter_col(a, b):
            if isinstance(a, StringColumn):
                return StringColumn(scatter2(a.chars, b.chars),
                                    scatter2(a.lens, b.lens),
                                    scatter2(a.validity, b.validity))
            from auron_tpu.columnar.decimal128 import Decimal128Column
            if isinstance(a, Decimal128Column):
                return Decimal128Column(scatter2(a.hi, b.hi),
                                        scatter2(a.lo, b.lo),
                                        scatter2(a.validity, b.validity))
            from auron_tpu.columnar.batch import ListColumn, StructColumn
            if isinstance(a, ListColumn):
                return ListColumn(scatter2(a.values, b.values),
                                  scatter2(a.elem_valid, b.elem_valid),
                                  scatter2(a.lens, b.lens),
                                  scatter2(a.validity, b.validity))
            if isinstance(a, StructColumn):
                return StructColumn(
                    tuple(scatter_col(ca, cb)
                          for ca, cb in zip(a.children, b.children)),
                    scatter2(a.validity, b.validity))
            return PrimitiveColumn(scatter2(a.data, b.data),
                                   scatter2(a.validity, b.validity))

        keys_m = tuple(scatter_col(a, b) for a, b in zip(keys_s, keys_b))
        accs_m = tuple(_scatter_acc(a, b, pos_s, pos_b, m)
                       for a, b in zip(accs_s, accs_b))
        h_m = scatter2(h_s, h_b)
        live_m = scatter2(live_s, live_b)
        return _reduce_sorted(keys_m, accs_m, live_m, h_m, acc_meta, out_cap)

    return auron_ops_agg_state_merge


# ---------------------------------------------------------------------------
# the operator
# ---------------------------------------------------------------------------

def _table_nbytes(tbl) -> int:
    from auron_tpu.columnar.batch import column_nbytes
    keys, accs, _num_groups, cap, hashes = tbl
    held = (sum(column_nbytes(k) for k in keys)
            + hashes.nbytes
            + sum(sum(x.nbytes for x in a) if isinstance(a, tuple)
                  else a.nbytes for a in accs))
    # a table not yet cut to its occupancy bucket (the program that reads
    # it next cuts it) is accounted at the bucket
    return held * cap // hashes.shape[0]


def _lvl_nbytes(lvl) -> int:
    from auron_tpu.hashtable import HashAggState
    if isinstance(lvl, HashAggState):
        return lvl.nbytes()
    return _table_nbytes(lvl)


def _state_nbytes(state) -> int:
    """Device bytes of a (main, hot) accumulator state — or a
    hash-table-backed state level — from array metadata only."""
    if state is None:
        return 0
    return sum(_lvl_nbytes(lvl) for lvl in state if lvl is not None)


#: single shared NaN object so NaN group keys rendezvous in host dicts
_CANONICAL_NAN = float("nan")


def _column_pyvalues(col, n: int) -> list:
    """First n rows of a column as python values (None where invalid);
    struct rows become tuples of child values (hashable → usable as
    host-dict keys)."""
    from auron_tpu.columnar.batch import StructColumn
    if isinstance(col, StructColumn):
        kids = [_column_pyvalues(ch, n) for ch in col.children]
        val = _profile.timed_get(col.validity[:n])
        return [tuple(k[i] for k in kids) if val[i] else None
                for i in range(n)]
    if isinstance(col, StringColumn):
        chars, lens, val = _profile.timed_get(
            (col.chars[:n], col.lens[:n], col.validity[:n]))
        return [bytes(chars[i, :lens[i]]).decode("utf-8", "surrogateescape")
                if val[i] else None for i in range(n)]
    data, val = _profile.timed_get((col.data[:n], col.validity[:n]))
    return [data[i].item() if val[i] else None for i in range(n)]


def _key_tuples_host(key_cols, n: int) -> list[tuple]:
    """Group-key tuples for the first n state rows (host python values) —
    the rendezvous between device group state and host-side (udaf)
    accumulators, which are keyed by value."""
    if not key_cols:
        return [() for _ in range(n)]
    per_col = [_column_pyvalues(c, n) for c in key_cols]

    def canon(x):
        # keys only (NOT aggregate inputs — Spark's NormalizeNaNAndZero
        # applies to group/join/window keys alone): one shared NaN object
        # so NaN keys rendezvous in host dicts via identity; -0.0 → 0.0
        if isinstance(x, float):
            if x != x:
                return _CANONICAL_NAN
            if x == 0.0:
                return 0.0
        return x

    return [tuple(canon(c[i]) for c in per_col) for i in range(n)]


def _host_string_column(values: list, cap: int) -> StringColumn:
    """Build a device StringColumn from python str/None values."""
    from auron_tpu.utils.shapes import bucket_string_width
    enc = [None if v is None else v.encode() for v in values]
    width = bucket_string_width(max([len(b) for b in enc if b is not None],
                                    default=1) or 1)
    chars = np.zeros((cap, width), np.uint8)
    lens = np.zeros(cap, np.int32)
    val = np.zeros(cap, bool)
    for i, b in enumerate(enc):
        if b is None:
            continue
        chars[i, :len(b)] = np.frombuffer(b, np.uint8)
        lens[i] = len(b)
        val[i] = True
    return StringColumn(jnp.asarray(chars), jnp.asarray(lens),
                        jnp.asarray(val))


def _read_state_accs(fields, batch: DeviceBatch, idx: int, accs: list) -> int:
    """Append the accumulators held by one function's state columns:
    ``fields`` are its (name, dtype, kind) state fields, the first of
    them column ``idx`` of ``batch``. Returns the column after them."""
    for (fname, _fdt, kind) in fields:
        col = batch.columns[idx]
        idx += 1
        if kind in HOST_KINDS:
            continue              # merged host-side (_HostAggState)
        if kind in ("collect_list", "collect_set"):
            accs.append((col.values, jnp.where(col.validity, col.lens, 0)))
        elif kind in _DCOLLECT:
            accs.append((col.keys, col.values,
                         jnp.where(col.validity, col.lens, 0)))
        elif kind in _STR_KINDS:
            accs.append((col.chars, col.lens, col.validity))
        elif kind in _DEC_KINDS:
            # limb pair; invalid state rows already hold the
            # reduce-neutral (partial emit / passthrough neutralized
            # them), so no re-masking needed
            accs.append((col.hi, col.lo))
        elif fname == "has":
            accs.append(col.data.astype(jnp.bool_) & col.validity)
        else:
            accs.append(col.data)   # min/max/first: validity is 'has'
    return idx


def _contribution_columns(group_exprs, state_idx: tuple, aggs, specs,
                          batch: DeviceBatch, in_schema: Schema,
                          ctx: EvalContext):
    """Evaluate group keys and per-row initial accumulator columns.
    ``state_idx`` says, function by function, where its input is: the
    column its state begins at (a function that merges: partial_merge,
    final), or None for one that starts from rows (partial, complete).

    Module-level (plan data in, columns out) so traced closures can use
    it without capturing the AggOp — the combine fold's stage closure
    (``build_combine_stage``) lands in the process-wide split-program
    cache, where a captured op would pin its whole subtree (including
    any broadcast build buffers below it) for the cache's lifetime."""
    keys = tuple(evaluate(e, batch, in_schema, ctx).col
                 for e in group_exprs)
    accs = []
    live = batch.row_mask()
    for agg, spec, idx in zip(aggs, specs, state_idx):
        if idx is not None:
            _read_state_accs(spec.state_fields, batch, idx, accs)
            continue
        if spec.state_fields and spec.state_fields[0][2] in HOST_KINDS:
            continue              # accumulated host-side
        if spec.state_fields[0][2] in ("collect_list", "collect_set"):
            # collect_* and the DISTINCT aggs share the padded-list
            # accumulator (one-element list per valid row; len 0
            # where null: Spark collect_*/distinct skip nulls)
            v = evaluate(agg.arg, batch, in_schema, ctx)
            if not isinstance(v.col, PrimitiveColumn):
                raise NotImplementedError(f"{agg.fn} over non-primitives")
            valid = v.validity & live
            accs.append((v.col.data[:, None], valid.astype(jnp.int32)))
            continue
        if spec.state_fields[0][2] in _DCOLLECT:
            from auron_tpu.columnar.decimal128 import Decimal128Column
            v = evaluate(agg.arg, batch, in_schema, ctx)
            if not isinstance(v.col, Decimal128Column):
                raise NotImplementedError(
                    f"{agg.fn}: expected two-limb decimal input")
            valid = v.validity & live
            accs.append((v.col.hi[:, None], v.col.lo[:, None],
                         valid.astype(jnp.int32)))
            continue
        if agg.fn in ("count", "count_star"):
            if agg.arg is None:
                c = live.astype(jnp.int64)
            else:
                v = evaluate(agg.arg, batch, in_schema, ctx)
                c = (v.validity & live).astype(jnp.int64)
            accs.append(c)
            continue
        v = evaluate(agg.arg, batch, in_schema, ctx)
        valid = v.validity & live
        if isinstance(v.col, StringColumn):
            if spec.state_fields[0][2] in _STR_KINDS:
                accs.append((v.col.chars, v.col.lens, valid))
                continue
            raise NotImplementedError(f"{agg.fn} over strings")
        from auron_tpu.columnar.decimal128 import Decimal128Column
        needs_limbs = any(k in _DEC_KINDS
                          for _f, _d, k in spec.state_fields)
        if isinstance(v.col, Decimal128Column) or needs_limbs:
            if isinstance(v.col, Decimal128Column):
                hi, lo = v.col.hi, v.col.lo
            else:
                # narrow decimal input promoted to two limbs: avg
                # with p+4>18 accumulates/returns wide (Spark
                # DecimalType.bounded promotion past 18 digits)
                from auron_tpu.columnar import decimal128 as d128
                hi, lo = d128.from_int64(v.col.data.astype(jnp.int64))
            for fname, fdt, kind in spec.state_fields:
                if fname == "has":
                    accs.append(valid)
                elif fname == "count":
                    accs.append(valid.astype(jnp.int64))
                elif kind == "dsum":
                    accs.append((jnp.where(valid, hi, 0),
                                 jnp.where(valid, lo, 0)))
                elif kind in ("dmin", "dmax"):
                    nh, nl = _DEC_NEUTRAL[kind]
                    accs.append((jnp.where(valid, hi, nh),
                                 jnp.where(valid, lo, nl)))
                elif kind == "dfirst":
                    accs.append((hi, lo))
                else:
                    raise ValueError(kind)
            continue
        for fname, fdt, kind in spec.state_fields:
            if fname == "has":
                accs.append(valid)
            elif fname == "count":
                accs.append(valid.astype(jnp.int64))
            elif kind == "sum":
                jdt = _JNPT[fdt]
                accs.append(jnp.where(valid, v.data, 0).astype(jdt))
            elif kind in ("min", "max"):
                neutral = _neutral(kind, v.data.dtype)
                accs.append(jnp.where(valid, v.data, neutral))
            elif kind == "first":
                accs.append(v.data)
            else:
                raise ValueError(kind)
    return keys, accs, live


def _passthrough_state_batch(keys, accs, live, num_rows) -> DeviceBatch:
    """One input batch re-expressed in partial-state layout without
    merging — each row is its own group (adaptive partial-agg
    skipping, reference: agg/agg_ctx.rs:63-196). Module-level for the
    same no-captured-op rule as ``_contribution_columns``."""
    cols = list(keys)
    for a in accs:
        if isinstance(a, tuple) and len(a) == 3:
            cols.append(StringColumn(a[0], a[1], a[2]))
        elif isinstance(a, tuple) and a[0].ndim == 1:
            from auron_tpu.columnar.decimal128 import Decimal128Column
            cols.append(Decimal128Column(a[0], a[1], live))
        elif isinstance(a, tuple):
            cols.append(_list_column_from_acc(a, live))
        else:
            cols.append(PrimitiveColumn(a, live))
    return DeviceBatch(tuple(cols), num_rows)


# ---------------------------------------------------------------------------
# fronts: a batch → its group keys and contributions, inside the program
# that consumes them
# ---------------------------------------------------------------------------
#
# Everything the operator does to a device array runs inside a program of
# runtime/programs.py. What turns an input batch into (keys, accs, live) is
# therefore PLAN DATA — a hashable callable the consuming program traces:
# the hash table's step (hashtable.agg_step), the batch reduce of the sort
# path and of an aggregation without keys (ops.agg.batch_reduce), the
# partial skip's pass-through. Aggregations with equal expressions over
# equal column types share their programs.

@dataclass(frozen=True)
class _RowsFront:
    """An operator's input batch — rows, or the state columns of the
    functions that merge — to its group keys and per-row contributions
    (``_contribution_columns``)."""
    group_exprs: tuple
    state_idx: tuple
    aggs: tuple
    specs: tuple
    in_schema: Schema

    def __call__(self, columns, num_rows, partition_id):
        ctx = EvalContext(partition_id=partition_id, memo={})
        keys, accs, live = _contribution_columns(
            self.group_exprs, self.state_idx, self.aggs, self.specs,
            DeviceBatch(tuple(columns), num_rows), self.in_schema, ctx)
        return keys, tuple(accs), live


@dataclass(frozen=True)
class _StateFront:
    """A batch in the operator's OWN state layout — its keys, then every
    device accumulator — back to contributions: how a spilled run, and a
    sorted state the hash table takes over, re-enter the merge
    (associativity of the accumulators makes re-merging exact)."""
    n_keys: int
    specs: tuple

    def __call__(self, columns, num_rows, partition_id):
        batch = DeviceBatch(tuple(columns), num_rows)
        accs: list = []
        idx = self.n_keys
        for spec in self.specs:
            idx = _read_state_accs(_device_fields(spec), batch, idx, accs)
        return tuple(columns[:self.n_keys]), tuple(accs), batch.row_mask()


class _Input(NamedTuple):
    """One batch as the merge takes it: ``front(*operands)`` is its
    (keys, accs, live), evaluated INSIDE the program that folds them into
    the state; ``layout`` stands for the operands' shapes in that
    program's key and ``shapes`` is the triple in the abstract — what the
    host needs of it beforehand (``_front_shapes``)."""
    front: object
    operands: tuple
    layout: tuple
    shapes: tuple

    @property
    def capacity(self) -> int:
        return self.shapes[2].shape[0]


@lru_cache(maxsize=512)
def _front_shapes(front, layout: tuple, capacity: int):
    """``front``'s (keys, accs, live) over a batch of this leaf layout
    and capacity, in the abstract: what the host needs of a batch's
    contributions before the program that evaluates them runs — the key
    codec and the accumulators' dtypes (the hash table's layout), the
    collect kinds' element widths (the reduce's). No program and no
    device array: a memo of one ``jax.eval_shape`` over plan data."""
    treedef, leaves = layout
    columns = jax.tree_util.tree_unflatten(
        treedef, [jax.ShapeDtypeStruct((capacity,) + shape, dtype)
                  for shape, dtype in leaves])
    scalar = jax.ShapeDtypeStruct((), jnp.int32)
    return jax.eval_shape(front, columns, scalar, scalar)


@program_cache("ops.agg.passthrough", maxsize=128)
def _passthrough_kernel(front, layout: tuple, capacity: int):
    """The partial skip's pass-through of one batch (each row its own
    group, in state layout) as one program."""

    @jax.jit
    def auron_ops_agg_passthrough(columns, num_rows, partition_id):
        keys, accs, live = front(columns, num_rows, partition_id)
        return _passthrough_state_batch(keys, accs, live, num_rows)

    return auron_ops_agg_passthrough


# ---------------------------------------------------------------------------
# emit: a group table → the operator's output batch, as one program
# ---------------------------------------------------------------------------

def _host_slots(specs: tuple, as_state: bool) -> list:
    """(position among the output's aggregate columns, spec index) of
    every column a host-side accumulator (bloom / udaf) fills: the emit
    program returns the device columns, these are spliced in after it."""
    slots, pos = [], 0
    for si, spec in enumerate(specs):
        fields = spec.state_fields
        host = bool(fields) and fields[0][2] in HOST_KINDS
        if host:
            slots.append((pos, si))
        pos += len(fields) if as_state else 1
    return slots


def _finalize_columns(specs: tuple, as_state: bool, accs, valid) -> list:
    """The aggregate columns of an output batch from a group table's
    accumulators — in state layout (``partial`` / ``partial_merge``; a
    spilled run) or finalized (``final`` / ``complete``) — host-side
    functions left out. Traced: ``ops.agg.emit``."""
    from auron_tpu.columnar import decimal128 as d128
    from auron_tpu.columnar.decimal128 import Decimal128Column
    out_cols = []

    def list_col(a):
        return _list_column_from_acc(a, valid)

    if as_state:
        i = 0
        for spec in specs:
            for (fname, fdt, kind) in _device_fields(spec):
                data = accs[i]
                i += 1
                if kind in _DCOLLECT:
                    out_cols.append(_map_carrier_from_dacc(data, valid))
                elif isinstance(data, tuple) and len(data) == 3:
                    out_cols.append(StringColumn(
                        data[0], data[1], data[2] & valid))
                elif isinstance(data, tuple) and data[0].ndim == 1:
                    out_cols.append(Decimal128Column(
                        data[0], data[1], valid))
                elif isinstance(data, tuple):
                    out_cols.append(list_col(data))
                else:
                    out_cols.append(PrimitiveColumn(data, valid))
        return out_cols

    # final/complete: finalize each agg
    i = 0
    for spec in specs:
        n_state = len(_device_fields(spec))
        state_vals = accs[i: i + n_state]
        i += n_state
        fn = spec.fn
        if fn in ("count", "count_star"):
            out_cols.append(PrimitiveColumn(state_vals[0], valid))
        elif fn == "sum":
            s, has = state_vals
            if isinstance(s, tuple):
                h, l = s
                # Spark non-ANSI: overflow beyond the declared
                # precision nulls the group
                fits = d128.fits_precision(h, l, spec.result[1])
                out_cols.append(Decimal128Column(
                    h, l, valid & has & fits))
            else:
                out_cols.append(PrimitiveColumn(s, valid & has))
        elif fn == "avg":
            s, cnt = state_vals
            res_dt = spec.result[0]
            safe = jnp.maximum(cnt, 1)
            if isinstance(s, tuple):
                # two-limb sum at the input scale: shift to the
                # result scale inside the HALF_UP division; Spark
                # nulls averages that overflow decimal(38)
                k = spec.result[2] - spec.state_ps[0][1]
                qh, ql, fits = d128.avg_pow10_div_half_up(
                    s[0], s[1], safe, k)
                out_cols.append(Decimal128Column(
                    qh, ql, valid & (cnt > 0) & fits))
            elif res_dt == DataType.DECIMAL:
                # scaled-int64 sum at the input scale; same
                # q*10^k + round(r*10^k/count) composition in
                # int64, overflow past the 18-digit result → null
                k = spec.result[2] - spec.state_ps[0][1]
                shift = 10 ** k
                a = jnp.abs(s)
                q0 = a // safe
                rem = a - q0 * safe
                fits = q0 < 10 ** (18 - k)
                frac = (2 * rem * shift + safe) // (2 * safe)
                q = q0 * shift + frac
                avg = jnp.where(s < 0, -q, q)
                out_cols.append(PrimitiveColumn(
                    avg, valid & (cnt > 0) & fits))
            else:
                avg = s.astype(jnp.float64) / safe
                out_cols.append(PrimitiveColumn(
                    avg, valid & (cnt > 0)))
        elif fn in ("min", "max", "first", "first_ignores_null"):
            if len(state_vals) == 1:   # string acc: validity inside
                chars, lens, sv = state_vals[0]
                out_cols.append(StringColumn(chars, lens, sv & valid))
            elif isinstance(state_vals[0], tuple):
                (h, l), has = state_vals
                out_cols.append(Decimal128Column(h, l, valid & has))
            else:
                v, has = state_vals
                out_cols.append(PrimitiveColumn(v, valid & has))
        elif fn in ("collect_list", "collect_set"):
            # empty list (not null) for groups with only nulls —
            # Spark's collect_* semantics
            if spec.state_fields[0][2] in _DCOLLECT:
                out_cols.append(_map_carrier_from_dacc(
                    state_vals[0], valid))
            else:
                out_cols.append(list_col(state_vals[0]))
        elif fn in ("count_distinct", "sum_distinct", "avg_distinct"):
            vals, lens = state_vals[0]  # deduped set per group
            if fn == "count_distinct":
                out_cols.append(PrimitiveColumn(
                    lens.astype(jnp.int64), valid))
            else:
                e = vals.shape[1]
                mask = (jnp.arange(e, dtype=jnp.int32)[None, :]
                        < lens[:, None])
                jdt = _JNPT[spec.result[0]]
                s = jnp.sum(jnp.where(mask, vals, 0),
                            axis=1).astype(jdt)
                if fn == "avg_distinct":
                    s = (s.astype(jnp.float64)
                         / jnp.maximum(lens, 1))
                # all-null group: no distinct values → NULL
                out_cols.append(PrimitiveColumn(s, valid & (lens > 0)))
        elif spec.state_fields and spec.state_fields[0][2] in HOST_KINDS:
            continue            # spliced in by the host (_host_slots)
        else:
            raise NotImplementedError(fn)
    return out_cols


@program_cache("ops.agg.emit", maxsize=512)
def _emit_kernel(specs: tuple, as_state: bool, out_cap: int,
                 table_meta: Optional[tuple]):
    """A group table → the operator's output batch (or its state as a
    batch, for a spill and for the hash table's take-over of a sorted
    state) as ONE program: the hash table's export where ``table_meta``
    is its key codec (the operands are then its slots: hashes, key store,
    accumulators), the cut to ``out_cap`` — the table's occupancy bucket,
    which the host picks from a count it has — and every function's
    finalisation. The operands' layouts key jax's own trace."""
    from auron_tpu.hashtable.agg import export_slots

    @jax.jit
    def auron_ops_agg_emit(*table):
        if table_meta is not None:
            th, store, accs = table
            keys, accs, num_groups, _h = export_slots(th, store, accs,
                                                      table_meta)
        else:
            keys, accs, num_groups = table
        keys, accs = _cut((keys, accs), out_cap)
        valid = jnp.arange(out_cap, dtype=jnp.int32) < num_groups
        cols = list(keys) + _finalize_columns(specs, as_state, accs, valid)
        return DeviceBatch(tuple(cols), num_groups)

    return auron_ops_agg_emit


class _HostAggState:
    """Host-side accumulation for bloom_filter and host-UDAF aggregates.

    The reference routes these through its JVM fallback (reference:
    datafusion-ext-plans/src/agg/spark_udaf_wrapper.rs:52-380 — per-group
    JVM buffer rows with update/merge/eval/spill entry points) and builds
    runtime bloom filters natively (agg/bloom_filter.rs). Here both are
    host-python escape hatches: udaf buffers live in a dict keyed by group
    key values (the value-keyed analogue of the wrapper's index caches),
    bloom filters accumulate via the vectorized SparkBloomFilter builder.
    State travels between partial/final stages as base64 inside STRING
    columns.

    Round 3: the buffer dict is spill-managed — it registers with the
    memory manager (size estimated from a sampled pickled buffer), and
    under pressure the whole dict serializes to tiered storage (the
    wrapper's spill/unspill entry points, spark_udaf_wrapper.rs:52-380);
    spilled states fold back in via udaf.merge before emit. Per-batch
    updates are bucketed per group so a UDAF exposing a vectorized
    ``update_batch(buf, values)`` hook is called once per group, not once
    per row.
    """

    consumer_name = "host-agg"

    def __init__(self, op: "AggOp", in_schema: Schema, mem=None,
                 metrics=None):
        self.op = op
        self.in_schema = in_schema
        self.mem = mem
        self.metrics = metrics
        self.entries: dict[int, list] = {}
        self.spills = []
        import threading
        self._buf_size_sample = 64
        self._sampled_at = 0     # group count at last buffer-size sample
        self._emitting = False   # spill() refuses once emit has begun
        #: guards the buffer dicts against an externally-triggered victim
        #: spill landing mid-update (same role as the device consumer's
        #: refuse-while-merging protocol)
        self._lock = threading.RLock()
        for si, (agg, spec) in enumerate(zip(op.aggs, op.specs)):
            if spec.fn == "bloom_filter":
                from auron_tpu.exprs.bloom import SparkBloomFilter
                if op.group_exprs:
                    raise NotImplementedError(
                        "bloom_filter aggregate with group keys")
                items = agg.expected_items or 100_000
                self.entries[si] = ["bloom", SparkBloomFilter.create(
                    items, agg.fpp or 0.03)]
            elif spec.fn.startswith("udaf:"):
                from auron_tpu.exprs.udf import lookup_udaf
                self.entries[si] = ["udaf", lookup_udaf(spec.fn[5:]), {}]
        self._spillable = (
            mem is not None
            and getattr(mem, "spill_manager", None) is not None
            and any(e[0] == "udaf" for e in self.entries.values()))
        if self._spillable:
            self.consumer_name = f"host-agg-{id(op):x}"
            mem.register_consumer(self)

    def empty(self) -> bool:
        return not self.entries

    def has_bloom(self) -> bool:
        return any(e[0] == "bloom" for e in self.entries.values())

    # -- MemConsumer ---------------------------------------------------------

    def _n_buffers(self) -> int:
        return sum(len(e[2]) for e in self.entries.values()
                   if e[0] == "udaf")

    def mem_used(self) -> int:
        # per-buffer estimate from a sampled pickle + dict/key overhead
        return self._n_buffers() * (self._buf_size_sample + 96)

    def _account(self) -> None:
        if self._spillable:
            self.mem.update_mem_used(self, self.mem_used())

    def spill(self) -> int:
        """Serialize every UDAF buffer dict to tiered storage and clear.
        Refuses during emit — the restored dict is being read — and takes
        the state lock so a victim spill can't snapshot-and-clear a dict
        another thread's update() is mutating."""
        import pickle
        with self._lock:
            if not self._spillable or self._n_buffers() == 0 \
                    or self._emitting:
                return 0
            freed = self.mem_used()
            payload = {si: list(e[2].items())
                       for si, e in self.entries.items()
                       if e[0] == "udaf"}
            for e in self.entries.values():
                if e[0] == "udaf":
                    e[2].clear()
        spill = self.mem.spill_manager.new_spill()
        spill.write_frame(pickle.dumps(payload))
        self.spills.append(spill.finish())
        if self.metrics is not None:
            self.metrics.counter("mem_spill_count").add(1)
            self.metrics.counter("mem_spill_size").add(freed)
        self.mem.update_mem_used(self, 0)
        return freed

    def restore_spills(self) -> None:
        """Fold spilled buffer dicts back in (udaf.merge) before emit;
        latches the emit phase, which blocks further spills of this
        state."""
        import pickle
        with self._lock:
            self._emitting = True
        if not self.spills:
            return
        spills, self.spills = self.spills, []
        for sp in spills:
            for frame in sp.frames():
                payload = pickle.loads(frame)
                for si, items in payload.items():
                    ent = self.entries.get(si)
                    if ent is None or ent[0] != "udaf":
                        continue
                    _, udaf, bufs = ent
                    for kt, buf in items:
                        old = bufs.get(kt)
                        bufs[kt] = buf if old is None \
                            else udaf.merge(old, buf)
            sp.release()

    def close(self) -> None:
        if self._spillable:
            self.mem.unregister_consumer(self)
        for sp in self.spills:
            sp.release()
        self.spills = []

    # -- update (partial / complete input rows) -----------------------------

    def update(self, batch: DeviceBatch, ectx: EvalContext) -> None:
        if not self.entries:
            return
        with self._lock:
            self._update_locked(batch, ectx)

    def _update_locked(self, batch: DeviceBatch, ectx: EvalContext) -> None:
        n = _profile.row_count(batch)
        key_tuples = None
        for si, ent in self.entries.items():
            agg = self.op.aggs[si]
            v = evaluate(agg.arg, batch, self.in_schema, ectx)
            if ent[0] == "bloom":
                data, valid = _profile.timed_get(
                    (v.col.data[:n], (v.validity & batch.row_mask())[:n]))
                ent[1].put_longs(data[valid].astype(np.int64))
            else:
                _, udaf, bufs = ent
                if key_tuples is None:
                    key_cols = [evaluate(e, batch, self.in_schema, ectx).col
                                for e in self.op.group_exprs]
                    key_tuples = _key_tuples_host(key_cols, n)
                vals = _column_pyvalues(v.col.with_validity(
                    v.validity & batch.row_mask()), n)
                # bucket rows by group: one update(_batch) call per group
                from collections import defaultdict
                per_group: dict = defaultdict(list)
                for i in range(n):
                    per_group[key_tuples[i]].append(vals[i])
                update_batch = getattr(udaf, "update_batch", None)
                for kt, group_vals in per_group.items():
                    buf = bufs.get(kt)
                    if buf is None:
                        buf = udaf.zero()
                    if update_batch is not None:
                        bufs[kt] = update_batch(buf, group_vals)
                    else:
                        for gv in group_vals:
                            buf = udaf.update(buf, gv)
                        bufs[kt] = buf
        self._sample_buf_size()
        self._account()

    def _sample_buf_size(self) -> None:
        # re-sample only when the group count doubles: pickling a large
        # accumulator every batch would make the hot path O(buffer bytes)
        import pickle
        n = self._n_buffers()
        if n < max(self._sampled_at * 2, 1):
            return
        self._sampled_at = n
        for e in self.entries.values():
            if e[0] == "udaf" and e[2]:
                buf = next(iter(e[2].values()))
                try:
                    self._buf_size_sample = max(
                        self._buf_size_sample, len(pickle.dumps(buf)))
                except Exception:   # graft: disable=GL004 -- size sampling is advisory; an unpicklable UDAF buffer must not fail the query
                    pass
                break

    # -- merge (final-mode input rows carry serialized states) --------------

    def merge_partial(self, batch: DeviceBatch) -> None:
        if not self.entries:
            return
        with self._lock:
            self._merge_partial_locked(batch)

    def _merge_partial_locked(self, batch: DeviceBatch) -> None:
        import base64
        import pickle
        n = _profile.row_count(batch)
        n_keys = len(self.op.group_exprs)
        key_tuples = _key_tuples_host(batch.columns[:n_keys], n)
        for si, ent in self.entries.items():
            col = batch.columns[self.op.state_idx[si]]
            states = _column_pyvalues(col, n)
            if ent[0] == "bloom":
                from auron_tpu.exprs.bloom import SparkBloomFilter
                for s in states:
                    if s:
                        ent[1].merge(SparkBloomFilter.deserialize(
                            base64.b64decode(s)))
            else:
                _, udaf, bufs = ent
                for i, s in enumerate(states):
                    if s is None:
                        continue
                    buf = pickle.loads(base64.b64decode(s))
                    kt = key_tuples[i]
                    old = bufs.get(kt)
                    bufs[kt] = buf if old is None else udaf.merge(old, buf)
        self._sample_buf_size()
        self._account()

    # -- emit ----------------------------------------------------------------

    def result_column(self, si: int, key_tuples: list[tuple], ng: int,
                      cap: int, partial: bool):
        import base64
        import pickle
        self.restore_spills()
        ent = self.entries[si]
        if ent[0] == "bloom":
            blob = base64.b64encode(ent[1].serialize()).decode()
            vals = [blob if i < ng else None for i in range(min(ng, 1))]
            vals += [None] * (cap - len(vals))
            return _host_string_column(vals[:cap], cap)
        _, udaf, bufs = ent
        out = []
        for i in range(ng):
            buf = bufs.get(key_tuples[i])
            if partial:
                out.append(None if buf is None
                           else base64.b64encode(pickle.dumps(buf)).decode())
            else:
                # missing buffer = no input rows reached the UDAF (empty
                # global input): Spark evaluates the initial buffer
                out.append(udaf.eval(udaf.zero() if buf is None else buf))
        out += [None] * (cap - ng)
        if partial:
            return _host_string_column(out, cap)
        spec = self.op.specs[si]
        jdt = _JNPT[spec.result[0]]
        data = np.zeros(cap, np.dtype(jnp.dtype(jdt)))
        valid = np.zeros(cap, bool)
        for i, v in enumerate(out[:cap]):
            if v is not None:
                data[i] = v
                valid[i] = True
        return PrimitiveColumn(jnp.asarray(data), jnp.asarray(valid))


class _AggSpillConsumer:
    """MemConsumer for AggOp: owns the accumulator state between merges.

    The operator checks the state out with ``take_state`` before each merge
    and checks the merged result back in with ``observe``. While checked
    out, an externally-triggered spill (another consumer's update picking
    this one as victim) must refuse — serializing a state the operator is
    about to fold new rows into would double-count every group on emit."""

    def __init__(self, op: "AggOp", mem_manager, metrics, conf=None):
        import threading
        from auron_tpu import config as cfg
        self.op = op
        self.mem = mem_manager
        self.metrics = metrics
        conf = conf or cfg.get_config()
        self.frame_rows = conf.get(cfg.SPILL_FRAME_ROWS)
        self.codec_level = conf.get(cfg.SPILL_CODEC_LEVEL)
        self.consumer_name = f"agg-{id(op):x}"
        self.state = None
        self.spills = []
        #: groups written to spill runs so far — feeds the partial-skip
        #: cardinality estimate (spilled keys are otherwise invisible at
        #: the decision point); an upper bound, since a key can appear in
        #: several runs
        self.spilled_groups = 0
        self._lock = threading.RLock()
        self._merging = False
        mem_manager.register_consumer(self)

    def take_state(self):
        with self._lock:
            self._merging = True
            state, self.state = self.state, None
            return state

    def observe(self, state):
        """Check the merged state back in; may spill it synchronously (the
        requester-side trigger). Returns the state the operator should
        continue with (None right after a spill). A None state still
        reports (as zero) so dropping the state — e.g. the partial-skip
        switchover — clears this consumer's accounted usage instead of
        leaving stale pressure on the manager."""
        with self._lock:
            self.state = state
            self._merging = False
        self.mem.update_mem_used(self, _state_nbytes(state))
        with self._lock:
            return self.state

    def mem_used(self) -> int:
        with self._lock:
            return 0 if self.state is None else _state_nbytes(self.state)

    def spill(self) -> int:
        from auron_tpu.columnar.serde import (batch_to_host,
                                              serialize_host_batch,
                                              slice_host_batch)
        with self._lock:
            if self.state is None or self._merging:
                return 0
            state, self.state = self.state, None
        freed = _state_nbytes(state)
        # each level of the (main, hot) state spills as its own run; the
        # restore path re-merges them, so level boundaries are free
        spill = self.mem.spill_manager.new_spill()
        for lvl in state:
            if lvl is None:
                continue
            state_batch = self.op._state_batch(lvl)
            n = _profile.row_count(state_batch)
            if n == 0:
                continue
            self.spilled_groups += n
            host = batch_to_host(state_batch, n)
            for lo in range(0, n, self.frame_rows):
                hi = min(lo + self.frame_rows, n)
                spill.write_frame(
                    serialize_host_batch(slice_host_batch(host, lo, hi),
                                         codec_level=self.codec_level))
        # an all-empty state yields an empty (frameless) spill — restore
        # simply yields nothing for it
        with self._lock:
            self.spills.append(spill.finish())
        self.metrics.counter("mem_spill_count").add(1)
        self.metrics.counter("mem_spill_size").add(freed)
        return freed

    @staticmethod
    def _restored_batches(spill):
        from auron_tpu.columnar.serde import (deserialize_host_batch,
                                              host_to_batch)
        from auron_tpu.utils.shapes import bucket_rows
        for frame in spill.frames():
            host, _ = deserialize_host_batch(frame)
            if host.num_rows:
                yield host_to_batch(host, bucket_rows(host.num_rows))

    def read_spilled_states(self):
        for spill in self.spills:
            yield from self._restored_batches(spill)

    def drain_spilled_states(self):
        """read_spilled_states, then release + clear — used when the
        operator folds spilled runs back in mid-stream (partial-agg skip
        switchover) rather than at close."""
        with self._lock:
            spills, self.spills = self.spills, []
        for spill in spills:
            yield from self._restored_batches(spill)
            spill.release()

    def close(self) -> None:
        self.mem.unregister_consumer(self)
        for s in self.spills:
            s.release()
        self.spills = []


class _HashPathCtl:
    """Per-execution hash-path control: the dispatch decision's knobs
    plus the mid-stream fallback latch (pathological probe overflow
    disables the hash path for the rest of the stream)."""

    __slots__ = ("load_factor", "max_probe_rounds", "metrics", "disabled")

    def __init__(self, decision, metrics):
        self.load_factor = decision.load_factor
        self.max_probe_rounds = decision.max_probe_rounds
        self.metrics = metrics
        self.disabled = False


#: aggregation modes (reference: AggMode, agg/agg_ctx.rs; Spark's
#: AggregateMode): which read state columns and which emit them
AGG_MODES = ("partial", "partial_merge", "final", "complete")
_READS_STATE = ("partial_merge", "final")
_EMITS_STATE = ("partial", "partial_merge")


def _state_columns(aggs, fn_modes, in_schema: Schema) -> tuple:
    """Where each function's input state begins in the child's output
    (None for a function that starts from rows). The functions that merge
    read the child's LAST columns, in their own order: an aggregation
    that emits state emits its group columns and then its states, and its
    parent may group by fewer of them (Spark's third aggregate of a
    single-DISTINCT plan drops the distinct column from the keys). A
    state is one column or two; which, the last one says (``has``, a
    bool, closes every two-column min / max / first state)."""
    starts = [None] * len(aggs)
    end = len(in_schema)
    for i in reversed(range(len(aggs))):
        if fn_modes[i] not in _READS_STATE:
            continue
        a = aggs[i]
        if a.fn in ("sum", "avg") and not a.distinct:
            width = 2
        elif a.fn in ("min", "max", "first", "first_ignores_null"):
            width = 2 if end and in_schema[end - 1].dtype == DataType.BOOL \
                else 1
        else:       # counts, lists, the DISTINCT set, bloom / udaf states
            width = 1
        end -= width
        if end < 0:
            raise ValueError(
                f"aggregation in a merging mode over {len(in_schema)} "
                f"columns: too few for the states of {len(aggs)} functions")
        starts[i] = end
    return tuple(starts)


class AggOp(PhysicalOp):
    """mode: 'partial' emits (keys..., state...); 'partial_merge' consumes
    state columns and emits them merged; 'final' consumes state columns;
    'complete' does full agg in one op (reference: AggMode,
    agg/agg_ctx.rs). A function may carry a mode of its own
    (``AggFunction.mode``): a node that emits state may merge some
    functions' states and start others from its input rows."""

    name = "agg"

    def __init__(self, child: PhysicalOp, group_exprs: list[ir.Expr],
                 aggs: list[ir.AggFunction], mode: str = "complete",
                 group_names: Optional[list[str]] = None,
                 agg_names: Optional[list[str]] = None,
                 initial_capacity: int = 4096,
                 key_domain: Optional[int] = None):
        assert mode in AGG_MODES
        self.child = child
        self.group_exprs = tuple(group_exprs)
        self.aggs = tuple(aggs)
        self.mode = mode
        self.emits_state = mode in _EMITS_STATE
        fn_modes = tuple(a.mode or mode for a in aggs)
        for a, m in zip(aggs, fn_modes):
            if m not in AGG_MODES or (m in _EMITS_STATE) != self.emits_state:
                raise ValueError(
                    f"{a.fn} in mode {m!r} inside a {mode!r} aggregation: "
                    "a node emits either states or results")
        self.initial_capacity = initial_capacity
        #: exclusive upper bound on the (non-negative, non-null) group
        #: key when the planner can prove one from table stats; feeds
        #: the dense-kernel dispatch (auron_tpu/kernels). The bound is a
        #: plan-time promise, verified at runtime: out-of-range or NULL
        #: keys fail the task with a deterministic ValueError.
        self.key_domain = key_domain
        #: SPMD layout (parallel/mesh.buffer_spec): a partial agg's
        #: state rows shard on the batch dim — they are exactly what a
        #: mesh-routed exchange moves through the all-to-all (the
        #: map-side-combine-before-exchange shape)
        self.mesh_buffer_kind = "agg_partial" if mode == "partial" else None
        #: set by a parent that counts one of this node's group columns
        #: (below): the groups that leave are then the distinct
        #: (keys, argument) pairs, ``counts.agg_distinct_groups``
        self.feeds_distinct = False
        in_schema = child.schema()

        #: per function: the child column its state begins at, or None
        self.state_idx = _state_columns(aggs, fn_modes, in_schema)
        #: rows only in: what the pre-aggregation passes, the dense
        #: kernels, the combine fold and the partial skip are written for
        self.from_rows = mode in ("partial", "complete") \
            and all(idx is None for idx in self.state_idx)
        self.specs = tuple(
            make_acc_spec(a, in_schema, mode) if idx is None
            else make_acc_spec_from_partial(a, in_schema, idx)
            for a, idx in zip(aggs, self.state_idx))
        #: what the programs trace to read a batch (plan data). Its schema
        #: goes by position: expressions read columns by index, and
        #: aggregations that differ in their columns' NAMES only — q28's
        #: six bands — share their programs
        self._front = _RowsFront(
            self.group_exprs, self.state_idx, self.aggs, self.specs,
            Schema(tuple(f.with_name(f"c{i}")
                         for i, f in enumerate(in_schema))))
        self._state_front = _StateFront(len(self.group_exprs), self.specs)
        if (len(set(fn_modes)) > 1 or mode == "partial_merge") and any(
                f[2] in HOST_KINDS for spec in self.specs
                for f in spec.state_fields):
            raise NotImplementedError(
                "bloom_filter / udaf aggregates in a partial_merge or "
                "mixed-mode aggregation")
        if mode == "partial_merge" and isinstance(child, AggOp) \
                and child.mode == "partial_merge" and child.group_exprs:
            # Spark's second and third aggregates of a single-DISTINCT
            # plan: the child's groups are (keys, x), this node starts
            # count(x) / sum(x) / avg(x) over the child's column x
            n_child_keys = len(child.group_exprs)
            child.feeds_distinct = any(
                idx is None and isinstance(a.arg, ir.ColumnRef)
                and a.arg.index < n_child_keys
                for a, idx in zip(aggs, self.state_idx))

        self.group_names = list(group_names or
                                [f"k{i}" for i in range(len(group_exprs))])
        self.agg_names = list(agg_names or [f"a{i}" for i in range(len(aggs))])

        key_fields = []
        for e, n in zip(self.group_exprs, self.group_names):
            # nested-aware: struct group keys keep their children metadata
            # through the output/partial schema (serde needs it)
            from auron_tpu.exprs.eval import infer_field
            key_fields.append(infer_field(e, in_schema, n))

        if self.emits_state:
            state_fields = []
            for spec, an in zip(self.specs, self.agg_names):
                for fi, (fname, fdt, kind) in enumerate(spec.state_fields):
                    if kind in ("collect_list", "collect_set") \
                            or kind in _DCOLLECT:
                        # element (p, s) riding the LIST slots covers
                        # decimal elements (0/0 for everything else)
                        state_fields.append(Field(
                            f"{an}#{fname}", DataType.LIST, True,
                            spec.result[1], spec.result[2],
                            elem=spec.elem))
                        continue
                    if spec.state_ps is not None:
                        prec, sc = spec.state_ps[fi]
                    elif fdt == DataType.DECIMAL:
                        prec, sc = spec.result[1], spec.result[2]
                    else:
                        prec, sc = 0, 0
                    state_fields.append(Field(f"{an}#{fname}", fdt, True, prec, sc))
            self._schema = Schema(tuple(key_fields + state_fields))
        else:
            out_fields = [Field(n, spec.result[0], True, spec.result[1],
                                spec.result[2], elem=spec.elem)
                          for spec, n in zip(self.specs, self.agg_names)]
            self._schema = Schema(tuple(key_fields + out_fields))

    @property
    def children(self):
        return [self.child]

    def schema(self) -> Schema:
        return self._schema

    # -- input row → state contributions -----------------------------------
    def _input(self, batch: DeviceBatch, partition: int = 0,
               front=None) -> _Input:
        """One batch as the merge takes it. ``front`` defaults to the
        operator's input; ``self._state_front`` reads a batch in its own
        state layout (a restored spill run, a state handed over)."""
        front = front or self._front
        layout = leaf_layout(batch.columns)
        return _Input(front,
                      (batch.columns, batch.num_rows, np.int32(partition)),
                      layout, _front_shapes(front, layout, batch.capacity))

    # -- merge driver -------------------------------------------------------
    #
    # Two-kernel incremental update (the sorted analogue of the reference
    # AggTable's probe-update, agg_table.rs:68-356):
    #   1. _batch_reduce_kernel sorts and reduces ONLY the incoming batch
    #      (O(B log B)) into a hash-sorted group table;
    #   2. _state_merge_kernel folds that table into the hash-sorted state
    #      by searchsorted + scatter (O(B log S + S)) — the state is never
    #      re-sorted and its hashes are computed exactly once.

    def _collect_elems(self, accs) -> list[int]:
        from auron_tpu.utils.shapes import next_pow2
        # list accumulators are (values[cap, E], lens[cap]) — or limb-pair
        # (hi[cap, E], lo[cap, E], lens[cap]) for dcollect; string accs
        # are also 3-tuples but their [1] (lens) is 1-D, and decimal limb
        # pairs are 2-tuples of 1-D arrays with no element width
        def elems(a):
            if not isinstance(a, tuple) or a[0].ndim != 2:
                return 0
            if len(a) == 2 or (len(a) == 3 and a[1].ndim == 2):
                return max(4, next_pow2(a[0].shape[1]))
            return 0
        return [elems(a) for a in accs]

    def _grow_check(self, kinds, out_elems, ng, out_cap, needed):
        """Shared capacity/element-overflow check; mutates out_elems.
        Returns (ok, new_out_cap)."""
        from auron_tpu.utils.shapes import next_pow2
        ok = ng <= out_cap
        ni = 0
        for i, k in enumerate(kinds):
            if k in ("collect_list", "collect_set") or k in _DCOLLECT:
                # graft: disable=GL001 -- `needed` came to the host with the group count (timed_get)
                nd = int(needed[ni])
                ni += 1
                if nd > out_elems[i]:
                    ok = False
                    out_elems[i] = max(4, next_pow2(nd))
        if ng > out_cap:
            # the merge that found this runs again at the wider capacity
            _trace.count("agg_state_grows")
            return ok, bucket_rows(ng)
        return ok, out_cap

    def _shrink_table(self, tbl, ng: int):
        """A group table at its occupancy bucket, carrying the count the
        host read when it last merged into it. Live groups are a
        hash-sorted prefix, so shrinking is a plain slice — which the
        program that reads the table next takes (``_cut``: the merge, the
        emit): only the capacity changes here, and the arrays stay at the
        capacity of the program that made them until then. Keeps
        small-cardinality states from carrying batch-sized buffers
        through every subsequent merge. The ``num_groups`` slot holds
        ``ng`` as a HOST integer (a numpy int32: the programs that take it
        as an operand compile for the aval the device scalar had), so
        whoever reads the table next — the merge, the emit, the spill, the
        partial-skip decision — asks the chip for nothing the host
        holds."""
        keys, accs, _n, cap, h = tbl
        return (keys, accs, np.int32(ng), self._occupancy_cap(cap, ng), h)

    def _occupancy_cap(self, cap: int, ng: int) -> int:
        return min(cap, max(bucket_rows(max(ng, 1)), self.initial_capacity))

    def _reduce_batch(self, inp, elapsed, donate=False):
        """Step 1: one batch → its hash-sorted group table, its keys and
        contributions evaluated inside the same program. ``donate`` (the
        owned-batch donation sweep) hands the batch's columns to XLA;
        callers may only pass it when the batch is owned and no collect
        kind can grow elements (the retry below reuses the inputs).
        Leaves that alias one buffer — columns of a scan-fed batch
        commonly share one all-valid mask — are programs.jit's to
        catch."""
        kinds = self._device_kinds()
        cap_b = inp.capacity
        out_elems = self._collect_elems(inp.shapes[1])
        # without keys the live rows are one group, so where the batch's
        # row count is a host integer (a merged exchange read, another
        # aggregate's output) the host knows the group count, and with no
        # collect kind ``needed`` is empty: nothing to read. A batch whose
        # count is on the device keeps the read
        rows = inp.operands[1]
        counted = not self.group_exprs and not self._collects() \
            and not isinstance(rows, jax.Array)
        while True:
            meta = tuple(zip(kinds, out_elems))
            kern = _batch_reduce_kernel(inp.front, inp.layout, meta, cap_b,
                                        donate)
            with timer(elapsed) as t:
                bk, ba, bh, bn, needed = kern(*inp.operands)
                if counted:
                    ng, needed_h = min(_profile.row_count(rows), 1), ()
                else:
                    # one batched round trip for every control scalar —
                    # each separate int() readback is its own device→host
                    # sync. The readback IS the sync point: attributed as
                    # device wait, obs/profile.timed_get
                    ng, needed_h = _profile.timed_get([bn, needed])
                    ng = int(ng)   # graft: disable=GL001 -- read by timed_get above
            ok, _cap = self._grow_check(kinds, out_elems, ng, cap_b,
                                        needed_h)
            if ok:
                return self._shrink_table((bk, ba, bn, cap_b, bh), ng)

    def _merge_tables(self, s, b, elapsed):
        """Fold group table ``b`` into group table ``s`` (both hash-sorted
        5-tuples) via the searchsorted merge kernel, growing capacity /
        element buckets as needed."""
        kinds = [kind for spec in self.specs
                 for (_n, _dt, kind) in _device_fields(spec)]
        s_keys, s_accs, s_n, s_cap, s_h = s
        bk, ba, bn, cap_b, bh = b
        out_cap = max(s_cap, self.initial_capacity)
        # string / list columns may land in different width buckets per
        # batch (and per restored spill run): the merge unifies them
        out_elems = [max(e) for e in zip(self._collect_elems(s_accs),
                                         self._collect_elems(ba))]
        while True:
            meta = tuple(zip(kinds, out_elems))
            kern = _state_merge_kernel(len(s_keys), meta, s_cap, cap_b,
                                       out_cap)
            with timer(elapsed) as t:
                new_keys, new_accs, h_out, num_groups, needed = kern(
                    s_keys, s_accs, s_h, s_n, bk, ba, bh, bn)
                ng, needed_h = _profile.timed_get([num_groups, needed])
                ng = int(ng)   # graft: disable=GL001 -- read by timed_get above
            ok, out_cap = self._grow_check(kinds, out_elems, ng, out_cap,
                                           needed_h)
            if ok:
                return self._shrink_table(
                    (new_keys, new_accs, num_groups, out_cap, h_out), ng)

    #: hot table folds into main once it has grown this many times the
    #: batch capacity — bounds the amortized main-merge cost to
    #: O(S / _HOT_FACTOR) per batch (LSM-style two-level state)
    _HOT_FACTOR = 8

    def _hash_dispatch(self, ctx: ExecContext):
        """Consult the general-path grouping policy (hashtable vs sort,
        kernels/dispatch.select_hash_agg)."""
        from auron_tpu.exprs.eval import infer_field
        from auron_tpu.kernels import dispatch as kdispatch
        in_schema = self.child.schema()
        key_dts = tuple(infer_field(e, in_schema, "k").dtype
                        for e in self.group_exprs)
        has_float_sum = any(
            kind == "sum" and fdt in (DataType.FLOAT32, DataType.FLOAT64)
            for spec in self.specs
            for (_f, fdt, kind) in _device_fields(spec))
        return kdispatch.select_hash_agg(
            key_dtypes=key_dts, acc_kinds=tuple(self._device_kinds()),
            has_float_sum=has_float_sum, conf=ctx.conf,
            metrics=ctx.metrics_for("kernels"))

    def _merge_hash(self, state, inp, elapsed, ht):
        """Hash-table update: the batch folds into the device table in
        one fused program (no per-batch state sort/merge). A sorted
        (tbl, None) state — the partial-skip decision's compaction, or a
        drained spill fold — re-enters the table as group-partial
        contributions (the same associativity the sorted merge relies
        on). Pathological probe overflow latches the sort path for the
        rest of the stream, salvaging the table as a sorted state."""
        from auron_tpu.hashtable import HashAggState, HashTableOverflow
        if state is not None and isinstance(state[0], HashAggState):
            hs = state[0]
            pending = [inp]
        else:
            hs = HashAggState(
                self._device_kinds(),
                initial_capacity=self.initial_capacity,
                load_factor=ht.load_factor,
                max_probe_rounds=ht.max_probe_rounds)
            pending = [self._state_input(self._state_batch(lvl))
                       for lvl in (state or ()) if lvl is not None]
            pending.append(inp)
        for i, batch_in in enumerate(pending):
            try:
                with timer(elapsed):    # update syncs via its readback
                    hs.update(*batch_in)
            except HashTableOverflow:
                # fall back mid-stream: export whatever the table holds
                # (updates are transactional — the failed batch is NOT
                # in it) and push it plus the unconsumed contributions
                # through the sort path
                ht.disabled = True
                ht.metrics.counter("hashtable_overflow_fallback").add(1)
                _trace.count("agg_sort_fallbacks")
                _trace.count("agg_demoted_to_sort")
                tbl = hs.to_sorted_table()
                sorted_state = None if tbl is None else \
                    (self._shrink_table(tbl, hs.count), None)
                for left in pending[i:]:
                    sorted_state = self._merge_sorted(sorted_state, left,
                                                      elapsed)
                return sorted_state
        return (hs,)

    def _merge(self, state, inp, elapsed, ht=None, donate=False):
        if ht is not None and not ht.disabled:
            # the hash step's overflow-retry protocol reuses its inputs
            # (PERF.md 'Pipelined execution'): no donation on this path
            return self._merge_hash(state, inp, elapsed, ht)
        # graft: donation-ok -- sorted path only: the hash branch
        # above latched off (its overflow retry reuses inputs)
        return self._merge_sorted(state, inp, elapsed, donate=donate)

    def _donate_contributions(self, ctx: ExecContext) -> bool:
        """Owned-batch donation gate for the per-batch reduce: the child
        must own its batches (dead after the reduce) and no collect kind
        may be present — collect-element growth retries the reduce with
        the same inputs, which donation would have invalidated."""
        from auron_tpu.ops.base import yields_owned_batches
        return yields_owned_batches(self.child) and not self._collects()

    def _collects(self) -> bool:
        """Whether any accumulator is a collect kind: its element buffers
        grow by a host-side retry of the program that found them short
        (``_grow_check``), on a width only the device knows."""
        return any(k in ("collect_list", "collect_set") or k in _DCOLLECT
                   for k in self._device_kinds())

    def _merge_sorted(self, state, inp, elapsed, donate=False):
        """state: None | (main, hot), each None | (keys, accs, num_groups,
        capacity, hashes). Two-level update: every batch merges into the
        small hot table (O(B log B + hot)); the hot table folds into main
        only on overflow, so the O(S) main-table pass is paid once per
        ~_HOT_FACTOR batches instead of per batch. The reference's
        open-addressing AggTable gets the same amortization from its
        in-memory table + sorted bucket spills (agg_table.rs:68-356)."""
        _trace.count("agg_sort_batches")
        # graft: donation-ok -- _donate_contributions gate (owned
        # child, no collect-kind growth retry, no aliased leaves)
        batch_tbl = self._reduce_batch(inp, elapsed, donate=donate)
        cap_b = inp.capacity
        main, hot = state if state is not None else (None, None)
        if hot is None:
            hot = batch_tbl
        else:
            hot = self._merge_tables(hot, batch_tbl, elapsed)
        # threshold must clear _shrink_table's initial_capacity floor, or
        # a small batch capacity would fold hot->main on EVERY batch (two
        # O(S) passes per batch — worse than the single-level design)
        if hot[3] >= self._HOT_FACTOR * max(cap_b, self.initial_capacity):
            main = hot if main is None else self._merge_tables(main, hot,
                                                               elapsed)
            hot = None
        return (main, hot)

    def _compact(self, state, elapsed):
        """Collapse (main, hot) into one table for emit / spill / the skip
        decision. Returns a 5-tuple or None. A hash-table-backed state
        exports through its hash-sorted conversion (the skip decision,
        which goes on with a sorted state; an emit exports inside its own
        program: ``_emit``)."""
        if state is None:
            return None
        from auron_tpu.hashtable import HashAggState
        if isinstance(state[0], HashAggState):
            hs = state[0]
            with timer(elapsed):
                tbl = hs.to_sorted_table()
            return None if tbl is None else \
                self._shrink_table(tbl, hs.count)
        main, hot = state
        if main is None:
            return hot
        if hot is None:
            return main
        return self._merge_tables(main, hot, elapsed)

    # -- finalize → output batch -------------------------------------------
    def _table_batch(self, table, as_state: bool, bloom: bool = False):
        """ONE program (``ops.agg.emit``) from a group table — a sorted
        5-tuple, or the hash table itself, whose export is folded in — to
        a batch of its groups at the table's occupancy bucket: the
        operator's output less its host-side columns, or (``as_state``)
        the state as a batch in the operator's own state layout (a spill
        run; a sorted state the hash table takes over). Returns the batch
        and its capacity. The batch leaves with the HOST's count of the
        table's groups (the hash table's ``count``, a sorted table's
        ``num_groups`` slot: ``_shrink_table``) — exactly what the program
        returns as its row count, so nobody waits for the program to learn
        it (the pattern of ``parallel/exchange._cut``)."""
        from auron_tpu.hashtable import HashAggState
        if isinstance(table, HashAggState):
            # the occupancy bucket from the count the host already has
            count = np.int32(table.count)
            cap = self._occupancy_cap(table.cap, table.count)
            operands = (table.th, table.store, table.accs)
            meta = table.key_meta
        else:
            keys, accs, count, cap, _hashes = table
            operands, meta = (keys, accs, count), None
        if bloom:
            # A global bloom state serializes to ~100 KB+ per row; the
            # (single-group) output leaves at the smallest capacity so
            # the string column isn't materialized at state capacity.
            cap = min(cap, bucket_rows(1, minimum=16))
        batch = _emit_kernel(self.specs, as_state, cap, meta)(*operands)
        return DeviceBatch(batch.columns, count), cap

    def _emit(self, state, elapsed, host=None) -> Optional[DeviceBatch]:
        """The operator's output batch from its state — the (main, hot)
        levels or the hash table — or None where it never saw a row. Reads
        nothing from the chip: the batch's row count is the host's
        (``_table_batch``), so ``count_output``'s read of it is free and a
        state whose last merge was dispatched and not read is not waited
        for here."""
        from auron_tpu.hashtable import HashAggState
        if state is not None and isinstance(state[0], HashAggState):
            table = state[0] if state[0].built else None
        else:
            table = self._compact(state, elapsed)
        if table is None:
            return None
        host_slots = _host_slots(self.specs, self.emits_state) \
            if host is not None and not host.empty() else []
        batch, out_cap = self._table_batch(
            table, self.emits_state,
            bloom=bool(host_slots) and host.has_bloom())
        ng = _profile.row_count(batch)      # the host's: no read
        if self.group_exprs:
            _trace.count("agg_groups", ng)
        if not host_slots:
            return batch

        # splice host-aggregated columns (bloom / udaf) at output capacity
        n_keys = len(self.group_exprs)
        key_tuples = _key_tuples_host(batch.columns[:n_keys], ng)
        cols = list(batch.columns)
        for pos, si in host_slots:
            cols.insert(n_keys + pos, host.result_column(
                si, key_tuples, ng, out_cap, partial=self.emits_state))
        return DeviceBatch(tuple(cols), batch.num_rows)

    # -- spill support ------------------------------------------------------
    # The reference spills the in-mem hash table as sorted buckets and
    # merges with a radix queue on output (agg/agg_table.rs:68-356). Here
    # the spilled unit is the whole accumulator table as a partial-layout
    # batch; on emit, spilled tables re-enter the same device merge kernel —
    # associativity of the accumulators makes re-merging exact.

    def _device_kinds(self) -> list[str]:
        return [kind for spec in self.specs
                for (_f, _d, kind) in _device_fields(spec)]

    def _state_batch(self, state) -> DeviceBatch:
        """A state level as a batch in the operator's own state layout:
        the spill / fold handoff (a hash table's export restores the
        hash-sorted run invariant the bucket spills rely on)."""
        return self._table_batch(state, as_state=True)[0]

    def _state_input(self, batch: DeviceBatch):
        """A batch in the operator's own state layout (``_state_batch``,
        a restored spill run) as the merge takes it."""
        return self._input(batch, front=self._state_front)

    # -- map-side combine fold (parallel/exchange + mesh_exchange) ----------
    #
    # A hash exchange whose child is an eligible partial agg elides the
    # partial-agg OPERATOR and folds a per-batch (stateless) combine into
    # the shuffle-split program: contributions → one hash-sort →
    # _reduce_sorted → partial-layout batch, all inside the already-fused
    # split kernel. Groups combine per map batch (host route) or per
    # shard round (all_to_all route) BEFORE rows cross the exchange.
    # Bit-identity: per-batch reduce is exactly today's _batch_reduce
    # step, and the cross-batch merge that the elided partial ladder used
    # to do is the SAME associative merge the final agg performs — so for
    # reassociation-exact kinds the result is unchanged. Float sums are
    # NOT reassociation-exact (the elided hot/main ladder and the final
    # agg's ladder add in different orders) and stay unfolded — the same
    # exactness rule the hashtable dispatch applies
    # (kernels/dispatch.select_hash_agg's float_sum_inexact fallback).

    def combine_fold_reason(self) -> Optional[str]:
        """None when this agg can fold into a shuffle-split program as a
        map-side combine, else why not (explain/telemetry vocabulary)."""
        if self.mode != "partial" or not self.from_rows:
            return "not_partial"
        if not self.group_exprs:
            return "no_group_keys"
        if self.key_domain is not None:
            return "dense_domain"   # keep the dense-kernel dispatch
        kinds = [kind for spec in self.specs
                 for (_f, _d, kind) in spec.state_fields]
        if any(k in HOST_KINDS for k in kinds):
            return "host_state"
        if any(k in ("collect_list", "collect_set") or k in _DCOLLECT
               for k in kinds):
            # element buffers grow by host-side retry; a fixed split
            # program cannot re-enter the growth loop
            return "collect_state"
        exact = {"sum", "min", "max", "or", "first"}
        exact.update(_STR_KINDS)
        exact.update(_DEC_KINDS)
        if any(k not in exact for k in kinds):
            return "unsupported_kind"
        if any(kind == "sum" and fdt in (DataType.FLOAT32, DataType.FLOAT64)
               for spec in self.specs
               for (_f, fdt, kind) in _device_fields(spec)):
            return "float_sum_inexact"
        return None

    def combine_signature(self) -> tuple:
        """Hashable trace signature of the folded combine stage — rides
        the split-program cache key (schema/capacity ride separately)."""
        return ("combine_v1", self.group_exprs, self.aggs)

    def build_combine_stage(self):
        """Traced (DeviceBatch → (partial-layout DeviceBatch, rows_in))
        stage folded into a shuffle-split program: merges the batch's
        groups (one stable hash-sort + segment reduce, the
        _batch_reduce_kernel body inlined — no carries, no growth retry:
        eligibility excluded collect kinds). rows_in is the pre-combine
        live-row count, read by the caller in its existing readback
        fence (combine telemetry)."""
        in_schema = self.child.schema()
        kinds = self._device_kinds()
        # plan DATA only below — this closure is stored in the process-wide
        # split-program cache, so capturing self would pin the whole op
        # subtree (broadcast build buffers included) past query teardown
        group_exprs, aggs, specs = self.group_exprs, self.aggs, self.specs

        def apply(batch: DeviceBatch):
            ectx = EvalContext()
            keys, accs, live = _contribution_columns(
                group_exprs, (None,) * len(aggs), aggs, specs, batch,
                in_schema, ectx)
            rows_in = jnp.sum(live.astype(jnp.int32))
            cap = int(live.shape[0])   # graft: disable=GL001 -- .shape[0] is a static python int, never device data
            h = hashing.xxhash64_columns(list(keys), cap).view(jnp.uint64)
            h = jnp.where(live, h, _HASH_SENTINEL)
            perm = jnp.argsort(h, stable=True)
            keys_s = tuple(gather_column(c, perm, jnp.ones(cap, bool))
                           for c in keys)
            accs_s = tuple(_gather_acc(a, perm) for a in accs)
            meta = tuple((k, 0) for k in kinds)
            new_keys, new_accs, _h, num_groups, _needed = _reduce_sorted(
                keys_s, accs_s, live[perm], h[perm], meta, cap)
            valid = jnp.arange(cap, dtype=jnp.int32) < num_groups
            cols = list(new_keys)
            for kind, a in zip(kinds, new_accs):
                if kind in _STR_KINDS:
                    cols.append(StringColumn(a[0], a[1], a[2] & valid))
                elif kind in _DEC_KINDS:
                    from auron_tpu.columnar.decimal128 import Decimal128Column
                    cols.append(Decimal128Column(a[0], a[1], valid))
                else:
                    cols.append(PrimitiveColumn(a, valid))
            return DeviceBatch(tuple(cols), num_groups), rows_in

        return apply

    # -- dense-domain fast path (auron_tpu/kernels) -------------------------
    #
    # With a planner-proved key-domain bound, grouped aggregation becomes
    # a dense accumulation over [0, key_domain): float sum/count grids run
    # on the dispatched MXU kernel (Pallas VMEM-accumulate on a real TPU,
    # one-hot matmul elsewhere — ~12 B/row HBM traffic instead of the
    # one-hot operands the generic XLA lowering materializes), while
    # integer sums and min/max run as exact dense scatters. The [domain]
    # state is bounded, so none of the spill / partial-skip machinery
    # applies; emit funnels through the general _emit for finalization.

    def _dense_dispatch(self, ctx: ExecContext):
        """Consult the kernel-selection policy (kernels/dispatch.py).
        Returns a dense KernelDecision, or None for the sort path."""
        if self.key_domain is None or not self.from_rows:
            return None
        from auron_tpu.kernels import dispatch as kdispatch
        in_schema = self.child.schema()
        key_dts = tuple(infer_dtype(e, in_schema)[0]
                        for e in self.group_exprs)
        value_dts = tuple(infer_dtype(a.arg, in_schema)[0]
                          for a in self.aggs if a.arg is not None)
        decision = kdispatch.select_grouped_agg(
            key_domain=self.key_domain, key_dtypes=key_dts,
            agg_fns=tuple(s.fn for s in self.specs),
            value_dtypes=value_dts, conf=ctx.conf,
            metrics=ctx.metrics_for("kernels"))
        return decision if decision.is_dense else None

    def _dense_batch_acc(self, agg, spec, batch, k, live, ectx,
                         in_schema, decision, domain, memo):
        """One batch's dense [domain] accumulator tuple for one spec.

        ``memo`` is the per-batch cache: aggregates over the same
        argument expression share one evaluation and one count scatter
        (sum+count+avg+min+max over a column is the common shape — five
        identical count kernels otherwise, and the eager host loop has
        no jit around it to CSE them)."""
        from auron_tpu.kernels import grouped_agg as gagg

        def counts_for(valid, ckey):
            cnt = memo.get(ckey)
            if cnt is None:
                cnt = gagg.scatter_reduce("count", k, None, valid,
                                          domain, jnp.int64)
                memo[ckey] = cnt
            return cnt

        fn = spec.fn
        if agg.arg is None:   # count_star: live rows per key (== "rows")
            return (counts_for(live, "rows"),)
        akey = repr(agg.arg)
        ev = memo.get(("eval", akey))
        if ev is None:
            v = evaluate(agg.arg, batch, in_schema, ectx)
            ev = (v.col.data, v.validity & live)
            memo[("eval", akey)] = ev
        data, valid = ev
        if fn in ("count", "count_star"):
            return (counts_for(valid, ("cnt", akey)),)
        if fn in ("sum", "avg"):
            sdt = _JNPT[spec.state_fields[0][1]]
            if jnp.issubdtype(jnp.dtype(sdt), jnp.floating):
                # float sums ride the dispatched MXU grids: one launch
                # yields the (sum, count) pair (per-batch counts are
                # 0/1-exact in f32; cross-batch accumulation is
                # f64/int64). The masked 3-term split inside the kernel
                # keeps ~1e-7 rel accuracy at DEFAULT precision.
                v32 = jnp.where(valid, data, 0).astype(jnp.float32)
                c32 = valid.astype(jnp.float32)
                s, c = gagg.sum_count(k, v32, c32, domain,
                                      backend=decision.kernel,
                                      interpret=decision.interpret)
                return (s.astype(jnp.float64), c.astype(jnp.int64))
            # integer sums are contractually exact: dense scatter-add
            s = gagg.scatter_reduce("sum", k, data, valid, domain, sdt)
            return (s, counts_for(valid, ("cnt", akey)))
        if fn in ("min", "max"):
            vdt = _JNPT[spec.state_fields[0][1]]
            val = gagg.scatter_reduce(fn, k, data, valid, domain, vdt)
            return (val, counts_for(valid, ("cnt", akey)))
        raise NotImplementedError(fn)   # unreachable: dispatch gated

    @staticmethod
    def _dense_merge(spec, a, b):
        if spec.fn in ("min", "max"):
            op = jnp.minimum if spec.fn == "min" else jnp.maximum
            return (op(a[0], b[0]), a[1] + b[1])
        return tuple(x + y for x, y in zip(a, b))

    def _dense_domain_stream(self, partition: int, ctx: ExecContext,
                             decision, metrics):
        from auron_tpu.kernels import dispatch as kdispatch
        domain = self.key_domain
        in_schema = self.child.schema()
        ectx = EvalContext(partition_id=partition)
        elapsed = metrics.counter("elapsed_compute")
        kmetrics = ctx.metrics_for("kernels")
        key_jdt = _JNPT[infer_dtype(self.group_exprs[0], in_schema)[0]]

        state = None    # per-spec dense accumulator tuples
        rows = None     # int64[domain] live rows per key (group existence)
        max_k = min_k = saw_null = None   # bound-check scalars (device)
        total_rows = None   # device scalar: readback deferred to emit

        for batch in self.child.execute(partition, ctx):
            ctx.check_cancelled()
            with timer(elapsed) as t:
                live = batch.row_mask()
                kv = evaluate(self.group_exprs[0], batch, in_schema, ectx)
                kdata = kv.col.data.astype(jnp.int64)
                key_live = live & kv.validity
                b_null = jnp.any(live & ~kv.validity)
                b_max = jnp.max(jnp.where(key_live, kdata, jnp.int64(-1)))
                b_min = jnp.min(jnp.where(key_live, kdata, jnp.int64(0)))
                k = jnp.clip(kdata, 0, domain - 1).astype(jnp.int32)
                from auron_tpu.kernels import grouped_agg as gagg
                memo = {"rows": gagg.scatter_reduce(
                    "count", k, None, live, domain, jnp.int64)}
                batch_accs = [
                    self._dense_batch_acc(agg, spec, batch, k, live,
                                          ectx, in_schema, decision,
                                          domain, memo)
                    for agg, spec in zip(self.aggs, self.specs)]
                if state is None:
                    state, rows = batch_accs, memo["rows"]
                    max_k, min_k, saw_null = b_max, b_min, b_null
                    total_rows = jnp.asarray(batch.num_rows, jnp.int64)
                else:
                    state = [self._dense_merge(spec, s, b)
                             for spec, s, b in zip(self.specs, state,
                                                   batch_accs)]
                    rows = rows + memo["rows"]
                    max_k = jnp.maximum(max_k, b_max)
                    min_k = jnp.minimum(min_k, b_min)
                    saw_null = saw_null | b_null
                    total_rows = total_rows + jnp.asarray(batch.num_rows,
                                                          jnp.int64)
                t.track(rows)
        if state is None:
            return

        touched = rows > 0
        ng_dev = jnp.sum(touched.astype(jnp.int32))
        order = jnp.argsort(~touched, stable=True)   # touched keys first
        # ONE batched readback for every control scalar (each separate
        # int() is its own device→host sync); routed
        # through the profiler so the wait books as device time at this
        # sync point, like the grow/overflow readbacks above
        ng, mx, mn, nulls, nrows = _profile.timed_get(
            [ng_dev, max_k, min_k, saw_null, total_rows])
        # graft: disable=GL001 -- the five came to the host in the timed_get above
        ng, mx, mn, nrows = int(ng), int(mx), int(mn), int(nrows)
        kdispatch.record_rows(decision, nrows, kmetrics)
        # the key_domain hint is a plan-time promise — violations are
        # deterministic defects and must fail the task, not mis-aggregate
        # (run_task_with_retries treats ValueError as no-retry)
        if bool(nulls):
            raise ValueError(
                "dense grouped-agg: NULL group keys under key_domain="
                f"{domain}; the planner's bound is invalid for this data")
        if mx >= domain or mn < 0:
            raise ValueError(
                f"dense grouped-agg: observed key range [{mn}, "
                f"{mx}] violates the planner's key_domain={domain}")
        cap = max(bucket_rows(max(ng, 1)), 16)
        take = order
        if cap > domain:
            take = jnp.concatenate(
                [order, jnp.zeros(cap - domain, order.dtype)])
        take = take[:cap]
        out_valid = jnp.arange(cap, dtype=jnp.int32) < ng_dev
        keys = (PrimitiveColumn(
            jnp.arange(domain, dtype=key_jdt)[take], out_valid),)
        accs = []
        for spec, acc in zip(self.specs, state):
            fn = spec.fn
            if fn in ("count", "count_star"):
                accs.append(acc[0][take])
            elif fn == "avg":
                accs.append(acc[0][take].astype(
                    _JNPT[spec.state_fields[0][1]]))
                accs.append(acc[1][take])
            else:   # sum / min / max: second state field is 'has'
                accs.append(acc[0][take].astype(
                    _JNPT[spec.state_fields[0][1]]))
                accs.append(acc[1][take] > 0)
        # the table leaves with the count the one readback above brought
        tbl = (keys, tuple(accs), np.int32(ng), cap,
               jnp.zeros(cap, jnp.uint64))
        yield self._emit((tbl, None), elapsed)

    def execute(self, partition: int, ctx: ExecContext) -> Iterator[DeviceBatch]:
        from auron_tpu import config as cfg
        from auron_tpu.kernels import dispatch as kdispatch
        metrics = ctx.metrics_for(self)
        decision = self._dense_dispatch(ctx)
        if decision is not None:
            # the chosen backend lands in THIS operator's finalize
            # metrics, so gate logs show which path each agg actually ran
            kdispatch.record_operator_choice(metrics, decision.kernel)
            return count_output(
                self._dense_domain_stream(partition, ctx, decision,
                                          metrics), metrics)
        ht_decision = self._hash_dispatch(ctx)
        ht_ctl = _HashPathCtl(ht_decision, metrics) \
            if ht_decision.is_hash else None
        kdispatch.record_operator_choice(
            metrics, "hashtable" if ht_ctl is not None else "sort")
        elapsed = metrics.counter("elapsed_compute")
        in_schema = self.child.schema()
        ectx = EvalContext(partition_id=partition)
        mem = ctx.mem_manager
        spillable = mem is not None and getattr(mem, "spill_manager", None) is not None
        conf = ctx.conf
        # adaptive partial-agg skipping: only meaningful for keyed partial
        # stages with pure device accumulators (host-side bloom/udaf state
        # cannot pass through row-wise)
        skip_enabled = (self.mode == "partial" and self.from_rows
                        and bool(self.group_exprs)
                        and conf.get(cfg.AGG_PARTIAL_SKIP_ENABLED))
        skip_ratio = conf.get(cfg.AGG_PARTIAL_SKIP_RATIO)
        skip_min_rows = conf.get(cfg.AGG_PARTIAL_SKIP_MIN_ROWS)
        donate_contribs = self._donate_contributions(ctx)

        def stream():
            consumer = _AggSpillConsumer(self, mem, metrics, conf) \
                if spillable else None
            host = _HostAggState(self, in_schema, mem=mem, metrics=metrics)
            state = None
            skipping = False
            rows_seen = 0
            # host-side bloom/udaf state cannot pass through row-wise
            skip_pending = skip_enabled and host.empty()
            skipped_rows = metrics.counter("partial_agg_skipped_rows")
            try:
                for batch in self.child.execute(partition, ctx):
                    ctx.check_cancelled()
                    if skipping:
                        # each row its own group, in state layout
                        # (adaptive partial-agg skipping, reference:
                        # agg/agg_ctx.rs:63-196)
                        inp = self._input(batch, partition)
                        skipped_rows.add(_profile.row_count(batch))
                        yield _passthrough_kernel(
                            inp.front, inp.layout,
                            inp.capacity)(*inp.operands)
                        continue
                    if self.mode in _READS_STATE:
                        host.merge_partial(batch)
                    else:
                        host.update(batch, ectx)
                    inp = self._input(batch, partition)
                    if consumer is not None:
                        # state lives in the consumer between merges so an
                        # external victim spill can take it atomically
                        state = consumer.take_state()
                    # graft: donation-ok -- donate_contribs is the
                    # _donate_contributions gate resolved above
                    state = self._merge(state, inp, elapsed, ht_ctl,
                                        donate=donate_contribs)
                    if consumer is not None:
                        state = consumer.observe(state)
                    if not skip_pending:
                        continue
                    # decide ONCE when min_rows is crossed, then latch
                    # either way (the reference also decides at a fixed
                    # observation point, agg_ctx.rs:63-196) — so the steady
                    # state pays no per-batch device sync for bookkeeping
                    rows_seen += _profile.row_count(batch)
                    if rows_seen < skip_min_rows:
                        continue
                    skip_pending = False  # decision point reached: latch
                    if consumer is not None:
                        state = consumer.take_state()
                    # exact distinct count needs the levels folded: a key
                    # present in both hot and main would count twice
                    tbl = self._compact(state, elapsed)
                    state = None if tbl is None else (tbl, None)
                    # a compacted table carries the host's count
                    # (``_shrink_table``): this reads nothing
                    ng = 0 if tbl is None else _profile.row_count(tbl[2])
                    # groups living only in spill runs are invisible in the
                    # in-memory table; without them a pre-decision spill
                    # would suppress skipping in exactly the
                    # memory-pressured high-cardinality case it targets
                    if consumer is not None:
                        ng += consumer.spilled_groups
                    if tbl is not None and ng >= skip_ratio * rows_seen:
                        # fold any spilled runs in, flush the merged
                        # state, then pass the rest of the input through
                        if consumer is not None:
                            for spilled in consumer.drain_spilled_states():
                                state = self._merge(
                                    state, self._state_input(spilled),
                                    elapsed, ht_ctl)
                        yield self._emit(state, elapsed, host)
                        state = None
                        skipping = True
                        if consumer is not None:
                            consumer.observe(None)
                        continue
                    if consumer is not None:
                        state = consumer.observe(state)
                if skipping:
                    return
                if consumer is not None:
                    # re-take: locks out external spills for the final merge
                    # (consumer.state is the source of truth, the local var
                    # may have been spilled away since the last observe)
                    state = consumer.take_state()
                    for spilled in consumer.read_spilled_states():
                        state = self._merge(state,
                                            self._state_input(spilled),
                                            elapsed, ht_ctl)
                out = self._emit(state, elapsed, host)
                if out is not None:
                    yield out
                elif not self.group_exprs \
                        and self.mode in ("final", "complete"):
                    # global agg over empty input: one row of neutral results
                    yield self._empty_global(host)
            finally:
                host.close()
                if consumer is not None:
                    consumer.close()

        return count_output(
            stream(), metrics,
            also=(("agg_distinct_groups", 1),) if self.feeds_distinct
            else ())

    def _empty_global(self, host=None) -> DeviceBatch:
        from auron_tpu.columnar.batch import ListColumn
        cols = []
        for si, spec in enumerate(self.specs):
            dt = spec.result[0]
            if spec.fn in ("count", "count_star", "count_distinct"):
                cols.append(PrimitiveColumn(jnp.zeros(1, jnp.int64),
                                            jnp.ones(1, bool)))
            elif spec.fn in ("collect_list", "collect_set"):
                if spec.state_fields[0][2] in _DCOLLECT:
                    from auron_tpu.columnar.batch import MapColumn
                    cols.append(MapColumn(
                        jnp.zeros((1, 1), jnp.int64),
                        jnp.zeros((1, 1), jnp.int64),
                        jnp.zeros((1, 1), bool), jnp.zeros(1, jnp.int32),
                        jnp.ones(1, bool)))
                else:
                    cols.append(ListColumn(
                        jnp.zeros((1, 1), _JNPT[spec.elem]),
                        jnp.zeros((1, 1), bool), jnp.zeros(1, jnp.int32),
                        jnp.ones(1, bool)))
            elif host is not None and si in host.entries:
                # empty-input bloom/udaf: serialized empty filter /
                # eval(zero()) — both via the normal result path
                cols.append(host.result_column(si, [()], 1, 1, partial=False))
            elif dt == DataType.STRING:
                cols.append(StringColumn(jnp.zeros((1, 1), jnp.uint8),
                                         jnp.zeros(1, jnp.int32),
                                         jnp.zeros(1, bool)))
            elif dt == DataType.DECIMAL and spec.result[1] > 18:
                from auron_tpu.columnar.decimal128 import Decimal128Column
                cols.append(Decimal128Column(jnp.zeros(1, jnp.int64),
                                             jnp.zeros(1, jnp.int64),
                                             jnp.zeros(1, bool)))
            else:
                jdt = _JNPT[dt]
                cols.append(PrimitiveColumn(jnp.zeros(1, jdt),
                                            jnp.zeros(1, bool)))
        return DeviceBatch(tuple(cols), jnp.asarray(1, jnp.int32))

    def __repr__(self):
        fns = ",".join(a.fn for a in self.aggs)
        return f"AggOp[{self.mode}: {len(self.group_exprs)} keys; {fns}]"


def make_acc_spec_from_partial(agg: ir.AggFunction, in_schema: Schema,
                               start_idx: int) -> AccSpec:
    """Spec for the final side: state dtypes read from the partial schema."""
    fn = agg.fn
    if agg.distinct and fn in ("count", "sum", "avg"):
        elem = in_schema[start_idx].elem
        res = {"count": (DataType.INT64, 0, 0),
               "sum": (_SUM_DTYPE[elem], 0, 0),
               "avg": (DataType.FLOAT64, 0, 0)}[fn]
        return AccSpec(f"{fn}_distinct",
                       (("set", elem, "collect_set"),), res, elem=elem)
    if fn in ("count", "count_star"):
        return AccSpec(fn, (("count", DataType.INT64, "sum"),),
                       (DataType.INT64, 0, 0))
    f0 = in_schema[start_idx]
    wide = f0.dtype == DataType.DECIMAL and f0.precision > 18
    if fn == "sum":
        return AccSpec(fn, (("sum", f0.dtype, "dsum" if wide else "sum"),
                            ("has", DataType.BOOL, "or")),
                       (f0.dtype, f0.precision, f0.scale))
    if fn == "avg":
        if f0.dtype == DataType.DECIMAL:
            # the partial side accumulated the sum at the input scale and
            # stashed the result (p, s) in the count field's metadata
            # slots (see make_acc_spec); fall back to an estimate for
            # partial layouts that predate the channel
            f1 = in_schema[start_idx + 1]
            cap = 38 if wide else 18
            rp = f1.precision or (cap if f0.precision >= cap
                                  else max(f0.precision - 10, 1))
            rs = f1.scale or min(f0.scale + 4, rp)
            return AccSpec(fn, (("sum", f0.dtype, "dsum" if wide else "sum"),
                                ("count", DataType.INT64, "sum")),
                           (DataType.DECIMAL, rp, rs),
                           state_ps=((f0.precision, f0.scale), (rp, rs)))
        return AccSpec(fn, (("sum", f0.dtype, "sum"), ("count", DataType.INT64, "sum")),
                       (DataType.FLOAT64, 0, 0))
    if fn in ("min", "max"):
        if f0.dtype == DataType.STRING:
            return AccSpec(fn, (("val", DataType.STRING, f"s{fn}"),),
                           (f0.dtype, f0.precision, f0.scale))
        return AccSpec(fn, (("val", f0.dtype, f"d{fn}" if wide else fn),
                            ("has", DataType.BOOL, "or")),
                       (f0.dtype, f0.precision, f0.scale))
    if fn in ("first", "first_ignores_null"):
        if f0.dtype == DataType.STRING:
            kind = "sfirst_ign" if fn == "first_ignores_null" else "sfirst"
            return AccSpec(fn, (("val", DataType.STRING, kind),),
                           (f0.dtype, f0.precision, f0.scale))
        return AccSpec(fn, (("val", f0.dtype, "dfirst" if wide else "first"),
                            ("has", DataType.BOOL, "or")),
                       (f0.dtype, f0.precision, f0.scale))
    if fn in ("collect_list", "collect_set"):
        if f0.elem == DataType.DECIMAL and f0.precision > 18:
            # the dcollect state field: element (p, s) rides the LIST
            # field's precision/scale slots (see make_acc_spec)
            return AccSpec(fn, (("list", f0.elem, f"d{fn}"),),
                           (DataType.LIST, f0.precision, f0.scale),
                           elem=f0.elem)
        # narrow elements keep their (p, s) the same way — dropping them
        # here made distributed collect over decimal(p<=18) emit raw
        # scaled ints (review finding)
        return AccSpec(fn, (("list", f0.elem, fn),),
                       (DataType.LIST, f0.precision, f0.scale),
                       elem=f0.elem)
    if fn == "bloom_filter":
        return AccSpec(fn, (("bloom", DataType.STRING, "bloom"),),
                       (DataType.STRING, 0, 0))
    if fn.startswith("udaf:"):
        from auron_tpu.exprs.udf import lookup_udaf
        udaf = lookup_udaf(fn[5:])
        rdt = getattr(udaf, "dtype", DataType.FLOAT64)
        return AccSpec(fn, (("udaf", DataType.STRING, "udaf"),),
                       (rdt, getattr(udaf, "precision", 0),
                        getattr(udaf, "scale", 0)))
    raise NotImplementedError(fn)
