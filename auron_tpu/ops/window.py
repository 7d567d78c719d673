"""Window operator.

Reference: datafusion-ext-plans/src/window_exec.rs + window/processors/*
(rank, row_number, dense_rank, lead/lag, nth_value, percent_rank, cume_dist,
agg-over-window) and the window-group-limit pushdown (auron.proto:590-593).

TPU design: the reference streams rows through per-partition processor state
(a sequential scan). Sequential row processing is hostile to a vector
machine, so here the whole operator is one data-parallel kernel over the
sorted partition:

  sort by (partition keys, order keys)           — reuses the sort kernels
  → segment-boundary flags via neighbor equality  — one vector compare
  → every window function is a closed-form gather / segmented scan over
    positions (row_number = pos - seg_start + 1, rank via cummax of
    order-boundary positions, running aggs via segmented prefix scans with
    jax.lax.associative_scan, lead/lag via shifted gathers)

Aggregates use Spark's default frame semantics: with ORDER BY, RANGE
UNBOUNDED PRECEDING..CURRENT ROW (peer rows share the value at their tie
group's end); without ORDER BY, the whole partition.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Iterator, Optional

import jax
import jax.numpy as jnp

from auron_tpu.columnar.batch import (DeviceBatch, PrimitiveColumn,
                                      StringColumn, gather_batch)
from auron_tpu.columnar.schema import DataType, Field, Schema
from auron_tpu.exprs import ir
from auron_tpu.exprs.eval import (EvalContext, TypedValue, evaluate,
                                  infer_dtype)
from auron_tpu.obs import profile as _profile
from auron_tpu.obs import trace as _trace
from auron_tpu.ops.base import ExecContext, PhysicalOp, count_output, timer
from auron_tpu.ops.sort import _concat_all, sort_permutation
from auron_tpu.runtime.programs import program_cache

RANK_LIKE = ("row_number", "rank", "dense_rank", "percent_rank",
             "cume_dist", "ntile")
OFFSET_FNS = ("lead", "lag", "nth_value", "first_value", "last_value")
AGG_FNS = ("sum", "count", "count_star", "avg", "min", "max")


@dataclass(frozen=True)
class WindowFunctionSpec:
    kind: str                      # rank_like | offset | agg
    fn: str
    arg: Optional[ir.Expr] = None
    offset: int = 1                # lead/lag distance, nth n, ntile buckets
    default: object = None         # lead/lag default value
    #: ROWS BETWEEN (lo, hi) relative offsets for 'agg' functions
    #: (lo=-1, hi=1 is 1 PRECEDING..1 FOLLOWING); None = Spark's default
    #: frame. Supported for sum/count/count_star/avg (prefix-sum
    #: invertible); min/max over sliding frames fail fast.
    frame: Optional[tuple] = None

    def __post_init__(self):
        if self.kind == "rank_like":
            assert self.fn in RANK_LIKE, self.fn
        elif self.kind == "offset":
            assert self.fn in OFFSET_FNS, self.fn
        elif self.kind == "agg":
            assert self.fn in AGG_FNS, self.fn
        else:
            raise ValueError(self.kind)
        if self.frame is not None:
            if self.kind != "agg" or self.fn in ("min", "max"):
                raise NotImplementedError(
                    "ROWS frames are supported for sum/count/avg window "
                    "aggregates only (min/max need non-invertible sliding "
                    "state)")
            lo, hi = self.frame
            assert lo <= hi, self.frame


# ---------------------------------------------------------------------------
# segment machinery
# ---------------------------------------------------------------------------

def _col_neq_prev(col) -> jax.Array:
    """bool[cap]: row i differs from row i-1 (null-aware, NaN == NaN,
    struct fieldwise; row 0 => True)."""
    from auron_tpu.ops.hashing import adjacent_eq
    return jnp.concatenate([jnp.ones(1, bool), ~adjacent_eq(col)])


def _segmented_cummax_pos(flags: jax.Array) -> jax.Array:
    """For each row, the last position <= i where flags was True."""
    cap = flags.shape[0]
    pos = jnp.arange(cap, dtype=jnp.int32)
    return jax.lax.cummax(jnp.where(flags, pos, -1))


def _segmented_scan(values, seg_new: jax.Array, combine):
    """Inclusive segmented prefix scan: resets at seg_new."""
    def op(a, b):
        fa, va = a
        fb, vb = b
        return (fa | fb, jnp.where(fb, vb, combine(va, vb)))

    _, out = jax.lax.associative_scan(op, (seg_new, values))
    return out


def _segmented_scan128(h, l, seg_new: jax.Array, combine128):
    """Segmented inclusive scan over two-limb (hi, lo) values; combine128
    takes (ah, al, bh, bl) -> (h, l) and must be associative (add128 and
    the cmp128-select min/max are)."""
    def op(a, b):
        fa, ha, la = a
        fb, hb, lb = b
        ch, cl = combine128(ha, la, hb, lb)
        return (fa | fb,
                jnp.where(fb, hb, ch), jnp.where(fb, lb, cl))

    _, oh, ol = jax.lax.associative_scan(op, (seg_new, h, l))
    return oh, ol


# ---------------------------------------------------------------------------
# kernel
# ---------------------------------------------------------------------------

def _result_field(spec: WindowFunctionSpec, name: str,
                  in_schema: Schema) -> Field:
    if spec.kind == "rank_like":
        if spec.fn in ("percent_rank", "cume_dist"):
            return Field(name, DataType.FLOAT64, False)
        return Field(name, DataType.INT64, False)
    if spec.kind == "offset":
        dt, p, s = infer_dtype(spec.arg, in_schema)
        return Field(name, dt, True, p, s)
    # agg
    if spec.fn in ("count", "count_star"):
        return Field(name, DataType.INT64, False)
    dt, p, s = infer_dtype(spec.arg, in_schema)
    if spec.fn == "avg":
        if dt == DataType.DECIMAL:
            if p + 4 > 18:
                # matches AggOp: avg past 18 digits promotes to the wide
                # representation with Spark's bounded(p+4, s+4) type
                from auron_tpu.ops.agg import decimal_avg_result
                p, s = decimal_avg_result(p, s)
            else:
                p, s = _decimal_avg_type(p, s)
        elif dt != DataType.FLOAT64:
            dt = DataType.FLOAT64
    if spec.fn == "sum" and dt == DataType.DECIMAL:
        # Spark sum headroom for narrow AND wide inputs: sum(decimal(p,s))
        # is decimal(p+10, s) capped at the 128-bit 38; narrow inputs with
        # p+10 > 18 promote to the two-limb representation (AggOp parity)
        p = min(p + 10, 38)
    if spec.fn == "sum" and dt.is_integer:
        dt = DataType.INT64   # kernel accumulates int64 (Spark: sum → long)
    return Field(name, dt, True, p, s)


def _decimal_half_up_div(total, count, shift: int):
    """Scaled-int decimal average: (total * shift) / count rounded
    HALF_UP away from zero (Spark Decimal.divide); quotient/remainder
    form keeps the intermediate within one 10^delta shift of the sum.
    Shared by the default-frame and ROWS-frame window avg paths."""
    num = total * shift
    safe = jnp.maximum(count, 1)
    a = jnp.abs(num)
    q0 = a // safe
    rem = a - q0 * safe
    q = q0 + (2 * rem >= safe)
    return jnp.where(num < 0, -q, q)


def _decimal_avg_type(p: int, s: int) -> tuple[int, int]:
    """Spark: avg(decimal(p,s)) -> decimal(p+4, s+4). This engine's
    decimals are int64-scaled, so precision caps at 18 and the scale is
    clamped to the capped precision (sums whose scaled value would exceed
    int64 are an accepted engine limitation, as for sum)."""
    p = p or 18
    s = s or 0
    np_ = min(p + 4, 18)
    return np_, min(s + 4, np_)


@program_cache("ops.window.window", maxsize=128)
def _window_kernel(partition_exprs: tuple, order_by: tuple, fn_specs: tuple,
                   in_schema: Schema, capacity: int, group_limit):
    n_funcs = len(fn_specs)

    @jax.jit
    def auron_ops_window_window(batch: DeviceBatch):
        ectx = EvalContext(memo={})
        pcols = [evaluate(e, batch, in_schema, ectx).col
                 for e in partition_exprs]
        ocols = [evaluate(o.expr, batch, in_schema, ectx).col
                 for o in order_by]
        key_cols = pcols + ocols
        orders = ([(True, True)] * len(pcols) +
                  [(o.ascending, o.nulls_first) for o in order_by])
        if key_cols:
            perm = sort_permutation(batch, key_cols, orders)
        else:
            perm = jnp.arange(batch.capacity, dtype=jnp.int32)
        sbatch = gather_batch(batch, perm, batch.num_rows)
        cap = sbatch.capacity
        live = sbatch.row_mask()
        pos = jnp.arange(cap, dtype=jnp.int32)
        n = sbatch.num_rows

        def sorted_col(c):
            from auron_tpu.columnar.batch import StructColumn
            from auron_tpu.columnar.decimal128 import Decimal128Column
            if isinstance(c, StringColumn):
                return StringColumn(c.chars[perm], c.lens[perm],
                                    c.validity[perm])
            if isinstance(c, Decimal128Column):
                return Decimal128Column(c.hi[perm], c.lo[perm],
                                        c.validity[perm])
            if isinstance(c, StructColumn):
                return StructColumn(tuple(sorted_col(ch)
                                          for ch in c.children),
                                    c.validity[perm])
            return PrimitiveColumn(c.data[perm], c.validity[perm])

        spcols = [sorted_col(c) for c in pcols]
        socols = [sorted_col(c) for c in ocols]

        # partition segment boundaries
        if spcols:
            seg_new = jnp.zeros(cap, bool)
            for c in spcols:
                seg_new = seg_new | _col_neq_prev(c)
            seg_new = seg_new.at[0].set(True)
        else:
            seg_new = jnp.zeros(cap, bool).at[0].set(True)
        # order-key (peer group) boundaries
        tie_new = seg_new
        for c in socols:
            tie_new = tie_new | _col_neq_prev(c)

        seg_start = _segmented_cummax_pos(seg_new)
        seg_id = jnp.cumsum(seg_new.astype(jnp.int32)) - 1
        # end of each row's segment: last live row with same seg_id, via
        # scatter-max of positions
        seg_end = jax.ops.segment_max(
            jnp.where(live, pos, -1), jnp.clip(seg_id, 0, cap - 1),
            num_segments=cap)
        seg_end_row = seg_end[jnp.clip(seg_id, 0, cap - 1)]
        npart = (seg_end_row - seg_start + 1).astype(jnp.int64)

        # peer (tie) group end: last row with same (segment, order keys)
        tie_id = jnp.cumsum(tie_new.astype(jnp.int32)) - 1
        tie_end = jax.ops.segment_max(
            jnp.where(live, pos, -1), jnp.clip(tie_id, 0, cap - 1),
            num_segments=cap)
        tie_end_row = tie_end[jnp.clip(tie_id, 0, cap - 1)]

        row_number = (pos - seg_start + 1).astype(jnp.int64)
        rank = (_segmented_cummax_pos(tie_new) - seg_start + 1).astype(jnp.int64)
        dense_rank = _segmented_scan(
            tie_new.astype(jnp.int64), seg_new, jnp.add)

        out_cols = []
        for spec in fn_specs:
            if spec.kind == "rank_like":
                if spec.fn == "row_number":
                    data = row_number
                elif spec.fn == "rank":
                    data = rank
                elif spec.fn == "dense_rank":
                    data = dense_rank
                elif spec.fn == "percent_rank":
                    data = jnp.where(npart > 1,
                                     (rank - 1).astype(jnp.float64)
                                     / jnp.maximum(npart - 1, 1), 0.0)
                elif spec.fn == "cume_dist":
                    data = (tie_end_row - seg_start + 1).astype(jnp.float64) \
                        / jnp.maximum(npart, 1)
                elif spec.fn == "ntile":
                    k = spec.offset
                    q, r = npart // k, npart % k
                    rn0 = row_number - 1
                    cutoff = (q + 1) * r
                    in_big = rn0 < cutoff
                    data = jnp.where(
                        in_big, rn0 // jnp.maximum(q + 1, 1) + 1,
                        r + (rn0 - cutoff) // jnp.maximum(q, 1) + 1)
                out_cols.append(PrimitiveColumn(data, live))
                continue

            v = evaluate(spec.arg, sbatch, in_schema, ectx) \
                if spec.arg is not None else None

            if spec.kind == "offset":
                col = v.col
                if spec.fn in ("lead", "lag"):
                    delta = spec.offset if spec.fn == "lead" else -spec.offset
                    src = pos + delta
                    in_seg = (src >= seg_start) & (src <= seg_end_row)
                    src_c = jnp.clip(src, 0, cap - 1)
                elif spec.fn == "first_value":
                    src_c, in_seg = seg_start, live
                elif spec.fn == "last_value":
                    # default frame: up to current peer group end
                    src_c = tie_end_row if order_by else seg_end_row
                    in_seg = live
                else:  # nth_value (frame-clipped like last_value)
                    src = seg_start + (spec.offset - 1)
                    bound = tie_end_row if order_by else seg_end_row
                    in_seg = (src <= bound) & live
                    src_c = jnp.clip(src, 0, cap - 1)
                from auron_tpu.columnar.decimal128 import Decimal128Column
                if isinstance(col, Decimal128Column):
                    if spec.default is not None:
                        raise NotImplementedError(
                            "lead/lag default over decimal(p>18)")
                    out_cols.append(Decimal128Column(
                        col.hi[src_c], col.lo[src_c],
                        col.validity[src_c] & in_seg & live))
                    continue
                if isinstance(col, StringColumn):
                    chars = col.chars[src_c]
                    lens = jnp.where(in_seg, col.lens[src_c], 0)
                    valid = col.validity[src_c] & in_seg & live
                    if spec.default is not None and spec.fn in ("lead", "lag"):
                        db = str(spec.default).encode()[:col.width]
                        drow = jnp.zeros(col.width, jnp.uint8).at[
                            :len(db)].set(jnp.asarray(list(db), jnp.uint8))
                        chars = jnp.where(in_seg[:, None], chars, drow[None, :])
                        lens = jnp.where(in_seg, lens, len(db))
                        valid = jnp.where(in_seg, valid, live)
                    out = StringColumn(chars, lens, valid)
                else:
                    data = col.data[src_c]
                    valid = col.validity[src_c] & in_seg & live
                    if spec.default is not None and spec.fn in ("lead", "lag"):
                        data = jnp.where(in_seg, data,
                                         jnp.asarray(spec.default, data.dtype))
                        valid = jnp.where(in_seg, valid, live)
                    out = PrimitiveColumn(data, valid)
                out_cols.append(out)
                continue

            if spec.frame is not None:
                # ROWS BETWEEN lo..hi: windowed segmented sums via prefix
                # differences — sum[i] = P[b] - P[a-1] with a/b clamped
                # into the row's segment (reference: the frame-bounded agg
                # processors in window/processors/agg.rs). Sums whose
                # declared type exceeds 18 digits (wide input, or narrow
                # promoted by the p+10 headroom) run the scan in 128-bit
                # limbs; framed avg over those still fails fast.
                from auron_tpu.columnar.decimal128 import Decimal128Column
                if v is not None and spec.fn == "avg":
                    _dt0, _p0, _s0 = infer_dtype(spec.arg, in_schema)
                    if isinstance(v.col, Decimal128Column) or (
                            _dt0 == DataType.DECIMAL and _p0 + 4 > 18):
                        raise NotImplementedError(
                            "ROWS frames over avg(decimal(p>14)): the "
                            "framed HALF_UP division runs on the int64 "
                            "path only")
                lo_off, hi_off = spec.frame

                # shared frame index math: prefix rows at the window's
                # inclusive end (bi) and exclusive start (ai, valid only
                # when has_lo), empty = window outside the segment
                a = pos + lo_off
                b = pos + hi_off
                f_empty = (a > seg_end_row) | (b < seg_start)
                a_c = jnp.clip(a, seg_start, seg_end_row)
                b_c = jnp.clip(b, seg_start, seg_end_row)
                f_bi = jnp.clip(b_c, 0, cap - 1)
                f_ai = jnp.clip(a_c - 1, 0, cap - 1)
                f_has_lo = a_c > seg_start

                def frame_window(prefix):
                    lo_v = jnp.where(f_has_lo, prefix[f_ai], 0)
                    return jnp.where(f_empty, 0, prefix[f_bi] - lo_v)

                if spec.fn == "count_star":
                    # one scan: the count prefix IS the value prefix here
                    p_cnt = _segmented_scan(live.astype(jnp.int64),
                                            seg_new, jnp.add)
                    out_cols.append(
                        PrimitiveColumn(frame_window(p_cnt), live))
                    continue
                vv = v.validity & live
                p_cnt = _segmented_scan(vv.astype(jnp.int64), seg_new,
                                        jnp.add)
                wcnt = frame_window(p_cnt)
                if spec.fn == "count":
                    out_cols.append(PrimitiveColumn(wcnt, live))
                    continue
                dt_in, _p, in_s = infer_dtype(spec.arg, in_schema)
                if spec.fn == "sum" and dt_in == DataType.DECIMAL \
                        and (_p + 10 > 18
                             or isinstance(v.col, Decimal128Column)):
                    # wide-typed frame sum: exact 128-bit prefix scan +
                    # limb-pair prefix differences, overflow-nulled at the
                    # declared precision like the running-window path (an
                    # int64 scan here can silently wrap inside a frame)
                    from auron_tpu.columnar import decimal128 as d128
                    if isinstance(v.col, Decimal128Column):
                        s_hi, s_lo = v.col.hi, v.col.lo
                    else:
                        s_hi, s_lo = d128.from_int64(
                            v.col.data.astype(jnp.int64))
                    ph, pl = _segmented_scan128(
                        jnp.where(vv, s_hi, 0), jnp.where(vv, s_lo, 0),
                        seg_new, d128.add128)
                    lh = jnp.where(f_has_lo, ph[f_ai], 0)
                    ll = jnp.where(f_has_lo, pl[f_ai], 0)
                    rh, rl = d128.sub128(ph[f_bi], pl[f_bi], lh, ll)
                    ok = ((wcnt > 0) & live & ~f_empty
                          & d128.fits_precision(rh, rl, min(_p + 10, 38)))
                    out_cols.append(Decimal128Column(rh, rl, ok))
                    continue
                vals = jnp.where(vv, v.col.data, 0)
                if jnp.issubdtype(vals.dtype, jnp.integer):
                    vals = vals.astype(jnp.int64)
                p_sum = _segmented_scan(vals, seg_new, jnp.add)
                wsum = frame_window(p_sum)
                if spec.fn == "avg":
                    if dt_in == DataType.DECIMAL:
                        _rp, rs = _decimal_avg_type(_p, in_s)
                        wsum = _decimal_half_up_div(
                            wsum, wcnt, 10 ** (rs - (in_s or 0)))
                    else:
                        wsum = wsum.astype(jnp.float64) \
                            / jnp.maximum(wcnt, 1)
                out_cols.append(PrimitiveColumn(wsum, (wcnt > 0) & live))
                continue

            # agg over window — two-limb decimal(p>18) values run the
            # same segmented scans in 128-bit limb arithmetic
            from auron_tpu.columnar.decimal128 import Decimal128Column
            if (v is not None and spec.fn in ("avg", "sum")
                    and not isinstance(v.col, Decimal128Column)):
                _dt, _p, _s = infer_dtype(spec.arg, in_schema)
                headroom = 4 if spec.fn == "avg" else 10
                if _dt == DataType.DECIMAL and _p + headroom > 18:
                    # same wide promotion as AggOp: window avg of
                    # decimal(15..18,s) returns Spark's decimal(p+4,s+4),
                    # window sum of decimal(9..18,s) decimal(p+10,s)
                    from auron_tpu.columnar import decimal128 as d128
                    _h, _l = d128.from_int64(v.col.data.astype(jnp.int64))
                    v = TypedValue(Decimal128Column(_h, _l, v.validity),
                                   DataType.DECIMAL, _p, _s)
            if v is not None and isinstance(v.col, Decimal128Column) \
                    and spec.fn != "count":
                from auron_tpu.columnar import decimal128 as d128
                from auron_tpu.ops.agg import _DEC_NEUTRAL
                vv = v.validity & live
                hi, lo = v.col.hi, v.col.lo
                has = _segmented_scan(vv.astype(jnp.int64), seg_new,
                                      jnp.add)
                if spec.fn in ("sum", "avg"):
                    rh, rl = _segmented_scan128(
                        jnp.where(vv, hi, 0), jnp.where(vv, lo, 0),
                        seg_new, d128.add128)
                else:   # min / max
                    nh, nl = _DEC_NEUTRAL[f"d{spec.fn}"]
                    def pick(ah, al, bh, bl, _mx=(spec.fn == "max")):
                        lt, _ = d128.cmp128(ah, al, bh, bl)
                        take_a = (~lt) if _mx else lt
                        return (jnp.where(take_a, ah, bh),
                                jnp.where(take_a, al, bl))
                    rh, rl = _segmented_scan128(
                        jnp.where(vv, hi, nh), jnp.where(vv, lo, nl),
                        seg_new, pick)
                end = tie_end_row if order_by else seg_end_row
                end_c = jnp.clip(end, 0, cap - 1)
                rh, rl, has_e = rh[end_c], rl[end_c], has[end_c]
                ok = has_e > 0
                if spec.fn == "sum":
                    # running sums past the declared precision null, like
                    # AggOp's wide sum (Spark non-ANSI overflow)
                    _dt, _p, _s = infer_dtype(spec.arg, in_schema)
                    ok = ok & d128.fits_precision(rh, rl, min(_p + 10, 38))
                if spec.fn == "avg":
                    _dt, _p, in_s = infer_dtype(spec.arg, in_schema)
                    from auron_tpu.ops.agg import decimal_avg_result
                    _rp, rs = decimal_avg_result(_p, in_s)
                    rh, rl, fits = d128.avg_pow10_div_half_up(
                        rh, rl, jnp.maximum(has_e, 1), rs - in_s)
                    ok = ok & fits
                out_cols.append(Decimal128Column(rh, rl, ok & live))
                continue
            if spec.fn == "count_star":
                run = _segmented_scan(live.astype(jnp.int64), seg_new, jnp.add)
                valid = live
            elif spec.fn == "count":
                run = _segmented_scan((v.validity & live).astype(jnp.int64),
                                      seg_new, jnp.add)
                valid = live
            elif spec.fn in ("sum", "avg"):
                vals = jnp.where(v.validity & live, v.col.data, 0)
                if jnp.issubdtype(vals.dtype, jnp.integer):
                    vals = vals.astype(jnp.int64)
                run = _segmented_scan(vals, seg_new, jnp.add)
                has = _segmented_scan((v.validity & live).astype(jnp.int64),
                                      seg_new, jnp.add)
                if spec.fn == "avg":
                    dt_in, _p, in_s = infer_dtype(spec.arg, in_schema)
                    if dt_in == DataType.DECIMAL:
                        # scaled-int divide at the (clamped) s+4 result
                        # scale (shared HALF_UP helper)
                        _rp, rs = _decimal_avg_type(_p, in_s)
                        run = _decimal_half_up_div(
                            run, has, 10 ** (rs - (in_s or 0)))
                    else:
                        run = run.astype(jnp.float64) / jnp.maximum(has, 1)
                valid = has > 0
            else:  # min / max
                big = jnp.asarray(
                    jnp.finfo(v.col.data.dtype).max
                    if jnp.issubdtype(v.col.data.dtype, jnp.floating)
                    else jnp.iinfo(v.col.data.dtype).max, v.col.data.dtype)
                neutral = big if spec.fn == "min" else (
                    -big if jnp.issubdtype(v.col.data.dtype, jnp.floating)
                    else jnp.asarray(
                        jnp.iinfo(v.col.data.dtype).min, v.col.data.dtype))
                vals = jnp.where(v.validity & live, v.col.data, neutral)
                run = _segmented_scan(
                    vals, seg_new,
                    jnp.minimum if spec.fn == "min" else jnp.maximum)
                has = _segmented_scan((v.validity & live).astype(jnp.int64),
                                      seg_new, jnp.add)
                valid = has > 0
            if order_by:
                # peers share the value at their tie group's end
                run = run[jnp.clip(tie_end_row, 0, cap - 1)]
                valid = valid[jnp.clip(tie_end_row, 0, cap - 1)] & live
            else:
                run = run[jnp.clip(seg_end_row, 0, cap - 1)]
                valid = valid[jnp.clip(seg_end_row, 0, cap - 1)] & live
            out_cols.append(PrimitiveColumn(run, valid))

        result = DeviceBatch(tuple(sbatch.columns) + tuple(out_cols), n)
        if group_limit is not None:
            from auron_tpu.columnar.batch import compact
            keep = (rank <= group_limit) & live
            result = compact(result, keep)
        # (rows in, partitions found): read with the output's row count
        stats = jnp.stack([jnp.asarray(n, jnp.int32),
                           jnp.sum(seg_new & live, dtype=jnp.int32)])
        return result, stats

    return auron_ops_window_window


# ---------------------------------------------------------------------------
# operator
# ---------------------------------------------------------------------------

class WindowOp(PhysicalOp):
    name = "window"

    def __init__(self, child: PhysicalOp, partition_by: list[ir.Expr],
                 order_by: list[ir.SortOrder],
                 functions: list[WindowFunctionSpec],
                 output_names: Optional[list[str]] = None,
                 group_limit: Optional[int] = None):
        self.child = child
        self.partition_by = tuple(partition_by)
        self.order_by = tuple(order_by)
        self.functions = tuple(functions)
        self.group_limit = group_limit
        names = output_names or [f"w{i}" for i in range(len(functions))]
        self.output_names = list(names)
        in_schema = child.schema()
        extra = [_result_field(spec, n, in_schema)
                 for spec, n in zip(self.functions, names)]
        self._schema = Schema(tuple(in_schema.fields) + tuple(extra))

    @property
    def children(self):
        return [self.child]

    def schema(self) -> Schema:
        return self._schema

    def execute(self, partition: int, ctx: ExecContext) -> Iterator[DeviceBatch]:
        metrics = ctx.metrics_for(self)
        elapsed = metrics.counter("elapsed_compute")
        in_schema = self.child.schema()

        def stream():
            batches = list(self.child.execute(partition, ctx))
            if not batches:
                return
            with timer(elapsed) as t:
                merged = _concat_all(batches) if len(batches) > 1 else batches[0]
                kern = _window_kernel(self.partition_by, self.order_by,
                                      self.functions, in_schema,
                                      merged.capacity, self.group_limit)
                out, stats = t.track(kern(merged))
                # the one read of this operator: its output's row count,
                # and with it what the program saw (``count_output``
                # would read the count alone)
                n_out, (n_in, n_parts) = _profile.row_count_and(out, stats)
                _trace.count("window_rows", n_in)
                _trace.count("window_partitions", n_parts)
            yield DeviceBatch(out.columns, n_out)

        return count_output(stream(), metrics)

    def __repr__(self):
        fns = ",".join(s.fn for s in self.functions)
        return (f"WindowOp[{fns} partition_by={len(self.partition_by)} "
                f"order_by={len(self.order_by)}]")
