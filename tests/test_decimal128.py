"""Decimal precision 19..38 — the two-limb device representation
(columnar/decimal128.py; reference computes these in Rust i128:
arrow/cast.rs decimal paths, spark_check_overflow.rs). Differential
against python Decimal with exact contexts."""

import decimal
import random

import numpy as np
import pyarrow as pa
import pytest

import jax.numpy as jnp

from auron_tpu.columnar import decimal128 as D
from auron_tpu.columnar.arrow_bridge import schema_from_arrow, to_arrow, to_device
from auron_tpu.exprs import ir
from auron_tpu.io.parquet import MemoryScanOp
from auron_tpu.ops.project import ProjectOp
from auron_tpu.ops.sort import SortOp
from auron_tpu.runtime.executor import collect

C = ir.ColumnRef
decimal.getcontext().prec = 80


def _dec_batch(vals, precision, scale, name="d"):
    return pa.record_batch({name: pa.array(
        [None if v is None else decimal.Decimal(v) for v in vals],
        pa.decimal128(precision, scale))})


def mem_scan(rb, capacity=16):
    return MemoryScanOp([[rb]], schema_from_arrow(rb.schema),
                        capacity=capacity)


class TestLimbMath:
    def test_random_roundtrip_and_ops(self):
        random.seed(11)
        N = 300
        a = [random.randint(-10 ** 38 + 1, 10 ** 38 - 1) for _ in range(N)]
        b = [random.randint(-10 ** 18, 10 ** 18) for _ in range(N)]
        ah, al, _ = D.limbs_from_ints(a, N)
        bh, bl, _ = D.limbs_from_ints(b, N)
        ah, al, bh, bl = map(jnp.asarray, (ah, al, bh, bl))
        wrap = lambda x: ((x + 2 ** 127) % 2 ** 128) - 2 ** 127

        def to_py(h, l):
            return D.ints_from_limbs(np.asarray(h), np.asarray(l),
                                     np.ones(N, bool))

        rh, rl = D.add128(ah, al, bh, bl)
        assert to_py(rh, rl) == [wrap(x + y) for x, y in zip(a, b)]
        rh, rl = D.mul128(ah, al, bh, bl)
        assert to_py(rh, rl) == [wrap(x * y) for x, y in zip(a, b)]
        for k in (3, 11, 20):
            rh, rl = D.div_pow10_half_up(ah, al, k)
            exp = [int(decimal.Decimal(x).scaleb(-k).to_integral_value(
                rounding=decimal.ROUND_HALF_UP)) for x in a]
            assert to_py(rh, rl) == exp, k
            rh, rl = D.div_pow10_trunc(ah, al, k)
            exp = [int(decimal.Decimal(x).scaleb(-k).to_integral_value(
                rounding=decimal.ROUND_DOWN)) for x in a]
            assert to_py(rh, rl) == exp, k


class TestArrowRoundtrip:
    def test_scan_project_collect(self):
        vals = ["12345678901234567890123456.789", "-0.001", None,
                "99999999999999999999999999999999.99"]
        rb = _dec_batch(vals, 38, 3)
        out = collect(ProjectOp(mem_scan(rb), [C(0)], ["d"]))
        got = out.column("d").to_pylist()
        exp = [None if v is None else decimal.Decimal(v).quantize(
            decimal.Decimal(1).scaleb(-3)) for v in vals]
        assert got == exp

    def test_wide_arithmetic(self):
        # products stay within precision 38 (overflow semantics tested
        # separately): dec(22,2) operands with modest magnitudes
        a = ["12345678901234567890.12", "-99999999999999999999.99", "0.01"]
        b = ["87654321.01", "0.01", "-0.01"]
        rb = pa.record_batch({
            "a": pa.array([decimal.Decimal(x) for x in a],
                          pa.decimal128(22, 2)),
            "b": pa.array([decimal.Decimal(x) for x in b],
                          pa.decimal128(22, 2)),
        })
        add = ir.BinaryExpr("+", C(0), C(1))
        mul = ir.BinaryExpr("*", C(0), C(1))
        lt = ir.BinaryExpr("<", C(0), C(1))
        out = collect(ProjectOp(mem_scan(rb), [add, mul, lt],
                                ["s", "m", "lt"]))
        exp_s = [decimal.Decimal(x) + decimal.Decimal(y)
                 for x, y in zip(a, b)]
        assert out.column("s").to_pylist() == exp_s
        exp_m = [decimal.Decimal(x) * decimal.Decimal(y)
                 for x, y in zip(a, b)]
        assert out.column("m").to_pylist() == exp_m
        assert out.column("lt").to_pylist() == [
            decimal.Decimal(x) < decimal.Decimal(y) for x, y in zip(a, b)]

    def test_narrow_times_narrow_promotes_wide(self):
        """dec(15,2) * dec(15,2) → dec(31,4): int64 payloads would wrap."""
        a, b = "9999999999999.99", "9999999999999.99"
        rb = pa.record_batch({
            "a": pa.array([decimal.Decimal(a)], pa.decimal128(15, 2)),
            "b": pa.array([decimal.Decimal(b)], pa.decimal128(15, 2)),
        })
        out = collect(ProjectOp(mem_scan(rb),
                                [ir.BinaryExpr("*", C(0), C(1))], ["m"]))
        assert out.column("m").to_pylist() == [
            decimal.Decimal(a) * decimal.Decimal(b)]

    def test_overflow_nulls(self):
        big = decimal.Decimal(10) ** 37
        rb = pa.record_batch({
            "a": pa.array([big, decimal.Decimal(2)], pa.decimal128(38, 0)),
            "b": pa.array([big, decimal.Decimal(3)], pa.decimal128(38, 0)),
        })
        out = collect(ProjectOp(mem_scan(rb),
                                [ir.BinaryExpr("*", C(0), C(1))], ["m"]))
        got = out.column("m").to_pylist()
        assert got[0] is None            # 10^74 overflows precision 38
        assert got[1] == decimal.Decimal(6)

    def test_casts(self):
        vals = ["123456789012345678901.5678", "-42.4444", "0.0001"]
        rb = _dec_batch(vals, 38, 4)
        from auron_tpu.columnar.schema import DataType
        exprs = [
            ir.Cast(C(0), DataType.DECIMAL, precision=38, scale=2),
            ir.Cast(C(0), DataType.FLOAT64),
            ir.Cast(C(0), DataType.INT64),
            ir.Cast(C(0), DataType.STRING),
        ]
        out = collect(ProjectOp(mem_scan(rb), exprs,
                                ["rescale", "f", "i", "s"]))
        exp_rescale = [decimal.Decimal(v).quantize(
            decimal.Decimal("0.01"),
            rounding=decimal.ROUND_HALF_UP) for v in vals]
        assert out.column("rescale").to_pylist() == exp_rescale
        np.testing.assert_allclose(
            out.column("f").to_pylist(),
            [float(decimal.Decimal(v)) for v in vals], rtol=1e-12)
        # index 0 exceeds int64 → null (Spark non-ANSI overflow-to-null)
        assert out.column("i").to_pylist() == [None, -42, 0]
        assert out.column("s").to_pylist() == vals

    def test_int_to_wide_decimal(self):
        from auron_tpu.columnar.schema import DataType
        rb = pa.record_batch({"x": pa.array([123456789, -42], pa.int64())})
        out = collect(ProjectOp(
            mem_scan(rb),
            [ir.Cast(C(0), DataType.DECIMAL, precision=30, scale=10)],
            ["d"]))
        assert out.column("d").to_pylist() == [
            decimal.Decimal(123456789).quantize(
                decimal.Decimal(1).scaleb(-10)),
            decimal.Decimal(-42).quantize(decimal.Decimal(1).scaleb(-10))]

    def test_sort_on_wide_decimal(self):
        vals = ["5.00", "-12345678901234567890123.45", None,
                "99999999999999999999999.99", "0.01"]
        rb = _dec_batch(vals, 38, 2)
        out = collect(SortOp(mem_scan(rb), [ir.SortOrder(C(0))]))
        got = out.column("d").to_pylist()
        nonnull = sorted(decimal.Decimal(v) for v in vals if v is not None)
        assert got[0] is None and [g for g in got if g is not None] == [
            v.quantize(decimal.Decimal("0.01")) for v in nonnull]


class TestReviewFixes:
    def test_ingest_exact_under_default_context(self):
        """29-38 digit values must survive ingest/egress even when the
        ambient decimal context is the 28-digit default."""
        with decimal.localcontext() as ctx:
            ctx.prec = 28   # the hostile default
            v = "12345678901234567890123456789012.345678"
            rb = _dec_batch([v], 38, 6)
            out = collect(ProjectOp(mem_scan(rb), [C(0)], ["d"]))
            with decimal.localcontext() as wide:
                wide.prec = 60
                assert out.column("d").to_pylist() == [decimal.Decimal(v)]

    def test_string_cast_plain_notation(self):
        from auron_tpu.columnar.schema import DataType
        with decimal.localcontext() as ctx:
            ctx.prec = 28
            v = "1234567890123456789012345678901234.5678"
            rb = _dec_batch([v], 38, 4)
            out = collect(ProjectOp(mem_scan(rb),
                                    [ir.Cast(C(0), DataType.STRING)], ["s"]))
            assert out.column("s").to_pylist() == [v]

    def test_precision_loss_scale_adjustment(self):
        """dec(38,10) + dec(38,10) → dec(38,9) (Spark adjustPrecisionScale),
        value rescaled HALF_UP."""
        a = decimal.Decimal("1.0000000005")
        b = decimal.Decimal("2.0000000000")
        rb = pa.record_batch({
            "a": pa.array([a], pa.decimal128(38, 10)),
            "b": pa.array([b], pa.decimal128(38, 10)),
        })
        out = collect(ProjectOp(mem_scan(rb),
                                [ir.BinaryExpr("+", C(0), C(1))], ["s"]))
        f = out.schema.field("s")
        assert (f.type.precision, f.type.scale) == (38, 9)
        assert out.column("s").to_pylist() == [decimal.Decimal("3.000000001")]

    def test_float_to_wide_decimal(self):
        from auron_tpu.columnar.schema import DataType
        rb = pa.record_batch({"x": pa.array([1e20, -2.5], pa.float64())})
        out = collect(ProjectOp(
            mem_scan(rb),
            [ir.Cast(C(0), DataType.DECIMAL, precision=38, scale=1)], ["d"]))
        got = out.column("d").to_pylist()
        assert got[0] == decimal.Decimal(10) ** 20
        assert got[1] == decimal.Decimal("-2.5")

    def test_wide_decimal_spills_through_sort(self):
        """External sort of wide decimals: spill serde round-trips limbs."""
        from auron_tpu.memmgr.manager import MemManager
        from auron_tpu.memmgr.spill import SpillManager
        rng = random.Random(3)
        vals = [decimal.Decimal(rng.randint(-10 ** 30, 10 ** 30))
                .scaleb(-2) for _ in range(2000)]
        rb = pa.record_batch({"d": pa.array(vals, pa.decimal128(38, 2))})
        rbs = [rb.slice(o, 256) for o in range(0, 2000, 256)]
        mm = MemManager(total_bytes=24 << 10, min_trigger=0,
                        spill_manager=SpillManager(host_budget_bytes=1 << 24))
        scan = MemoryScanOp([rbs], schema_from_arrow(rb.schema),
                            capacity=256)
        out = collect(SortOp(scan, [ir.SortOrder(C(0))]), mem_manager=mm)
        assert mm.num_spills > 0
        got = out.column("d").to_pylist()
        assert got == sorted(vals)

    def test_rescale_wrap_guard_on_compare(self):
        """Comparing wildly different scales must not wrap: 10^21 at
        scale 0 vs tiny at scale 18."""
        rb = pa.record_batch({
            "a": pa.array([decimal.Decimal(10) ** 21], pa.decimal128(38, 0)),
            "b": pa.array([decimal.Decimal("0.000000000000000001")],
                          pa.decimal128(38, 18)),
        })
        out = collect(ProjectOp(mem_scan(rb),
                                [ir.BinaryExpr("<", C(0), C(1)),
                                 ir.BinaryExpr(">", C(0), C(1))],
                                ["lt", "gt"]))
        assert out.column("lt").to_pylist() == [False]
        assert out.column("gt").to_pylist() == [True]


class TestWrapGuards:
    def test_add_wrap_nulls_not_wrong_value(self):
        """Raw sum past 2^127 must null, not return a wrapped value."""
        v = decimal.Decimal(9) * 10 ** 27   # unscaled 9e37 at scale 10
        rb = pa.record_batch({
            "a": pa.array([v], pa.decimal128(38, 10)),
            "b": pa.array([v], pa.decimal128(38, 10)),
        })
        out = collect(ProjectOp(mem_scan(rb),
                                [ir.BinaryExpr("+", C(0), C(1))], ["s"]))
        assert out.column("s").to_pylist() == [None]

    def test_halfup_boundary_k38(self):
        """k=38 rescale with remainder >= 2^126: the bump test must not
        signed-wrap (0.9 at scale 38 → 1 at scale 0)."""
        from auron_tpu.columnar.schema import DataType
        rb = _dec_batch(["0.9" + "0" * 36], 38, 38)
        out = collect(ProjectOp(
            mem_scan(rb),
            [ir.Cast(C(0), DataType.DECIMAL, precision=38, scale=0)], ["r"]))
        assert out.column("r").to_pylist() == [decimal.Decimal(1)]

    def test_high_scale_mul_rescale_past_38(self):
        """full_s - adjusted_s > 38 must not crash (rounds to the adjusted
        scale; tiny values become zero)."""
        rb = pa.record_batch({
            "a": pa.array([decimal.Decimal("0." + "0" * 35 + "5")],
                          pa.decimal128(38, 36)),
            "b": pa.array([decimal.Decimal("0." + "0" * 35 + "4")],
                          pa.decimal128(38, 36)),
        })
        out = collect(ProjectOp(mem_scan(rb),
                                [ir.BinaryExpr("*", C(0), C(1))], ["m"]))
        got = out.column("m").to_pylist()
        assert got == [decimal.Decimal(0).scaleb(-6).quantize(
            decimal.Decimal(1).scaleb(-6))]

    def test_unsafe_compare_boundary_not_equal(self):
        """Values float64 cannot distinguish must still order correctly
        via sign dominance (the float fallback reported equality here)."""
        rb = pa.record_batch({
            "a": pa.array([decimal.Decimal(10) ** 20], pa.decimal128(38, 0)),
            "b": pa.array([decimal.Decimal("99999999999999999999."
                                           "999999999999999999")],
                          pa.decimal128(38, 18)),
        })
        out = collect(ProjectOp(mem_scan(rb),
                                [ir.BinaryExpr(">", C(0), C(1)),
                                 ir.BinaryExpr("==", C(0), C(1))],
                                ["gt", "eq"]))
        assert out.column("gt").to_pylist() == [True]
        assert out.column("eq").to_pylist() == [False]

    def test_wide_distinct_rejects_clearly(self):
        from auron_tpu.ops.agg import AggOp
        rb = _dec_batch(["1.00"], 25, 2)
        with pytest.raises(NotImplementedError, match="decimal"):
            AggOp(mem_scan(rb), [], [ir.AggFunction("sum", C(0),
                                                    distinct=True)],
                  mode="complete")


def _wide_agg_data(seed=3, n=400, n_groups=7, precision=38, scale=2,
                   null_every=9):
    """Group keys + wide decimal values incl. negatives, nulls, and
    magnitudes far past int64."""
    rng = random.Random(seed)
    groups, vals = [], []
    for i in range(n):
        groups.append(rng.randrange(n_groups))
        if null_every and i % null_every == 0:
            vals.append(None)
        else:
            digits = precision - 2 if rng.random() < 0.5 else 12
            mag = rng.randint(0, 10 ** digits - 1)
            vals.append(decimal.Decimal(mag if rng.random() < 0.5 else -mag)
                        .scaleb(-scale))
    return groups, vals


def _group_oracle(groups, vals):
    per: dict = {}
    for g, v in zip(groups, vals):
        per.setdefault(g, []).append(v)
    return per


class TestWideDecimalAgg:
    """VERDICT r3 directive 4: two-limb accumulators in the merge kernel
    (reference: datafusion-ext-plans/src/agg/sum.rs + acc.rs i128 state)."""

    def _run(self, groups, vals, aggs, precision=38, scale=2, mode="complete",
             capacity=64):
        import pyarrow as pa
        from auron_tpu.ops.agg import AggOp
        tbl_in = pa.table({
            "g": pa.array(groups, pa.int64()),
            "d": pa.array(vals, pa.decimal128(precision, scale))})
        rbs = pa.Table.from_batches(
            tbl_in.to_batches(max_chunksize=capacity)).to_batches()
        scan = MemoryScanOp([rbs], schema_from_arrow(tbl_in.schema),
                            capacity=capacity)
        if mode == "partial_final":
            op = AggOp(AggOp(scan, [C(0)], aggs, mode="partial"),
                       [C(0)], aggs, mode="final")
        else:
            op = AggOp(scan, [C(0)], aggs, mode="complete")
        tbl = collect(op).to_pandas().set_index("k0").sort_index()
        return op, tbl

    @pytest.mark.parametrize("mode", ["complete", "partial_final"])
    def test_sum_min_max_first_vs_decimal_oracle(self, mode):
        groups, vals = _wide_agg_data()
        op, got = self._run(groups, vals,
                            [ir.AggFunction("sum", C(1)),
                             ir.AggFunction("min", C(1)),
                             ir.AggFunction("max", C(1))], mode=mode)
        per = _group_oracle(groups, vals)
        for g, gvals in per.items():
            nn = [v for v in gvals if v is not None]
            assert got.loc[g, "a0"] == sum(nn)
            assert got.loc[g, "a1"] == min(nn)
            assert got.loc[g, "a2"] == max(nn)

    @pytest.mark.parametrize("mode", ["complete", "partial_final"])
    def test_avg_halfup_at_spark_scale(self, mode):
        groups, vals = _wide_agg_data(seed=5, precision=30, scale=3)
        op, got = self._run(groups, vals, [ir.AggFunction("avg", C(1))],
                            precision=30, scale=3, mode=mode)
        f = [f for f in op.schema()][1]
        assert (f.precision, f.scale) == (34, 7)  # Spark: (p+4, s+4)
        per = _group_oracle(groups, vals)
        for g, gvals in per.items():
            nn = [v for v in gvals if v is not None]
            exp = (sum(nn) / len(nn)).quantize(
                decimal.Decimal(1).scaleb(-7),
                rounding=decimal.ROUND_HALF_UP)
            assert got.loc[g, "a0"] == exp, g

    def test_all_null_group_and_count(self):
        groups = [0, 0, 1, 1]
        vals = [None, None, decimal.Decimal("7.25"),
                decimal.Decimal("-0.25")]
        _op, got = self._run(groups, vals,
                             [ir.AggFunction("sum", C(1)),
                              ir.AggFunction("count", C(1)),
                              ir.AggFunction("avg", C(1))])
        assert got.loc[0, "a0"] is None and got.loc[0, "a2"] is None
        assert got.loc[0, "a1"] == 0
        assert got.loc[1, "a0"] == decimal.Decimal("7.00")
        assert got.loc[1, "a1"] == 2
        assert got.loc[1, "a2"] == decimal.Decimal("3.500000")

    def test_avg_overflow_beyond_result_precision_nulls(self):
        # avg magnitude ~9e35 at scale 2 → scaled to result scale 6 it
        # exceeds decimal(38)'s 32 integral digits → Spark nulls; a small
        # group stays exact
        big = decimal.Decimal(9 * 10 ** 35).scaleb(-2)
        _op, got = self._run([0, 0, 1, 1],
                             [big, big, decimal.Decimal("2.00"),
                              decimal.Decimal("3.01")],
                             [ir.AggFunction("avg", C(1))])
        assert got.loc[0, "a0"] is None
        assert got.loc[1, "a0"] == decimal.Decimal("2.505000")

    def test_sum_overflow_beyond_declared_precision_nulls(self):
        # two values of 38 digits each: their sum exceeds 10^38 and the
        # declared precision stays 38 (p+10 caps) → Spark nulls the group
        big = decimal.Decimal(10 ** 37 * 9).scaleb(-2)
        _op, got = self._run([0, 0], [big, big],
                             [ir.AggFunction("sum", C(1))])
        assert got.loc[0, "a0"] is None

    def test_wide_decimal_group_key_hash_agg(self):
        # wide decimals as GROUP KEYS exercise limb-pair hashing
        # (ops/hashing.py) + limb key equality in the merge kernel
        import pyarrow as pa
        from auron_tpu.ops.agg import AggOp
        rng = random.Random(8)
        keys = [decimal.Decimal(rng.choice(
            [10 ** 30 + 7, -10 ** 25, 3, 10 ** 36])).scaleb(-2)
            for _ in range(200)]
        ones = list(range(200))
        tbl_in = pa.table({
            "k": pa.array(keys, pa.decimal128(38, 2)),
            "v": pa.array(ones, pa.int64())})
        rbs = tbl_in.to_batches(max_chunksize=64)
        scan = MemoryScanOp([rbs], schema_from_arrow(tbl_in.schema),
                            capacity=64)
        op = AggOp(scan, [C(0)],
                   [ir.AggFunction("sum", C(1)),
                    ir.AggFunction("count", C(1))], mode="complete")
        got = collect(op).to_pandas().set_index("k0").sort_index()
        per: dict = {}
        for k, v in zip(keys, ones):
            per.setdefault(k, []).append(v)
        assert len(got) == len(per)
        for k, gvals in per.items():
            assert got.loc[k, "a0"] == sum(gvals)
            assert got.loc[k, "a1"] == len(gvals)

    def test_window_running_aggs_wide(self):
        # running sum/min/max/avg + lag over decimal(38,2) partitions
        import pyarrow as pa
        from auron_tpu.ops.window import WindowOp, WindowFunctionSpec
        rng = random.Random(4)
        n, n_groups = 120, 5
        groups = [rng.randrange(n_groups) for _ in range(n)]
        order = list(range(n))
        vals = [None if i % 7 == 0 else
                decimal.Decimal(rng.randint(-10 ** 30, 10 ** 30)).scaleb(-2)
                for i in range(n)]
        rb = pa.record_batch({
            "g": pa.array(groups, pa.int64()),
            "o": pa.array(order, pa.int64()),
            "d": pa.array(vals, pa.decimal128(38, 2))})
        op = WindowOp(mem_scan(rb, capacity=128), [C(0)],
                      [ir.SortOrder(C(1), True, True)],
                      [WindowFunctionSpec("agg", "sum", arg=C(2)),
                       WindowFunctionSpec("agg", "min", arg=C(2)),
                       WindowFunctionSpec("agg", "max", arg=C(2)),
                       WindowFunctionSpec("agg", "avg", arg=C(2)),
                       WindowFunctionSpec("offset", "lag", arg=C(2),
                                          offset=1)],
                      output_names=["s", "mn", "mx", "av", "lg"])
        got = collect(op).to_pandas().sort_values("o").reset_index(drop=True)
        # oracle: running values per group in order
        state: dict = {}
        prev: dict = {}
        q6 = decimal.Decimal(1).scaleb(-6)
        for i in range(n):
            g, v = groups[i], vals[i]
            row = got.iloc[i]
            assert row["o"] == i
            seen = state.setdefault(g, [])
            if v is not None:
                seen.append(v)
            if seen:
                assert row["s"] == sum(seen), i
                assert row["mn"] == min(seen)
                assert row["mx"] == max(seen)
                assert row["av"] == (sum(seen) / len(seen)).quantize(
                    decimal.ROUND_HALF_UP and q6,
                    rounding=decimal.ROUND_HALF_UP)
            else:
                assert row["s"] is None and row["av"] is None
            assert row["lg"] == prev.get(g)
            prev[g] = v


    def test_window_sum_narrow_promotes_like_agg(self):
        """AggOp/WindowOp parity: sum over decimal(12,2) declares Spark's
        decimal(22,2) and rides the two-limb representation (running AND
        ROWS-frame paths); totals stay exact past int64-scaled range."""
        import pyarrow as pa
        from auron_tpu.ops.window import WindowOp, WindowFunctionSpec
        rng = random.Random(6)
        n = 40
        groups = [rng.randrange(3) for i in range(n)]
        vals = [None if i % 9 == 0 else
                decimal.Decimal(rng.randint(-10 ** 10, 10 ** 10)).scaleb(-2)
                for i in range(n)]
        rb = pa.record_batch({
            "g": pa.array(groups, pa.int64()),
            "o": pa.array(list(range(n)), pa.int64()),
            "d": pa.array(vals, pa.decimal128(12, 2))})
        op = WindowOp(mem_scan(rb, capacity=64), [C(0)],
                      [ir.SortOrder(C(1), True, True)],
                      [WindowFunctionSpec("agg", "sum", arg=C(2)),
                       WindowFunctionSpec("agg", "sum", arg=C(2),
                                          frame=(-2, 0))],
                      output_names=["s", "fs"])
        sf = [f for f in op.schema() if f.name == "s"][0]
        assert (sf.precision, sf.scale) == (22, 2)
        ff = [f for f in op.schema() if f.name == "fs"][0]
        assert (ff.precision, ff.scale) == (22, 2)
        got = collect(op).to_pandas().sort_values("o").reset_index(drop=True)
        state: dict = {}
        hist: dict = {}
        for i in range(n):
            g, v = groups[i], vals[i]
            row = got.iloc[i]
            seen = state.setdefault(g, [])
            h = hist.setdefault(g, [])
            h.append(v)
            if v is not None:
                seen.append(v)
            if seen:
                assert row["s"] == sum(seen), i
            else:
                assert row["s"] is None
            win = [x for x in h[-3:] if x is not None]
            if win:
                assert row["fs"] == sum(win), i
            else:
                assert row["fs"] is None, i


    def test_rows_frame_sum_128bit_no_wrap(self):
        """Review finding: framed sums that exceed int64 in the scaled
        representation must stay exact (128-bit scan), not wrap. Eleven
        9.2e15.00 values in one 11-row frame total 1.012e17 — past
        int64's 9.22e18 in cents? No: past it via the PREFIX (running
        prefix of 40 such rows is 3.7e19 cents > 2^63), which is where
        the int64 scan wrapped."""
        import pyarrow as pa
        from auron_tpu.ops.window import WindowOp, WindowFunctionSpec
        n = 40
        big = decimal.Decimal("9200000000000000.00")   # 9.2e17 cents
        vals = [big] * n
        rb = pa.record_batch({
            "g": pa.array([1] * n, pa.int64()),
            "o": pa.array(list(range(n)), pa.int64()),
            "d": pa.array(vals, pa.decimal128(18, 2))})
        op = WindowOp(mem_scan(rb, capacity=64), [C(0)],
                      [ir.SortOrder(C(1), True, True)],
                      [WindowFunctionSpec("agg", "sum", arg=C(2),
                                          frame=(-10, 0))],
                      output_names=["fs"])
        got = collect(op).to_pandas().sort_values("o").reset_index(drop=True)
        for i in range(n):
            w = min(i + 1, 11)
            assert got.loc[i, "fs"] == big * w, i

    def test_rows_frame_sum_wide_input(self):
        """ROWS frames over genuinely wide decimal(38,2) input (was a
        fail-fast) now run the limb scan; overflow past decimal(38)
        nulls like the running path."""
        import pyarrow as pa
        from auron_tpu.ops.window import WindowOp, WindowFunctionSpec
        rng = random.Random(12)
        n = 30
        vals = [None if i % 6 == 5 else
                decimal.Decimal(rng.randint(-10 ** 30, 10 ** 30)).scaleb(-2)
                for i in range(n)]
        rb = pa.record_batch({
            "g": pa.array([i % 2 for i in range(n)], pa.int64()),
            "o": pa.array(list(range(n)), pa.int64()),
            "d": pa.array(vals, pa.decimal128(38, 2))})
        op = WindowOp(mem_scan(rb, capacity=32), [C(0)],
                      [ir.SortOrder(C(1), True, True)],
                      [WindowFunctionSpec("agg", "sum", arg=C(2),
                                          frame=(-2, 1))],
                      output_names=["fs"])
        got = collect(op).to_pandas().sort_values("o").reset_index(drop=True)
        hist: dict = {}
        rows_by_g: dict = {}
        for i in range(n):
            rows_by_g.setdefault(i % 2, []).append(i)
        pos_in_g = {}
        for g, idxs in rows_by_g.items():
            for j, i in enumerate(idxs):
                pos_in_g[i] = (g, j, idxs)
        for i in range(n):
            g, j, idxs = pos_in_g[i]
            win = [vals[idxs[t]] for t in range(max(0, j - 2),
                                               min(len(idxs), j + 2))]
            nn = [v for v in win if v is not None]
            if nn:
                assert got.loc[i, "fs"] == sum(nn), i
            else:
                assert got.loc[i, "fs"] is None, i

    def test_hash_join_on_wide_key(self):
        # review finding: hash join needs limb equality in _keys_match
        import pyarrow as pa
        from auron_tpu.ops.joins import HashJoinOp
        keys = [decimal.Decimal(10 ** 30 + i).scaleb(-2) for i in range(6)]
        left = pa.record_batch({
            "k": pa.array([keys[i % 4] for i in range(12)],
                          pa.decimal128(38, 2)),
            "v": pa.array(list(range(12)), pa.int64())})
        right = pa.record_batch({
            "k": pa.array(keys[:5], pa.decimal128(38, 2)),
            "w": pa.array([10, 20, 30, 40, 50], pa.int64())})
        op = HashJoinOp(mem_scan(left), mem_scan(right), [C(0)], [C(0)],
                        join_type="inner")
        got = collect(op).to_pandas()
        assert len(got) == 12  # every left row matches exactly one right
        for _i, row in got.iterrows():
            assert row.iloc[0] == row.iloc[2]
            assert row.iloc[3] == (keys.index(row.iloc[0]) + 1) * 10

    def test_window_sum_overflow_nulls(self):
        # review finding: running sums past decimal(38) must null like
        # AggOp's wide sum, not crash the Arrow bridge with 39 digits
        import pyarrow as pa
        from auron_tpu.ops.window import WindowOp, WindowFunctionSpec
        big = decimal.Decimal(9 * 10 ** 37).scaleb(-2)
        rb = pa.record_batch({
            "g": pa.array([0, 0], pa.int64()),
            "o": pa.array([0, 1], pa.int64()),
            "d": pa.array([big, big], pa.decimal128(38, 2))})
        op = WindowOp(mem_scan(rb), [C(0)],
                      [ir.SortOrder(C(1), True, True)],
                      [WindowFunctionSpec("agg", "sum", arg=C(2))],
                      output_names=["s"])
        got = collect(op).to_pandas().sort_values("o")
        assert got["s"].tolist()[0] == big
        assert got["s"].tolist()[1] is None

    def test_hash_partition_wide_key_consistent(self):
        # equal wide keys must land in the same partition, and the spread
        # must actually use multiple partitions (limb-pair murmur3)
        from auron_tpu.ops import hashing
        from auron_tpu.columnar.decimal128 import Decimal128Column
        vals = [((10 ** 30 + i) if i % 2 else -(10 ** 28 + i))
                for i in range(64)] * 2
        h, l, va = D.limbs_from_ints(vals, 128)
        col = Decimal128Column(jnp.asarray(h), jnp.asarray(l),
                               jnp.asarray(va))
        hh = np.asarray(hashing.murmur3_columns([col], 128))
        parts = hh % 16
        assert np.array_equal(parts[:64], parts[64:])  # deterministic
        assert len(set(parts.tolist())) > 4            # spread


class TestWideDistinctRewrite:
    """count/sum/avg DISTINCT over decimal(p>18) via the frontend's
    single-DISTINCT plan (GroupedData._plan_one_distinct, PR 44; the
    decimal-only rewrite it replaced, _rewrite_wide_distinct, was a
    special case of it): aggregates on (keys, arg) dedupe the two-limb
    values with the wide group-key machinery, then the plain wide
    aggregate starts over the deduped column. Spark plans a distinct
    aggregate the same way; the AggOp-level fail-fast (test above) still
    guards the direct-proto path."""

    def _frame(self, seed=7, n=200, n_groups=4):
        import pyarrow as pa
        rng = random.Random(seed)
        pool = [decimal.Decimal(x).scaleb(-2) for x in
                (10 ** 25 + 1, -(10 ** 30 + 7), 42, 10 ** 19, 0, -5)]
        groups = [rng.randrange(n_groups) for _ in range(n)]
        vals = [None if i % 11 == 0 else rng.choice(pool)
                for i in range(n)]
        tbl = pa.table({"g": pa.array(groups, pa.int64()),
                        "d": pa.array(vals, pa.decimal128(31, 2))})
        per: dict = {}
        for g, v in zip(groups, vals):
            per.setdefault(g, set())
            if v is not None:
                per[g].add(v)
        return tbl, per

    @pytest.mark.parametrize("nparts", [1, 3])
    def test_count_sum_avg_distinct(self, nparts):
        from auron_tpu.frontend.session import Session
        from auron_tpu.frontend.dataframe import functions as F, col
        tbl, per = self._frame()
        s = Session(batch_capacity=64)
        df = s.from_arrow(tbl)
        if nparts > 1:
            df = df.repartition(nparts)
        out = s.execute(df.group_by("g").agg(
            F.count(col("d"), distinct=True).alias("c"),
            F.sum(col("d"), distinct=True).alias("s"),
            F.avg(col("d"), distinct=True).alias("a")))
        rows = {r["g"]: r for r in out.to_pylist()}
        assert set(rows) == set(per)
        for g, dset in per.items():
            assert rows[g]["c"] == len(dset)
            assert rows[g]["s"] == sum(dset)
            exp_avg = (sum(dset) / len(dset)).quantize(
                decimal.Decimal(1).scaleb(-6),
                rounding=decimal.ROUND_HALF_UP)
            assert rows[g]["a"] == exp_avg, g

    def test_global_distinct_no_keys(self):
        from auron_tpu.frontend.session import Session
        from auron_tpu.frontend.dataframe import functions as F, col
        tbl, per = self._frame(seed=9, n_groups=1)
        allv = set().union(*per.values())
        s = Session(batch_capacity=64)
        df = s.from_arrow(tbl).repartition(2)
        out = s.execute(df.group_by().agg(
            F.count(col("d"), distinct=True).alias("c"),
            F.sum(col("d"), distinct=True).alias("s")))
        [row] = out.to_pylist()
        assert row["c"] == len(allv)
        assert row["s"] == sum(allv)


    def test_narrow_decimal_distinct_spark_types(self):
        """The regroup rewrite covers narrow decimals too: the set path
        would return float avg / typeless sum, but Spark types
        sum(DISTINCT decimal(10,2)) as decimal(20,2) and avg as
        decimal(14,6) HALF_UP."""
        import pyarrow as pa
        from auron_tpu.frontend.session import Session
        from auron_tpu.frontend.dataframe import functions as F, col
        vals = [decimal.Decimal(v).scaleb(-2)
                for v in (125, 125, -300, 42, 42, 7)] + [None]
        tbl = pa.table({"g": pa.array([0] * 7, pa.int64()),
                        "d": pa.array(vals, pa.decimal128(10, 2))})
        s = Session(batch_capacity=16)
        out = s.execute(s.from_arrow(tbl).group_by("g").agg(
            F.sum(col("d"), distinct=True).alias("s"),
            F.avg(col("d"), distinct=True).alias("a")))
        fs = {f.name: f.type for f in out.schema}
        assert str(fs["s"]) == "decimal128(20, 2)", fs
        assert str(fs["a"]) == "decimal128(14, 6)", fs
        [row] = out.to_pylist()
        dset = {v for v in vals if v is not None}
        assert row["s"] == sum(dset)
        assert row["a"] == (sum(dset) / len(dset)).quantize(
            decimal.Decimal(1).scaleb(-6), rounding=decimal.ROUND_HALF_UP)

    def test_a_mix_over_one_argument_answers_and_differing_args_fail(self):
        """PR 44: decimal DISTINCT beside plain functions of the same or
        another column is Spark's four aggregates now (this mix raised
        NotImplementedError before); DISTINCT over two arguments is
        Spark's Expand plan, and still refused."""
        import pyarrow as pa
        from auron_tpu.frontend.session import Session
        from auron_tpu.frontend.dataframe import functions as F, col
        D = decimal.Decimal
        tbl = pa.table({"g": pa.array([0, 0, 0, 1], pa.int64()),
                        "d": pa.array([D("1.00"), D("1.00"), D("2.50"),
                                       None], pa.decimal128(25, 2)),
                        "e": pa.array([D("2.00"), D("4.00"), None,
                                       D("6.00")], pa.decimal128(25, 2))})
        s = Session(batch_capacity=16)
        df = s.from_arrow(tbl)
        out = s.execute(df.group_by("g").agg(
            F.sum(col("d"), distinct=True).alias("sd"),
            F.count(col("d")).alias("c"),
            F.sum(col("e")).alias("se")))
        fs = {f.name: str(f.type) for f in out.schema}
        assert fs["sd"] == fs["se"] == "decimal128(35, 2)", fs
        rows = {r["g"]: r for r in out.to_pylist()}
        assert rows[0] == {"g": 0, "sd": D("3.50"), "c": 3, "se": D("6.00")}
        assert rows[1] == {"g": 1, "sd": None, "c": 0, "se": D("6.00")}
        with pytest.raises(NotImplementedError, match="one argument"):
            df.group_by("g").agg(F.sum(col("d"), distinct=True),
                                 F.count(col("e"), distinct=True))

    def test_narrow_count_distinct_mixed_with_count_star(self):
        """count-distinct over NARROW decimal mixed with other aggregates
        (review finding of the rewrite this replaced: it must keep
        working): one regroup plan, like every single-DISTINCT mix."""
        import pyarrow as pa
        from auron_tpu.frontend.session import Session
        from auron_tpu.frontend.dataframe import functions as F, col
        vals = [decimal.Decimal(v).scaleb(-2)
                for v in (100, 100, 250, 250, 250, -7)]
        tbl = pa.table({"g": pa.array([0, 0, 0, 1, 1, 1], pa.int64()),
                        "d": pa.array(vals, pa.decimal128(10, 2))})
        s = Session(batch_capacity=16)
        out = s.execute(s.from_arrow(tbl).group_by("g").agg(
            F.count(col("d"), distinct=True).alias("cd"),
            F.count_star().alias("n")))
        rows = {r["g"]: r for r in out.to_pylist()}
        assert rows[0]["cd"] == 2 and rows[0]["n"] == 3
        assert rows[1]["cd"] == 2 and rows[1]["n"] == 3


class TestWideCollect:
    """collect_list / collect_set over decimal(p>18): the dcollect
    accumulator carries limb-pair element matrices and the output rides
    the MapColumn carrier rendered as list<decimal128(p,s)> (reference
    keeps these as native Decimal128 arrays in its AccColumn,
    agg/acc.rs). Narrow decimal collect now renders list<decimal(p,s)>
    too instead of raw scaled ints."""

    def _data(self, seed=5, n=120, n_groups=4):
        import pyarrow as pa
        rng = random.Random(seed)
        pool = [decimal.Decimal(x).scaleb(-2)
                for x in (10 ** 25 + 1, -(10 ** 30 + 7), 42, 0, 10 ** 19)]
        groups = [rng.randrange(n_groups) for _ in range(n)]
        vals = [None if i % 9 == 0 else rng.choice(pool)
                for i in range(n)]
        rb = pa.record_batch({"g": pa.array(groups, pa.int64()),
                              "d": pa.array(vals, pa.decimal128(31, 2))})
        exp: dict = {}
        for g, v in zip(groups, vals):
            exp.setdefault(g, [])
            if v is not None:
                exp[g].append(v)
        return rb, exp

    def test_complete_list_and_set(self):
        from auron_tpu.ops.agg import AggOp
        rb, exp = self._data()
        op = AggOp(mem_scan(rb, capacity=128), [C(0)],
                   [ir.AggFunction("collect_list", C(1)),
                    ir.AggFunction("collect_set", C(1))],
                   mode="complete", group_names=["g"],
                   agg_names=["cl", "cs"], initial_capacity=8)
        out = collect(op)
        assert str(out.schema.field("cl").type) == \
            "list<item: decimal128(31, 2)>"
        rows = {r["g"]: r for r in out.to_pylist()}
        for g in exp:
            assert sorted(rows[g]["cl"]) == sorted(exp[g]), g
            assert sorted(rows[g]["cs"]) == sorted(set(exp[g])), g

    def test_partial_final_arrow_roundtrip(self):
        import pyarrow as pa
        from auron_tpu.ops.agg import AggOp
        rb, exp = self._data(seed=9)
        kw = dict(group_names=["g"], agg_names=["cl"], initial_capacity=8)
        p1 = collect(AggOp(mem_scan(rb, capacity=128), [C(0)],
                           [ir.AggFunction("collect_list", C(1))],
                           mode="partial", **kw))
        merged = p1.combine_chunks().to_batches()[0]
        fin = AggOp(mem_scan(merged, capacity=64), [C(0)],
                    [ir.AggFunction("collect_list", None)],
                    mode="final", **kw)
        rows = {r["g"]: sorted(r["cl"])
                for r in collect(fin).to_pylist()}
        for g in exp:
            assert rows[g] == sorted(exp[g]), g

    def test_frontend_distributed_collect_set(self):
        import pyarrow as pa
        from auron_tpu.frontend.session import Session
        from auron_tpu.frontend.dataframe import functions as F, col
        rb, exp = self._data(seed=11)
        tbl = pa.Table.from_batches([rb])
        s = Session(batch_capacity=32)
        df = s.from_arrow(tbl).repartition(3)
        out = s.execute(df.group_by("g").agg(
            F.collect_set(col("d")).alias("cs")))
        rows = {r["g"]: r["cs"] for r in out.to_pylist()}
        for g in exp:
            assert sorted(rows[g]) == sorted(set(exp[g])), g


    def test_narrow_distributed_collect_keeps_scale(self):
        """Review finding: partial/final collect over decimal(p<=18) must
        carry the element (p, s) through the wire state — dropping it
        made distributed results raw scaled ints (1.25 -> 125)."""
        import pyarrow as pa
        from auron_tpu.frontend.session import Session
        from auron_tpu.frontend.dataframe import functions as F, col
        vals = [decimal.Decimal(v).scaleb(-2)
                for v in (125, -350, 777, 125)]
        tbl = pa.table({"g": pa.array([0, 0, 1, 1], pa.int64()),
                        "d": pa.array(vals, pa.decimal128(10, 2))})
        s = Session(batch_capacity=8)
        df = s.from_arrow(tbl).repartition(2)
        out = s.execute(df.group_by("g").agg(
            F.collect_list(col("d")).alias("cl")))
        assert str(out.schema.field("cl").type) == \
            "list<item: decimal128(10, 2)>"
        rows = {r["g"]: sorted(r["cl"]) for r in out.to_pylist()}
        assert rows[0] == [decimal.Decimal("-3.50"),
                           decimal.Decimal("1.25")]
        assert rows[1] == [decimal.Decimal("1.25"),
                           decimal.Decimal("7.77")]

    def test_narrow_decimal_collect_renders_decimal(self):
        import pyarrow as pa
        from auron_tpu.ops.agg import AggOp
        rb = pa.record_batch({
            "g": pa.array([0, 0, 1], pa.int64()),
            "d": pa.array([decimal.Decimal("1.25"),
                           decimal.Decimal("-3.50"), None],
                          pa.decimal128(10, 2))})
        out = collect(AggOp(mem_scan(rb, capacity=8), [C(0)],
                            [ir.AggFunction("collect_list", C(1))],
                            mode="complete", group_names=["g"],
                            agg_names=["cl"], initial_capacity=4))
        assert str(out.schema.field("cl").type) == \
            "list<item: decimal128(10, 2)>"
        rows = {r["g"]: r["cl"] for r in out.to_pylist()}
        assert sorted(rows[0]) == [decimal.Decimal("-3.50"),
                                   decimal.Decimal("1.25")]
        assert rows[1] == []
