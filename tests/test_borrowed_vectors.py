"""Borrowed Spark correctness vectors (VERDICT r3 directive 7).

The reference re-runs thousands of Spark's own SQL assertions against
the native engine (auron-spark-tests/common/.../SparkTestsBase.scala:
10-70). PySpark is not in this image, so this battery encodes the same
idea as GOLDEN VECTORS: literal input→expected tables transcribed from
Spark's documented/observed semantics (casts, strings, dates, decimals,
NaN/null ordering — the edge values Spark's own suites hammer), run
through the engine's scan→project pipeline via a parquet round trip and
asserted cell-by-cell. 500+ assertions across the groups below; every
row is one borrowed behavior.
"""

from __future__ import annotations

import datetime
import decimal
import math

import numpy as np
import pyarrow as pa
import pytest

from auron_tpu.columnar.arrow_bridge import schema_from_arrow
from auron_tpu.columnar.schema import DataType
from auron_tpu.exprs import ir
from auron_tpu.io.parquet import MemoryScanOp
from auron_tpu.ops.project import ProjectOp
from auron_tpu.runtime.executor import collect

C = ir.ColumnRef
D = decimal.Decimal

ASSERTIONS = {"n": 0}


def _run_expr(expr, arrays: dict, out_name="out"):
    """Evaluate one expression over literal input columns through the
    full scan→project pipeline (parquet-typed batch)."""
    rb = pa.record_batch(arrays)
    scan = MemoryScanOp([[rb]], schema_from_arrow(rb.schema),
                        capacity=max(16, 1 << (rb.num_rows - 1)
                                     .bit_length()))
    op = ProjectOp(scan, [expr], [out_name])
    return collect(op).column(out_name).to_pylist()


def _check_vector(expr, arrays, expected, label):
    got = _run_expr(expr, arrays)
    assert len(got) == len(expected), label
    for i, (g, e) in enumerate(zip(got, expected)):
        if isinstance(e, float) and e is not None and g is not None \
                and not (isinstance(g, str)):
            if math.isnan(e):
                assert isinstance(g, float) and math.isnan(g), \
                    f"{label}[{i}]: {g!r} != NaN"
            else:
                assert g == pytest.approx(e, rel=1e-12), \
                    f"{label}[{i}]: {g!r} != {e!r}"
        else:
            assert g == e, f"{label}[{i}]: {g!r} != {e!r}"
        ASSERTIONS["n"] += 1


def cast_(dtype, precision=0, scale=0, col=0):
    return ir.Cast(C(col), dtype, precision, scale, safe=True)


def fn(name, *args):
    return ir.ScalarFunction(name, tuple(
        a if isinstance(a, ir.Expr) else a for a in args))


def lit(v, dt, p=0, s=0):
    return ir.Literal(v, dt, p, s)


# ---------------------------------------------------------------------------
# casts
# ---------------------------------------------------------------------------

class TestCastVectors:
    def test_string_to_int(self):
        # Spark non-ANSI: trims, parses leading sign, decimals truncate
        # toward zero, malformed → NULL, out-of-range → NULL
        vec = [("42", 42), ("  42  ", 42), ("-7", -7), ("+9", 9),
               ("4.5", 4), ("-4.9", -4), ("0", 0), ("", None),
               ("abc", None), ("4a", None), ("2147483647", 2147483647),
               ("2147483648", None), ("-2147483648", -2147483648),
               ("-2147483649", None), (" 1.0 ", 1), (".5", 0),
               ("1e2", None), (None, None), ("00012", 12), ("-0", 0)]
        _check_vector(cast_(DataType.INT32),
                      {"c": pa.array([v for v, _ in vec], pa.string())},
                      [e for _, e in vec], "string->int")

    def test_string_to_long(self):
        vec = [("9223372036854775807", 9223372036854775807),
               ("9223372036854775808", None),
               ("-9223372036854775808", -9223372036854775808),
               ("123", 123), ("12.99", 12), ("-12.99", -12),
               ("", None), ("x", None), (None, None), ("  -5 ", -5)]
        _check_vector(cast_(DataType.INT64),
                      {"c": pa.array([v for v, _ in vec], pa.string())},
                      [e for _, e in vec], "string->long")

    def test_string_to_double(self):
        vec = [("1.5", 1.5), (" 2.25 ", 2.25), ("-0.0", -0.0),
               ("1e3", 1000.0), ("1E-2", 0.01), ("Infinity", math.inf),
               ("-Infinity", -math.inf), ("NaN", math.nan),
               ("", None), ("abc", None), (None, None), ("3", 3.0),
               (".5", 0.5), ("5.", 5.0), ("+4.5", 4.5)]
        _check_vector(cast_(DataType.FLOAT64),
                      {"c": pa.array([v for v, _ in vec], pa.string())},
                      [e for _, e in vec], "string->double")

    def test_double_to_int(self):
        # Spark: truncation toward zero; NaN/inf/overflow → NULL non-ANSI
        vec = [(4.9, 4), (-4.9, -4), (0.0, 0), (2147483646.7, 2147483646),
               (2.5e9, None), (-2.5e9, None), (math.nan, None),
               (math.inf, None), (-math.inf, None), (None, None),
               (1e-300, 0), (-0.5, 0)]
        _check_vector(cast_(DataType.INT32),
                      {"c": pa.array([v for v, _ in vec], pa.float64())},
                      [e for _, e in vec], "double->int")

    def test_int_to_string(self):
        vec = [(0, "0"), (42, "42"), (-7, "-7"),
               (9223372036854775807, "9223372036854775807"),
               (-9223372036854775808, "-9223372036854775808"),
               (None, None)]
        _check_vector(cast_(DataType.STRING),
                      {"c": pa.array([v for v, _ in vec], pa.int64())},
                      [e for _, e in vec], "long->string")

    def test_string_to_date(self):
        # Spark accepts yyyy-[m]m-[d]d (with optional trailing junk ONLY
        # pre-3.0; modern Spark nulls malformed)
        vec = [("2020-01-01", datetime.date(2020, 1, 1)),
               ("1970-01-01", datetime.date(1970, 1, 1)),
               ("1969-12-31", datetime.date(1969, 12, 31)),
               ("2000-02-29", datetime.date(2000, 2, 29)),
               ("1900-02-28", datetime.date(1900, 2, 28)),
               ("2001-02-29", None), ("2020-13-01", None),
               ("2020-00-10", None), ("2020-01-32", None),
               ("not a date", None), ("", None), (None, None),
               ("2020-1-2", datetime.date(2020, 1, 2)),
               ("0001-01-01", datetime.date(1, 1, 1))]
        _check_vector(cast_(DataType.DATE32),
                      {"c": pa.array([v for v, _ in vec], pa.string())},
                      [e for _, e in vec], "string->date")

    def test_bool_casts(self):
        vec = [("true", True), ("TRUE", True), ("t", True), ("1", True),
               ("false", False), ("FALSE", False), ("f", False),
               ("0", False), ("yes", True), ("no", False), ("y", True),
               ("n", False), ("maybe", None), ("", None), (None, None)]
        _check_vector(cast_(DataType.BOOL),
                      {"c": pa.array([v for v, _ in vec], pa.string())},
                      [e for _, e in vec], "string->bool")

    def test_decimal_rescale_half_up(self):
        # Spark rescale rounds HALF_UP (round away from zero at .5)
        vec = [("1.005", D("1.01")), ("1.004", D("1.00")),
               ("-1.005", D("-1.01")), ("-1.004", D("-1.00")),
               ("2.675", D("2.68")), ("0.001", D("0.00")),
               ("-0.005", D("-0.01")), ("9.999", D("10.00")),
               ("0.000", D("0.00")), (None, None),
               ("123.456", D("123.46")), ("-123.454", D("-123.45"))]
        _check_vector(
            cast_(DataType.DECIMAL, 10, 2),
            {"c": pa.array([None if v is None else D(v)
                            for v, _ in vec], pa.decimal128(10, 3))},
            [e for _, e in vec], "decimal rescale")

    def test_string_to_decimal(self):
        vec = [("1.23", D("1.23")), ("  1.23 ", D("1.23")),
               ("-0.5", D("-0.50")), ("1.005", D("1.01")),
               ("abc", None), ("", None), (None, None),
               ("12345678.91", D("12345678.91")),
               ("123456789012.3", None),   # > precision → null
               ("0", D("0.00"))]
        _check_vector(
            cast_(DataType.DECIMAL, 10, 2),
            {"c": pa.array([v for v, _ in vec], pa.string())},
            [e for _, e in vec], "string->decimal")

    def test_decimal_overflow_to_narrower_nulls(self):
        vec = [("99999.99", None), ("-99999.99", None),
               ("999.99", D("999.99")), ("1000.00", None),
               ("0.01", D("0.01")), (None, None)]
        _check_vector(
            cast_(DataType.DECIMAL, 5, 2),
            {"c": pa.array([None if v is None else D(v) for v, _ in vec],
                           pa.decimal128(10, 2))},
            [e for _, e in vec], "decimal narrow overflow")


# ---------------------------------------------------------------------------
# strings
# ---------------------------------------------------------------------------

class TestStringVectors:
    def test_substring(self):
        # Spark substring is 1-based; pos 0 behaves like 1; negative pos
        # counts from the end; len clamps
        cases = [("hello", 1, 3, "hel"), ("hello", 0, 3, "hel"),
                 ("hello", 2, 10, "ello"), ("hello", -3, 2, "ll"),
                 ("hello", -10, 2, ""), ("hello", 6, 2, ""),
                 ("", 1, 2, ""), (None, 1, 2, None),
                 ("hello", 3, 0, ""), ("ab", -1, 5, "b"),
                 ("spark sql", 7, 3, "sql"), ("x", 1, 1, "x")]
        for s, p, ln, e in cases:
            got = _run_expr(
                fn("substring", C(0), lit(p, DataType.INT32),
                   lit(ln, DataType.INT32)),
                {"c": pa.array([s], pa.string())})
            assert got[0] == e, (s, p, ln, got[0], e)
            ASSERTIONS["n"] += 1

    def test_concat_null_propagation(self):
        # Spark concat: ANY null argument → null result
        vec = [("a", "b", "ab"), ("", "b", "b"), ("a", "", "a"),
               (None, "b", None), ("a", None, None), (None, None, None),
               ("x", "yz", "xyz")]
        _check_vector(fn("concat", C(0), C(1)),
                      {"a": pa.array([a for a, _, _ in vec], pa.string()),
                       "b": pa.array([b for _, b, _ in vec], pa.string())},
                      [e for _, _, e in vec], "concat")

    def test_trim_family(self):
        vec = [("  hi  ", "hi", "hi  ", "  hi"),
               ("hi", "hi", "hi", "hi"),
               ("   ", "", "", ""),
               ("", "", "", ""),
               (None, None, None, None),
               (" a b ", "a b", "a b ", " a b")]
        for i, fname in enumerate(("trim", "ltrim", "rtrim")):
            _check_vector(fn(fname, C(0)),
                          {"c": pa.array([v[0] for v in vec],
                                         pa.string())},
                          [v[i + 1] for v in vec], fname)

    def test_pad(self):
        cases = [("hi", 5, "*", "***hi", "hi***"),
                 ("hi", 1, "*", "h", "h"),
                 ("hi", 2, "*", "hi", "hi"),
                 ("", 3, "ab", "aba", "aba"),
                 (None, 3, "*", None, None),
                 ("abc", 7, "xy", "xyxyabc", "abcxyxy")]
        for s, n, p, el, er in cases:
            gl = _run_expr(fn("lpad", C(0), lit(n, DataType.INT32),
                              lit(p, DataType.STRING)),
                           {"c": pa.array([s], pa.string())})
            gr = _run_expr(fn("rpad", C(0), lit(n, DataType.INT32),
                              lit(p, DataType.STRING)),
                           {"c": pa.array([s], pa.string())})
            assert gl[0] == el and gr[0] == er, (s, n, p, gl, gr)
            ASSERTIONS["n"] += 2

    def test_instr_substring_index(self):
        cases = [("hello world", "o", 5), ("hello", "z", 0),
                 ("", "a", 0), ("aaa", "aa", 1), (None, "a", None)]
        for s, sub, e in cases:
            got = _run_expr(fn("instr", C(0), lit(sub, DataType.STRING)),
                            {"c": pa.array([s], pa.string())})
            assert got[0] == e, (s, sub, got[0])
            ASSERTIONS["n"] += 1
        cases2 = [("a.b.c", ".", 2, "a.b"), ("a.b.c", ".", -1, "c"),
                  ("a.b.c", ".", 0, ""), ("abc", ".", 2, "abc"),
                  (None, ".", 1, None)]
        for s, d, n, e in cases2:
            got = _run_expr(
                fn("substring_index", C(0), lit(d, DataType.STRING),
                   lit(n, DataType.INT32)),
                {"c": pa.array([s], pa.string())})
            assert got[0] == e, (s, d, n, got[0])
            ASSERTIONS["n"] += 1

    def test_upper_lower_length_reverse(self):
        vec = [("MiXeD", "MIXED", "mixed", 5, "DeXiM"),
               ("", "", "", 0, ""), (None, None, None, None, None),
               ("abc123", "ABC123", "abc123", 6, "321cba")]
        _check_vector(fn("upper", C(0)),
                      {"c": pa.array([v[0] for v in vec], pa.string())},
                      [v[1] for v in vec], "upper")
        _check_vector(fn("lower", C(0)),
                      {"c": pa.array([v[0] for v in vec], pa.string())},
                      [v[2] for v in vec], "lower")
        _check_vector(fn("length", C(0)),
                      {"c": pa.array([v[0] for v in vec], pa.string())},
                      [v[3] for v in vec], "length")
        _check_vector(fn("reverse", C(0)),
                      {"c": pa.array([v[0] for v in vec], pa.string())},
                      [v[4] for v in vec], "reverse")

    def test_translate_ascii_chr(self):
        got = _run_expr(fn("translate", C(0),
                           lit("abc", DataType.STRING),
                           lit("xy", DataType.STRING)),
                        {"c": pa.array(["aabbcc", "", None, "cab"],
                                       pa.string())})
        # Spark: a->x, b->y, c deleted
        assert got == ["xxyy", "", None, "xy"]
        ASSERTIONS["n"] += 4
        got = _run_expr(fn("ascii", C(0)),
                        {"c": pa.array(["A", "abc", "", None],
                                       pa.string())})
        assert got == [65, 97, 0, None]
        ASSERTIONS["n"] += 4


# ---------------------------------------------------------------------------
# dates
# ---------------------------------------------------------------------------

class TestDateVectors:
    DATES = [datetime.date(2020, 2, 29), datetime.date(1970, 1, 1),
             datetime.date(1969, 12, 31), datetime.date(2000, 12, 31),
             datetime.date(1582, 10, 15), datetime.date(9999, 12, 31),
             None, datetime.date(2024, 3, 1)]

    def _col(self):
        return {"c": pa.array(self.DATES, pa.date32())}

    def test_extract_fields(self):
        exp_y = [2020, 1970, 1969, 2000, 1582, 9999, None, 2024]
        exp_m = [2, 1, 12, 12, 10, 12, None, 3]
        exp_d = [29, 1, 31, 31, 15, 31, None, 1]
        exp_doy = [60, 1, 365, 366, None, None, None, 61]
        _check_vector(fn("year", C(0)), self._col(), exp_y, "year")
        _check_vector(fn("month", C(0)), self._col(), exp_m, "month")
        _check_vector(fn("day", C(0)), self._col(), exp_d, "day")
        got = _run_expr(fn("dayofyear", C(0)), self._col())
        for g, e in zip(got[:4] + [got[7]], exp_doy[:4] + [exp_doy[7]]):
            assert g == e
            ASSERTIONS["n"] += 1

    def test_date_add_sub_diff(self):
        base = {"c": pa.array([datetime.date(2020, 1, 31),
                               datetime.date(2020, 2, 28), None],
                              pa.date32())}
        got = _run_expr(fn("date_add", C(0), lit(1, DataType.INT32)), base)
        assert got == [datetime.date(2020, 2, 1),
                       datetime.date(2020, 2, 29), None]
        got = _run_expr(fn("date_sub", C(0), lit(31, DataType.INT32)),
                        base)
        assert got == [datetime.date(2019, 12, 31),
                       datetime.date(2020, 1, 28), None]
        ASSERTIONS["n"] += 6
        two = {"a": pa.array([datetime.date(2020, 3, 1),
                              datetime.date(2020, 1, 1), None],
                             pa.date32()),
               "b": pa.array([datetime.date(2020, 2, 1),
                              datetime.date(2020, 3, 1),
                              datetime.date(2020, 1, 1)], pa.date32())}
        got = _run_expr(fn("datediff", C(0), C(1)), two)
        assert got == [29, -60, None]
        ASSERTIONS["n"] += 3

    def test_last_day_trunc(self):
        base = {"c": pa.array([datetime.date(2020, 2, 10),
                               datetime.date(2021, 2, 10),
                               datetime.date(2020, 12, 31), None],
                              pa.date32())}
        got = _run_expr(fn("last_day", C(0)), base)
        assert got == [datetime.date(2020, 2, 29),
                       datetime.date(2021, 2, 28),
                       datetime.date(2020, 12, 31), None]
        ASSERTIONS["n"] += 4
        got = _run_expr(fn("trunc", C(0), lit("MM", DataType.STRING)),
                        base)
        assert got == [datetime.date(2020, 2, 1),
                       datetime.date(2021, 2, 1),
                       datetime.date(2020, 12, 1), None]
        got = _run_expr(fn("trunc", C(0), lit("YEAR", DataType.STRING)),
                        base)
        assert got == [datetime.date(2020, 1, 1),
                       datetime.date(2021, 1, 1),
                       datetime.date(2020, 1, 1), None]
        ASSERTIONS["n"] += 8


# ---------------------------------------------------------------------------
# decimal arithmetic result types + values
# ---------------------------------------------------------------------------

class TestDecimalArithVectors:
    def test_add_result_type_and_values(self):
        a = pa.array([D("1.10"), D("99999999.99"), D("-5.00"), None],
                     pa.decimal128(10, 2))
        b = pa.array([D("2.205"), D("0.005"), D("5.000"), D("1.000")],
                     pa.decimal128(10, 3))
        rb = {"a": a, "b": b}
        got = _run_expr(ir.BinaryExpr("+", C(0), C(1)), rb)
        # Spark: decimal(10,2)+decimal(10,3) -> decimal(12,3)
        assert got == [D("3.305"), D("99999999.995"), D("0.000"), None]
        ASSERTIONS["n"] += 4
        got = _run_expr(ir.BinaryExpr("*", C(0), C(1)), rb)
        # (10,2)*(10,3) -> p=21,s=5
        assert got == [D("2.42550"), D("499999.99995"), D("-25.00000"),
                       None]
        ASSERTIONS["n"] += 4

    def test_div_is_sparks_decimal_divide(self):
        """decimal(10,2) / decimal(10,2) = decimal(23,13), HALF_UP; a
        double until PR 42 (16 of these digits)."""
        rb = {"a": pa.array([D("1.00"), D("7.00"), None, D("-2.00"),
                             D("5.00")], pa.decimal128(10, 2)),
              "b": pa.array([D("3.00"), D("2.00"), D("1.00"), D("3.00"),
                             D("0.00")], pa.decimal128(10, 2))}
        got = _run_expr(ir.BinaryExpr("/", C(0), C(1)), rb)
        assert got == [D("0.3333333333333"), D("3.5000000000000"), None,
                       D("-0.6666666666667"), None]
        ASSERTIONS["n"] += 5


# ---------------------------------------------------------------------------
# NaN / null ordering and equality (Spark semantics)
# ---------------------------------------------------------------------------

class TestNanNullSemantics:
    def test_sort_nan_last_nulls_first(self):
        from auron_tpu.ops.sort import SortOp
        vals = [1.0, math.nan, -math.inf, None, 0.0, math.inf, -1.0,
                math.nan, None]
        rb = pa.record_batch({"x": pa.array(vals, pa.float64())})
        scan = MemoryScanOp([[rb]], schema_from_arrow(rb.schema),
                            capacity=16)
        op = SortOp(scan, [ir.SortOrder(C(0), True, True)])
        got = collect(op).column("x").to_pylist()
        # Spark ascending nulls_first: NULLs, then -inf..values..inf, NaN
        assert got[0] is None and got[1] is None
        assert got[2] == -math.inf
        assert got[3:7] == [-1.0, 0.0, 1.0, math.inf]
        assert math.isnan(got[7]) and math.isnan(got[8])
        ASSERTIONS["n"] += 9
        op = SortOp(scan, [ir.SortOrder(C(0), False, False)])
        got = collect(op).column("x").to_pylist()
        # descending nulls_last: NaN first (greatest), nulls at the end
        assert math.isnan(got[0]) and math.isnan(got[1])
        assert got[2] == math.inf
        assert got[-1] is None and got[-2] is None
        ASSERTIONS["n"] += 5

    def test_nan_equality_in_groupby(self):
        # Spark: NaN == NaN inside GROUP BY (normalized), one group
        from auron_tpu.ops.agg import AggOp
        vals = [math.nan, math.nan, 1.0, math.nan]
        rb = pa.record_batch({"x": pa.array(vals, pa.float64())})
        scan = MemoryScanOp([[rb]], schema_from_arrow(rb.schema),
                            capacity=16)
        op = AggOp(scan, [C(0)], [ir.AggFunction("count", None)],
                   mode="complete")
        got = collect(op).to_pylist()
        assert len(got) == 2
        by_nan = {math.isnan(r["k0"]): r["a0"] for r in got}
        assert by_nan[True] == 3 and by_nan[False] == 1
        ASSERTIONS["n"] += 3

    def test_comparison_null_propagation(self):
        rb = {"a": pa.array([1.0, None, math.nan], pa.float64()),
              "b": pa.array([1.0, 1.0, math.nan], pa.float64())}
        got = _run_expr(ir.BinaryExpr("==", C(0), C(1)), rb)
        # = with any NULL → NULL; NaN == NaN is FALSE in expressions
        assert got[0] is True and got[1] is None and got[2] is False
        ASSERTIONS["n"] += 3


# ---------------------------------------------------------------------------
# math / arithmetic
# ---------------------------------------------------------------------------

class TestMathVectors:
    def test_round_bround(self):
        # Spark round = HALF_UP (away from zero at .5); bround = HALF_EVEN
        _check_vector(fn("round", C(0)),
                      {"c": pa.array([2.5, -2.5, 2.4, 3.5, -3.5, 0.5, None],
                                     pa.float64())},
                      [3.0, -3.0, 2.0, 4.0, -4.0, 1.0, None], "round")
        _check_vector(fn("round", C(0), lit(2, DataType.INT32)),
                      {"c": pa.array([2.675, 1.234, -2.675, None],
                                     pa.float64())},
                      [2.68, 1.23, -2.68, None], "round2")
        _check_vector(fn("bround", C(0)),
                      {"c": pa.array([2.5, 3.5, -2.5, 0.5, None],
                                     pa.float64())},
                      [2.0, 4.0, -2.0, 0.0, None], "bround")

    def test_ceil_floor(self):
        vec = [(1.1, 2, 1), (-1.1, -1, -2), (0.0, 0, 0), (-0.5, 0, -1),
               (5.0, 5, 5), (None, None, None)]
        _check_vector(fn("ceil", C(0)),
                      {"c": pa.array([v for v, _, _ in vec], pa.float64())},
                      [e for _, e, _ in vec], "ceil")
        _check_vector(fn("floor", C(0)),
                      {"c": pa.array([v for v, _, _ in vec], pa.float64())},
                      [e for _, _, e in vec], "floor")

    def test_abs_sign(self):
        _check_vector(fn("abs", C(0)),
                      {"c": pa.array([-5, 5, 0, None], pa.int64())},
                      [5, 5, 0, None], "abs")
        _check_vector(fn("sign", C(0)),
                      {"c": pa.array([-3.5, 0.0, 7.0, None], pa.float64())},
                      [-1.0, 0.0, 1.0, None], "sign")

    def test_pmod(self):
        # Spark pmod: ((a % n) + n) % n
        _check_vector(fn("pmod", C(0), lit(3, DataType.INT32)),
                      {"c": pa.array([10, -7, 0, None], pa.int32())},
                      [1, 2, 0, None], "pmod+")
        _check_vector(fn("pmod", C(0), lit(-3, DataType.INT32)),
                      {"c": pa.array([7, -7], pa.int32())},
                      [-2, -1], "pmod-")

    def test_pow_sqrt_exp_log(self):
        _check_vector(fn("pow", C(0), lit(10.0, DataType.FLOAT64)),
                      {"c": pa.array([2.0, 0.0, None], pa.float64())},
                      [1024.0, 0.0, None], "pow")
        _check_vector(fn("sqrt", C(0)),
                      {"c": pa.array([4.0, 0.0, -1.0, None], pa.float64())},
                      [2.0, 0.0, math.nan, None], "sqrt")
        _check_vector(fn("exp", C(0)),
                      {"c": pa.array([0.0, 1.0, None], pa.float64())},
                      [1.0, math.e, None], "exp")
        # Spark ln/log of non-positive → NULL (not -inf/NaN)
        _check_vector(fn("ln", C(0)),
                      {"c": pa.array([math.e, 1.0, 0.0, -1.0, None],
                                     pa.float64())},
                      [1.0, 0.0, None, None, None], "ln")
        _check_vector(fn("hypot", C(0), C(1)),
                      {"a": pa.array([3.0, 0.0], pa.float64()),
                       "b": pa.array([4.0, 0.0], pa.float64())},
                      [5.0, 0.0], "hypot")

    def test_factorial(self):
        # Spark factorial: 0..20 only, else NULL
        _check_vector(fn("factorial", C(0)),
                      {"c": pa.array([0, 5, 20, 21, -1, None], pa.int32())},
                      [1, 120, 2432902008176640000, None, None, None],
                      "factorial")

    def test_greatest_least_skip_nulls(self):
        # Spark greatest/least SKIP nulls (unlike binary comparison);
        # NaN is greatest
        a = pa.array([1.0, None, float("nan"), None], pa.float64())
        b = pa.array([2.0, 3.0, 1.0, None], pa.float64())
        _check_vector(fn("greatest", C(0), C(1)), {"a": a, "b": b},
                      [2.0, 3.0, math.nan, None], "greatest")
        _check_vector(fn("least", C(0), C(1)), {"a": a, "b": b},
                      [1.0, 3.0, 1.0, None], "least")

    def test_isnan_nanvl(self):
        # Spark IsNaN(NULL) is false, not null
        _check_vector(fn("isnan", C(0)),
                      {"c": pa.array([float("nan"), 1.0, None],
                                     pa.float64())},
                      [True, False, False], "isnan")
        _check_vector(fn("nanvl", C(0), C(1)),
                      {"a": pa.array([float("nan"), 1.0, None],
                                     pa.float64()),
                       "b": pa.array([5.0, 9.0, 2.0], pa.float64())},
                      [5.0, 1.0, None], "nanvl")


# ---------------------------------------------------------------------------
# more strings
# ---------------------------------------------------------------------------

class TestMoreStringVectors:
    def test_locate_position(self):
        # locate(substr, str): 1-based, 0 when absent
        _check_vector(fn("locate", lit("l", DataType.STRING), C(0)),
                      {"c": pa.array(["hello", "world", "xyz", "", None])},
                      [3, 4, 0, 0, None], "locate")
        _check_vector(fn("position", lit("o", DataType.STRING), C(0)),
                      {"c": pa.array(["hello world", "xyz"])},
                      [5, 0], "position")

    def test_repeat_initcap(self):
        _check_vector(fn("repeat", C(0), lit(3, DataType.INT32)),
                      {"c": pa.array(["ab", "", None])},
                      ["ababab", "", None], "repeat")
        _check_vector(fn("repeat", C(0), lit(0, DataType.INT32)),
                      {"c": pa.array(["ab"])}, [""], "repeat0")
        _check_vector(fn("initcap", C(0)),
                      {"c": pa.array(["hello world", "hELLO", "a b", "",
                                      None])},
                      ["Hello World", "Hello", "A B", "", None], "initcap")

    def test_concat_ws_skips_nulls(self):
        # concat_ws skips null args (unlike concat which nulls out)
        _check_vector(fn("concat_ws", lit("-", DataType.STRING), C(0), C(1)),
                      {"a": pa.array(["a", None, "x", None]),
                       "b": pa.array(["b", "c", None, None])},
                      ["a-b", "c", "x", ""], "concat_ws")

    def test_chr_ascii_char(self):
        _check_vector(fn("chr", C(0)),
                      {"c": pa.array([65, 97, 48, None], pa.int64())},
                      ["A", "a", "0", None], "chr")
        _check_vector(fn("char", C(0)),
                      {"c": pa.array([66], pa.int64())}, ["B"], "char")

    def test_base64_hex(self):
        _check_vector(fn("base64", C(0)),
                      {"c": pa.array(["abc", "", None])},
                      ["YWJj", "", None], "base64")
        _check_vector(fn("hex", C(0)),
                      {"c": pa.array([255, 0, 16, None], pa.int64())},
                      ["FF", "0", "10", None], "hex")

    def test_crypto_known_answers(self):
        # textbook digests of 'abc'
        _check_vector(fn("md5", C(0)), {"c": pa.array(["abc", None])},
                      ["900150983cd24fb0d6963f7d28e17f72", None], "md5")
        _check_vector(fn("sha1", C(0)), {"c": pa.array(["abc"])},
                      ["a9993e364706816aba3e25717850c26c9cd0d89d"], "sha1")
        _check_vector(fn("sha2", C(0), lit(256, DataType.INT32)),
                      {"c": pa.array(["abc"])},
                      ["ba7816bf8f01cfea414140de5dae2223b00361a396177a"
                       "9cb410ff61f20015ad"], "sha256")
        _check_vector(fn("crc32", C(0)), {"c": pa.array(["abc", ""])},
                      [891568578, 0], "crc32")

    def test_substring_clamp_subtleties(self):
        # start clamps to 0 only AFTER the end is computed: -10 over a
        # 9-char string keeps one char, over a 5-char string keeps none
        cases = [("spark sql", -10, 2, "s"), ("hello", -5, 2, "he"),
                 ("hello", -4, 10, "ello"), ("hello", 1, 0, "")]
        for s, p, ln, e in cases:
            got = _run_expr(
                fn("substring", C(0), lit(p, DataType.INT32),
                   lit(ln, DataType.INT32)),
                {"c": pa.array([s], pa.string())})
            assert got[0] == e, (s, p, ln, got[0], e)
            ASSERTIONS["n"] += 1

    def test_char_length(self):
        _check_vector(fn("char_length", C(0)),
                      {"c": pa.array(["abc", "", None])},
                      [3, 0, None], "char_length")


# ---------------------------------------------------------------------------
# more dates / timestamps
# ---------------------------------------------------------------------------

class TestMoreDateVectors:
    def test_add_months(self):
        # Spark clamps the day to the target month's end but does NOT
        # preserve "last day" (unlike Hive): 2020-02-29 +1 → 2020-03-29
        base = {"c": pa.array([datetime.date(2020, 1, 31),
                               datetime.date(2020, 2, 29),
                               datetime.date(2020, 11, 30), None],
                              pa.date32())}
        _check_vector(fn("add_months", C(0), lit(1, DataType.INT32)), base,
                      [datetime.date(2020, 2, 29),
                       datetime.date(2020, 3, 29),
                       datetime.date(2020, 12, 30), None], "add_months")
        _check_vector(fn("add_months", C(0), lit(-12, DataType.INT32)),
                      base,
                      [datetime.date(2019, 1, 31),
                       datetime.date(2019, 2, 28),
                       datetime.date(2019, 11, 30), None], "add_months-12")

    def test_months_between(self):
        # both-last-day and same-day cases are integral
        _check_vector(
            fn("months_between", C(0), C(1)),
            {"a": pa.array([datetime.date(2020, 3, 15),
                            datetime.date(2020, 2, 29), None],
                           pa.date32()),
             "b": pa.array([datetime.date(2020, 1, 15),
                            datetime.date(2020, 1, 31),
                            datetime.date(2020, 1, 1)], pa.date32())},
            [2.0, 1.0, None], "months_between")

    def test_next_day_weekofyear(self):
        _check_vector(fn("next_day", C(0), lit("Sunday", DataType.STRING)),
                      {"c": pa.array([datetime.date(2020, 1, 1),
                                      datetime.date(2020, 1, 5), None],
                                     pa.date32())},
                      [datetime.date(2020, 1, 5),
                       datetime.date(2020, 1, 12), None], "next_day")
        # ISO weeks: 2016-01-01 is week 53 of 2015
        _check_vector(fn("weekofyear", C(0)),
                      {"c": pa.array([datetime.date(2020, 1, 1),
                                      datetime.date(2016, 1, 1),
                                      datetime.date(2020, 12, 31), None],
                                     pa.date32())},
                      [1, 53, 53, None], "weekofyear")

    def test_dayofweek_quarter(self):
        # dayofweek: 1 = Sunday
        _check_vector(fn("dayofweek", C(0)),
                      {"c": pa.array([datetime.date(2020, 1, 1),
                                      datetime.date(2020, 1, 5),
                                      datetime.date(2020, 1, 6), None],
                                     pa.date32())},
                      [4, 1, 2, None], "dayofweek")
        _check_vector(fn("quarter", C(0)),
                      {"c": pa.array([datetime.date(2020, 1, 1),
                                      datetime.date(2020, 5, 1),
                                      datetime.date(2020, 12, 31), None],
                                     pa.date32())},
                      [1, 2, 4, None], "quarter")

    def test_make_date_to_date(self):
        _check_vector(
            fn("make_date", C(0), C(1), C(2)),
            {"y": pa.array([2020, 2020, 2019, None], pa.int32()),
             "m": pa.array([2, 13, 2, 1], pa.int32()),
             "d": pa.array([29, 1, 29, 1], pa.int32())},
            [datetime.date(2020, 2, 29), None, None, None], "make_date")
        _check_vector(fn("to_date", C(0)),
                      {"c": pa.array(["2020-01-01", "bad", "", None])},
                      [datetime.date(2020, 1, 1), None, None, None],
                      "to_date")

    def test_date_format_from_unixtime(self):
        _check_vector(
            fn("date_format", C(0), lit("yyyy-MM-dd", DataType.STRING)),
            {"c": pa.array([datetime.date(2020, 1, 5), None],
                           pa.date32())},
            ["2020-01-05", None], "date_format")
        _check_vector(fn("from_unixtime", C(0)),
                      {"c": pa.array([0, 86400, 86399, None], pa.int64())},
                      ["1970-01-01 00:00:00", "1970-01-02 00:00:00",
                       "1970-01-01 23:59:59", None], "from_unixtime")
        _check_vector(fn("unix_timestamp", C(0)),
                      {"c": pa.array(["1970-01-01 00:00:01"])},
                      [1], "unix_timestamp")

    def test_timestamp_fields(self):
        ts = {"c": pa.array([datetime.datetime(2020, 1, 2, 13, 45, 59),
                             datetime.datetime(1970, 1, 1, 0, 0, 0), None],
                            pa.timestamp("us"))}
        _check_vector(fn("hour", C(0)), ts, [13, 0, None], "hour")
        _check_vector(fn("minute", C(0)), ts, [45, 0, None], "minute")
        _check_vector(fn("second", C(0)), ts, [59, 0, None], "second")

    def test_trunc_quarter_week(self):
        base = {"c": pa.array([datetime.date(2020, 5, 20), None],
                              pa.date32())}
        _check_vector(fn("trunc", C(0), lit("QUARTER", DataType.STRING)),
                      base, [datetime.date(2020, 4, 1), None], "truncQ")


# ---------------------------------------------------------------------------
# regexp + json
# ---------------------------------------------------------------------------

class TestRegexpJsonVectors:
    def test_regexp_extract(self):
        # no match → empty string (not null); null in → null out
        _check_vector(
            fn("regexp_extract", C(0), lit(r"(\d+)-(\d+)", DataType.STRING),
               lit(1, DataType.INT32)),
            {"c": pa.array(["100-200", "abc", "7-8", "", None])},
            ["100", "", "7", "", None], "regexp_extract g1")
        _check_vector(
            fn("regexp_extract", C(0), lit(r"(\d+)-(\d+)", DataType.STRING),
               lit(2, DataType.INT32)),
            {"c": pa.array(["100-200"])}, ["200"], "regexp_extract g2")

    def test_regexp_replace_rlike(self):
        _check_vector(
            fn("regexp_replace", C(0), lit(r"\d+", DataType.STRING),
               lit("#", DataType.STRING)),
            {"c": pa.array(["abc123x45", "none", "", None])},
            ["abc#x#", "none", "", None], "regexp_replace")
        _check_vector(fn("rlike", C(0), lit("^a.*c$", DataType.STRING)),
                      {"c": pa.array(["abc", "ac", "bc", "abcd", None])},
                      [True, True, False, False, None], "rlike")

    def test_get_json_object(self):
        col = {"c": pa.array(['{"a":1}', '{"a":"b"}', '{"x":2}',
                              '{"a":{"b":7}}', "not json", None])}
        _check_vector(fn("get_json_object", C(0),
                         lit("$.a", DataType.STRING)), col,
                      ["1", "b", None, '{"b":7}', None, None], "json $.a")
        _check_vector(fn("get_json_object", C(0),
                         lit("$.a.b", DataType.STRING)), col,
                      [None, None, None, "7", None, None], "json $.a.b")

    def test_json_array_length(self):
        _check_vector(fn("json_array_length", C(0)),
                      {"c": pa.array(["[1,2,3]", "[]", "nope", None])},
                      [3, 0, None, None], "json_array_length")


# ---------------------------------------------------------------------------
# conditionals
# ---------------------------------------------------------------------------

class TestConditionalVectors:
    def test_coalesce(self):
        _check_vector(fn("coalesce", C(0), C(1)),
                      {"a": pa.array([None, 5, None], pa.int64()),
                       "b": pa.array([2, 9, None], pa.int64())},
                      [2, 5, None], "coalesce")

    def test_nullif(self):
        _check_vector(fn("nullif", C(0), lit(1, DataType.INT64)),
                      {"c": pa.array([1, 2, None], pa.int64())},
                      [None, 2, None], "nullif")

    def test_if(self):
        _check_vector(
            fn("if", ir.BinaryExpr(">", C(0), lit(0, DataType.INT64)),
               lit("pos", DataType.STRING), lit("neg", DataType.STRING)),
            {"c": pa.array([5, -5, 0], pa.int64())},
            ["pos", "neg", "neg"], "if")

    def test_case_when_null_condition_falls_through(self):
        # CASE WHEN null-cond THEN ... falls through to ELSE
        expr = ir.CaseWhen(
            ((ir.BinaryExpr(">", C(0), lit(0, DataType.INT64)),
              lit("pos", DataType.STRING)),),
            otherwise=lit("other", DataType.STRING))
        _check_vector(expr,
                      {"c": pa.array([3, -3, None], pa.int64())},
                      ["pos", "other", "other"], "case_when")


# ---------------------------------------------------------------------------
# arrays + maps
# ---------------------------------------------------------------------------

class TestArrayMapVectors:
    LCOL = None

    def _l(self):
        return {"c": pa.array([[3, 1, 2], [], None, [5, None]],
                              pa.list_(pa.int64()))}

    def test_size_cardinality(self):
        # default (legacy sizeOfNull): size(NULL) = -1
        _check_vector(fn("size", C(0)), self._l(),
                      [3, 0, -1, 2], "size")
        _check_vector(fn("cardinality", C(0)), self._l(),
                      [3, 0, -1, 2], "cardinality")

    def test_array_contains_three_valued(self):
        # no match + null element present → NULL, not false
        _check_vector(fn("array_contains", C(0),
                         lit(1, DataType.INT64)), self._l(),
                      [True, False, None, None], "array_contains 1")
        _check_vector(fn("array_contains", C(0),
                         lit(5, DataType.INT64)), self._l(),
                      [False, False, None, True], "array_contains 5")

    def test_array_contains_nan_needle(self):
        # Spark's ArrayContains compares with NaN == NaN semantics
        _check_vector(
            fn("array_contains", C(0), lit(math.nan, DataType.FLOAT64)),
            {"c": pa.array([[math.nan, 1.0], [1.0, 2.0]],
                           pa.list_(pa.float64()))},
            [True, False], "array_contains NaN")

    def test_element_at_array(self):
        # 1-based; negative counts from the end; out of range → NULL
        _check_vector(fn("element_at", C(0), lit(1, DataType.INT32)),
                      self._l(), [3, None, None, 5], "element_at 1")
        _check_vector(fn("element_at", C(0), lit(-1, DataType.INT32)),
                      self._l(), [2, None, None, None], "element_at -1")
        _check_vector(fn("element_at", C(0), lit(9, DataType.INT32)),
                      self._l(), [None, None, None, None], "element_at 9")

    def test_array_min_max_position(self):
        _check_vector(fn("array_min", C(0)), self._l(),
                      [1, None, None, 5], "array_min")
        _check_vector(fn("array_max", C(0)), self._l(),
                      [3, None, None, 5], "array_max")
        _check_vector(fn("array_position", C(0), lit(2, DataType.INT64)),
                      self._l(), [3, 0, None, 0], "array_position")

    def test_sort_array_repeat(self):
        _check_vector(fn("sort_array", C(0)), self._l(),
                      [[1, 2, 3], [], None, [None, 5]], "sort_array")
        _check_vector(fn("array_repeat", C(0), lit(3, DataType.INT32)),
                      {"c": pa.array([7, None], pa.int64())},
                      [[7, 7, 7], [None, None, None]], "array_repeat")

    def test_array_set_ops(self):
        # Spark ArrayDistinct/Union/Intersect/Except: first-occurrence
        # order, nulls dedupe to one, NaN == NaN
        two = {"a": pa.array([[1, 2, 2, None, None, 1], [], None, [3]],
                             pa.list_(pa.int64())),
               "b": pa.array([[2, 4, None], [1], [1], None],
                             pa.list_(pa.int64()))}
        _check_vector(fn("array_distinct", C(0)), two,
                      [[1, 2, None], [], None, [3]], "array_distinct")
        _check_vector(fn("array_union", C(0), C(1)), two,
                      [[1, 2, None, 4], [1], None, None], "array_union")
        _check_vector(fn("array_intersect", C(0), C(1)), two,
                      [[2, None], [], None, None], "array_intersect")
        _check_vector(fn("array_except", C(0), C(1)), two,
                      [[1], [], None, None], "array_except")

    def test_arrays_overlap_three_valued(self):
        two = {"a": pa.array([[1, 2], [1, None], [1], [], [None]],
                             pa.list_(pa.int64())),
               "b": pa.array([[2, 3], [3], [2], [1], [1]],
                             pa.list_(pa.int64()))}
        # common non-null → true; none but a null present (both
        # non-empty) → NULL; empty side → false
        _check_vector(fn("arrays_overlap", C(0), C(1)), two,
                      [True, None, False, False, None], "arrays_overlap")

    def test_split_array_join(self):
        # Spark split keeps empty parts with the default -1 limit;
        # array_join skips nulls without a replacement
        _check_vector(fn("split", C(0), lit(",", DataType.STRING)),
                      {"c": pa.array(["a,b,c", "", None, "a,,b", "x"])},
                      [["a", "b", "c"], [""], None, ["a", "", "b"],
                       ["x"]], "split")
        _check_vector(
            fn("array_join", C(0), lit("-", DataType.STRING)),
            {"c": pa.array([["a", "bb", None], [], None, ["q"]],
                           pa.list_(pa.string()))},
            ["a-bb", "", None, "q"], "array_join")
        _check_vector(
            fn("array_join", C(0), lit("-", DataType.STRING),
               lit("NA", DataType.STRING)),
            {"c": pa.array([["a", None, "b"]], pa.list_(pa.string()))},
            ["a-NA-b"], "array_join repl")

    def test_str_to_map_vectors(self):
        _check_vector(fn("str_to_map", C(0)),
                      {"c": pa.array(["a:1,b:2", "k", "", None])},
                      [[("a", "1"), ("b", "2")], [("k", None)],
                       [("", None)], None], "str_to_map")
        _check_vector(
            fn("element_at", fn("str_to_map", C(0)),
               lit("b", DataType.STRING)),
            {"c": pa.array(["a:1,b:2", "b:9,b:7", "x:0"])},
            ["2", "7", None], "str_to_map lookup LAST_WINS")

    def test_sort_array_strings_vector(self):
        _check_vector(fn("sort_array", C(0)),
                      {"c": pa.array([["pear", "apple", None], [], None],
                                     pa.list_(pa.string()))},
                      [[None, "apple", "pear"], [], None],
                      "sort_array strings")

    def test_map_family(self):
        m = {"c": pa.array([[(1, 10), (2, 20)], []],
                           pa.map_(pa.int64(), pa.int64()))}
        _check_vector(fn("map_keys", C(0)), m, [[1, 2], []], "map_keys")
        _check_vector(fn("map_values", C(0)), m, [[10, 20], []],
                      "map_values")
        _check_vector(fn("map_contains_key", C(0), lit(1, DataType.INT64)),
                      m, [True, False], "map_contains_key")
        _check_vector(fn("element_at", C(0), lit(2, DataType.INT64)),
                      m, [20, None], "element_at map")
        _check_vector(fn("size", C(0)), m, [2, 0], "map size")


class TestEntryListVectors:
    """Spark golden vectors for the round-5 map_entries /
    map_from_entries family (Spark `SELECT map_entries(map(1,'a'))` class
    results) and wide-decimal collect semantics."""

    def test_map_entries_vector(self):
        m = {"c": pa.array([[(1, 10), (2, None)], [], None],
                           pa.map_(pa.int64(), pa.int64()))}
        _check_vector(fn("map_entries", C(0)), m,
                      [[{"key": 1, "value": 10}, {"key": 2, "value": None}],
                       [], None], "map_entries")

    def test_map_from_entries_vector(self):
        t = pa.list_(pa.struct([pa.field("key", pa.int64(), False),
                                pa.field("value", pa.int64())]))
        ents = {"c": pa.array(
            [[{"key": 1, "value": 10}, {"key": 1, "value": 99}],
             [{"key": 7, "value": None}], None, []], t)}
        # LAST_WINS dedup like map()/map_from_arrays; null map rows pass
        got = _run_expr(fn("map_from_entries", C(0)), ents)
        assert got[0] == [(1, 99)]     # truly deduped, not dict-collapsed
        assert got[1] == [(7, None)]
        assert got[2] is None
        assert got[3] == []
        ASSERTIONS["n"] += 4

    def test_entries_roundtrip_vector(self):
        m = {"c": pa.array([[(5, 50)], [(3, 30), (4, 40)]],
                           pa.map_(pa.int64(), pa.int64()))}
        _check_vector(fn("map_from_entries", fn("map_entries", C(0))), m,
                      [[(5, 50)], [(3, 30), (4, 40)]],
                      "map_from_entries . map_entries == id")


class TestWideDecimalAggVectors:
    """Spark golden semantics for wide-decimal aggregates added in
    round 5: sum/avg result types past 18 digits, collect over two-limb
    values (SparkTestsBase AuronPercentileSuite-class coverage)."""

    def _agg(self, vals, precision, scale, aggfn, distinct=False):
        rb = pa.record_batch({
            "g": pa.array([0] * len(vals), pa.int64()),
            "d": pa.array([None if v is None else decimal.Decimal(v)
                           for v in vals],
                          pa.decimal128(precision, scale))})
        scan = MemoryScanOp([[rb]], schema_from_arrow(rb.schema),
                            capacity=16)
        from auron_tpu.ops.agg import AggOp
        op = AggOp(scan, [C(0)],
                   [ir.AggFunction(aggfn, C(1), distinct=distinct)],
                   mode="complete", group_names=["g"], agg_names=["a"],
                   initial_capacity=4)
        tbl = collect(op)
        return tbl.schema.field("a").type, tbl.column("a").to_pylist()[0]

    def test_wide_sum_type_and_value(self):
        t, v = self._agg(["99999999999999999999.01", "0.99", None],
                         25, 2, "sum")
        assert str(t) == "decimal128(35, 2)"     # min(p+10, 38)
        assert v == decimal.Decimal("100000000000000000000.00")
        ASSERTIONS["n"] += 2

    def test_narrow_sum_promotes_past_18(self):
        t, v = self._agg(["9999999999.25", "0.75"], 12, 2, "sum")
        assert str(t) == "decimal128(22, 2)"     # Spark p+10, two-limb
        assert v == decimal.Decimal("10000000000.00")
        ASSERTIONS["n"] += 2

    def test_wide_avg_halfup(self):
        # sum = 10.000000000000000002, /3 = 3.333...334 at scale 22 after
        # HALF_UP on the repeating tail (truncation/HALF_EVEN differ)
        t, v = self._agg(["10.000000000000000001", "0.000000000000000001",
                          "0.000000000000000000"], 38, 18, "avg")
        assert str(t) == "decimal128(38, 22)"    # bounded(p+4, s+4)
        assert v == decimal.Decimal("3.3333333333333333340000") \
            .quantize(decimal.Decimal(1).scaleb(-22)), v
        ASSERTIONS["n"] += 2

    def test_wide_collect_set_dedup(self):
        t, v = self._agg(["123456789012345678901234.50",
                          "123456789012345678901234.50", "1.00", None],
                         30, 2, "collect_set")
        assert str(t) == "list<item: decimal128(30, 2)>"
        assert sorted(v) == [decimal.Decimal("1.00"),
                             decimal.Decimal("123456789012345678901234.50")]
        ASSERTIONS["n"] += 2


def test_assertion_floor():
    """The battery above must keep covering 500+ borrowed assertions —
    run last (alphabetical classes first, functions after)."""
    # Each _check_vector row and explicit assert bumps the counter; the
    # floor guards against silently shrinking coverage.
    if ASSERTIONS["n"] == 0:
        pytest.skip("battery deselected (-k): nothing to measure")
    print(f"\nborrowed-vector assertions counted: {ASSERTIONS['n']}")
    assert ASSERTIONS["n"] >= 500, ASSERTIONS["n"]
