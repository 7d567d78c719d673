"""decimal / decimal as Spark's Divide types and rounds it (PR 42; tier-1,
on the CPU: types and digits, no timings).

``exprs/eval.decimal_result_type("/")`` against a table of Spark's own
result types (``DecimalPrecision`` with ``adjustPrecisionScale``), and the
evaluated quotient against Python's ``decimal`` module — the exact
quotient, HALF_UP once at the result scale — over random operands of
every type pair the four ratio reports divide plus the edges: negative
operands, a zero divisor, nulls, HALF_UP ties, 38-digit quotients, a
quotient past the result precision (null), a divisor past 64 bits, a
dividend that passes 128 bits once scaled. The quotient is never a
double: ``infer_dtype`` and the evaluated column agree on the decimal
type.
"""

import decimal
import random
from decimal import Decimal

import jax.numpy as jnp
import numpy as np
import pytest

from auron_tpu.columnar import decimal128 as D
from auron_tpu.columnar.batch import DeviceBatch, PrimitiveColumn
from auron_tpu.columnar.schema import DataType, Field, Schema
from auron_tpu.exprs import ir
from auron_tpu.exprs.eval import (decimal_divides, decimal_result_type,
                                  evaluate, infer_dtype)

decimal.getcontext().prec = 120

#: (dividend, divisor) -> Spark's type of the quotient
SPARK_TYPES = {
    ((17, 2), (17, 2)): (37, 20),    # q36, q59: sum / sum
    ((21, 2), (27, 2)): (38, 17),    # q98: sum * 100 / windowed sum
    ((22, 6), (21, 6)): (38, 16),    # q53: |sum - avg| / avg
    ((7, 2), (7, 2)): (17, 10),      # money / money: fits one word
    ((10, 0), (10, 0)): (21, 11),
    ((38, 10), (38, 10)): (38, 6),   # the floor of adjustPrecisionScale
    ((38, 18), (3, 0)): (38, 18),
    ((5, 2), (38, 10)): (38, 25),
    ((38, 2), (10, 2)): (38, 6),     # the dividend scaled passes 128 bits
    ((18, 0), (1, 0)): (24, 6),
}


@pytest.mark.parametrize("left, right", list(SPARK_TYPES))
def test_the_result_type_is_sparks(left, right):
    p, s, full = decimal_result_type("/", *left, *right)
    assert (p, s) == SPARK_TYPES[(left, right)]
    assert full == s        # the quotient is computed at the result scale


def _column(values, p):
    if p <= 18:
        return PrimitiveColumn(
            jnp.asarray(np.array([v or 0 for v in values], np.int64)),
            jnp.asarray(np.array([v is not None for v in values])))
    hi, lo, valid = D.limbs_from_ints(values, len(values))
    return D.Decimal128Column(jnp.asarray(hi), jnp.asarray(lo),
                              jnp.asarray(valid))


def _unscaled(col):
    if isinstance(col, D.Decimal128Column):
        return D.ints_from_limbs(np.asarray(col.hi), np.asarray(col.lo),
                                 np.asarray(col.validity))
    return [int(v) if ok else None
            for v, ok in zip(np.asarray(col.data), np.asarray(col.validity))]


def _divide(a, b, left, right):
    """(unscaled quotients, (p, s)) of the engine's a / b."""
    schema = Schema((Field("a", DataType.DECIMAL, True, *left),
                     Field("b", DataType.DECIMAL, True, *right)))
    batch = DeviceBatch((_column(a, left[0]), _column(b, right[0])),
                        jnp.int32(len(a)))
    expr = ir.BinaryExpr("/", ir.ColumnRef(0), ir.ColumnRef(1))
    got = evaluate(expr, batch, schema)
    assert infer_dtype(expr, schema) == (got.dtype, got.precision, got.scale)
    assert got.dtype == DataType.DECIMAL
    assert isinstance(got.col, D.Decimal128Column) == (got.precision > 18)
    return _unscaled(got.col), (got.precision, got.scale)


def _want(x, y, left, right):
    """Python's decimal: the exact quotient, HALF_UP at Spark's scale,
    None on a null, a zero divisor, or past Spark's precision."""
    if x is None or y is None or y == 0:
        return None
    p, s = SPARK_TYPES[(left, right)]
    q = (Decimal(x).scaleb(-left[1]) / Decimal(y).scaleb(-right[1])) \
        .quantize(Decimal(1).scaleb(-s), rounding=decimal.ROUND_HALF_UP)
    q = int(q.scaleb(s))
    return q if abs(q) < 10 ** p else None


@pytest.mark.parametrize("left, right", list(SPARK_TYPES))
def test_random_operands_divide_as_pythons_decimal(left, right):
    rng = random.Random(hash((left, right)) & 0xFFFF)

    def draw(p):
        # every magnitude the type admits, either sign
        return rng.choice((-1, 1)) * rng.randrange(
            10 ** rng.randrange(1, p + 1))

    n = 256
    a = [draw(left[0]) for _ in range(n)]
    b = [draw(right[0]) or 1 for _ in range(n)]
    a[0], b[1], b[2], a[3] = None, None, 0, 0
    a[4], b[4] = 10 ** left[0] - 1, 1            # the widest quotient
    a[5], b[5] = -(10 ** left[0] - 1), 10 ** right[0] - 1
    got, typ = _divide(a, b, left, right)
    assert typ == SPARK_TYPES[(left, right)]
    want = [_want(x, y, left, right) for x, y in zip(a, b)]
    assert got == want
    assert got[0] is None and got[1] is None and got[2] is None
    assert got[3] == 0


def test_half_up_ties_round_away_from_zero():
    # decimal(10,0) / decimal(10,0) = decimal(21,11): x / 2^12 ends in
    # ...5 at the twelfth digit for every odd x
    left = right = (10, 0)
    a = [1, -1, 3, -3, 5, 4095, -4095, 2, 4096]
    got, typ = _divide(a, [4096] * len(a), left, right)
    assert typ == (21, 11)
    assert got == [24414063, -24414063, 73242188, -73242188, 122070313,
                   99975585938, -99975585938, 48828125, 10 ** 11]
    assert got == [_want(x, 4096, left, right) for x in a]


def test_a_38_digit_quotient_and_the_overflow_beside_it():
    left = right = (38, 10)            # -> decimal(38,6)
    a = [10 ** 37, 10 ** 37, -(10 ** 37), 10 ** 37 + 7]
    b = [123457, 3, 3, 10 ** 37 + 7]
    got, typ = _divide(a, b, left, right)
    assert typ == (38, 6)
    assert len(str(got[0])) == 38      # 8.1e37: every digit of the type
    assert got[0] == _want(a[0], b[0], left, right)
    assert got[1] is None and got[2] is None      # 3.3e42: Spark's null
    assert got[3] == 10 ** 6


def test_a_divisor_past_64_bits():
    left, right = (21, 2), (27, 2)     # q98's pair -> decimal(38,17)
    a = [10 ** 21 - 1, 123456789012345678901, -(10 ** 20)]
    b = [10 ** 27 - 1, 2 ** 64 + 12345, -(2 ** 80)]
    assert all(abs(v) >= 2 ** 64 for v in b)
    got, _typ = _divide(a, b, left, right)
    assert got == [_want(x, y, left, right) for x, y in zip(a, b)]
    assert all(g is not None and g != 0 for g in got)


def test_an_operand_past_its_declared_precision_is_null():
    # the iteration count of the division is the dividend TYPE's: a
    # value the type does not admit must not be divided silently
    got, _ = _divide([10 ** 7, 5], [3, 10 ** 7], (7, 2), (7, 2))
    assert got == [None, None]


def test_a_case_over_a_two_limb_quotient_and_its_null_else():
    """q53's filter: CASE WHEN avg > 0 THEN abs(sum - avg) / avg END."""
    schema = Schema((Field("sum", DataType.DECIMAL, True, 17, 2),
                     Field("avg", DataType.DECIMAL, True, 21, 6)))
    sums = [150_00, 50_00, 10_00, 70_00]
    avgs = [100_000000, 100_000000, 0, -5_000000]
    batch = DeviceBatch((_column(sums, 17), _column(avgs, 21)),
                        jnp.int32(4))
    s, a = ir.ColumnRef(0), ir.ColumnRef(1)
    zero = ir.Literal(0, DataType.DECIMAL, 1, 0)
    dev = ir.CaseWhen(((ir.BinaryExpr(">", a, zero), ir.BinaryExpr(
        "/", ir.ScalarFunction("abs", (ir.BinaryExpr("-", s, a),)), a)),),
        None)
    assert infer_dtype(dev, schema) == (DataType.DECIMAL, 38, 16)
    got = evaluate(dev, batch, schema)
    assert (got.precision, got.scale) == (38, 16)
    assert _unscaled(got.col) == [5 * 10 ** 15, 5 * 10 ** 15, None, None]
    neg = evaluate(ir.Negative(ir.BinaryExpr("-", s, a)), batch, schema)
    assert _unscaled(neg.col)[:2] == [-50_000000, 50_000000]
    assert decimal_divides([dev, s], schema) == 1
    assert decimal_divides([ir.BinaryExpr("/", dev, dev)], schema) == 3


def test_a_double_still_divides_as_a_double():
    schema = Schema((Field("d", DataType.DECIMAL, True, 17, 2),))
    batch = DeviceBatch((_column([250], 17),), jnp.int32(1))
    as_double = ir.Cast(ir.ColumnRef(0), DataType.FLOAT64)
    expr = ir.BinaryExpr("/", as_double, as_double)
    assert infer_dtype(expr, schema)[0] == DataType.FLOAT64
    assert float(evaluate(expr, batch, schema).data[0]) == 1.0
    assert decimal_divides([expr], schema) == 0
