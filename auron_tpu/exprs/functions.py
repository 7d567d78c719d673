"""Scalar function registry.

The TPU counterpart of the reference's Spark-exact function library
(reference: datafusion-ext-functions/src/lib.rs registry; spark_dates.rs,
spark_strings.rs, spark_bround.rs, ...). Functions take evaluated TypedValue
args and return a TypedValue; everything traces into the enclosing jit.
"""

from __future__ import annotations

import jax.numpy as jnp

from auron_tpu.columnar.batch import PrimitiveColumn, StringColumn
from auron_tpu.columnar.schema import DataType, Schema
from auron_tpu.exprs import ir
from auron_tpu.exprs.eval import TypedValue, cast_value, evaluate, infer_dtype
from auron_tpu.ops import hashing
from auron_tpu.ops import strings as S
from auron_tpu.utils.shapes import bucket_string_width

_REGISTRY = {}
_RESULT_TYPE = {}
#: name → callable(expr, schema) -> Field, for functions whose result is
#: nested (map/struct/list) and cannot be described by a (dtype, p, s)
_RESULT_FIELD = {}


def register(name, result_type=None, result_field=None):
    def deco(fn):
        _REGISTRY[name] = fn
        if result_type is not None:
            _RESULT_TYPE[name] = result_type
        if result_field is not None:
            _RESULT_FIELD[name] = result_field
        return fn
    return deco


def function_result_field(expr: ir.ScalarFunction, schema: Schema):
    """Full result Field for nested-returning functions; None when the
    (dtype, p, s) 3-tuple from function_result_type is the whole story."""
    rf = _RESULT_FIELD.get(expr.name)
    return rf(expr, schema) if rf is not None else None


def dispatch_function(expr: ir.ScalarFunction, batch, schema, ctx) -> TypedValue:
    fn = _REGISTRY.get(expr.name)
    if fn is None:
        raise NotImplementedError(f"scalar function {expr.name!r}")
    args = [evaluate(a, batch, schema, ctx) for a in expr.args]
    return fn(args, expr, batch, schema, ctx)


def function_result_type(expr: ir.ScalarFunction, schema: Schema):
    if expr.dtype is not None:
        return expr.dtype, expr.precision, expr.scale
    rt = _RESULT_TYPE.get(expr.name)
    if rt is None:
        # default: same as first arg
        return infer_dtype(expr.args[0], schema)
    if callable(rt):
        return rt(expr, schema)
    return rt, 0, 0


# ---------------------------------------------------------------------------
# date/time (civil-from-days, Hinnant algorithm — pure integer ops)
# ---------------------------------------------------------------------------

def _civil_from_days(days):
    """days since 1970-01-01 → (year, month, day), vectorized int32."""
    z = days.astype(jnp.int32) + 719468
    era = jnp.floor_divide(z, 146097)
    doe = z - era * 146097
    yoe = jnp.floor_divide(
        doe - jnp.floor_divide(doe, 1460) + jnp.floor_divide(doe, 36524)
        - jnp.floor_divide(doe, 146096), 365)
    y = yoe + era * 400
    doy = doe - (365 * yoe + jnp.floor_divide(yoe, 4) - jnp.floor_divide(yoe, 100))
    mp = jnp.floor_divide(5 * doy + 2, 153)
    d = doy - jnp.floor_divide(153 * mp + 2, 5) + 1
    m = mp + jnp.where(mp < 10, 3, -9)
    year = y + (m <= 2)
    return year.astype(jnp.int32), m.astype(jnp.int32), d.astype(jnp.int32)


def _days_arg(v: TypedValue):
    if v.dtype == DataType.TIMESTAMP_US:
        return jnp.floor_divide(v.data, 86_400_000_000).astype(jnp.int32)
    return v.data.astype(jnp.int32)


@register("year", DataType.INT32)
def _year(args, expr, batch, schema, ctx):
    y, _, _ = _civil_from_days(_days_arg(args[0]))
    return TypedValue(PrimitiveColumn(y, args[0].validity), DataType.INT32)


@register("month", DataType.INT32)
def _month(args, expr, batch, schema, ctx):
    _, m, _ = _civil_from_days(_days_arg(args[0]))
    return TypedValue(PrimitiveColumn(m, args[0].validity), DataType.INT32)


@register("day", DataType.INT32)
@register("dayofmonth", DataType.INT32)
def _day(args, expr, batch, schema, ctx):
    _, _, d = _civil_from_days(_days_arg(args[0]))
    return TypedValue(PrimitiveColumn(d, args[0].validity), DataType.INT32)


@register("quarter", DataType.INT32)
def _quarter(args, expr, batch, schema, ctx):
    _, m, _ = _civil_from_days(_days_arg(args[0]))
    return TypedValue(PrimitiveColumn((m - 1) // 3 + 1, args[0].validity),
                      DataType.INT32)


@register("dayofweek", DataType.INT32)
def _dayofweek(args, expr, batch, schema, ctx):
    # Spark: 1 = Sunday. 1970-01-01 was a Thursday (=5).
    days = _days_arg(args[0])
    dow = jnp.mod(days + 4, 7) + 1
    return TypedValue(PrimitiveColumn(dow.astype(jnp.int32), args[0].validity),
                      DataType.INT32)


@register("dayofyear", DataType.INT32)
def _dayofyear(args, expr, batch, schema, ctx):
    days = _days_arg(args[0])
    y, _, _ = _civil_from_days(days)
    # days since Jan 1 of the same year
    jan1 = _days_from_civil(y, jnp.ones_like(y), jnp.ones_like(y))
    return TypedValue(PrimitiveColumn((days - jan1 + 1).astype(jnp.int32),
                                      args[0].validity), DataType.INT32)


def _days_from_civil(y, m, d):
    y = y - (m <= 2)
    era = jnp.floor_divide(y, 400)
    yoe = y - era * 400
    mp = jnp.mod(m + 9, 12)
    doy = jnp.floor_divide(153 * mp + 2, 5) + d - 1
    doe = yoe * 365 + jnp.floor_divide(yoe, 4) - jnp.floor_divide(yoe, 100) + doy
    return era * 146097 + doe - 719468


@register("date_add", DataType.DATE32)
def _date_add(args, expr, batch, schema, ctx):
    d = args[0].data.astype(jnp.int32) + args[1].data.astype(jnp.int32)
    return TypedValue(PrimitiveColumn(d, args[0].validity & args[1].validity),
                      DataType.DATE32)


@register("date_sub", DataType.DATE32)
def _date_sub(args, expr, batch, schema, ctx):
    d = args[0].data.astype(jnp.int32) - args[1].data.astype(jnp.int32)
    return TypedValue(PrimitiveColumn(d, args[0].validity & args[1].validity),
                      DataType.DATE32)


@register("datediff", DataType.INT32)
def _datediff(args, expr, batch, schema, ctx):
    d = args[0].data.astype(jnp.int32) - args[1].data.astype(jnp.int32)
    return TypedValue(PrimitiveColumn(d, args[0].validity & args[1].validity),
                      DataType.INT32)


# ---------------------------------------------------------------------------
# math
# ---------------------------------------------------------------------------

@register("abs")
def _abs(args, expr, batch, schema, ctx):
    v = args[0]
    from auron_tpu.columnar import decimal128 as D
    if isinstance(v.col, D.Decimal128Column):
        return TypedValue(
            D.Decimal128Column(*D.abs128(v.col.hi, v.col.lo), v.validity),
            v.dtype, v.precision, v.scale)
    return TypedValue(PrimitiveColumn(jnp.abs(v.data), v.validity),
                      v.dtype, v.precision, v.scale)


@register("sqrt", DataType.FLOAT64)
def _sqrt(args, expr, batch, schema, ctx):
    v = cast_value(args[0], DataType.FLOAT64)
    return TypedValue(PrimitiveColumn(jnp.sqrt(v.data), v.validity),
                      DataType.FLOAT64)


@register("floor", DataType.INT64)
def _floor(args, expr, batch, schema, ctx):
    v = args[0]
    if v.dtype.is_integer:
        return TypedValue(PrimitiveColumn(v.data.astype(jnp.int64), v.validity),
                          DataType.INT64)
    return TypedValue(PrimitiveColumn(jnp.floor(v.data).astype(jnp.int64),
                                      v.validity), DataType.INT64)


@register("ceil", DataType.INT64)
def _ceil(args, expr, batch, schema, ctx):
    v = args[0]
    if v.dtype.is_integer:
        return TypedValue(PrimitiveColumn(v.data.astype(jnp.int64), v.validity),
                          DataType.INT64)
    return TypedValue(PrimitiveColumn(jnp.ceil(v.data).astype(jnp.int64),
                                      v.validity), DataType.INT64)


def _round_half_up(x, digits):
    factor = 10.0 ** digits
    scaled = x * factor
    # Spark ROUND = HALF_UP (away from zero on .5)
    return jnp.sign(scaled) * jnp.floor(jnp.abs(scaled) + 0.5) / factor


def _round_digits(expr):
    """Static digits argument of round/bround, read from the EXPRESSION
    (Spark requires a foldable scale): reading the evaluated arg would
    trace a device value and crash under jit. A typed-NULL scale returns
    None (round(x, NULL) is NULL in Spark, not an error)."""
    if len(expr.args) <= 1:
        return 0
    a = expr.args[1]
    if not isinstance(a, ir.Literal):
        raise NotImplementedError(
            f"{expr.name}: the scale argument must be a literal")
    return None if a.value is None else int(a.value)


@register("round")
def _round(args, expr, batch, schema, ctx):
    """Spark round: HALF_UP (reference: spark_bround.rs / spark_round)."""
    v = args[0]
    digits = _round_digits(expr)
    if digits is None:
        # all-null result of the input's own column type (wide decimals
        # are limb pairs, not .data columns)
        return TypedValue(v.col.with_validity(jnp.zeros_like(v.validity)),
                          v.dtype, v.precision, v.scale)
    if v.dtype == DataType.DECIMAL:
        shift = v.scale - digits
        if shift <= 0:
            return v
        p = 10 ** shift
        half = p // 2
        d = v.data
        rounded = jnp.sign(d) * ((jnp.abs(d) + half) // p)
        return TypedValue(PrimitiveColumn(rounded, v.validity),
                          DataType.DECIMAL, v.precision, digits)
    if v.dtype.is_integer:
        return v
    return TypedValue(PrimitiveColumn(_round_half_up(v.data, digits), v.validity),
                      v.dtype)


@register("bround")
def _bround(args, expr, batch, schema, ctx):
    """Spark bround: HALF_EVEN (banker's rounding)."""
    v = args[0]
    digits = _round_digits(expr)
    if digits is None:
        return TypedValue(v.col.with_validity(jnp.zeros_like(v.validity)),
                          v.dtype, v.precision, v.scale)
    if v.dtype.is_integer:
        return v
    factor = 10.0 ** digits
    data = jnp.round(v.data * factor) / factor  # jnp.round is half-even
    return TypedValue(PrimitiveColumn(data, v.validity), v.dtype)


@register("pow", DataType.FLOAT64)
@register("power", DataType.FLOAT64)
def _pow(args, expr, batch, schema, ctx):
    a = cast_value(args[0], DataType.FLOAT64)
    b = cast_value(args[1], DataType.FLOAT64)
    return TypedValue(PrimitiveColumn(jnp.power(a.data, b.data),
                                      a.validity & b.validity), DataType.FLOAT64)


@register("exp", DataType.FLOAT64)
def _exp(args, expr, batch, schema, ctx):
    v = cast_value(args[0], DataType.FLOAT64)
    return TypedValue(PrimitiveColumn(jnp.exp(v.data), v.validity), DataType.FLOAT64)


@register("log", DataType.FLOAT64)
@register("ln", DataType.FLOAT64)
def _log(args, expr, batch, schema, ctx):
    v = cast_value(args[0], DataType.FLOAT64)
    ok = v.data > 0
    safe = jnp.where(ok, v.data, 1.0)
    return TypedValue(PrimitiveColumn(jnp.log(safe), v.validity & ok),
                      DataType.FLOAT64)


@register("isnan", DataType.BOOL)
def _isnan(args, expr, batch, schema, ctx):
    v = args[0]
    if not v.dtype.is_floating:
        return TypedValue(PrimitiveColumn(jnp.zeros_like(v.validity),
                                          jnp.ones_like(v.validity)), DataType.BOOL)
    return TypedValue(PrimitiveColumn(jnp.isnan(v.data) & v.validity,
                                      jnp.ones_like(v.validity)), DataType.BOOL)


@register("nanvl")
def _nanvl(args, expr, batch, schema, ctx):
    a, b = args
    take_b = jnp.isnan(a.data)
    return TypedValue(
        PrimitiveColumn(jnp.where(take_b, b.data, a.data),
                        jnp.where(take_b, b.validity, a.validity)),
        a.dtype)


@register("normalize_nan_and_zero")
def _normalize(args, expr, batch, schema, ctx):
    """reference: spark_normalize_nan_and_zero — canonical NaN, -0.0 → 0.0."""
    v = args[0]
    d = jnp.where(jnp.isnan(v.data), jnp.asarray(float("nan"), v.data.dtype), v.data)
    d = jnp.where(d == 0.0, jnp.asarray(0.0, v.data.dtype), d)
    return TypedValue(PrimitiveColumn(d, v.validity), v.dtype)


def _nan_gt(a, b):
    """Spark ordering '>': NaN is the greatest value (a != a means NaN;
    no-op for ints)."""
    return (a > b) | ((a != a) & (b == b))


@register("greatest")
def _greatest(args, expr, batch, schema, ctx):
    out = args[0]
    for v in args[1:]:
        take = (~out.validity) | (v.validity & _nan_gt(v.data, out.data))
        out = TypedValue(PrimitiveColumn(jnp.where(take, v.data, out.data),
                                         out.validity | v.validity), out.dtype,
                         out.precision, out.scale)
    return out


@register("least")
def _least(args, expr, batch, schema, ctx):
    out = args[0]
    for v in args[1:]:
        take = (~out.validity) | (v.validity & _nan_gt(out.data, v.data))
        out = TypedValue(PrimitiveColumn(jnp.where(take, v.data, out.data),
                                         out.validity | v.validity), out.dtype,
                         out.precision, out.scale)
    return out


_UNARY_F64 = {
    "sin": jnp.sin, "cos": jnp.cos, "tan": jnp.tan,
    "asin": jnp.arcsin, "acos": jnp.arccos, "atan": jnp.arctan,
    "sinh": jnp.sinh, "cosh": jnp.cosh, "tanh": jnp.tanh,
    "log10": jnp.log10, "log2": jnp.log2, "log1p": jnp.log1p,
    "expm1": jnp.expm1, "cbrt": jnp.cbrt,
    "degrees": jnp.degrees, "radians": jnp.radians,
    "rint": jnp.round,
}


def _register_unary_f64():
    for fname, jfn in _UNARY_F64.items():
        def make(jf):
            def impl(args, expr, batch, schema, ctx):
                v = cast_value(args[0], DataType.FLOAT64)
                return TypedValue(PrimitiveColumn(jf(v.data), v.validity),
                                  DataType.FLOAT64)
            return impl
        register(fname, DataType.FLOAT64)(make(jfn))


_register_unary_f64()


@register("signum", DataType.FLOAT64)
@register("sign", DataType.FLOAT64)
def _signum(args, expr, batch, schema, ctx):
    v = cast_value(args[0], DataType.FLOAT64)
    return TypedValue(PrimitiveColumn(jnp.sign(v.data), v.validity),
                      DataType.FLOAT64)


@register("atan2", DataType.FLOAT64)
def _atan2(args, expr, batch, schema, ctx):
    a = cast_value(args[0], DataType.FLOAT64)
    b = cast_value(args[1], DataType.FLOAT64)
    return TypedValue(PrimitiveColumn(jnp.arctan2(a.data, b.data),
                                      a.validity & b.validity),
                      DataType.FLOAT64)


@register("hypot", DataType.FLOAT64)
def _hypot(args, expr, batch, schema, ctx):
    a = cast_value(args[0], DataType.FLOAT64)
    b = cast_value(args[1], DataType.FLOAT64)
    return TypedValue(PrimitiveColumn(jnp.hypot(a.data, b.data),
                                      a.validity & b.validity),
                      DataType.FLOAT64)


@register("pi", DataType.FLOAT64)
def _pi(args, expr, batch, schema, ctx):
    import math
    return TypedValue(PrimitiveColumn(
        jnp.full(batch.capacity, math.pi, jnp.float64),
        jnp.ones(batch.capacity, bool)), DataType.FLOAT64)


@register("e", DataType.FLOAT64)
def _e(args, expr, batch, schema, ctx):
    import math
    return TypedValue(PrimitiveColumn(
        jnp.full(batch.capacity, math.e, jnp.float64),
        jnp.ones(batch.capacity, bool)), DataType.FLOAT64)


def _pmod_result(expr, schema):
    lt, _, _ = infer_dtype(expr.args[0], schema)
    rt, _, _ = infer_dtype(expr.args[1], schema)
    if lt.is_floating or rt.is_floating:
        return DataType.FLOAT64, 0, 0
    return DataType.INT64, 0, 0


@register("pmod", _pmod_result)
def _pmod(args, expr, batch, schema, ctx):
    """Spark pmod(a, n) = ((a % n) + n) % n with Java remainder — which
    is exactly floor-mod for every sign combination (verified: (-7,3)->2,
    (7,-3)->-2, (-7,-3)->-1). Null on zero divisor."""
    a, b = args
    target = DataType.FLOAT64 if (a.dtype.is_floating
                                  or b.dtype.is_floating) else DataType.INT64
    av = cast_value(a, target)
    bv = cast_value(b, target)
    nz = bv.data != 0
    safe_b = jnp.where(nz, bv.data, 1)
    r = jnp.mod(av.data, safe_b)            # jnp.mod is floor-mod
    return TypedValue(PrimitiveColumn(r, av.validity & bv.validity & nz),
                      target)


@register("factorial", DataType.INT64)
def _factorial(args, expr, batch, schema, ctx):
    """Spark factorial: defined for 0..20, null outside."""
    import math
    table = jnp.asarray([math.factorial(i) for i in range(21)], jnp.int64)
    v = cast_value(args[0], DataType.INT64)
    ok = (v.data >= 0) & (v.data <= 20)
    idx = jnp.clip(v.data, 0, 20)
    return TypedValue(PrimitiveColumn(table[idx], v.validity & ok),
                      DataType.INT64)


# ---------------------------------------------------------------------------
# conditional / null
# ---------------------------------------------------------------------------

@register("coalesce")
def _coalesce(args, expr, batch, schema, ctx):
    out = args[0]
    for v in args[1:]:
        take = ~out.validity
        if isinstance(out.col, StringColumn):
            w = max(out.col.width, v.col.width)
            oc = _widen(out.col, w)
            vc = _widen(v.col, w)
            col = StringColumn(jnp.where(take[:, None], vc.chars, oc.chars),
                               jnp.where(take, vc.lens, oc.lens),
                               oc.validity | vc.validity)
        else:
            col = PrimitiveColumn(jnp.where(take, v.data, out.data),
                                  out.validity | v.validity)
        out = TypedValue(col, out.dtype, out.precision, out.scale)
    return out


def _widen(col: StringColumn, width: int) -> StringColumn:
    if col.width == width:
        return col
    return StringColumn(jnp.pad(col.chars, ((0, 0), (0, width - col.width))),
                        col.lens, col.validity)


@register("nullif")
@register("null_if")
def _nullif(args, expr, batch, schema, ctx):
    a, b = args
    if isinstance(a.col, StringColumn):
        _, eq = S.compare(a.col.chars, a.col.lens, b.col.chars, b.col.lens)
    else:
        eq = a.data == b.data
    eq = eq & a.validity & b.validity
    return TypedValue(a.col.with_validity(a.validity & ~eq),
                      a.dtype, a.precision, a.scale)


def _if_result(expr, schema):
    # the result type is the THEN branch's (args[1]), not the condition's
    return infer_dtype(expr.args[1], schema)


@register("if", _if_result)
def _if(args, expr, batch, schema, ctx):
    c, t, f = args
    take = c.data.astype(bool) & c.validity
    if isinstance(t.col, StringColumn):
        w = max(t.col.width, f.col.width)
        tc, fc = _widen(t.col, w), _widen(f.col, w)
        col = StringColumn(jnp.where(take[:, None], tc.chars, fc.chars),
                           jnp.where(take, tc.lens, fc.lens),
                           jnp.where(take, tc.validity, fc.validity))
    else:
        col = PrimitiveColumn(jnp.where(take, t.data, f.data),
                              jnp.where(take, t.validity, f.validity))
    return TypedValue(col, t.dtype, t.precision, t.scale)


# ---------------------------------------------------------------------------
# strings
# ---------------------------------------------------------------------------

def _string_result(expr, schema):
    return DataType.STRING, 0, 0


@register("length", DataType.INT32)
@register("char_length", DataType.INT32)
def _length(args, expr, batch, schema, ctx):
    v = args[0]
    assert isinstance(v.col, StringColumn)
    # NOTE: byte length == char length only for ASCII; UTF-8 aware length
    # subtracts continuation bytes (0b10xxxxxx).
    cont = ((v.col.chars & 0xC0) == 0x80) & (
        jnp.arange(v.col.width)[None, :] < v.col.lens[:, None])
    chars_len = v.col.lens - jnp.sum(cont, axis=1).astype(jnp.int32)
    return TypedValue(PrimitiveColumn(chars_len, v.validity), DataType.INT32)


@register("upper", _string_result)
def _upper(args, expr, batch, schema, ctx):
    return TypedValue(S.upper(args[0].col), DataType.STRING)


@register("lower", _string_result)
def _lower(args, expr, batch, schema, ctx):
    return TypedValue(S.lower(args[0].col), DataType.STRING)


@register("trim", _string_result)
def _trim(args, expr, batch, schema, ctx):
    return TypedValue(S.trim(args[0].col), DataType.STRING)


@register("ltrim", _string_result)
def _ltrim(args, expr, batch, schema, ctx):
    return TypedValue(S.trim(args[0].col, right=False), DataType.STRING)


@register("rtrim", _string_result)
def _rtrim(args, expr, batch, schema, ctx):
    return TypedValue(S.trim(args[0].col, left=False), DataType.STRING)


@register("substring", _string_result)
@register("substr", _string_result)
def _substring(args, expr, batch, schema, ctx):
    v = args[0]
    start = args[1].data.astype(jnp.int32)
    length = (args[2].data.astype(jnp.int32) if len(args) > 2
              else jnp.full_like(start, 2**30))
    return TypedValue(S.substring(v.col, start, length), DataType.STRING)


@register("concat", _string_result)
def _concat(args, expr, batch, schema, ctx):
    cols = [a.col for a in args]
    out_w = bucket_string_width(sum(c.width for c in cols))
    return TypedValue(S.concat(cols, out_w), DataType.STRING)


# ---------------------------------------------------------------------------
# hashes
# ---------------------------------------------------------------------------

@register("hash", DataType.INT32)
@register("murmur3_hash", DataType.INT32)
def _hash(args, expr, batch, schema, ctx):
    h = hashing.murmur3_columns([a.col for a in args], batch.capacity, 42)
    return TypedValue(PrimitiveColumn(h, jnp.ones(batch.capacity, bool)),
                      DataType.INT32)


@register("xxhash64", DataType.INT64)
def _xxhash64(args, expr, batch, schema, ctx):
    h = hashing.xxhash64_columns([a.col for a in args], batch.capacity, 42)
    return TypedValue(PrimitiveColumn(h, jnp.ones(batch.capacity, bool)),
                      DataType.INT64)


# ---------------------------------------------------------------------------
# extended surface — importing these modules populates the registry
# (strings/dates on device; json/regex as host callbacks; md5/sha256 as
# vectorized device kernels)
# ---------------------------------------------------------------------------

from auron_tpu.exprs import fn_arrays   # noqa: E402,F401
from auron_tpu.exprs import fn_structs  # noqa: E402,F401
from auron_tpu.exprs import fn_crypto   # noqa: E402,F401
from auron_tpu.exprs import fn_dates    # noqa: E402,F401
from auron_tpu.exprs import fn_json     # noqa: E402,F401
from auron_tpu.exprs import fn_strings  # noqa: E402,F401
