import decimal
from decimal import Decimal

import numpy as np
import pyarrow as pa
import pytest

import jax.numpy as jnp

from auron_tpu.columnar import batch as B
from auron_tpu.columnar.arrow_bridge import (_column_to_device, to_arrow,
                                             to_device)
from auron_tpu.columnar.decimal128 import Decimal128Column
from auron_tpu.columnar.schema import DataType, Field
from auron_tpu.obs import trace


def roundtrip(rb, **kw):
    dev, schema = to_device(rb, **kw)
    return to_arrow(dev, schema)


def test_roundtrip_primitives():
    rb = pa.record_batch({
        "i32": pa.array([1, None, -3], pa.int32()),
        "i64": pa.array([10, 20, None], pa.int64()),
        "f64": pa.array([1.5, None, -2.5], pa.float64()),
        "b": pa.array([True, False, None], pa.bool_()),
    })
    out = roundtrip(rb)
    assert out.equals(rb)


def test_roundtrip_strings():
    rb = pa.record_batch({
        "s": pa.array(["", "hello", None, "wörld", "a" * 30], pa.string()),
    })
    out = roundtrip(rb)
    assert out.equals(rb)


def test_roundtrip_date_timestamp_decimal():
    rb = pa.record_batch({
        "d": pa.array([0, 19000, None], pa.date32()),
        "ts": pa.array([0, 1_700_000_000_000_000, None], pa.timestamp("us")),
        "dec": pa.array([None, Decimal("123.45"), Decimal("-0.01")],
                        pa.decimal128(10, 2)),
    })
    out = roundtrip(rb)
    assert out.equals(rb)


def test_capacity_padding_and_mask():
    rb = pa.record_batch({"x": pa.array([1, 2, 3], pa.int64())})
    dev, schema = to_device(rb, capacity=16)
    assert dev.capacity == 16
    assert int(dev.num_rows) == 3
    np.testing.assert_array_equal(
        np.asarray(dev.row_mask()), [True] * 3 + [False] * 13)
    assert to_arrow(dev, schema).equals(rb)


def test_compact():
    rb = pa.record_batch({
        "x": pa.array([1, 2, 3, 4, 5], pa.int64()),
        "s": pa.array(["a", "bb", "ccc", None, "e"], pa.string()),
    })
    dev, schema = to_device(rb, capacity=8)
    keep = jnp.asarray([True, False, True, True, False, True, True, True])
    out = B.compact(dev, keep)
    assert int(out.num_rows) == 3
    got = to_arrow(out, schema)
    assert got.column(0).to_pylist() == [1, 3, 4]
    assert got.column(1).to_pylist() == ["a", "ccc", None]


def test_concat_batches():
    rb1 = pa.record_batch({"x": pa.array([1, 2], pa.int64())})
    rb2 = pa.record_batch({"x": pa.array([3, 4, 5], pa.int64())})
    d1, schema = to_device(rb1, capacity=4)
    d2, _ = to_device(rb2, capacity=4)
    out = B.concat_batches(d1, d2)
    assert out.capacity == 8
    assert int(out.num_rows) == 5
    assert to_arrow(out, schema).column(0).to_pylist() == [1, 2, 3, 4, 5]


def test_resize():
    rb = pa.record_batch({"x": pa.array([1, 2, 3], pa.int64()),
                          "s": pa.array(["a", "b", "c"], pa.string())})
    dev, schema = to_device(rb, capacity=4)
    grown = B.resize(dev, 16)
    assert grown.capacity == 16
    assert to_arrow(grown, schema).equals(to_arrow(dev, schema))


# ---------------------------------------------------------------------------
# decimal encode: the view of the decimal128 limbs (PR 26) against the
# per-row conversion it replaced, kept here as the reference
# ---------------------------------------------------------------------------

def _decimal_values(precision, scale):
    """Unscaled extremes of the precision, both signs, and a spread of
    magnitudes between (every limb boundary a value can cross)."""
    top = 10 ** precision - 1
    unscaled = [0, 1, -1, top, -top, top // 7, -(top // 3), 12345, -99]
    unscaled += [s * (10 ** k + k) for k in range(0, precision, 3)
                 for s in (1, -1) if 10 ** k + k <= top]
    with decimal.localcontext() as ctx:
        ctx.prec = 80
        return [Decimal(u).scaleb(-scale) for u in unscaled]


def _reference_limbs(pyvals, scale, cap):
    """One Python ``Decimal`` a value → (hi, lo, validity): the two
    little-endian int64 limbs of the 128-bit two's-complement unscaled
    value; null and padding slots 0."""
    hi = np.zeros(cap, np.int64)
    lo = np.zeros(cap, np.int64)
    validity = np.zeros(cap, bool)
    with decimal.localcontext() as ctx:
        ctx.prec = 80
        for i, v in enumerate(pyvals):
            if v is None:
                continue
            u = int(v.scaleb(scale).to_integral_value())
            lo[i], hi[i] = np.frombuffer(
                u.to_bytes(16, "little", signed=True), np.int64)
            validity[i] = True
    return hi, lo, validity


def _with_nulls(vals):
    return [None if i % 3 == 1 else v for i, v in enumerate(vals)]


def _garbage_in_null_slots(vals, t):
    """An array whose null slots hold non-zero bytes, as Arrow allows."""
    arr = pa.array(_with_nulls(vals), t)
    filled = pa.array(vals, t)
    return pa.Array.from_buffers(t, len(arr), [arr.buffers()[0],
                                               filled.buffers()[1]])


_DECIMAL_LAYOUTS = {
    # name -> (vals, type) -> (arrow array handed to the encode, capacity)
    "no_nulls": lambda v, t: (pa.array(v, t), 64),
    "nulls": lambda v, t: (pa.array(_with_nulls(v), t), 64),
    "garbage_null_slots": lambda v, t: (_garbage_in_null_slots(v, t), 64),
    "sliced": lambda v, t: (pa.array(_with_nulls(v), t).slice(3, len(v) - 5),
                            64),
    "sliced_no_nulls": lambda v, t: (pa.array(v, t).slice(2), 64),
    "chunked": lambda v, t: (pa.chunked_array(
        [pa.array(_with_nulls(v), t).slice(1, 4), pa.array([], t),
         pa.array(v, t).slice(5)]), 64),
    "dictionary": lambda v, t: (
        pa.array(_with_nulls(v + v), t).dictionary_encode(), 128),
    "zero_rows": lambda v, t: (pa.array([], t), 8),
    "zero_chunks": lambda v, t: (pa.chunked_array([], t), 8),
    "all_null": lambda v, t: (pa.array([None] * 5, t), 8),
    "all_null_no_buffer": lambda v, t: (pa.nulls(5, t), 8),
    "exact_capacity": lambda v, t: (pa.array(_with_nulls(v), t), len(v)),
    "padded": lambda v, t: (pa.array(_with_nulls(v), t), 4096),
}


def _assert_decimal_column_equals_reference(col, pyvals, precision, scale,
                                            cap):
    hi, lo, validity = _reference_limbs(pyvals, scale, cap)
    if precision > 18:
        assert isinstance(col, Decimal128Column)
        got = {"hi": col.hi, "lo": col.lo, "validity": col.validity}
        want = {"hi": hi, "lo": lo, "validity": validity}
    else:
        assert isinstance(col, B.PrimitiveColumn)
        # <= 18 digits fit the low limb: the high one is its sign
        np.testing.assert_array_equal(hi, lo >> 63)
        got = {"data": col.data, "validity": col.validity}
        want = {"data": lo, "validity": validity}
    for name, arr in got.items():
        assert arr.shape == (cap,), name
        assert arr.dtype == want[name].dtype, name
        np.testing.assert_array_equal(np.asarray(arr), want[name], name)


@pytest.mark.parametrize("layout", list(_DECIMAL_LAYOUTS))
@pytest.mark.parametrize("precision,scale",
                         [(7, 2), (18, 0), (19, 4), (38, 10)])
def test_decimal_encode_equals_per_row_reference(precision, scale, layout):
    field = Field("m", DataType.DECIMAL, True, precision, scale)
    arr, cap = _DECIMAL_LAYOUTS[layout](
        _decimal_values(precision, scale), pa.decimal128(precision, scale))
    pyvals = arr.to_pylist()
    assert len(pyvals) <= cap
    col = _column_to_device(field, arr, cap, None)
    _assert_decimal_column_equals_reference(col, pyvals, precision, scale,
                                            cap)


@pytest.mark.parametrize("maker,precision,scale", [
    (name, p, s)
    for name, p, s in [("decimal32", 7, 2), ("decimal64", 18, 0),
                       ("decimal64", 12, 2), ("decimal256", 19, 4),
                       ("decimal256", 38, 10), ("decimal256", 7, 2)]
    if hasattr(pa, name)])
def test_decimal_encode_of_other_arrow_widths(maker, precision, scale):
    """decimal32/64/256 come to decimal128(p, s) by Arrow's cast and take
    the same view — through ``to_device``, the scan's entry."""
    vals = _with_nulls(_decimal_values(precision, scale))
    rb = pa.record_batch(
        {"m": pa.array(vals, getattr(pa, maker)(precision, scale))}
    ).slice(2)
    with trace.task_scope("q-width") as acc:
        dev, schema = to_device(rb, capacity=64)
        counts = acc.sealed(1.0)["counts"]
    assert counts["encode_pyloop_values"] == 0
    assert (schema[0].precision, schema[0].scale) == (precision, scale)
    _assert_decimal_column_equals_reference(dev.columns[0], vals[2:],
                                            precision, scale, 64)


@pytest.mark.parametrize("precision,scale", [(7, 2), (38, 10)])
def test_decimal_arrow_cannot_cast_takes_the_counted_loop(precision, scale):
    """A field scale below the array's with digits to drop: Arrow refuses
    the cast ("would cause data loss"), the per-row loop rounds as it
    always did, and the task's ledger counts the values."""
    vals = [None if u is None else Decimal(u).scaleb(-scale - 3)
            for u in (1234567, None, -99999995, 5, 1500, -2500)]
    arr = pa.array(vals, pa.decimal128(min(precision + 3, 38), scale + 3))
    field = Field("m", DataType.DECIMAL, True, precision, scale)
    with trace.task_scope("q-loop") as acc:
        col = _column_to_device(field, arr, 8, None)
        counts = acc.sealed(1.0)["counts"]
    assert counts["encode_pyloop_values"] == len(vals)
    _assert_decimal_column_equals_reference(col, vals, precision, scale, 8)
