"""pyarrow RecordBatch <-> DeviceBatch conversion.

This is the host<->device boundary, the analogue of the reference's Arrow
C-FFI import/export between JVM and native (reference: auron-core/src/main/
java/org/apache/auron/arrowio/..., native-engine/auron/src/rt.rs:252-282).
Host → device runs in two steps a batch, each under its layer span
(obs/trace.py): *encode* builds every column as dense padded numpy buffers
(the ``_*_to_device`` helpers below return columns with numpy leaves), then
*h2d* transfers the batch's buffers — no per-row work on either side of the
wall.
"""

from __future__ import annotations

import numpy as np
import pyarrow as pa

import jax
import jax.numpy as jnp

from auron_tpu.columnar.batch import (DeviceBatch, ListColumn,
                                      PrimitiveColumn, StringColumn)
from auron_tpu.columnar.schema import DataType, Field, Schema
from auron_tpu.obs import trace
from auron_tpu.utils.shapes import bucket_rows, bucket_string_width

#: fallback precision for a LIST-of-decimal field whose precision slot is
#: 0 (pre-fix partial layouts): ONE constant shared by schema_to_arrow
#: and every child-array render site — diverging fallbacks (38 in the
#: schema vs 18 in the HostList child) made the child array type
#: mismatch the declared schema at table assembly (ADVICE round 5)
_LIST_DECIMAL_FALLBACK_PRECISION = 38

_PA_TO_DT = {
    pa.bool_(): DataType.BOOL,
    pa.int8(): DataType.INT8,
    pa.int16(): DataType.INT16,
    pa.int32(): DataType.INT32,
    pa.int64(): DataType.INT64,
    pa.float32(): DataType.FLOAT32,
    pa.float64(): DataType.FLOAT64,
    pa.date32(): DataType.DATE32,
    pa.timestamp("us"): DataType.TIMESTAMP_US,
    pa.string(): DataType.STRING,
    pa.large_string(): DataType.STRING,
    pa.null(): DataType.NULL,
}


def schema_from_arrow(sch: pa.Schema) -> Schema:
    fields = []
    for f in sch:
        t = f.type
        if pa.types.is_decimal(t):
            if t.precision > 38:
                raise NotImplementedError(
                    f"decimal precision {t.precision} > 38 not supported")
            fields.append(Field(f.name, DataType.DECIMAL, f.nullable, t.precision, t.scale))
        elif pa.types.is_dictionary(t):
            inner = _PA_TO_DT.get(t.value_type)
            if inner is None:
                raise NotImplementedError(f"dictionary of {t.value_type}")
            fields.append(Field(f.name, inner, f.nullable))
        elif t in _PA_TO_DT:
            fields.append(Field(f.name, _PA_TO_DT[t], f.nullable))
        elif pa.types.is_timestamp(t):
            fields.append(Field(f.name, DataType.TIMESTAMP_US, f.nullable))
        elif pa.types.is_list(t) or pa.types.is_large_list(t):
            if pa.types.is_string(t.value_type) \
                    or pa.types.is_large_string(t.value_type):
                fields.append(Field(f.name, DataType.LIST, f.nullable,
                                    elem=DataType.STRING))
            elif pa.types.is_struct(t.value_type):
                # entry list — list<struct<K, V>> with two primitive
                # children (the map_entries / map_from_entries shape,
                # reference: spark_map.rs:553 MapFromEntries). Carried on
                # device by the MapColumn layout; Field.children hold the
                # entry struct's fields.
                st = t.value_type
                if st.num_fields != 2:
                    raise NotImplementedError(
                        f"list of {st}: only 2-field entry structs "
                        "(key/value) are materialized")
                kids = []
                for i in range(st.num_fields):
                    cf = st.field(i)
                    cdt = _PA_TO_DT.get(cf.type)
                    if cdt in (None, DataType.NULL, DataType.STRING):
                        # the MapColumn carrier holds numeric matrices
                        # only — no char-tensor slot for string children
                        raise NotImplementedError(
                            f"entry-struct child {cf.name}: {cf.type} "
                            "(numeric primitive children only)")
                    kids.append(Field(cf.name, cdt, cf.nullable))
                fields.append(Field(f.name, DataType.LIST, f.nullable,
                                    elem=DataType.STRUCT,
                                    children=tuple(kids)))
            elif pa.types.is_decimal(t.value_type):
                if t.value_type.precision > 38:
                    raise NotImplementedError(
                        f"list of {t.value_type}: precision > 38")
                fields.append(Field(f.name, DataType.LIST, f.nullable,
                                    t.value_type.precision,
                                    t.value_type.scale,
                                    elem=DataType.DECIMAL))
            else:
                elem = _PA_TO_DT.get(t.value_type)
                if elem is None or elem == DataType.NULL:
                    raise NotImplementedError(f"list of {t.value_type}")
                fields.append(Field(f.name, DataType.LIST, f.nullable,
                                    elem=elem))
        elif pa.types.is_map(t):
            key = _PA_TO_DT.get(t.key_type)
            val = _PA_TO_DT.get(t.item_type)
            if key == DataType.STRING and val == DataType.STRING:
                fields.append(Field(f.name, DataType.MAP, f.nullable,
                                    elem=DataType.STRING,
                                    key=DataType.STRING))
            elif key in (None, DataType.STRING, DataType.NULL) \
                    or val in (None, DataType.STRING, DataType.NULL):
                raise NotImplementedError(
                    f"map<{t.key_type}, {t.item_type}>: primitive "
                    "keys/values or map<string,string> only")
            else:
                fields.append(Field(f.name, DataType.MAP, f.nullable,
                                    elem=val, key=key))
        elif pa.types.is_struct(t):
            kids = []
            for i in range(t.num_fields):
                cf = t.field(i)
                sub = schema_from_arrow(pa.schema([cf]))
                if sub[0].dtype in (DataType.MAP, DataType.STRUCT,
                                    DataType.LIST):
                    raise NotImplementedError(
                        f"struct child {cf.name}: nested map/struct/list "
                        "children are not materialized yet")
                kids.append(sub[0])
            fields.append(Field(f.name, DataType.STRUCT, f.nullable,
                                children=tuple(kids)))
        else:
            raise NotImplementedError(f"arrow type {t} not supported")
    return Schema(tuple(fields))


def schema_to_arrow(schema: Schema) -> pa.Schema:
    out = []
    for f in schema:
        if f.dtype == DataType.STRING:
            t = pa.string()
        elif f.dtype == DataType.DECIMAL:
            t = pa.decimal128(f.precision, f.scale)
        elif f.dtype == DataType.DATE32:
            t = pa.date32()
        elif f.dtype == DataType.TIMESTAMP_US:
            t = pa.timestamp("us")
        elif f.dtype == DataType.NULL:
            t = pa.null()
        elif f.dtype == DataType.LIST:
            if f.elem == DataType.STRUCT:
                t = pa.list_(pa.struct(
                    [pa.field(cf.name, pa.from_numpy_dtype(cf.dtype.to_np()),
                              cf.nullable) for cf in f.children]))
            elif f.elem == DataType.DECIMAL:
                # element (p, s) rides the LIST field's precision/scale
                # slots (wide collect_* results; ops/agg.py make_acc_spec)
                t = pa.list_(pa.decimal128(
                    f.precision or _LIST_DECIMAL_FALLBACK_PRECISION,
                    f.scale))
            else:
                t = pa.list_(pa.string() if f.elem == DataType.STRING
                             else pa.from_numpy_dtype(f.elem.to_np()))
        elif f.dtype == DataType.MAP:
            t = pa.map_(pa.string() if f.key == DataType.STRING
                        else pa.from_numpy_dtype(f.key.to_np()),
                        pa.string() if f.elem == DataType.STRING
                        else pa.from_numpy_dtype(f.elem.to_np()))
        elif f.dtype == DataType.STRUCT:
            t = pa.struct([schema_to_arrow(Schema((cf,)))[0]
                           for cf in f.children])
        else:
            t = pa.from_numpy_dtype(f.dtype.to_np())
        out.append(pa.field(f.name, t, f.nullable))
    return pa.schema(out)


def _string_arrays(arr: pa.Array, capacity: int, width: int | None):
    """Extract (chars[cap, w], lens[cap], validity[cap]) from a pyarrow
    string array using its offsets/data buffers (no per-row Python)."""
    arr = arr.cast(pa.string()) if not pa.types.is_string(arr.type) else arr
    arr = arr.combine_chunks() if isinstance(arr, pa.ChunkedArray) else arr
    n = len(arr)
    offsets = np.frombuffer(arr.buffers()[1], dtype=np.int32,
                            count=n + 1, offset=arr.offset * 4)
    data_buf = arr.buffers()[2]
    data = np.frombuffer(data_buf, dtype=np.uint8) if data_buf is not None else np.zeros(0, np.uint8)
    lens = (offsets[1:] - offsets[:-1]).astype(np.int32)
    max_len = int(lens.max()) if n else 0
    w = width if width is not None else bucket_string_width(max_len)
    if max_len > w:
        raise ValueError(f"string of length {max_len} exceeds width bucket {w}")
    chars = np.zeros((capacity, w), dtype=np.uint8)
    if n:
        # Gather bytes: chars[i, j] = data[offsets[i] + j] for j < lens[i].
        col_idx = np.arange(w, dtype=np.int64)[None, :]
        src = offsets[:-1, None].astype(np.int64) + col_idx
        in_range = col_idx < lens[:, None]
        src = np.where(in_range, src, 0)
        if data.size == 0:
            data = np.zeros(1, np.uint8)
        chars[:n] = np.where(in_range, data[np.clip(src, 0, data.size - 1)], 0)
    lens_full = np.zeros(capacity, np.int32)
    lens_full[:n] = lens
    validity = np.zeros(capacity, bool)
    if arr.null_count:
        validity[:n] = ~np.asarray(arr.is_null())
    else:
        validity[:n] = True
    lens_full[:capacity][~validity] = 0
    return chars, lens_full, validity


def _list_arrays(arr: pa.Array, capacity: int, elem_np) -> tuple:
    """Extract (values[cap, m], elem_valid[cap, m], lens[cap], validity[cap])
    from a pyarrow list array via its offsets (no per-row Python)."""
    if isinstance(arr, pa.ChunkedArray):
        arr = arr.combine_chunks()
    arr = arr.cast(pa.list_(arr.type.value_type))
    n = len(arr)
    offsets = np.asarray(arr.offsets)[: n + 1]
    child = arr.values
    child_np = np.asarray(child.fill_null(0)).astype(elem_np)
    child_valid = (~np.asarray(child.is_null()) if child.null_count
                   else np.ones(len(child), bool))
    lens = (offsets[1:] - offsets[:-1]).astype(np.int32)
    validity = (~np.asarray(arr.is_null()) if arr.null_count
                else np.ones(n, bool))
    lens = np.where(validity, lens, 0)
    m = max(int(lens.max()) if n else 0, 1)
    values = np.zeros((capacity, m), elem_np)
    elem_valid = np.zeros((capacity, m), bool)
    if n:
        col_idx = np.arange(m, dtype=np.int64)[None, :]
        src = offsets[:-1, None].astype(np.int64) + col_idx
        in_range = col_idx < lens[:, None]
        src = np.clip(src, 0, max(len(child_np) - 1, 0))
        if len(child_np) == 0:
            child_np = np.zeros(1, elem_np)
            child_valid = np.zeros(1, bool)
        values[:n] = np.where(in_range, child_np[src], 0)
        elem_valid[:n] = in_range & child_valid[src]
    lens_full = np.zeros(capacity, np.int32)
    lens_full[:n] = lens
    validity_full = np.zeros(capacity, bool)
    validity_full[:n] = validity
    return values, elem_valid, lens_full, validity_full


def _map_to_device(field: Field, arr: pa.Array, cap: int):
    """MapArray → MapColumn via two list-view extractions over the shared
    offsets (keys carry no element validity — Spark map keys are
    non-null)."""
    if isinstance(arr, pa.ChunkedArray):
        arr = arr.combine_chunks()
    return _kv_lists_to_map_column(arr, arr.keys, arr.items,
                                   field.key.to_np(), field.elem.to_np(),
                                   cap)


def _kv_lists_to_map_column(arr: pa.Array, karr: pa.Array, varr: pa.Array,
                            key_np, val_np, cap: int):
    """Shared MapColumn-carrier assembly for every offsets-over-(K,V)
    arrow shape (MapArray, entry-list ListArray): two list-view
    extractions over the shared offsets, null-row len zeroing, and
    element-bucket unification."""
    from auron_tpu.columnar.batch import MapColumn
    n = len(arr)
    offsets = np.asarray(arr.offsets)[: n + 1]
    off = pa.array(offsets.astype(np.int32), pa.int32())
    keys_list = pa.ListArray.from_arrays(off, karr)
    items_list = pa.ListArray.from_arrays(off, varr)
    kv, _kev, lens, _ = _list_arrays(keys_list, cap, key_np)
    vv, vev, _vlens, _ = _list_arrays(items_list, cap, val_np)
    validity = np.zeros(cap, bool)
    validity[:n] = (~np.asarray(arr.is_null()) if arr.null_count
                    else np.ones(n, bool))
    lens = np.where(validity, lens, 0).astype(np.int32)
    m = max(kv.shape[1], vv.shape[1])
    kv = np.pad(kv, ((0, 0), (0, m - kv.shape[1])))
    vv = np.pad(vv, ((0, 0), (0, m - vv.shape[1])))
    vev = np.pad(vev, ((0, 0), (0, m - vev.shape[1])))
    return MapColumn(kv, vv, vev,
                     lens, validity)


def _decimal128_limbs(arr: pa.Array) -> np.ndarray:
    """int64[n, 2] view of a decimal128 array's value buffer: column 0
    the low limb's bit pattern, column 1 the signed high limb (Arrow
    stores a value as 16 little-endian two's-complement bytes). Null
    slots hold whatever the writer left there."""
    buf = arr.buffers()[1]
    if not len(arr) or buf is None:
        return np.zeros((len(arr), 2), np.int64)
    return np.frombuffer(buf, np.int64, count=2 * len(arr),
                         offset=arr.offset * 16).reshape(-1, 2)


def _decimal_list_to_device(field: Field, arr: pa.Array, cap: int):
    """list<decimal128(p,s)> → ListColumn with scaled-int64 payload
    (p<=18) or the MapColumn limb carrier (p>18). The child decimal
    buffer IS two little-endian int64 limbs per value, so the limbs are
    a zero-copy view re-wrapped as int64 list arrays over the shared
    offsets."""
    from auron_tpu.columnar.batch import ListColumn, MapColumn
    if isinstance(arr, pa.ChunkedArray):
        arr = arr.combine_chunks()
    n = len(arr)
    child = arr.values
    limbs = _decimal128_limbs(child)
    mask = (np.asarray(child.is_null()) if child.null_count
            else np.zeros(len(child), bool))
    offsets = np.asarray(arr.offsets)[: n + 1]
    off = pa.array(offsets.astype(np.int32), pa.int32())
    lo_list = pa.ListArray.from_arrays(
        off, pa.array(np.ascontiguousarray(limbs[:, 0]), pa.int64(),
                      mask=mask))
    lo_m, ev, lens, _ = _list_arrays(lo_list, cap, np.int64)
    validity = np.zeros(cap, bool)
    validity[:n] = (~np.asarray(arr.is_null()) if arr.null_count
                    else np.ones(n, bool))
    lens = np.where(validity, lens, 0).astype(np.int32)
    if field.precision <= 18:
        return ListColumn(lo_m, ev,
                          lens, validity)
    hi_list = pa.ListArray.from_arrays(
        off, pa.array(np.ascontiguousarray(limbs[:, 1]), pa.int64(),
                      mask=mask))
    hi_m, _hev, _l, _ = _list_arrays(hi_list, cap, np.int64)
    return MapColumn(hi_m, lo_m,
                     ev, lens,
                     validity)


def _entry_list_to_device(field: Field, arr: pa.Array, cap: int):
    """list<struct<K,V>> (entry list) → MapColumn carrier: the parallel
    key/value matrices + shared lens ARE the list-of-entry-structs layout
    (reference renders MapArray the same offsets-over-struct way).

    A row containing a NULL entry struct renders as a NULL row — the
    reference's map_from_entries semantics ('null array entry => null',
    spark_map.rs) — by folding those rows into the carrier's row
    validity, so the dead entries never need a slot. NULL first-child
    ("key") values in surviving rows still fail fast: Spark map keys are
    non-null."""
    if isinstance(arr, pa.ChunkedArray):
        arr = arr.combine_chunks()
    struct_child = arr.values
    n = len(arr)
    if struct_child.null_count:
        entry_null = np.asarray(struct_child.is_null())
        offsets = np.asarray(arr.offsets)[: n + 1].astype(np.int64)
        cum = np.concatenate([[0], np.cumsum(entry_null)])
        row_has_null = (cum[offsets[1:]] - cum[offsets[:-1]]) > 0
        validity = (~np.asarray(arr.is_null()) if arr.null_count
                    else np.ones(n, bool)) & ~row_has_null
        arr = pa.ListArray.from_arrays(
            pa.array(offsets.astype(np.int32), pa.int32()), struct_child,
            mask=pa.array(~validity))
    else:
        entry_null = None
    kf, vf = field.children
    karr = struct_child.field(0)
    if karr.null_count:
        # keys inside dead entries (null structs, entries of NULL rows)
        # have no semantics and no carrier slot; only a null key of a
        # LIVE entry in a surviving row raises
        key_null = np.asarray(karr.is_null())
        offsets = np.asarray(arr.offsets)[: n + 1].astype(np.int64)
        live_row = (~np.asarray(arr.is_null()) if arr.null_count
                    else np.ones(n, bool))
        ne = len(key_null)
        mark = np.zeros(ne + 1, np.int32)
        np.add.at(mark, np.clip(offsets[:-1][live_row], 0, ne), 1)
        np.add.at(mark, np.clip(offsets[1:][live_row], 0, ne), -1)
        key_null = key_null & (np.cumsum(mark[:ne]) > 0)
        if entry_null is not None:
            key_null = key_null & ~entry_null
        if key_null.any():
            raise NotImplementedError(
                "entry list with NULL key children (Spark map keys are "
                "non-null)")
    return _kv_lists_to_map_column(arr, karr, struct_child.field(1),
                                   kf.dtype.to_np(), vf.dtype.to_np(), cap)


def _struct_to_device(field: Field, arr: pa.Array, cap: int):
    from auron_tpu.columnar.batch import StructColumn
    if isinstance(arr, pa.ChunkedArray):
        arr = arr.combine_chunks()
    n = len(arr)
    kids = tuple(
        _column_to_device(cf, arr.field(i), cap, None)
        for i, cf in enumerate(field.children))
    validity = np.zeros(cap, bool)
    validity[:n] = (~np.asarray(arr.is_null()) if arr.null_count
                    else np.ones(n, bool))
    return StructColumn(kids, validity)


def to_device(rb: pa.RecordBatch, capacity: int | None = None,
              string_widths: dict[str, int] | None = None) -> tuple[DeviceBatch, Schema]:
    """Convert a pyarrow RecordBatch into a padded DeviceBatch."""
    schema = schema_from_arrow(rb.schema)
    n = rb.num_rows
    cap = capacity if capacity is not None else bucket_rows(n)
    if n > cap:
        raise ValueError(f"batch of {n} rows exceeds capacity {cap}")
    with trace.layer_span("scan", "encode"):
        cols = [_column_to_device(field, arr, cap, string_widths)
                for field, arr in zip(schema, rb.columns)]
        leaves, treedef = jax.tree_util.tree_flatten(
            DeviceBatch(tuple(cols), np.asarray(n, np.int32)))
    with trace.layer_span("scan", "h2d"):
        # one transfer a buffer, as before the split; the count is what
        # a batched device_put would remove
        leaves = [jnp.asarray(leaf) for leaf in leaves]
        trace.count("h2d_transfers", len(leaves))
        trace.count("h2d_bytes", sum(leaf.nbytes for leaf in leaves))
    return jax.tree_util.tree_unflatten(treedef, leaves), schema


def _string_list_to_device(arr: pa.Array, cap: int):
    """pyarrow list<string> → StringListColumn (padded char tensor)."""
    from auron_tpu.columnar.batch import StringListColumn
    from auron_tpu.utils.shapes import bucket_string_width
    if isinstance(arr, pa.ChunkedArray):
        arr = arr.combine_chunks()
    arr = arr.cast(pa.list_(pa.string()))
    n = len(arr)
    trace.count("encode_pyloop_values", n)
    pyrows = arr.to_pylist()
    max_e, max_w = 1, 1
    for row in pyrows:
        if row:
            max_e = max(max_e, len(row))
            for s in row:
                if s is not None:
                    max_w = max(max_w, len(s.encode()))
    width = bucket_string_width(max_w)
    chars = np.zeros((cap, max_e, width), np.uint8)
    slens = np.zeros((cap, max_e), np.int32)
    ev = np.zeros((cap, max_e), bool)
    lens = np.zeros(cap, np.int32)
    validity = np.zeros(cap, bool)
    for i, row in enumerate(pyrows):
        if row is None:
            continue
        validity[i] = True
        lens[i] = len(row)
        for j, s in enumerate(row):
            if s is None:
                continue
            b = s.encode()
            chars[i, j, :len(b)] = np.frombuffer(b, np.uint8)
            slens[i, j] = len(b)
            ev[i, j] = True
    return StringListColumn(chars, slens,
                            ev, lens,
                            validity)


def _string_map_to_device(arr: pa.Array, cap: int):
    """pyarrow map<string,string> → StringMapColumn."""
    from auron_tpu.columnar.batch import StringMapColumn
    from auron_tpu.utils.shapes import bucket_string_width
    if isinstance(arr, pa.ChunkedArray):
        arr = arr.combine_chunks()
    trace.count("encode_pyloop_values", len(arr))
    pyrows = arr.to_pylist()
    max_e, kw, vw = 1, 1, 1
    for row in pyrows:
        if row:
            max_e = max(max_e, len(row))
            for k, v in row:
                kw = max(kw, len(k.encode()))
                if v is not None:
                    vw = max(vw, len(v.encode()))
    kw, vw = bucket_string_width(kw), bucket_string_width(vw)
    kchars = np.zeros((cap, max_e, kw), np.uint8)
    kslens = np.zeros((cap, max_e), np.int32)
    vchars = np.zeros((cap, max_e, vw), np.uint8)
    vslens = np.zeros((cap, max_e), np.int32)
    vv = np.zeros((cap, max_e), bool)
    lens = np.zeros(cap, np.int32)
    validity = np.zeros(cap, bool)
    for i, row in enumerate(pyrows):
        if row is None:
            continue
        validity[i] = True
        lens[i] = len(row)
        for j, (k, v) in enumerate(row):
            kb = k.encode()
            kchars[i, j, :len(kb)] = np.frombuffer(kb, np.uint8)
            kslens[i, j] = len(kb)
            if v is not None:
                vb = v.encode()
                vchars[i, j, :len(vb)] = np.frombuffer(vb, np.uint8)
                vslens[i, j] = len(vb)
                vv[i, j] = True
    return StringMapColumn(kchars, kslens,
                           vchars, vslens,
                           vv, lens,
                           validity)


def _decimal_to_device(field: Field, arr: pa.Array, cap: int):
    """decimal(p, s) → scaled-int64 ``PrimitiveColumn`` (p <= 18; reference:
    datafusion-ext-functions/src/spark_make_decimal.rs) or the two-limb
    ``Decimal128Column`` (p 19..38; columnar/decimal128.py, the reference
    stores Decimal128 and computes in i128, arrow/cast.rs decimal paths).
    An Arrow decimal128 value buffer IS two little-endian int64 limbs a
    value, so the column is a strided view of it; any other decimal width
    or scale is brought there by Arrow's cast, and only what Arrow cannot
    cast takes the counted per-row loop. Null and padding slots hold 0
    (Arrow leaves a null slot's bytes undefined; hashing sees the
    payload)."""
    want = pa.decimal128(field.precision, field.scale)
    if arr.type != want:
        try:
            arr = arr.cast(want)
        except pa.ArrowInvalid:
            return _decimal_pyloop_to_device(field, arr, cap)
    n = len(arr)
    limbs = _decimal128_limbs(arr)
    valid = ~np.asarray(arr.is_null()) if arr.null_count else True
    validity = np.zeros(cap, bool)
    validity[:n] = valid
    lo = np.zeros(cap, np.int64)
    np.copyto(lo[:n], limbs[:, 0], where=valid)
    if field.precision > 18:
        from auron_tpu.columnar.decimal128 import Decimal128Column
        hi = np.zeros(cap, np.int64)
        np.copyto(hi[:n], limbs[:, 1], where=valid)
        return Decimal128Column(hi, lo, validity)
    return PrimitiveColumn(lo, validity)


def _decimal_pyloop_to_device(field: Field, arr: pa.Array, cap: int):
    """The per-row decimal conversion, for what Arrow cannot cast to
    ``decimal128(field.precision, field.scale)`` (a decimal256 value past
    38 digits, a rescale that drops digits): one Python ``Decimal`` a
    value, rounded to the field's scale, holding the interpreter lock."""
    import decimal as _dec

    from auron_tpu.columnar.decimal128 import (Decimal128Column,
                                               limbs_from_ints)
    trace.count("encode_pyloop_values", len(arr))
    with _dec.localcontext() as _ctx:
        # the default context (prec=28) would silently round longer
        # values during scaleb; a decimal256 holds up to 76 digits
        _ctx.prec = 80
        ints = [None if v is None
                else int(v.scaleb(field.scale).to_integral_value())
                for v in arr.to_pylist()]
    if field.precision > 18:
        hi, lo, validity = limbs_from_ints(ints, cap)
        return Decimal128Column(hi, lo, validity)
    validity = np.zeros(cap, bool)
    data = np.zeros(cap, np.int64)
    validity[:len(ints)] = [v is not None for v in ints]
    data[:len(ints)] = [v or 0 for v in ints]
    return PrimitiveColumn(data, validity)


def _column_to_device(field: Field, arr, cap: int,
                      string_widths: dict[str, int] | None):
    """One Arrow column → one device-layout column with numpy leaves
    (``to_device`` transfers them). Chunked and dictionary arrays are
    flattened first. Read from the Arrow buffers with no per-row Python:
    primitives, dates, timestamps, strings (offsets + data), lists and
    maps of primitives, entry lists, list<decimal>, and decimal of any
    Arrow width (32/64/128/256 bits, sliced or not) through
    ``_decimal_to_device``'s view of the decimal128 limbs. Per-row
    fallbacks, each counted in the task's ``counts.encode_pyloop_values``:
    list<string>, map<string,string>, and a decimal Arrow cannot cast to
    the field's decimal128(p, s)."""
    n = len(arr)
    if isinstance(arr, pa.ChunkedArray):
        arr = arr.combine_chunks()
    if pa.types.is_dictionary(arr.type):
        arr = arr.dictionary_decode()
    if field.dtype == DataType.STRING:
        w = (string_widths or {}).get(field.name)
        chars, lens, validity = _string_arrays(arr, cap, w)
        return StringColumn(chars, lens,
                            validity)
    if field.dtype == DataType.LIST:
        if field.elem == DataType.STRING:
            return _string_list_to_device(arr, cap)
        if field.elem == DataType.STRUCT:
            return _entry_list_to_device(field, arr, cap)
        if field.elem == DataType.DECIMAL:
            return _decimal_list_to_device(field, arr, cap)
        values, ev, lens, validity = _list_arrays(arr, cap,
                                                  field.elem.to_np())
        return ListColumn(values, ev,
                          lens, validity)
    if field.dtype == DataType.MAP:
        if field.key == DataType.STRING:
            return _string_map_to_device(arr, cap)
        return _map_to_device(field, arr, cap)
    if field.dtype == DataType.STRUCT:
        return _struct_to_device(field, arr, cap)
    if field.dtype == DataType.DECIMAL:
        return _decimal_to_device(field, arr, cap)
    np_dtype = field.dtype.to_np()
    validity = np.zeros(cap, bool)
    data = np.zeros(cap, np_dtype)
    if field.dtype == DataType.NULL:
        return PrimitiveColumn(data, validity)
    if field.dtype == DataType.TIMESTAMP_US:
        arr_us = arr.cast(pa.timestamp("us"))
        vals = arr_us.cast(pa.int64())
        data[:n] = np.asarray(vals.fill_null(0))
        validity[:n] = ~np.asarray(arr.is_null()) if arr.null_count else True
    elif field.dtype == DataType.DATE32:
        vals = arr.cast(pa.int32())
        data[:n] = np.asarray(vals.fill_null(0))
        validity[:n] = ~np.asarray(arr.is_null()) if arr.null_count else True
    else:
        vals = arr.fill_null(False) if field.dtype == DataType.BOOL else arr.fill_null(0)
        data[:n] = np.asarray(vals)
        validity[:n] = ~np.asarray(arr.is_null()) if arr.null_count else True
    return PrimitiveColumn(data, validity)


def to_arrow(batch: DeviceBatch, schema: Schema) -> pa.RecordBatch:
    """Materialize a DeviceBatch back to a pyarrow RecordBatch — ONE packed
    device→host transfer for the whole batch (columnar.serde.fetch_batch_numpy;
    per-array fetches each pay a transfer's fixed latency).
    Every column routes through the one host→arrow converter
    (_host_col_to_arrow) so top-level and struct-child renderings of the
    same logical type cannot drift."""
    from auron_tpu.columnar.serde import (_slice_host_col, fetch_batch_numpy,
                                          host_col_from_device)
    fetched, n = fetch_batch_numpy(batch)
    arrays = []
    for field, col, col_arrs in zip(schema, batch.columns, fetched):
        hc = _slice_host_col(host_col_from_device(col, iter(col_arrs)), 0, n)
        arrays.append(_host_col_to_arrow(field, hc, n))
    return pa.RecordBatch.from_arrays(arrays, schema=schema_to_arrow(schema))


def _list_offsets(lens: np.ndarray, validity: np.ndarray, n: int):
    """int32 Arrow offsets (+ None at null rows) from per-row lengths —
    shared by every list-shaped to-arrow arm (list / string list / map)."""
    offsets = np.zeros(n + 1, np.int32)
    np.cumsum(lens, out=offsets[1:])
    if validity.all():
        return pa.array(offsets, pa.int32())
    return pa.array(
        [None if not v else int(o)
         for o, v in zip(offsets[:-1], validity)] + [int(offsets[-1])],
        pa.int32())


def _host_col_to_arrow(field: Field, hc, n: int) -> pa.Array:
    """ONE host column → pyarrow array; the single conversion point for
    every logical type (top-level columns and struct children alike)."""
    from auron_tpu.columnar.serde import (HostDecimal128, HostList, HostMap,
                                          HostString, HostStringList,
                                          HostStringMap, HostStruct)
    if isinstance(hc, HostStringMap):
        validity = hc.validity
        lens = np.where(validity, hc.lens.astype(np.int64), 0)
        keys, vals = [], []
        for i in range(n):
            for j in range(int(lens[i])):
                keys.append(bytes(hc.kchars[i, j, :hc.kslens[i, j]])
                            .decode("utf-8", "replace"))
                vals.append(
                    bytes(hc.vchars[i, j, :hc.vslens[i, j]])
                    .decode("utf-8", "replace")
                    if hc.val_valid[i, j] else None)
        off_arr = _list_offsets(lens, validity, n)
        return pa.MapArray.from_arrays(off_arr,
                                       pa.array(keys, pa.string()),
                                       pa.array(vals, pa.string()))
    if isinstance(hc, HostStringList):
        validity = hc.validity
        lens = np.where(validity, hc.lens.astype(np.int64), 0)
        vals = []
        for i in range(n):
            for j in range(int(lens[i])):
                if hc.elem_valid[i, j]:
                    vals.append(bytes(
                        hc.chars[i, j, :hc.slens[i, j]]).decode(
                            "utf-8", "replace"))
                else:
                    vals.append(None)
        child = pa.array(vals, pa.string())
        off_arr = _list_offsets(lens, validity, n)
        return pa.ListArray.from_arrays(off_arr, child)
    if isinstance(hc, HostList):
        validity = hc.validity
        lens = np.where(validity, hc.lens.astype(np.int64), 0)
        take = np.arange(hc.values.shape[1])[None, :] < lens[:, None]
        flat_vals = hc.values[take]
        flat_valid = hc.elem_valid[take]
        if field.elem == DataType.DECIMAL:
            # scaled-int64 payload → decimal(p,s) child (narrow lists;
            # wide ones ride the HostMap limb carrier)
            child = pa.array(
                [_int_to_decimal(int(x), field.scale) for x in flat_vals],
                pa.decimal128(
                    field.precision or _LIST_DECIMAL_FALLBACK_PRECISION,
                    field.scale))
        else:
            child = pa.array(flat_vals,
                             pa.from_numpy_dtype(field.elem.to_np()))
        if not flat_valid.all():
            child = _with_nulls(child, flat_valid)
        off_arr = _list_offsets(lens, validity, n)
        return pa.ListArray.from_arrays(off_arr, child)
    if isinstance(hc, HostMap):
        validity = hc.validity
        lens = np.where(validity, hc.lens, 0).astype(np.int64)
        take = np.arange(hc.keys.shape[1])[None, :] < lens[:, None]
        if field.dtype == DataType.LIST and field.elem == DataType.DECIMAL:
            # list<decimal128>: the carrier's keys/values matrices are the
            # hi/lo limbs of each element; element nulls ride val_valid
            from auron_tpu.columnar.decimal128 import ints_from_limbs
            flat_hi = hc.keys[take]
            flat_lo = hc.values[take]
            flat_vv = hc.val_valid[take]
            ints = ints_from_limbs(flat_hi, flat_lo, flat_vv)
            vals = [None if x is None else _int_to_decimal(x, field.scale)
                    for x in ints]
            child = pa.array(vals, pa.decimal128(
                field.precision or _LIST_DECIMAL_FALLBACK_PRECISION,
                field.scale))
            off_arr = _list_offsets(lens, validity, n)
            return pa.ListArray.from_arrays(off_arr, child)
        if field.dtype == DataType.LIST:
            # entry list: same carrier, rendered as list<struct<K,V>>
            kf, vf = field.children
            karr = pa.array(hc.keys[take],
                            pa.from_numpy_dtype(kf.dtype.to_np()))
            varr = pa.array(hc.values[take],
                            pa.from_numpy_dtype(vf.dtype.to_np()))
            flat_vv = hc.val_valid[take]
            if not flat_vv.all():
                varr = _with_nulls(varr, flat_vv)
            entries = pa.StructArray.from_arrays(
                [karr, varr], names=[kf.name, vf.name])
            off_arr = _list_offsets(lens, validity, n)
            return pa.ListArray.from_arrays(off_arr, entries)
        karr = pa.array(hc.keys[take],
                        pa.from_numpy_dtype(field.key.to_np()))
        varr = pa.array(hc.values[take],
                        pa.from_numpy_dtype(field.elem.to_np()))
        flat_vv = hc.val_valid[take]
        if not flat_vv.all():
            varr = _with_nulls(varr, flat_vv)
        off_arr = _list_offsets(lens, validity, n)
        return pa.MapArray.from_arrays(off_arr, karr, varr)
    if isinstance(hc, HostStruct):
        kids = [_host_col_to_arrow(cf, ch, n)
                for cf, ch in zip(field.children, hc.children)]
        mask = None if hc.validity.all() \
            else pa.array(~hc.validity, pa.bool_())
        arr = pa.StructArray.from_arrays(
            kids, names=[cf.name for cf in field.children], mask=mask)
        return arr.cast(schema_to_arrow(Schema((field,)))[0].type)
    if isinstance(hc, HostString):
        validity = hc.validity
        lens = np.where(validity, hc.lens.astype(np.int64), 0)
        offsets = np.zeros(n + 1, np.int32)
        np.cumsum(lens, out=offsets[1:])
        take = np.arange(hc.chars.shape[1])[None, :] < lens[:, None]
        flat = hc.chars[take].astype(np.uint8)
        return pa.StringArray.from_buffers(
            n, pa.py_buffer(offsets.tobytes()),
            pa.py_buffer(flat.tobytes()),
            pa.py_buffer(np.packbits(validity,
                                     bitorder="little").tobytes()),
            int((~validity).sum()))
    if isinstance(hc, HostDecimal128):
        from auron_tpu.columnar.decimal128 import ints_from_limbs
        ints = ints_from_limbs(hc.hi, hc.lo, hc.validity)
        vals = [None if x is None else _int_to_decimal(x, field.scale)
                for x in ints]
        return pa.array(vals,
                        type=pa.decimal128(field.precision, field.scale))
    # primitives
    data, validity = hc.data, hc.validity
    if field.dtype == DataType.NULL:
        return pa.nulls(n)
    if field.dtype == DataType.DECIMAL:
        vals = [None if not v else _int_to_decimal(int(x), field.scale)
                for x, v in zip(data, validity)]
        return pa.array(vals,
                        type=pa.decimal128(field.precision, field.scale))
    if field.dtype == DataType.DATE32:
        a = pa.array(np.where(validity, data, 0), pa.int32()).cast(pa.date32())
        return a if validity.all() else _with_nulls(a, validity)
    if field.dtype == DataType.TIMESTAMP_US:
        a = pa.array(np.where(validity, data, 0),
                     pa.int64()).cast(pa.timestamp("us"))
        return a if validity.all() else _with_nulls(a, validity)
    a = pa.array(data)
    return a if validity.all() else _with_nulls(a, validity)


def _with_nulls(arr: pa.Array, validity: np.ndarray) -> pa.Array:
    return pa.array(
        [v if ok else None for v, ok in zip(arr.to_pylist(), validity)],
        type=arr.type)


def _int_to_decimal(unscaled: int, scale: int):
    import decimal
    with decimal.localcontext() as ctx:
        ctx.prec = 60   # default prec=28 rounds away 29-38 digit values
        return decimal.Decimal(unscaled).scaleb(-scale)
