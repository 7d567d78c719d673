"""Device-resident columnar batches.

The TPU analogue of an Arrow RecordBatch (which is what flows between the
reference's operators, reference: native-engine/auron/src/rt.rs:149-205):

- every column is padded to a static ``capacity`` so kernels compile once per
  shape bucket; the true row count is a device scalar (``num_rows``),
- validity is a dense bool mask (Arrow's validity bitmap, unpacked — TPU has
  no cheap bit addressing and the VPU is happiest on bool/int8 lanes),
- strings are fixed-width byte matrices ``[capacity, width]`` plus a length
  column. Variable-length offsets+bytes (Arrow's native layout) are hostile
  to a static-shape compiler; padded widths are bucketed (8..256) so the
  overwhelmingly short SQL strings stay cheap and every string kernel
  (compare / hash / substr) is a dense vector op.

Batches are pytrees, so they pass straight through jit / shard_map / scan.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, replace

from auron_tpu.columnar.decimal128 import Decimal128Column
from typing import Optional, Sequence, Union

import numpy as np

import jax
import jax.numpy as jnp

from auron_tpu.runtime.programs import program_cache


@jax.tree_util.register_dataclass
@dataclass(frozen=True)
class PrimitiveColumn:
    """Fixed-width column: data[capacity] + validity[capacity]."""

    data: jax.Array
    validity: jax.Array  # bool[capacity]

    @property
    def capacity(self) -> int:
        return self.data.shape[0]

    def with_validity(self, validity: jax.Array) -> "PrimitiveColumn":
        return replace(self, validity=validity)


@jax.tree_util.register_dataclass
@dataclass(frozen=True)
class StringColumn:
    """Fixed-width string column: chars[capacity, width] (zero padded),
    lens[capacity], validity[capacity]."""

    chars: jax.Array  # uint8[capacity, width]
    lens: jax.Array   # int32[capacity]
    validity: jax.Array  # bool[capacity]

    @property
    def capacity(self) -> int:
        return self.chars.shape[0]

    @property
    def width(self) -> int:
        return self.chars.shape[1]

    def with_validity(self, validity: jax.Array) -> "StringColumn":
        return replace(self, validity=validity)


@jax.tree_util.register_dataclass
@dataclass(frozen=True)
class ListColumn:
    """Padded list-of-primitive column: values[capacity, max_elems] +
    per-element validity + list lengths + row validity.

    The device layout for Arrow list arrays (the reference's explode /
    UserDefinedArray paths, reference: datafusion-ext-plans/src/generate/,
    datafusion-ext-commons/src/uda.rs): offsets+child become a dense padded
    matrix so explode is one mask+compact kernel."""

    values: jax.Array      # [capacity, max_elems] primitive payload
    elem_valid: jax.Array  # bool[capacity, max_elems]
    lens: jax.Array        # int32[capacity]
    validity: jax.Array    # bool[capacity]  (row null = whole list null)

    @property
    def capacity(self) -> int:
        return self.values.shape[0]

    @property
    def max_elems(self) -> int:
        return self.values.shape[1]

    def with_validity(self, validity: jax.Array) -> "ListColumn":
        return replace(self, validity=validity)


@jax.tree_util.register_dataclass
@dataclass(frozen=True)
class StringListColumn:
    """Padded list-of-STRING column: a [capacity, max_elems, width] char
    tensor + per-element byte lengths. The string analogue of ListColumn
    (reference: Arrow ListArray over a StringArray child — offsets over
    offsets; here both levels become dense padded matrices so explode /
    element_at are one gather)."""

    chars: jax.Array       # uint8[capacity, max_elems, width]
    slens: jax.Array       # int32[capacity, max_elems] per-element bytes
    elem_valid: jax.Array  # bool[capacity, max_elems]
    lens: jax.Array        # int32[capacity]
    validity: jax.Array    # bool[capacity]  (row null = whole list null)

    @property
    def capacity(self) -> int:
        return self.chars.shape[0]

    @property
    def max_elems(self) -> int:
        return self.chars.shape[1]

    @property
    def width(self) -> int:
        return self.chars.shape[2]

    def with_validity(self, validity: jax.Array) -> "StringListColumn":
        return replace(self, validity=validity)


@jax.tree_util.register_dataclass
@dataclass(frozen=True)
class StringMapColumn:
    """Padded map<string,string> column: parallel key/value CHAR tensors
    sharing one length column (reference: spark_map.rs str_to_map builds
    Arrow MapArray over utf8 children). Spark map keys cannot be null,
    so keys carry no element validity; values can be null per entry."""

    kchars: jax.Array      # uint8[capacity, max_elems, kwidth]
    kslens: jax.Array      # int32[capacity, max_elems]
    vchars: jax.Array      # uint8[capacity, max_elems, vwidth]
    vslens: jax.Array      # int32[capacity, max_elems]
    val_valid: jax.Array   # bool[capacity, max_elems]
    lens: jax.Array        # int32[capacity]
    validity: jax.Array    # bool[capacity]

    @property
    def capacity(self) -> int:
        return self.kchars.shape[0]

    @property
    def max_elems(self) -> int:
        return self.kchars.shape[1]

    def with_validity(self, validity: jax.Array) -> "StringMapColumn":
        return replace(self, validity=validity)


@jax.tree_util.register_dataclass
@dataclass(frozen=True)
class MapColumn:
    """Padded map column: parallel key/value matrices sharing one length
    column (reference stores these as Arrow MapArray — offsets over a
    struct<key,value> child, datafusion-ext-functions/src/spark_map.rs;
    here the offsets+child become dense padded matrices like ListColumn).
    Keys and values are primitive payloads; Spark map keys cannot be null
    so keys carry no element validity."""

    keys: jax.Array        # [capacity, max_elems] primitive key payload
    values: jax.Array      # [capacity, max_elems] primitive value payload
    val_valid: jax.Array   # bool[capacity, max_elems]
    lens: jax.Array        # int32[capacity]  entry count per row
    validity: jax.Array    # bool[capacity]   (row null = whole map null)

    @property
    def capacity(self) -> int:
        return self.keys.shape[0]

    @property
    def max_elems(self) -> int:
        return self.keys.shape[1]

    def with_validity(self, validity: jax.Array) -> "MapColumn":
        return replace(self, validity=validity)


@jax.tree_util.register_dataclass
@dataclass(frozen=True)
class StructColumn:
    """Struct column: per-field child columns + row validity (Arrow
    StructArray, reference: datafusion-ext-exprs/src/named_struct.rs /
    get_indexed_field.rs). Field names/types live in the schema's Field
    children, never on the device."""

    children: tuple        # tuple[Column, ...] (no nested struct/map yet)
    validity: jax.Array    # bool[capacity]

    @property
    def capacity(self) -> int:
        return self.validity.shape[0]

    def with_validity(self, validity: jax.Array) -> "StructColumn":
        return replace(self, validity=validity)


Column = Union[PrimitiveColumn, StringColumn, ListColumn,
               StringListColumn, Decimal128Column, MapColumn,
               StringMapColumn, StructColumn]


@jax.tree_util.register_dataclass
@dataclass(frozen=True)
class DeviceBatch:
    """A bundle of equal-capacity columns plus the dynamic row count."""

    columns: tuple[Column, ...]
    num_rows: jax.Array  # int32 scalar, <= capacity

    @property
    def capacity(self) -> int:
        if not self.columns:
            return 0
        return self.columns[0].capacity

    @property
    def num_columns(self) -> int:
        return len(self.columns)

    def row_mask(self) -> jax.Array:
        """bool[capacity]: True for live rows."""
        return jnp.arange(self.capacity, dtype=jnp.int32) < self.num_rows

    def column(self, i: int) -> Column:
        return self.columns[i]

    def with_columns(self, columns) -> "DeviceBatch":
        return DeviceBatch(tuple(columns), self.num_rows)

    def select(self, indices) -> "DeviceBatch":
        return DeviceBatch(tuple(self.columns[i] for i in indices), self.num_rows)


def column_nbytes(col: Column) -> int:
    """Device bytes held by one column (at capacity, incl. padding)."""
    if isinstance(col, StringColumn):
        return col.chars.nbytes + col.lens.nbytes + col.validity.nbytes
    if isinstance(col, ListColumn):
        return (col.values.nbytes + col.elem_valid.nbytes
                + col.lens.nbytes + col.validity.nbytes)
    if isinstance(col, StringListColumn):
        return (col.chars.nbytes + col.slens.nbytes
                + col.elem_valid.nbytes + col.lens.nbytes
                + col.validity.nbytes)
    if isinstance(col, Decimal128Column):
        return col.hi.nbytes + col.lo.nbytes + col.validity.nbytes
    if isinstance(col, MapColumn):
        return (col.keys.nbytes + col.values.nbytes + col.val_valid.nbytes
                + col.lens.nbytes + col.validity.nbytes)
    if isinstance(col, StringMapColumn):
        return (col.kchars.nbytes + col.kslens.nbytes + col.vchars.nbytes
                + col.vslens.nbytes + col.val_valid.nbytes
                + col.lens.nbytes + col.validity.nbytes)
    if isinstance(col, StructColumn):
        return (sum(column_nbytes(c) for c in col.children)
                + col.validity.nbytes)
    return col.data.nbytes + col.validity.nbytes


def batch_nbytes(batch: DeviceBatch) -> int:
    """Device bytes held by the batch (at capacity, incl. padding)."""
    return sum(column_nbytes(c) for c in batch.columns)


def mask_validity(batch: DeviceBatch) -> DeviceBatch:
    """Force validity False on padding rows (defensive normalization)."""
    mask = batch.row_mask()
    return batch.with_columns(
        c.with_validity(c.validity & mask) for c in batch.columns
    )


def gather_column(col: Column, indices: jax.Array, valid: jax.Array) -> Column:
    """Take rows ``indices`` from ``col``; rows where ``valid`` is False become
    null. Core primitive behind filter compaction, sort reordering and join
    probing (the reference does the same with Arrow take kernels, reference:
    native-engine/datafusion-ext-commons/src/arrow/selection.rs)."""
    if isinstance(col, StringColumn):
        return StringColumn(
            chars=col.chars[indices],
            lens=jnp.where(valid, col.lens[indices], 0),
            validity=col.validity[indices] & valid,
        )
    if isinstance(col, ListColumn):
        return ListColumn(
            values=col.values[indices],
            elem_valid=col.elem_valid[indices] & valid[:, None],
            lens=jnp.where(valid, col.lens[indices], 0),
            validity=col.validity[indices] & valid,
        )
    if isinstance(col, StringListColumn):
        return StringListColumn(
            chars=col.chars[indices],
            slens=col.slens[indices],
            elem_valid=col.elem_valid[indices] & valid[:, None],
            lens=jnp.where(valid, col.lens[indices], 0),
            validity=col.validity[indices] & valid,
        )
    if isinstance(col, Decimal128Column):
        return Decimal128Column(
            hi=col.hi[indices], lo=col.lo[indices],
            validity=col.validity[indices] & valid,
        )
    if isinstance(col, MapColumn):
        return MapColumn(
            keys=col.keys[indices],
            values=col.values[indices],
            val_valid=col.val_valid[indices] & valid[:, None],
            lens=jnp.where(valid, col.lens[indices], 0),
            validity=col.validity[indices] & valid,
        )
    if isinstance(col, StringMapColumn):
        return StringMapColumn(
            kchars=col.kchars[indices], kslens=col.kslens[indices],
            vchars=col.vchars[indices], vslens=col.vslens[indices],
            val_valid=col.val_valid[indices] & valid[:, None],
            lens=jnp.where(valid, col.lens[indices], 0),
            validity=col.validity[indices] & valid,
        )
    if isinstance(col, StructColumn):
        return StructColumn(
            children=tuple(gather_column(c, indices, valid)
                           for c in col.children),
            validity=col.validity[indices] & valid,
        )
    return PrimitiveColumn(
        data=col.data[indices],
        validity=col.validity[indices] & valid,
    )


def gather_batch(batch: DeviceBatch, indices: jax.Array, num_rows: jax.Array) -> DeviceBatch:
    """Take ``indices`` (shape [new_capacity]) from every column; entries with
    position >= num_rows are padding."""
    new_cap = indices.shape[0]
    valid = jnp.arange(new_cap, dtype=jnp.int32) < num_rows
    return DeviceBatch(
        tuple(gather_column(c, indices, valid) for c in batch.columns),
        jnp.asarray(num_rows, jnp.int32),
    )


def pad_string_width(col: StringColumn, width: int) -> StringColumn:
    """Zero-pad a string column's char matrix out to `width` bytes/slot."""
    if col.width >= width:
        return col
    return StringColumn(
        jnp.pad(col.chars, ((0, 0), (0, width - col.width))),
        col.lens, col.validity)


def pad_list_elems(col: ListColumn, max_elems: int) -> ListColumn:
    """Pad a list column's element axis out to `max_elems` slots."""
    if col.max_elems >= max_elems:
        return col
    pad = max_elems - col.max_elems
    return ListColumn(
        jnp.pad(col.values, ((0, 0), (0, pad))),
        jnp.pad(col.elem_valid, ((0, 0), (0, pad))),
        col.lens, col.validity)


def pad_map_elems(col: "MapColumn", max_elems: int) -> "MapColumn":
    """Pad a map column's entry axis out to `max_elems` slots."""
    if col.max_elems >= max_elems:
        return col
    pad = max_elems - col.max_elems
    return MapColumn(
        jnp.pad(col.keys, ((0, 0), (0, pad))),
        jnp.pad(col.values, ((0, 0), (0, pad))),
        jnp.pad(col.val_valid, ((0, 0), (0, pad))),
        col.lens, col.validity)


def unify_column_widths(cols: Sequence[Column]) -> list[Column]:
    """Pad string widths / list element counts to the max across `cols` so
    they can be concatenated (capacities may differ; widths must not)."""
    if isinstance(cols[0], StringColumn):
        w = max(c.width for c in cols)
        return [pad_string_width(c, w) for c in cols]
    if isinstance(cols[0], ListColumn):
        m = max(c.max_elems for c in cols)
        return [pad_list_elems(c, m) for c in cols]
    if isinstance(cols[0], StringListColumn):
        m = max(c.max_elems for c in cols)
        w = max(c.width for c in cols)
        out = []
        for c in cols:
            pe, pw = m - c.max_elems, w - c.width
            out.append(StringListColumn(
                jnp.pad(c.chars, ((0, 0), (0, pe), (0, pw))),
                jnp.pad(c.slens, ((0, 0), (0, pe))),
                jnp.pad(c.elem_valid, ((0, 0), (0, pe))),
                c.lens, c.validity))
        return out
    if isinstance(cols[0], MapColumn):
        m = max(c.max_elems for c in cols)
        return [pad_map_elems(c, m) for c in cols]
    if isinstance(cols[0], StringMapColumn):
        m = max(c.max_elems for c in cols)
        kw = max(c.kchars.shape[2] for c in cols)
        vw = max(c.vchars.shape[2] for c in cols)
        out = []
        for c in cols:
            pe = m - c.max_elems
            out.append(StringMapColumn(
                jnp.pad(c.kchars, ((0, 0), (0, pe),
                                   (0, kw - c.kchars.shape[2]))),
                jnp.pad(c.kslens, ((0, 0), (0, pe))),
                jnp.pad(c.vchars, ((0, 0), (0, pe),
                                   (0, vw - c.vchars.shape[2]))),
                jnp.pad(c.vslens, ((0, 0), (0, pe))),
                jnp.pad(c.val_valid, ((0, 0), (0, pe))),
                c.lens, c.validity))
        return out
    if isinstance(cols[0], StructColumn):
        per_child = [unify_column_widths([c.children[i] for c in cols])
                     for i in range(len(cols[0].children))]
        return [StructColumn(tuple(per_child[i][j]
                                   for i in range(len(per_child))),
                             c.validity)
                for j, c in enumerate(cols)]
    return list(cols)


def concat_columns(a: Column, b: Column) -> Column:
    """Stack two columns (capacities add). String widths / list elem counts
    must match — callers re-bucket beforehand."""
    if isinstance(a, StringColumn):
        assert isinstance(b, StringColumn) and a.width == b.width
        return StringColumn(
            chars=jnp.concatenate([a.chars, b.chars], axis=0),
            lens=jnp.concatenate([a.lens, b.lens]),
            validity=jnp.concatenate([a.validity, b.validity]),
        )
    if isinstance(a, ListColumn):
        assert isinstance(b, ListColumn) and a.max_elems == b.max_elems
        return ListColumn(
            values=jnp.concatenate([a.values, b.values], axis=0),
            elem_valid=jnp.concatenate([a.elem_valid, b.elem_valid], axis=0),
            lens=jnp.concatenate([a.lens, b.lens]),
            validity=jnp.concatenate([a.validity, b.validity]),
        )
    if isinstance(a, StringListColumn):
        assert isinstance(b, StringListColumn) \
            and a.max_elems == b.max_elems and a.width == b.width
        return StringListColumn(
            chars=jnp.concatenate([a.chars, b.chars], axis=0),
            slens=jnp.concatenate([a.slens, b.slens], axis=0),
            elem_valid=jnp.concatenate([a.elem_valid, b.elem_valid], axis=0),
            lens=jnp.concatenate([a.lens, b.lens]),
            validity=jnp.concatenate([a.validity, b.validity]),
        )
    if isinstance(a, Decimal128Column):
        assert isinstance(b, Decimal128Column)
        return Decimal128Column(
            hi=jnp.concatenate([a.hi, b.hi]),
            lo=jnp.concatenate([a.lo, b.lo]),
            validity=jnp.concatenate([a.validity, b.validity]),
        )
    if isinstance(a, MapColumn):
        assert isinstance(b, MapColumn) and a.max_elems == b.max_elems
        return MapColumn(
            keys=jnp.concatenate([a.keys, b.keys], axis=0),
            values=jnp.concatenate([a.values, b.values], axis=0),
            val_valid=jnp.concatenate([a.val_valid, b.val_valid], axis=0),
            lens=jnp.concatenate([a.lens, b.lens]),
            validity=jnp.concatenate([a.validity, b.validity]),
        )
    if isinstance(a, StringMapColumn):
        assert isinstance(b, StringMapColumn) \
            and a.max_elems == b.max_elems \
            and a.kchars.shape[2] == b.kchars.shape[2] \
            and a.vchars.shape[2] == b.vchars.shape[2]
        return StringMapColumn(
            kchars=jnp.concatenate([a.kchars, b.kchars], axis=0),
            kslens=jnp.concatenate([a.kslens, b.kslens], axis=0),
            vchars=jnp.concatenate([a.vchars, b.vchars], axis=0),
            vslens=jnp.concatenate([a.vslens, b.vslens], axis=0),
            val_valid=jnp.concatenate([a.val_valid, b.val_valid], axis=0),
            lens=jnp.concatenate([a.lens, b.lens]),
            validity=jnp.concatenate([a.validity, b.validity]),
        )
    if isinstance(a, StructColumn):
        assert isinstance(b, StructColumn)
        return StructColumn(
            children=tuple(concat_columns(ca, cb)
                           for ca, cb in zip(a.children, b.children)),
            validity=jnp.concatenate([a.validity, b.validity]),
        )
    assert isinstance(b, PrimitiveColumn)
    return PrimitiveColumn(
        data=jnp.concatenate([a.data, b.data]),
        validity=jnp.concatenate([a.validity, b.validity]),
    )


def concat_live_rows(batches: Sequence[DeviceBatch],
                     capacity: int) -> DeviceBatch:
    """The live rows of ``batches`` — a prefix of each — one batch after
    another, as ONE batch of ``capacity`` rows, the rest padding (traced;
    for a program's body). Every leaf is stacked (string widths and list
    element counts unified first) and output row j is row (j - live rows
    before its batch) of the batch it falls in: no sort, one gather. The
    row counts are operands and their sum is computed here."""
    cols = tuple(
        functools.reduce(concat_columns, unify_column_widths(
            [b.columns[i] for b in batches]))
        for i in range(batches[0].num_columns))
    counts = jnp.stack([jnp.asarray(b.num_rows, jnp.int32)
                        for b in batches])
    ends = jnp.cumsum(counts)
    rows = jnp.arange(capacity, dtype=jnp.int32)
    k = jnp.minimum(
        jnp.sum(rows[:, None] >= ends[None, :], axis=1, dtype=jnp.int32),
        len(batches) - 1)
    capacities = [b.capacity for b in batches]
    starts = jnp.asarray(np.cumsum([0] + capacities[:-1]), jnp.int32)
    src = starts[k] + rows - (ends - counts)[k]
    return gather_batch(DeviceBatch(cols, ends[-1]),
                        jnp.clip(src, 0, sum(capacities) - 1), ends[-1])


def compact(batch: DeviceBatch, keep: jax.Array) -> DeviceBatch:
    """Stable-compact live rows where ``keep`` is True to the front.

    ``keep`` is bool[capacity]; padding rows must already be False. The
    output batch has the same capacity with num_rows = sum(keep). This is the
    device analogue of Arrow's filter kernel used by FilterExec (reference:
    native-engine/datafusion-ext-plans/src/filter_exec.rs).
    """
    keep = keep & batch.row_mask()
    cap = batch.capacity
    n_keep = jnp.sum(keep.astype(jnp.int32))
    # Stable partition: keys = position for kept rows, capacity+position for
    # dropped ones; argsort is ascending and stable on ties.
    order_keys = jnp.where(keep, 0, cap) + jnp.arange(cap, dtype=jnp.int32)
    indices = jnp.argsort(order_keys)
    return gather_batch(batch, indices, n_keep)


def resize(batch: DeviceBatch, new_capacity: int) -> DeviceBatch:
    """Grow or shrink capacity (shrink drops padding only if num_rows fits —
    caller's responsibility)."""
    cap = batch.capacity
    if new_capacity == cap:
        return batch

    def resize_col(c: Column) -> Column:
        if isinstance(c, StructColumn):
            return StructColumn(
                children=tuple(resize_col(ch) for ch in c.children),
                validity=(jnp.pad(c.validity, (0, new_capacity - cap))
                          if new_capacity > cap
                          else c.validity[:new_capacity]))
        if isinstance(c, MapColumn):
            if new_capacity > cap:
                pad = new_capacity - cap
                return MapColumn(
                    keys=jnp.pad(c.keys, ((0, pad), (0, 0))),
                    values=jnp.pad(c.values, ((0, pad), (0, 0))),
                    val_valid=jnp.pad(c.val_valid, ((0, pad), (0, 0))),
                    lens=jnp.pad(c.lens, (0, pad)),
                    validity=jnp.pad(c.validity, (0, pad)))
            return MapColumn(
                keys=c.keys[:new_capacity], values=c.values[:new_capacity],
                val_valid=c.val_valid[:new_capacity],
                lens=c.lens[:new_capacity],
                validity=c.validity[:new_capacity])
        if new_capacity > cap:
            pad = new_capacity - cap
            if isinstance(c, StringColumn):
                return StringColumn(
                    chars=jnp.pad(c.chars, ((0, pad), (0, 0))),
                    lens=jnp.pad(c.lens, (0, pad)),
                    validity=jnp.pad(c.validity, (0, pad)),
                )
            if isinstance(c, ListColumn):
                return ListColumn(
                    values=jnp.pad(c.values, ((0, pad), (0, 0))),
                    elem_valid=jnp.pad(c.elem_valid, ((0, pad), (0, 0))),
                    lens=jnp.pad(c.lens, (0, pad)),
                    validity=jnp.pad(c.validity, (0, pad)),
                )
            if isinstance(c, StringListColumn):
                return StringListColumn(
                    chars=jnp.pad(c.chars, ((0, pad), (0, 0), (0, 0))),
                    slens=jnp.pad(c.slens, ((0, pad), (0, 0))),
                    elem_valid=jnp.pad(c.elem_valid, ((0, pad), (0, 0))),
                    lens=jnp.pad(c.lens, (0, pad)),
                    validity=jnp.pad(c.validity, (0, pad)),
                )
            if isinstance(c, StringMapColumn):
                return StringMapColumn(
                    kchars=jnp.pad(c.kchars, ((0, pad), (0, 0), (0, 0))),
                    kslens=jnp.pad(c.kslens, ((0, pad), (0, 0))),
                    vchars=jnp.pad(c.vchars, ((0, pad), (0, 0), (0, 0))),
                    vslens=jnp.pad(c.vslens, ((0, pad), (0, 0))),
                    val_valid=jnp.pad(c.val_valid, ((0, pad), (0, 0))),
                    lens=jnp.pad(c.lens, (0, pad)),
                    validity=jnp.pad(c.validity, (0, pad)),
                )
            if isinstance(c, Decimal128Column):
                return Decimal128Column(
                    hi=jnp.pad(c.hi, (0, pad)),
                    lo=jnp.pad(c.lo, (0, pad)),
                    validity=jnp.pad(c.validity, (0, pad)),
                )
            return PrimitiveColumn(
                data=jnp.pad(c.data, (0, pad)),
                validity=jnp.pad(c.validity, (0, pad)),
            )
        if isinstance(c, StringColumn):
            return StringColumn(
                chars=c.chars[:new_capacity],
                lens=c.lens[:new_capacity],
                validity=c.validity[:new_capacity],
            )
        if isinstance(c, ListColumn):
            return ListColumn(
                values=c.values[:new_capacity],
                elem_valid=c.elem_valid[:new_capacity],
                lens=c.lens[:new_capacity],
                validity=c.validity[:new_capacity],
            )
        if isinstance(c, StringListColumn):
            return StringListColumn(
                chars=c.chars[:new_capacity], slens=c.slens[:new_capacity],
                elem_valid=c.elem_valid[:new_capacity],
                lens=c.lens[:new_capacity],
                validity=c.validity[:new_capacity])
        if isinstance(c, StringMapColumn):
            return StringMapColumn(
                kchars=c.kchars[:new_capacity],
                kslens=c.kslens[:new_capacity],
                vchars=c.vchars[:new_capacity],
                vslens=c.vslens[:new_capacity],
                val_valid=c.val_valid[:new_capacity],
                lens=c.lens[:new_capacity],
                validity=c.validity[:new_capacity])
        if isinstance(c, Decimal128Column):
            return Decimal128Column(hi=c.hi[:new_capacity],
                                    lo=c.lo[:new_capacity],
                                    validity=c.validity[:new_capacity])
        return PrimitiveColumn(data=c.data[:new_capacity], validity=c.validity[:new_capacity])

    return DeviceBatch(tuple(resize_col(c) for c in batch.columns), batch.num_rows)


#: the shrink rule's two constants (``shrink_target``): the rungs a
#: capacity may be cut to, as divisors of it — functions of the capacity
#: alone, never of a row count's power of two, so a stream whose counts
#: wander compiles for at most three shapes a capacity — and the least
#: lanes a cut must save: a launch is ~0.5 ms of the host and a lane of
#: a keyed update ~0.25-0.5 us of the chip, so under a few thousand
#: lanes saved the launch costs more than it saves (PERF.md section 6,
#: PR 45)
SHRINK_RUNGS = (64, 8)
SHRINK_MIN_LANES = 16_384


def shrink_target(capacity: int, n: int) -> Optional[int]:
    """The capacity a batch of ``capacity`` slots and ``n`` live rows is
    handed on at, or None where it keeps its own: the smaller of
    ``capacity // 64`` and ``capacity // 8`` that holds ``n``, where
    that saves ``SHRINK_MIN_LANES``. 65,536 -> 1,024 or 8,192; 32,768
    -> 512 or 4,096; nothing at or under 16,384."""
    for div in SHRINK_RUNGS:
        target = capacity // div
        if n <= target and capacity - target >= SHRINK_MIN_LANES:
            return target
    return None


def leaf_layout(columns) -> tuple:
    """A column tree's kinds, widths and dtypes without its capacity:
    hashable, the part of a program's key that stands for the schema
    where the program reads no field of one."""
    leaves, treedef = jax.tree_util.tree_flatten(columns)
    return treedef, tuple((leaf.shape[1:], leaf.dtype) for leaf in leaves)


@program_cache("columnar.batch.shrink", maxsize=256)
def _shrink_kernel(layout: tuple, capacity: int, target: int):
    """One program a (leaf layout, capacity, target): the prefix
    ``[:target]`` of every leaf, whatever the column kinds — every leaf
    of every kind leads with the capacity."""

    @jax.jit
    def auron_columnar_batch_shrink(batch: DeviceBatch):
        cols = jax.tree_util.tree_map(lambda leaf: leaf[:target],
                                      batch.columns)
        return DeviceBatch(cols, jnp.asarray(batch.num_rows, jnp.int32))

    return auron_columnar_batch_shrink


def shrink(batch: DeviceBatch, n: int) -> DeviceBatch:
    """``batch`` at the capacity ``shrink_target`` gives its ``n`` live
    rows (the caller has read them: ``ops/base.count_output``), or
    ``batch`` itself where the rule keeps it. The live rows of a batch
    are a prefix of it, so a prefix is the whole answer: ONE launch of
    ``columnar.batch.shrink``, no readback, ``num_rows`` handed through
    on the device. (``resize`` called eagerly would be one launch a
    leaf.)"""
    target = shrink_target(batch.capacity, n)
    if target is None:
        return batch
    return _shrink_kernel(leaf_layout(batch.columns), batch.capacity,
                          target)(batch)


def concat_batches(a: DeviceBatch, b: DeviceBatch) -> DeviceBatch:
    """Concatenate b's live rows after a's live rows.

    Implemented as stacked-capacity concat + compaction of live rows, keeping
    everything static-shape: result capacity = a.capacity + b.capacity.
    """
    stacked = DeviceBatch(
        tuple(concat_columns(ca, cb) for ca, cb in zip(a.columns, b.columns)),
        a.num_rows + b.num_rows,
    )
    keep = jnp.concatenate([a.row_mask(), b.row_mask()])
    return compact(replace(stacked, num_rows=jnp.asarray(a.capacity + b.capacity, jnp.int32)), keep)
