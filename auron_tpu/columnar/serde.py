"""Batch ↔ bytes serde for spill and shuffle payloads.

The analogue of the reference's length-prefixed columnar wire format +
block-compressed IPC framing (reference:
datafusion-ext-commons/src/io/batch_serde.rs:68-149,
io/ipc_compression.rs:35-280). Layout per frame:

    magic 'ATB1' | u8 codec | u32 body_len | body (maybe compressed)

body:
    u32 num_rows | u16 num_cols | u16 num_extras
    per column:   u8 kind (0 prim / 1 string) | dtype tag | buffers
    per extra:    name | uint64 word matrix   (sort-key words for merge)

Buffers are raw little-endian numpy bytes, each u32-length-prefixed. Only
live rows travel — capacity padding is re-applied on load. Compression is
zstd level 1 (the codec baked into this image; the reference defaults to
lz4 with zstd as option, conf.rs SPILL_COMPRESSION_CODEC).
"""

from __future__ import annotations

import io
import struct
from dataclasses import dataclass
from typing import Optional, Union

import numpy as np

try:
    import zstandard
except ImportError:    # gated dependency: fall back to CODEC_NONE frames
    zstandard = None

from auron_tpu.columnar.batch import (DeviceBatch, ListColumn,
                                      PrimitiveColumn, StringColumn)

MAGIC = b"ATB1"
CODEC_NONE = 0
CODEC_ZSTD = 1

# zstd contexts are not safe for concurrent use; spills may run from
# multiple threads, so keep one per thread
import threading

_tls = threading.local()


def _compressor(level: int = None):
    if level is None:
        from auron_tpu import config as cfg
        level = cfg.get_config().get(cfg.SPILL_CODEC_LEVEL)
    if getattr(_tls, "level", None) != level:
        _tls.c = zstandard.ZstdCompressor(level=level)
        _tls.level = level
    return _tls.c


def _decompressor():
    if zstandard is None:
        raise RuntimeError(
            "frame was written with the zstd codec but the zstandard "
            "module is not installed in this environment")
    if not hasattr(_tls, "d"):
        _tls.d = zstandard.ZstdDecompressor()
    return _tls.d


# ---------------------------------------------------------------------------
# host-side batch representation
# ---------------------------------------------------------------------------

@dataclass
class HostPrimitive:
    data: np.ndarray       # [n]
    validity: np.ndarray   # bool[n]


@dataclass
class HostString:
    chars: np.ndarray      # uint8[n, width]
    lens: np.ndarray       # int32[n]
    validity: np.ndarray   # bool[n]


@dataclass
class HostList:
    values: np.ndarray     # [n, max_elems]
    elem_valid: np.ndarray  # bool[n, max_elems]
    lens: np.ndarray       # int32[n]
    validity: np.ndarray   # bool[n]


@dataclass
class HostStringList:
    chars: np.ndarray       # uint8[n, max_elems, width]
    slens: np.ndarray       # int32[n, max_elems]
    elem_valid: np.ndarray  # bool[n, max_elems]
    lens: np.ndarray        # int32[n]
    validity: np.ndarray    # bool[n]


@dataclass
class HostDecimal128:
    hi: np.ndarray         # int64[n]
    lo: np.ndarray         # int64[n] (unsigned bit pattern)
    validity: np.ndarray   # bool[n]


@dataclass
class HostMap:
    keys: np.ndarray       # [n, max_elems]
    values: np.ndarray     # [n, max_elems]
    val_valid: np.ndarray  # bool[n, max_elems]
    lens: np.ndarray       # int32[n]
    validity: np.ndarray   # bool[n]


@dataclass
class HostStringMap:
    kchars: np.ndarray      # uint8[n, max_elems, kw]
    kslens: np.ndarray      # int32[n, max_elems]
    vchars: np.ndarray      # uint8[n, max_elems, vw]
    vslens: np.ndarray      # int32[n, max_elems]
    val_valid: np.ndarray   # bool[n, max_elems]
    lens: np.ndarray        # int32[n]
    validity: np.ndarray    # bool[n]


@dataclass
class HostStruct:
    children: list         # list[HostColumn]
    validity: np.ndarray   # bool[n]


HostColumn = Union[HostPrimitive, HostString, HostList, HostStringList,
                   HostDecimal128, HostMap, HostStringMap, HostStruct]


def _host_col_nbytes(c: HostColumn) -> int:
    if isinstance(c, HostString):
        return c.chars.nbytes + c.lens.nbytes + c.validity.nbytes
    if isinstance(c, HostList):
        return (c.values.nbytes + c.elem_valid.nbytes
                + c.lens.nbytes + c.validity.nbytes)
    if isinstance(c, HostStringList):
        return (c.chars.nbytes + c.slens.nbytes + c.elem_valid.nbytes
                + c.lens.nbytes + c.validity.nbytes)
    if isinstance(c, HostDecimal128):
        return c.hi.nbytes + c.lo.nbytes + c.validity.nbytes
    if isinstance(c, HostMap):
        return (c.keys.nbytes + c.values.nbytes + c.val_valid.nbytes
                + c.lens.nbytes + c.validity.nbytes)
    if isinstance(c, HostStringMap):
        return (c.kchars.nbytes + c.kslens.nbytes + c.vchars.nbytes
                + c.vslens.nbytes + c.val_valid.nbytes + c.lens.nbytes
                + c.validity.nbytes)
    if isinstance(c, HostStruct):
        return sum(_host_col_nbytes(ch) for ch in c.children) \
            + c.validity.nbytes
    return c.data.nbytes + c.validity.nbytes


@dataclass
class HostBatch:
    columns: list
    num_rows: int

    @property
    def nbytes(self) -> int:
        return sum(_host_col_nbytes(c) for c in self.columns)


def _slice_host_col(c: HostColumn, lo: int, hi: int) -> HostColumn:
    if isinstance(c, HostString):
        return HostString(c.chars[lo:hi], c.lens[lo:hi], c.validity[lo:hi])
    if isinstance(c, HostList):
        return HostList(c.values[lo:hi], c.elem_valid[lo:hi],
                        c.lens[lo:hi], c.validity[lo:hi])
    if isinstance(c, HostStringList):
        return HostStringList(c.chars[lo:hi], c.slens[lo:hi],
                              c.elem_valid[lo:hi], c.lens[lo:hi],
                              c.validity[lo:hi])
    if isinstance(c, HostDecimal128):
        return HostDecimal128(c.hi[lo:hi], c.lo[lo:hi], c.validity[lo:hi])
    if isinstance(c, HostMap):
        return HostMap(c.keys[lo:hi], c.values[lo:hi], c.val_valid[lo:hi],
                       c.lens[lo:hi], c.validity[lo:hi])
    if isinstance(c, HostStringMap):
        return HostStringMap(c.kchars[lo:hi], c.kslens[lo:hi],
                             c.vchars[lo:hi], c.vslens[lo:hi],
                             c.val_valid[lo:hi], c.lens[lo:hi],
                             c.validity[lo:hi])
    if isinstance(c, HostStruct):
        return HostStruct([_slice_host_col(ch, lo, hi) for ch in c.children],
                          c.validity[lo:hi])
    return HostPrimitive(c.data[lo:hi], c.validity[lo:hi])


def slice_host_batch(host: HostBatch, lo: int, hi: int) -> HostBatch:
    """Row-range view [lo, hi) over every column."""
    return HostBatch([_slice_host_col(c, lo, hi) for c in host.columns],
                     hi - lo)


def fetch_leaves(leaves: list) -> list[np.ndarray]:
    """Fetch many device arrays in ONE batched round trip.

    A blocking per-array fetch pays a fixed latency regardless of size,
    so `np.asarray` per buffer (10+ per batch) adds up; `jax.device_get`
    on the whole list issues the transfers together and awaits them
    once. It is one of the task's ``readbacks`` (obs/trace.py); the
    layer span around it is its caller's (``auron:convert/to_arrow``,
    an operator's timer)."""
    import jax

    from auron_tpu.obs import trace
    out = list(jax.device_get(list(leaves)))
    trace.count("readbacks")
    trace.count("d2h_bytes", sum(a.nbytes for a in out))
    return out


def host_col_from_device(c, it) -> HostColumn:
    """Rebuild one host column from a device column TEMPLATE plus an
    iterator over its fetched numpy leaves (depth-first dataclass field
    order — the jax pytree flattening order of the registered column
    dataclasses)."""
    from auron_tpu.columnar.batch import MapColumn, StructColumn
    from auron_tpu.columnar.decimal128 import Decimal128Column
    if isinstance(c, StringColumn):
        return HostString(next(it), next(it), next(it))
    if isinstance(c, ListColumn):
        return HostList(next(it), next(it), next(it), next(it))
    from auron_tpu.columnar.batch import StringListColumn
    if isinstance(c, StringListColumn):
        return HostStringList(next(it), next(it), next(it), next(it),
                              next(it))
    if isinstance(c, Decimal128Column):
        return HostDecimal128(next(it), next(it), next(it))
    if isinstance(c, MapColumn):
        return HostMap(next(it), next(it), next(it), next(it), next(it))
    from auron_tpu.columnar.batch import StringMapColumn
    if isinstance(c, StringMapColumn):
        return HostStringMap(next(it), next(it), next(it), next(it),
                             next(it), next(it), next(it))
    if isinstance(c, StructColumn):
        kids = [host_col_from_device(ch, it) for ch in c.children]
        return HostStruct(kids, next(it))
    return HostPrimitive(next(it), next(it))


def fetch_batch_numpy(batch: DeviceBatch) -> tuple[list[list[np.ndarray]], int]:
    """All column arrays of a batch (full capacity) + the row count, in a
    single device→host transfer. Returns (per-column leaf lists in pytree
    order — see host_col_from_device — and n)."""
    import jax
    per_col = [jax.tree_util.tree_leaves(c) for c in batch.columns]
    leaves = [a for arrs in per_col for a in arrs]
    import jax.numpy as jnp
    leaves.append(jnp.asarray(batch.num_rows, jnp.int32).reshape(1))
    fetched = fetch_leaves(leaves)
    n = int(fetched[-1][0])
    cols = []
    pos = 0
    for arrs in per_col:
        cols.append(fetched[pos:pos + len(arrs)])
        pos += len(arrs)
    return cols, n


def batch_to_host(batch: DeviceBatch,
                  num_rows: Optional[int] = None) -> HostBatch:
    """Device → host, keeping only live rows — ONE batched transfer for
    the whole batch (fetch_leaves). When the caller knows ``num_rows``
    (every spill path does), only the live row prefix is transferred —
    spills run exactly when memory is tight, so shipping capacity padding
    there would be self-defeating. Every column leaf is row-major with
    capacity on axis 0, so the prefix slice is uniform."""
    import jax
    if num_rows is not None:
        n = num_rows
        per_col = [[a[:n] for a in jax.tree_util.tree_leaves(c)]
                   for c in batch.columns]
        flat = fetch_leaves([a for arrs in per_col for a in arrs])
        fetched = []
        pos = 0
        for arrs in per_col:
            fetched.append(flat[pos:pos + len(arrs)])
            pos += len(arrs)
    else:
        fetched, n = fetch_batch_numpy(batch)
        fetched = [[a[:n] for a in arrs] for arrs in fetched]
    cols: list[HostColumn] = []
    for c, arrs in zip(batch.columns, fetched):
        cols.append(host_col_from_device(
            c, iter([np.ascontiguousarray(a) for a in arrs])))
    return HostBatch(cols, n)


def _host_col_to_device(c: HostColumn, pad: int):
    import jax.numpy as jnp
    from auron_tpu.columnar.batch import MapColumn, StructColumn

    def p1(a):
        return np.pad(a, (0, pad)) if pad else a

    def p2(a):
        return np.pad(a, ((0, pad), (0, 0))) if pad else a

    if isinstance(c, HostMap):
        return MapColumn(jnp.asarray(p2(c.keys)), jnp.asarray(p2(c.values)),
                         jnp.asarray(p2(c.val_valid)),
                         jnp.asarray(p1(c.lens)), jnp.asarray(p1(c.validity)))
    if isinstance(c, HostStringMap):
        from auron_tpu.columnar.batch import StringMapColumn

        def p3m(a):
            return np.pad(a, ((0, pad), (0, 0), (0, 0))) if pad else a

        return StringMapColumn(
            jnp.asarray(p3m(c.kchars)), jnp.asarray(p2(c.kslens)),
            jnp.asarray(p3m(c.vchars)), jnp.asarray(p2(c.vslens)),
            jnp.asarray(p2(c.val_valid)), jnp.asarray(p1(c.lens)),
            jnp.asarray(p1(c.validity)))
    if isinstance(c, HostStruct):
        return StructColumn(tuple(_host_col_to_device(ch, pad)
                                  for ch in c.children),
                            jnp.asarray(p1(c.validity)))
    if isinstance(c, HostString):
        return StringColumn(jnp.asarray(p2(c.chars)), jnp.asarray(p1(c.lens)),
                            jnp.asarray(p1(c.validity)))
    if isinstance(c, HostList):
        return ListColumn(jnp.asarray(p2(c.values)),
                          jnp.asarray(p2(c.elem_valid)),
                          jnp.asarray(p1(c.lens)), jnp.asarray(p1(c.validity)))
    if isinstance(c, HostStringList):
        from auron_tpu.columnar.batch import StringListColumn

        def p3(a):
            return np.pad(a, ((0, pad), (0, 0), (0, 0))) if pad else a

        return StringListColumn(jnp.asarray(p3(c.chars)),
                                jnp.asarray(p2(c.slens)),
                                jnp.asarray(p2(c.elem_valid)),
                                jnp.asarray(p1(c.lens)),
                                jnp.asarray(p1(c.validity)))
    if isinstance(c, HostDecimal128):
        from auron_tpu.columnar.decimal128 import Decimal128Column
        return Decimal128Column(jnp.asarray(p1(c.hi)), jnp.asarray(p1(c.lo)),
                                jnp.asarray(p1(c.validity)))
    return PrimitiveColumn(jnp.asarray(p1(c.data)),
                           jnp.asarray(p1(c.validity)))


def host_to_batch(host: HostBatch, capacity: Optional[int] = None) -> DeviceBatch:
    """Host → device with padding back to ``capacity`` (>= num_rows)."""
    import jax.numpy as jnp
    n = host.num_rows
    cap = capacity or n
    assert cap >= n, (cap, n)
    pad = cap - n
    cols = []
    for c in host.columns:
        if isinstance(c, (HostMap, HostStruct, HostStringMap,
                          HostStringList)):
            cols.append(_host_col_to_device(c, pad))
        elif isinstance(c, HostString):
            chars = np.pad(c.chars, ((0, pad), (0, 0))) if pad else c.chars
            lens = np.pad(c.lens, (0, pad)) if pad else c.lens
            val = np.pad(c.validity, (0, pad)) if pad else c.validity
            cols.append(StringColumn(jnp.asarray(chars), jnp.asarray(lens),
                                     jnp.asarray(val)))
        elif isinstance(c, HostList):
            values = np.pad(c.values, ((0, pad), (0, 0))) if pad else c.values
            ev = np.pad(c.elem_valid, ((0, pad), (0, 0))) if pad else c.elem_valid
            lens = np.pad(c.lens, (0, pad)) if pad else c.lens
            val = np.pad(c.validity, (0, pad)) if pad else c.validity
            cols.append(ListColumn(jnp.asarray(values), jnp.asarray(ev),
                                   jnp.asarray(lens), jnp.asarray(val)))
        elif isinstance(c, HostDecimal128):
            from auron_tpu.columnar.decimal128 import Decimal128Column
            hi = np.pad(c.hi, (0, pad)) if pad else c.hi
            lo = np.pad(c.lo, (0, pad)) if pad else c.lo
            val = np.pad(c.validity, (0, pad)) if pad else c.validity
            cols.append(Decimal128Column(jnp.asarray(hi), jnp.asarray(lo),
                                         jnp.asarray(val)))
        else:
            data = np.pad(c.data, (0, pad)) if pad else c.data
            val = np.pad(c.validity, (0, pad)) if pad else c.validity
            cols.append(PrimitiveColumn(jnp.asarray(data), jnp.asarray(val)))
    return DeviceBatch(tuple(cols), jnp.asarray(n, jnp.int32))


# ---------------------------------------------------------------------------
# wire format
# ---------------------------------------------------------------------------

def _put_buf(out: io.BytesIO, arr: np.ndarray) -> None:
    b = np.ascontiguousarray(arr).tobytes()
    out.write(struct.pack("<I", len(b)))
    out.write(b)


def _get_buf(src: io.BytesIO, dtype, shape) -> np.ndarray:
    (ln,) = struct.unpack("<I", src.read(4))
    return np.frombuffer(src.read(ln), dtype=dtype).reshape(shape).copy()


def _write_host_col(body: io.BytesIO, c: HostColumn) -> None:
    if isinstance(c, HostString):
        body.write(struct.pack("<BH", 1, c.chars.shape[1]))
        _put_buf(body, c.chars)
        _put_buf(body, c.lens.astype(np.int32))
        _put_buf(body, c.validity.astype(np.bool_))
    elif isinstance(c, HostList):
        tag = c.values.dtype.str.encode()
        body.write(struct.pack("<BHB", 2, c.values.shape[1], len(tag)))
        body.write(tag)
        _put_buf(body, c.values)
        _put_buf(body, c.elem_valid.astype(np.bool_))
        _put_buf(body, c.lens.astype(np.int32))
        _put_buf(body, c.validity.astype(np.bool_))
    elif isinstance(c, HostStringList):
        body.write(struct.pack("<BHH", 6, c.chars.shape[1],
                               c.chars.shape[2]))
        _put_buf(body, c.chars)
        _put_buf(body, c.slens.astype(np.int32))
        _put_buf(body, c.elem_valid.astype(np.bool_))
        _put_buf(body, c.lens.astype(np.int32))
        _put_buf(body, c.validity.astype(np.bool_))
    elif isinstance(c, HostDecimal128):
        body.write(struct.pack("<B", 3))
        _put_buf(body, c.hi.astype(np.int64))
        _put_buf(body, c.lo.astype(np.int64))
        _put_buf(body, c.validity.astype(np.bool_))
    elif isinstance(c, HostMap):
        ktag = c.keys.dtype.str.encode()
        vtag = c.values.dtype.str.encode()
        body.write(struct.pack("<BHBB", 4, c.keys.shape[1],
                               len(ktag), len(vtag)))
        body.write(ktag)
        body.write(vtag)
        _put_buf(body, c.keys)
        _put_buf(body, c.values)
        _put_buf(body, c.val_valid.astype(np.bool_))
        _put_buf(body, c.lens.astype(np.int32))
        _put_buf(body, c.validity.astype(np.bool_))
    elif isinstance(c, HostStringMap):
        body.write(struct.pack("<BHHH", 7, c.kchars.shape[1],
                               c.kchars.shape[2], c.vchars.shape[2]))
        _put_buf(body, c.kchars)
        _put_buf(body, c.kslens.astype(np.int32))
        _put_buf(body, c.vchars)
        _put_buf(body, c.vslens.astype(np.int32))
        _put_buf(body, c.val_valid.astype(np.bool_))
        _put_buf(body, c.lens.astype(np.int32))
        _put_buf(body, c.validity.astype(np.bool_))
    elif isinstance(c, HostStruct):
        body.write(struct.pack("<BB", 5, len(c.children)))
        for ch in c.children:
            _write_host_col(body, ch)
        _put_buf(body, c.validity.astype(np.bool_))
    else:
        tag = c.data.dtype.str.encode()
        body.write(struct.pack("<BB", 0, len(tag)))
        body.write(tag)
        _put_buf(body, c.data)
        _put_buf(body, c.validity.astype(np.bool_))


def serialize_host_batch(host: HostBatch,
                         extras: Optional[dict[str, np.ndarray]] = None,
                         codec: str = "zstd",
                         codec_level: Optional[int] = None) -> bytes:
    extras = extras or {}
    body = io.BytesIO()
    body.write(struct.pack("<IHH", host.num_rows, len(host.columns),
                           len(extras)))
    for c in host.columns:
        _write_host_col(body, c)
    for name, arr in extras.items():
        nb = name.encode()
        assert arr.ndim == 2 and arr.dtype == np.uint64, name
        body.write(struct.pack("<BIH", len(nb), arr.shape[0], arr.shape[1]))
        body.write(nb)
        _put_buf(body, arr)

    raw = body.getvalue()
    if codec == "zstd" and zstandard is not None:
        payload = _compressor(codec_level).compress(raw)
        code = CODEC_ZSTD
    else:
        # zstandard absent: uncompressed frames keep serde functional
        # (the codec byte makes readers self-describing either way)
        payload, code = raw, CODEC_NONE
    return MAGIC + struct.pack("<BI", code, len(payload)) + payload


def _read_host_col(src: io.BytesIO, num_rows: int) -> HostColumn:
    kind = struct.unpack("<B", src.read(1))[0]
    if kind == 1:
        (width,) = struct.unpack("<H", src.read(2))
        chars = _get_buf(src, np.uint8, (num_rows, width))
        lens = _get_buf(src, np.int32, (num_rows,))
        val = _get_buf(src, np.bool_, (num_rows,))
        return HostString(chars, lens, val)
    if kind == 2:
        m, tag_len = struct.unpack("<HB", src.read(3))
        dt = np.dtype(src.read(tag_len).decode())
        values = _get_buf(src, dt, (num_rows, m))
        ev = _get_buf(src, np.bool_, (num_rows, m))
        lens = _get_buf(src, np.int32, (num_rows,))
        val = _get_buf(src, np.bool_, (num_rows,))
        return HostList(values, ev, lens, val)
    if kind == 3:
        hi = _get_buf(src, np.int64, (num_rows,))
        lo = _get_buf(src, np.int64, (num_rows,))
        val = _get_buf(src, np.bool_, (num_rows,))
        return HostDecimal128(hi, lo, val)
    if kind == 4:
        m, ktag_len, vtag_len = struct.unpack("<HBB", src.read(4))
        kdt = np.dtype(src.read(ktag_len).decode())
        vdt = np.dtype(src.read(vtag_len).decode())
        keys = _get_buf(src, kdt, (num_rows, m))
        values = _get_buf(src, vdt, (num_rows, m))
        vv = _get_buf(src, np.bool_, (num_rows, m))
        lens = _get_buf(src, np.int32, (num_rows,))
        val = _get_buf(src, np.bool_, (num_rows,))
        return HostMap(keys, values, vv, lens, val)
    if kind == 7:
        m, kw, vw = struct.unpack("<HHH", src.read(6))
        kchars = _get_buf(src, np.uint8, (num_rows, m, kw))
        kslens = _get_buf(src, np.int32, (num_rows, m))
        vchars = _get_buf(src, np.uint8, (num_rows, m, vw))
        vslens = _get_buf(src, np.int32, (num_rows, m))
        vv = _get_buf(src, np.bool_, (num_rows, m))
        lens = _get_buf(src, np.int32, (num_rows,))
        val = _get_buf(src, np.bool_, (num_rows,))
        return HostStringMap(kchars, kslens, vchars, vslens, vv, lens, val)
    if kind == 6:
        m, width = struct.unpack("<HH", src.read(4))
        chars = _get_buf(src, np.uint8, (num_rows, m, width))
        slens = _get_buf(src, np.int32, (num_rows, m))
        ev = _get_buf(src, np.bool_, (num_rows, m))
        lens = _get_buf(src, np.int32, (num_rows,))
        val = _get_buf(src, np.bool_, (num_rows,))
        return HostStringList(chars, slens, ev, lens, val)
    if kind == 5:
        (n_children,) = struct.unpack("<B", src.read(1))
        kids = [_read_host_col(src, num_rows) for _ in range(n_children)]
        val = _get_buf(src, np.bool_, (num_rows,))
        return HostStruct(kids, val)
    (tag_len,) = struct.unpack("<B", src.read(1))
    dt = np.dtype(src.read(tag_len).decode())
    data_arr = _get_buf(src, dt, (num_rows,))
    val = _get_buf(src, np.bool_, (num_rows,))
    return HostPrimitive(data_arr, val)


def deserialize_host_batch(data: bytes) -> tuple[HostBatch, dict[str, np.ndarray]]:
    if data[:4] != MAGIC:
        raise ValueError("bad batch frame magic")
    code, body_len = struct.unpack("<BI", data[4:9])
    payload = data[9:9 + body_len]
    raw = _decompressor().decompress(payload) if code == CODEC_ZSTD else payload
    src = io.BytesIO(raw)
    num_rows, num_cols, num_extras = struct.unpack("<IHH", src.read(8))
    cols = [_read_host_col(src, num_rows) for _ in range(num_cols)]
    extras: dict[str, np.ndarray] = {}
    for _ in range(num_extras):
        name_len, rows, words = struct.unpack("<BIH", src.read(7))
        name = src.read(name_len).decode()
        extras[name] = _get_buf(src, np.uint64, (rows, words))
    return HostBatch(cols, num_rows), extras


def serialize_batch(batch: DeviceBatch, codec: str = "zstd",
                    codec_level: Optional[int] = None) -> bytes:
    return serialize_host_batch(batch_to_host(batch), codec=codec,
                                codec_level=codec_level)


def deserialize_batch(data: bytes,
                      capacity: Optional[int] = None) -> DeviceBatch:
    host, _ = deserialize_host_batch(data)
    return host_to_batch(host, capacity)
