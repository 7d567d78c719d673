"""Spark-compatible bloom filter + bit array.

Byte-compatible with org.apache.spark.util.sketch.BloomFilterImpl V1 (the
reference re-implements the same: datafusion-ext-commons/src/
spark_bloom_filter.rs, spark_bit_array.rs): big-endian i32 version(=1),
i32 numHashFunctions, i32 word count, i64 words; double hashing
h1 = murmur3(item, 0), h2 = murmur3(item, h1), bit_i = (h1 + i*h2) with a
sign flip, i in 1..=k.

TPU split: *building* is a vectorized numpy pass on the host (build sides
are small and scatter-OR is host-friendly); *probing* — the hot path, a
semi-join filter inside scans — is a device kernel over the words array.
"""

from __future__ import annotations

import math
import struct
from functools import lru_cache

import numpy as np

import jax
import jax.numpy as jnp

from auron_tpu.ops import hashing
from auron_tpu.runtime.programs import program_cache

_M1 = np.uint32(0xCC9E2D51)
_M2 = np.uint32(0x1B873593)


def _np_murmur3_long(values: np.ndarray, seed: np.ndarray) -> np.ndarray:
    """Vectorized Spark murmur3 hashLong (two 32-bit rounds), numpy mirror
    of ops.hashing.murmur3_int64 — build side runs on host."""
    def mix_k1(k1):
        k1 = (k1 * _M1).astype(np.uint32)
        k1 = (k1 << np.uint32(15)) | (k1 >> np.uint32(17))
        return (k1 * _M2).astype(np.uint32)

    def mix_h1(h1, k1):
        h1 = h1 ^ k1
        h1 = (h1 << np.uint32(13)) | (h1 >> np.uint32(19))
        return (h1 * np.uint32(5) + np.uint32(0xE6546B64)).astype(np.uint32)

    v = values.astype(np.int64).view(np.uint64)
    low = (v & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    high = (v >> np.uint64(32)).astype(np.uint32)
    h1 = seed.astype(np.uint32)
    h1 = mix_h1(h1, mix_k1(low))
    h1 = mix_h1(h1, mix_k1(high))
    h1 = h1 ^ np.uint32(8)
    h1 = h1 ^ (h1 >> np.uint32(16))
    h1 = (h1 * np.uint32(0x85EBCA6B)).astype(np.uint32)
    h1 = h1 ^ (h1 >> np.uint32(13))
    h1 = (h1 * np.uint32(0xC2B2AE35)).astype(np.uint32)
    h1 = h1 ^ (h1 >> np.uint32(16))
    return h1.astype(np.int32)


class SparkBloomFilter:
    def __init__(self, num_hash_functions: int, num_bits: int):
        num_bits = max((num_bits + 63) // 64, 1) * 64
        self.num_hash_functions = num_hash_functions
        self.words = np.zeros(num_bits // 64, np.uint64)

    # -- sizing (Spark BloomFilter.optimalNumOf*) ---------------------------

    @staticmethod
    def optimal_num_bits(expected_items: int, fpp: float) -> int:
        # Spark BloomFilter.optimalNumOfBits — no word rounding here; k is
        # derived from this raw count, the bit array rounds up separately
        return max(int(-expected_items * math.log(fpp)
                       / (math.log(2) ** 2)), 1)

    @classmethod
    def create(cls, expected_items: int,
               fpp: float = 0.03) -> "SparkBloomFilter":
        m = cls.optimal_num_bits(expected_items, fpp)
        k = max(round(m / expected_items * math.log(2)), 1)
        return cls(k, m)

    @property
    def bit_size(self) -> int:
        return len(self.words) * 64

    # -- build (host, vectorized) ------------------------------------------

    def _indices(self, items: np.ndarray) -> np.ndarray:
        """[n, k] bit indices for int64 items."""
        h1 = _np_murmur3_long(items, np.int32(0))
        h2 = _np_murmur3_long(items, h1)
        k = self.num_hash_functions
        i = np.arange(1, k + 1, dtype=np.int32)[None, :]
        combined = (h1[:, None].astype(np.int32)
                    + (i * h2[:, None].astype(np.int32)).astype(np.int32))
        combined = np.where(combined < 0, ~combined, combined)
        # Spark: int hash % long bitSize — keep the modulo in 64 bits so
        # filters past 2^31 bits work
        return combined.astype(np.int64) % np.int64(self.bit_size)

    def put_longs(self, items: np.ndarray) -> None:
        items = np.asarray(items, np.int64)
        if items.size == 0:
            return
        idx = self._indices(items).reshape(-1).astype(np.uint64)
        np.bitwise_or.at(self.words, (idx >> np.uint64(6)).astype(np.int64),
                         np.uint64(1) << (idx & np.uint64(63)))

    def might_contain_longs_host(self, items: np.ndarray) -> np.ndarray:
        items = np.asarray(items, np.int64)
        idx = self._indices(items).astype(np.uint64)
        bits = (self.words[(idx >> np.uint64(6)).astype(np.int64)]
                >> (idx & np.uint64(63))) & np.uint64(1)
        return bits.all(axis=1)

    def merge(self, other: "SparkBloomFilter") -> None:
        assert (self.bit_size == other.bit_size
                and self.num_hash_functions == other.num_hash_functions), \
            "cannot merge bloom filters with different layouts"
        self.words |= other.words

    # -- Spark V1 serde -----------------------------------------------------

    def serialize(self) -> bytes:
        out = struct.pack(">iii", 1, self.num_hash_functions, len(self.words))
        return out + self.words.view(np.int64).astype(">i8").tobytes()

    @classmethod
    def deserialize(cls, data: bytes) -> "SparkBloomFilter":
        if len(data) < 12:
            raise ValueError(
                f"bad bloom filter bytes: {len(data)} < 12-byte header")
        version, k, n_words = struct.unpack(">iii", data[:12])
        if version != 1:
            raise ValueError(f"unsupported bloom filter version {version}")
        if n_words <= 0:
            raise ValueError(f"bad bloom filter bytes: word count {n_words}")
        if len(data) < 12 + n_words * 8:
            raise ValueError(
                f"bad bloom filter bytes: truncated word array "
                f"({len(data) - 12} of {n_words * 8} bytes)")
        f = cls(k, n_words * 64)
        f.words = np.frombuffer(data[12:12 + n_words * 8],
                                dtype=">i8").astype(np.int64).view(np.uint64)
        return f


# ---------------------------------------------------------------------------
# device probe kernel
# ---------------------------------------------------------------------------

@program_cache("exprs.bloom.probe", maxsize=64)
def _probe_kernel(num_hash_functions: int, bit_size: int):
    k = num_hash_functions

    @jax.jit
    def auron_exprs_bloom_probe(words: jax.Array, values: jax.Array):
        h1 = hashing.murmur3_int64(values, jnp.uint32(0)).astype(jnp.int32)
        h2 = hashing.murmur3_int64(values, h1.view(jnp.uint32)) \
            .astype(jnp.int32)
        i = jnp.arange(1, k + 1, dtype=jnp.int32)[None, :]
        combined = h1[:, None] + i * h2[:, None]
        combined = jnp.where(combined < 0, ~combined, combined)
        idx = (combined.astype(jnp.int64)
               % jnp.int64(bit_size)).astype(jnp.uint64)
        bits = (words[idx >> jnp.uint64(6)]
                >> (idx & jnp.uint64(63))) & jnp.uint64(1)
        return jnp.all(bits == 1, axis=1)

    return auron_exprs_bloom_probe


def might_contain_device(filter_bytes: bytes, values: jax.Array) -> jax.Array:
    """bool[capacity]: device-side membership probe against a serialized
    Spark bloom filter."""
    f = _cached_filter(filter_bytes)
    words = _cached_words(filter_bytes)
    kern = _probe_kernel(f.num_hash_functions, f.bit_size)
    return kern(words, values)


@lru_cache(maxsize=32)
def _cached_filter(filter_bytes: bytes) -> SparkBloomFilter:
    return SparkBloomFilter.deserialize(filter_bytes)


@lru_cache(maxsize=32)
def _cached_words(filter_bytes: bytes):
    return jnp.asarray(_cached_filter(filter_bytes).words)
