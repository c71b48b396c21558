"""Vectorized 64-bit key hashing.

Reference parity: ``InterpretedHashGenerator`` / the XxHash64-based
``CombineHashFunction`` used by ``GroupByHash`` and the
``LocalPartitionGenerator`` [SURVEY §2.1; reference tree unavailable].
TPU-first: a splitmix64 finalizer chain over int64 lanes — pure VPU
bit-math, no lookup tables. The same function must be used engine-wide:
partitioned exchanges rely on every device computing identical
partition ids for a key.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

_M1 = np.uint64(0xBF58476D1CE4E5B9)
_M2 = np.uint64(0x94D049BB133111EB)
_GOLDEN = np.uint64(0x9E3779B97F4A7C15)


def mix64(x):
    """splitmix64 finalizer: uint64 -> uint64, good avalanche."""
    x = x.astype(jnp.uint64)
    x = (x ^ (x >> np.uint64(30))) * _M1
    x = (x ^ (x >> np.uint64(27))) * _M2
    return x ^ (x >> np.uint64(31))


def hash_columns(columns) -> jnp.ndarray:
    """Combined uint64 hash of one or more key arrays (int-like).

    Combine rule: h = mix(h*GOLDEN ^ mix(col)) — order-sensitive, so
    (a, b) and (b, a) hash differently.
    """
    h = None
    for c in columns:
        hc = mix64(c.astype(jnp.int64).view(jnp.uint64) if c.dtype == jnp.int64 else c.astype(jnp.uint64))
        h = hc if h is None else mix64(h * _GOLDEN ^ hc)
    return h


def partition_ids(columns, num_partitions: int) -> jnp.ndarray:
    """Hash-partition assignment in [0, num_partitions): the exchange's
    row->consumer map (reference: PagePartitioner)."""
    h = hash_columns(columns)
    return (h % np.uint64(num_partitions)).astype(jnp.int32)


# ---------------------------------------------------------------------------
# 32-bit mixing for the runtime-join-filter Bloom bitmasks: int32
# arithmetic only, arithmetic shifts masked back to logical. Build
# (bloom_build) and scan-side test (bloom_test) MUST use the same
# functions or bits and tests would disagree.
# ---------------------------------------------------------------------------

_M32A = np.int32(np.uint32(0x85EBCA6B).view(np.int32))
_M32B = np.int32(np.uint32(0xC2B2AE35).view(np.int32))
#: second-hash input perturbation for the two-bit Bloom
SKETCH_SEED = np.int32(np.uint32(0x9E3779B9).view(np.int32))


def mix32(x):
    """murmur3 finalizer on int32 lanes (wrapping int32 multiplies;
    logical shifts emulated as arithmetic-shift-then-mask). Keys wider
    than 32 bits are truncated first — fine for membership sketches
    (an aliased wide key can only add a false positive)."""
    x = x.astype(jnp.int32)
    x = x ^ ((x >> np.int32(16)) & np.int32(0xFFFF))
    x = x * _M32A
    x = x ^ ((x >> np.int32(13)) & np.int32((1 << 19) - 1))
    x = x * _M32B
    return x ^ ((x >> np.int32(16)) & np.int32(0xFFFF))


def mix32_slots(keys, nbits: int):
    """The two Bloom bit slots of each key in [0, nbits); ``nbits``
    must be a power of two (the mask keeps slots non-negative)."""
    assert nbits & (nbits - 1) == 0, "nbits must be a power of two"
    mask = np.int32(nbits - 1)
    k = keys.astype(jnp.int32)
    return mix32(k) & mask, mix32(k ^ SKETCH_SEED) & mask


def bloom_build(keys, live, nbits: int):
    """[nbits/32] int32 packed two-hash Bloom words over the live keys
    (XLA side: the runtime-join-filter build product). Bit packing
    goes through a byte-per-bit scatter so duplicate keys OR cleanly."""
    s1, s2 = mix32_slots(keys, nbits)
    p = jnp.zeros(nbits, jnp.int8)
    p = p.at[jnp.where(live, s1, nbits)].set(1, mode="drop")
    p = p.at[jnp.where(live, s2, nbits)].set(1, mode="drop")
    p = p.reshape(nbits // 32, 32).astype(jnp.int64)
    return (p << jnp.arange(32, dtype=jnp.int64)).sum(
        axis=1, dtype=jnp.int64).astype(jnp.int32)


def bloom_test(words, keys):
    """bool [n]: Bloom membership (false positives possible, never
    false negatives). ``words`` from ``bloom_build``."""
    nbits = words.shape[0] * 32
    s1, s2 = mix32_slots(keys, nbits)

    def bit(s):
        w = words[(s >> np.int32(5)).astype(jnp.int32)]
        return ((w >> (s & np.int32(31))) & np.int32(1)) != 0

    return bit(s1) & bit(s2)


_BUCKET_SEED = np.uint64(0xA24BAED4963EE407)


def bucket_ids(columns, num_buckets: int) -> jnp.ndarray:
    """Grouped-execution bucket assignment in [0, num_buckets).

    Applies one extra seeded mix on top of ``hash_columns`` so bucket
    ids are DECORRELATED from ``partition_ids`` over the same key:
    ``h % B`` and ``h % P`` share low-bit structure whenever B and P
    share factors, which would route each bucket's rows onto a subset
    of the mesh during the in-bucket repartition exchange."""
    h = mix64(hash_columns(columns) ^ _BUCKET_SEED)
    return (h % np.uint64(num_buckets)).astype(jnp.int32)
