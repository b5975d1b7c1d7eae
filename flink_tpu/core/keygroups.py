"""Key groups: the state-sharding / rescaling unit, and the TPU sharding axis.

Mirrors the contract of the reference's key-group assignment
(``flink-runtime/src/main/java/org/apache/flink/runtime/state/KeyGroupRangeAssignment.java:50-84``
and ``flink-core/src/main/java/org/apache/flink/util/MathUtils.java:137`` murmur
finalizer): ``key_group = murmur(key_hash) % max_parallelism`` and contiguous
key-group *ranges* per parallel subtask, so state laid out by key group can be
rescaled/resharded without rehashing keys.

Everything here is vectorized numpy over ``int32`` key hashes — the host-side
router uses it to split record batches across device shards (the analog of
``KeyGroupStreamPartitioner``), and snapshots index state by key-group range.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Iterator, List, Tuple

import numpy as np

_C1 = np.uint32(0xCC9E2D51)
_C2 = np.uint32(0x1B873593)
_M5 = np.uint32(5)
_N = np.uint32(0xE6546B64)


def _rotl32(x: np.ndarray, r: int) -> np.ndarray:
    return (x << np.uint32(r)) | (x >> np.uint32(32 - r))


def murmur_hash(code: np.ndarray | int) -> np.ndarray:
    """Vectorized equivalent of ``MathUtils.murmurHash(int)`` (MathUtils.java:137).

    Accepts int32-like input, returns non-negative int32 values with identical
    results to the reference for every input (including the
    ``Integer.MIN_VALUE -> 0`` edge case).
    """
    code = np.asarray(code, dtype=np.int64).astype(np.uint32)
    with np.errstate(over="ignore"):
        code = code * _C1
        code = _rotl32(code, 15)
        code = code * _C2
        code = _rotl32(code, 13)
        code = code * _M5 + _N
        code = code ^ np.uint32(4)
        # bitMix (MathUtils.java:194)
        code ^= code >> np.uint32(16)
        code = code * np.uint32(0x85EBCA6B)
        code ^= code >> np.uint32(13)
        code = code * np.uint32(0xC2B2AE35)
        code ^= code >> np.uint32(16)
    signed = code.astype(np.int32)
    out = np.where(signed >= 0, signed, np.where(signed == np.int32(-2147483648), 0, -signed))
    return out.astype(np.int32)


def java_int_hash(values: np.ndarray) -> np.ndarray:
    """``Integer.hashCode`` / ``Long.hashCode`` analog for numpy int arrays."""
    v = np.asarray(values)
    if v.dtype in (np.int64, np.uint64):
        u = v.astype(np.uint64)
        return (u ^ (u >> np.uint64(32))).astype(np.uint32).astype(np.int32)
    return v.astype(np.int32)


def assign_to_key_group(key_hashes: np.ndarray, max_parallelism: int) -> np.ndarray:
    """``KeyGroupRangeAssignment.computeKeyGroupForKeyHash:75``: murmur % maxParallelism."""
    return murmur_hash(key_hashes) % np.int32(max_parallelism)


_string_hash_cache: dict = {}
_STRING_HASH_CACHE_MAX = 1 << 22  # bound: reset rather than leak unboundedly


def java_string_hash(values: np.ndarray) -> np.ndarray:
    """``String.hashCode`` (s[0]*31^(n-1) + ...) per element of an object array.

    Cache persists across batches (hot path: keyBy on string keys re-sees the
    same key universe every batch); size-bounded against high-cardinality
    streams."""
    if len(_string_hash_cache) > _STRING_HASH_CACHE_MAX:
        _string_hash_cache.clear()
    cache = _string_hash_cache
    out = np.empty(len(values), np.int64)
    for i, s in enumerate(values):
        h = cache.get(s)
        if h is None:
            acc = 0
            for ch in str(s):
                acc = (acc * 31 + ord(ch)) & 0xFFFFFFFF
            cache[s] = h = acc
        out[i] = h
    return out.astype(np.uint32).astype(np.int32)


def hash_keys(keys: np.ndarray) -> np.ndarray:
    """Key column (int or object dtype) -> int32 hashes (``Object.hashCode``)."""
    keys = np.asarray(keys)
    if keys.dtype.kind in "iu":
        return java_int_hash(keys)
    if keys.dtype.kind == "V" and keys.dtype.itemsize % 8 == 0:
        # packed composite keys (void bytes, see dataset _composite_key):
        # vectorized polynomial mix over the 8-byte words
        words = keys.view(np.int64).reshape(len(keys), -1)
        h = np.zeros(len(keys), np.int64)
        with np.errstate(over="ignore"):
            for j in range(words.shape[1]):
                h = h * np.int64(31) + words[:, j]
        return java_int_hash(h)
    return java_string_hash(keys)


@dataclass(frozen=True)
class KeyGroupRange:
    """Inclusive [start, end] range of key groups (``KeyGroupRange.java``)."""

    start: int
    end: int

    def __post_init__(self):
        if self.end < self.start:
            object.__setattr__(self, "start", 0)
            object.__setattr__(self, "end", -1)

    @property
    def num_key_groups(self) -> int:
        return self.end - self.start + 1

    def contains(self, key_group: int) -> bool:
        return self.start <= key_group <= self.end

    def __iter__(self) -> Iterator[int]:
        return iter(range(self.start, self.end + 1))

    def intersection(self, other: "KeyGroupRange") -> "KeyGroupRange":
        return KeyGroupRange(max(self.start, other.start), min(self.end, other.end))


def compute_key_group_range(max_parallelism: int, parallelism: int, operator_index: int) -> KeyGroupRange:
    """``KeyGroupRangeAssignment.computeKeyGroupRangeForOperatorIndex``."""
    if parallelism > max_parallelism:
        raise ValueError(f"parallelism {parallelism} > max_parallelism {max_parallelism}")
    start = (operator_index * max_parallelism + parallelism - 1) // parallelism
    end = ((operator_index + 1) * max_parallelism - 1) // parallelism
    return KeyGroupRange(start, end)


def compute_operator_index_for_key_group(max_parallelism: int, parallelism: int, key_group: int) -> int:
    """``KeyGroupRangeAssignment.computeOperatorIndexForKeyGroup``."""
    return key_group * parallelism // max_parallelism


def assign_key_to_parallel_operator(key_hashes: np.ndarray, max_parallelism: int, parallelism: int) -> np.ndarray:
    """Vectorized ``assignKeyToParallelOperator:50`` — subtask index per key."""
    kg = assign_to_key_group(key_hashes, max_parallelism)
    return (kg.astype(np.int64) * parallelism // max_parallelism).astype(np.int32)


def key_group_ranges(max_parallelism: int, parallelism: int) -> List[KeyGroupRange]:
    return [compute_key_group_range(max_parallelism, parallelism, i) for i in range(parallelism)]


def route_raw_keys(keys: np.ndarray, parallelism: int,
                   max_parallelism: int = 128) -> np.ndarray:
    """RAW key column -> owning parallel-operator/shard index per key
    (key hash -> murmur key group -> contiguous range): THE single
    routing assignment shared by the record router, the queryable tier's
    client-side routing (``queryable/view.route_keys``) and
    ``ShardLayout.route_keys`` — one implementation so client routing can
    never desynchronize from state ownership."""
    if parallelism <= 1:
        return np.zeros(len(keys), np.int32)
    return assign_key_to_parallel_operator(hash_keys(np.asarray(keys)),
                                           max_parallelism, parallelism)


def keyed_for_edge(batch, key_column, max_parallelism: int):
    """``batch`` as a hash edge on ``key_column`` routes it — the live
    dispatcher (``cluster.channels.OutputDispatcher``) and the rescale of
    persisted in-flight batches (``state.redistribute``) alike.  The keying
    operator lives at the consumer chain head; the producer-side partitioner
    names the key itself (KeyGroupStreamPartitioner's key selector) unless
    the batch carries key groups of that very key, or of no named one, and
    what it puts carries them on to that operator."""
    if key_column is None or (
            batch.key_spec is None and batch.key_groups_derived):
        return batch
    return batch.keyed_by(key_column, max_parallelism)


def rows_by_target(key_groups: np.ndarray, max_parallelism: int,
                   parallelism: int) -> Tuple[np.ndarray, List[int]]:
    """``computeOperatorIndexForKeyGroup`` for every record, as one index
    pass: ``(order, bounds)`` with the rows of target ``t``, in row order,
    at ``order[bounds[t]:bounds[t + 1]]``."""
    # the reference's own int arithmetic where the product fits 32 bits (it
    # does up to Flink's bound of 2^15 key groups); 16 bits: a radix sort
    wide = np.int32 if max_parallelism * parallelism < 2 ** 31 else np.int64
    target = (np.asarray(key_groups, wide) * wide(parallelism)
              // wide(max_parallelism)).astype(np.uint16)
    order = np.argsort(target, kind="stable")
    counts = np.bincount(target, minlength=parallelism)[:parallelism]
    return order, [0, *np.cumsum(counts).tolist()]
