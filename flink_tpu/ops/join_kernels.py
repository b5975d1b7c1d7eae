"""Device equi-join kernels: sorted-merge pair enumeration on the MXU host.

The device analog of the blink join runtime's sort/hash machinery
(``flink-table-runtime-blink/.../operators/join/stream/StreamingJoinOperator.java``,
``hashtable/BytesHashMap.java``): both key columns are sorted on device,
matching key spans are intersected, and every cross pair is enumerated by a
vectorized prefix-sum expansion — no Python loop over keys.

Two-phase static-shape protocol (XLA needs static output shapes):
phase 1 returns the exact pair count (one scalar sync); phase 2 compiles at
a pow2/4-quantized capacity and fills ``(left_idx, right_idx)`` padded with
``-1``.  The jit caches are keyed on (L, R, cap) so steady workloads compile
O(log) times.

When to use: pipelines whose batches already live on device (the mesh
runtime, device-resident table programs) or whose join sides are large
enough that sort cost dominates transfer.  Host pipelines over numpy batches
default to the numpy span-intersection join (``operators/joins._join_pairs``)
— whether the device→host index download pays for the sort speedup is
unmeasured on a directly attached chip — ROADMAP A1.  Enable globally with
``FLINK_TPU_DEVICE_JOIN=1`` or per-call via ``device_join_pairs``.
"""

from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np


@jax.jit
def _pair_count(lk, rk):
    """Exact number of equi-join pairs: for each left row, the size of the
    matching right span (searchsorted bounds on the sorted right keys)."""
    rks = jnp.sort(rk)
    lo = jnp.searchsorted(rks, lk, side="left")
    hi = jnp.searchsorted(rks, lk, side="right")
    return (hi - lo).sum()


@partial(jax.jit, static_argnums=(2,))
def _pair_emit(lk, rk, cap: int):
    """(left_idx[cap], right_idx[cap], n) — pairs in left-major order,
    right matches in right-sort order; padding rows are -1."""
    L = lk.shape[0]
    ro = jnp.argsort(rk, stable=True)
    rks = rk[ro]
    lo = jnp.searchsorted(rks, lk, side="left")
    hi = jnp.searchsorted(rks, lk, side="right")
    counts = hi - lo
    off = jnp.cumsum(counts) - counts          # start offset per left row
    n = counts.sum()
    pos = jnp.arange(cap)
    # which left row does output position p belong to?
    li = jnp.searchsorted(off + counts, pos, side="right")
    li = jnp.minimum(li, L - 1)
    within = pos - off[li]
    ri = ro[jnp.minimum(lo[li] + within, rk.shape[0] - 1)]
    valid = pos < n
    return (jnp.where(valid, li, -1).astype(jnp.int32),
            jnp.where(valid, ri, -1).astype(jnp.int32), n)


from flink_tpu.ops.shapes import quantize_pow2 as _quantize


def device_join_pairs(lk: np.ndarray, rk: np.ndarray
                      ) -> Tuple[np.ndarray, np.ndarray]:
    """Device sorted-merge equi-join; same contract as
    ``operators.joins._join_pairs`` (all cross pairs with equal keys).
    Integer keys only — factorize object keys first (``state/keyindex``)."""
    lk = np.ascontiguousarray(lk)
    rk = np.ascontiguousarray(rk)
    if lk.size == 0 or rk.size == 0:
        return np.zeros(0, np.int64), np.zeros(0, np.int64)
    # ALWAYS factorize to dense codes first: jnp defaults to int32, so raw
    # int64 keys would silently truncate; dense codes also make the device
    # sort radix-friendly.  Absent right keys get distinct negative codes
    # (they join with nothing; left codes are all >= 0).
    if lk.dtype.kind in "iu" and rk.dtype.kind in "iu":
        from flink_tpu.state.keyindex import KeyIndex
        ki = KeyIndex()
        lcodes = ki.lookup_or_insert(lk).astype(np.int64)
        rcodes = ki.lookup(rk).astype(np.int64)
    else:
        from flink_tpu.state.keyindex import ObjectKeyIndex
        ki = ObjectKeyIndex()
        lcodes = ki.lookup_or_insert(lk).astype(np.int64)
        rcodes = ki.lookup(rk).astype(np.int64)
    lk = lcodes
    rk = np.where(rcodes < 0, -(np.arange(rcodes.size) + 2), rcodes)
    n = int(_pair_count(jnp.asarray(lk), jnp.asarray(rk)))
    if n == 0:
        return np.zeros(0, np.int64), np.zeros(0, np.int64)
    cap = _quantize(n)
    li, ri, _ = _pair_emit(jnp.asarray(lk), jnp.asarray(rk), cap)
    li = np.asarray(li)[:n].astype(np.int64)
    ri = np.asarray(ri)[:n].astype(np.int64)
    return li, ri
