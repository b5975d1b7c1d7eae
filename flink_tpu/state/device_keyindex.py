"""Device-resident key index: probe warm keys INSIDE the jitted step.

The host C probe+mirror fold (``wm_probe_update2``) is the one remaining
hot-path wall (~70% of elapsed on the 1M-key tumbling-sum bench): every
batch, every record pays a random host-memory probe plus a mirror fold.
The reference pays this as a per-record hash probe in
``CopyOnWriteStateMap.java``; our batched analog can do what Flink never
could — resolve warm keys *on the accelerator, inside the already-
dispatched XLA step*, so the host pass touches only misses.

This module holds the device half of that split:

- An **open-addressing int64 -> int32 hash table as device arrays**: two
  int32 key planes (lo/hi words — jax runs with x64 disabled, so int64
  never rides the device) plus a ``slot + 1`` plane whose zero state IS the
  empty table (the same trick as the native ``KeyDict``).  Bucket starts
  come from the SAME splitmix64 ``_mix64`` family as
  :mod:`flink_tpu.state.keyindex`, computed on the host as one streaming
  vectorized pass (no random access, no insert — the wall is the probe
  walk + fold, not the hash), so slot ids agree with the host KeyIndex by
  construction.
- :func:`lax_probe` — the vectorized probe loop, the only probe: a
  ``while_loop`` of XLA gathers from the HBM-resident table.
- :class:`DeviceKeyIndex` — the host-side owner: a numpy occupancy shadow
  decides insert buckets (the device table is only ever written by our
  scatters, so shadow and table cannot diverge), ``ensure_loaded`` bulk-
  inserts whatever tail of the KeyIndex the table is missing (initial
  load, restore, and per-batch miss inserts are all the same code path),
  and capacity is a **sticky pow2 high-water** so growth cannot recompile
  the consuming step more than O(log) times per run.
- :func:`calibrated_device_probe` — the measured A/B (device probe + fold
  dispatch vs the fused C pass) behind ``--device-probe auto``; the same
  measure-don't-assume pattern as the device-sync transport calibration
  and ``native_mirror.calibrated_shards``.
"""

from __future__ import annotations

import os
import threading
from typing import Optional, Tuple

import numpy as np

from flink_tpu.state.keyindex import _mix64

#: probe miss marker in the slot output
MISS = np.int32(-1)


# ---------------------------------------------------------------------------
# host-side helpers: key split + bucket starts (streaming, no random access)
# ---------------------------------------------------------------------------

def split_keys(keys: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """int64 keys -> (lo, hi) int32 word planes (device-safe under x64-off)."""
    u = np.ascontiguousarray(keys, np.int64).view(np.uint64)
    lo = (u & np.uint64(0xFFFFFFFF)).astype(np.uint32).view(np.int32)
    hi = (u >> np.uint64(32)).astype(np.uint32).view(np.int32)
    return lo, hi


def probe_starts(keys: np.ndarray, capacity: int) -> np.ndarray:
    """Bucket start per key: ``_mix64(key) & (capacity - 1)`` as int32."""
    h = _mix64(np.ascontiguousarray(keys, np.int64).view(np.uint64))
    return (h & np.uint64(capacity - 1)).astype(np.int64).astype(np.int32)


# ---------------------------------------------------------------------------
# probe implementations (device side)
# ---------------------------------------------------------------------------

def lax_probe(tab_lo, tab_hi, tab_slot1, key_lo, key_hi, start):
    """Vectorized open-addressing probe: returns int32 slots, -1 = miss.

    One ``while_loop`` round gathers every still-pending record's bucket;
    hits resolve to ``slot1 - 1``, empty buckets resolve to miss, occupied-
    by-another-key records step to the next bucket.  Load factor <= 0.5
    keeps expected rounds ~2 and the loop bound is the longest probe chain.
    """
    import jax.numpy as jnp
    from jax import lax

    cap = tab_slot1.shape[0]
    maskv = jnp.int32(cap - 1)

    def cond(state):
        pending, _idx, _slot = state
        return jnp.any(pending)

    def body(state):
        pending, idx, slot = state
        b_s = tab_slot1[idx]
        b_lo = tab_lo[idx]
        b_hi = tab_hi[idx]
        empty = b_s == 0
        hit = (~empty) & (b_lo == key_lo) & (b_hi == key_hi)
        slot = jnp.where(pending & hit, b_s - 1, slot)
        pending = pending & ~(hit | empty)
        idx = jnp.where(pending, (idx + 1) & maskv, idx)
        return pending, idx, slot

    pending0 = jnp.ones(start.shape, bool)
    slot0 = jnp.full(start.shape, MISS, jnp.int32)
    _p, _i, slot = lax.while_loop(cond, body, (pending0, start, slot0))
    return slot


# ---------------------------------------------------------------------------
# DeviceKeyIndex — host-side owner of the device table
# ---------------------------------------------------------------------------

class DeviceKeyIndex:
    """Device twin of a :class:`~flink_tpu.state.keyindex.KeyIndex`.

    The KeyIndex (host C keydict) stays the slot-id authority; this class
    keeps a device-resident probe table in lockstep via ``ensure_loaded``:
    whatever tail of slots the table has not seen yet is placed in the host
    occupancy shadow (vectorized, the same linear probing the device walk
    runs) and shipped as ONE scatter.  The device never inserts, so shadow
    and table cannot diverge.  Capacity is a sticky pow2 high-water —
    growth rebuilds shadow + table at the doubled size and recompiles the
    consuming step once per capacity, never per batch.
    """

    def __init__(self, initial_capacity: int = 1 << 16,
                 max_load: float = 0.5, sharding=None):
        cap = 1 << 10
        while cap < initial_capacity:
            cap <<= 1
        self._max_load = max_load
        self._sharding = sharding
        self._n = 0               # slots loaded into the table
        self._alloc(cap)

    # -- internals ----------------------------------------------------------
    def _alloc(self, cap: int) -> None:
        import jax
        import jax.numpy as jnp

        self.capacity = cap
        self._shadow_used = np.zeros(cap, bool)
        lo = jnp.zeros(cap, jnp.int32)
        hi = jnp.zeros(cap, jnp.int32)
        s1 = jnp.zeros(cap, jnp.int32)
        if self._sharding is not None:
            lo = jax.device_put(lo, self._sharding)
            hi = jax.device_put(hi, self._sharding)
            s1 = jax.device_put(s1, self._sharding)
        self.tab_lo, self.tab_hi, self.tab_slot1 = lo, hi, s1
        self._insert_fn = self._make_insert_fn()

    def _make_insert_fn(self):
        import jax

        sharding = self._sharding

        def insert(tab_lo, tab_hi, tab_slot1, buckets, klo, khi, slot1):
            new_lo = tab_lo.at[buckets].set(klo, mode="drop")
            new_hi = tab_hi.at[buckets].set(khi, mode="drop")
            new_s1 = tab_slot1.at[buckets].set(slot1, mode="drop")
            if sharding is not None:
                from jax.lax import with_sharding_constraint as wsc
                new_lo = wsc(new_lo, sharding)
                new_hi = wsc(new_hi, sharding)
                new_s1 = wsc(new_s1, sharding)
            return new_lo, new_hi, new_s1

        return jax.jit(insert, donate_argnums=(0, 1, 2))

    def _place(self, keys: np.ndarray) -> np.ndarray:
        """Claim one shadow bucket per (unique) key via the device's own
        linear probing; returns the bucket indices.  Vectorized rounds:
        same-bucket races resolve by first-in-batch, losers re-probe."""
        n = keys.size
        buckets = np.full(n, -1, np.int64)
        idx = probe_starts(keys, self.capacity).astype(np.int64)
        pending = np.arange(n, dtype=np.int64)
        pidx = idx
        maskv = np.int64(self.capacity - 1)
        while pending.size:
            free = ~self._shadow_used[pidx]
            f_pend = pending[free]
            f_idx = pidx[free]
            if f_pend.size:
                win_idx, first = np.unique(f_idx, return_index=True)
                w_pend = f_pend[first]
                self._shadow_used[win_idx] = True
                buckets[w_pend] = win_idx
            unresolved = buckets[pending] < 0
            pending = pending[unresolved]
            pidx = (pidx[unresolved] + 1) & maskv
        return buckets

    # -- public -------------------------------------------------------------
    @property
    def num_loaded(self) -> int:
        return self._n

    def table(self):
        return self.tab_lo, self.tab_hi, self.tab_slot1

    def prepare_batch(self, keys: np.ndarray
                      ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """(key_lo, key_hi, start) int32 planes for one batch — the only
        per-record host work of the device probe: streaming hash + split,
        no random access, no insert."""
        lo, hi = split_keys(keys)
        return lo, hi, probe_starts(keys, self.capacity)

    def ensure_loaded(self, key_index) -> int:
        """Bring the device table up to date with ``key_index``: insert
        slots [num_loaded, num_keys) — initial bulk load, restore reload,
        and per-batch miss inserts are all this one path.  Returns the
        number of newly inserted keys."""
        n = int(key_index.num_keys)
        if n == self._n:
            return 0
        if n < self._n:
            # the key index was reset/restored under us: rebuild from empty
            self._n = 0
            self._alloc(self.capacity)
        if n > int(self.capacity * self._max_load):
            self._grow(n)
        rev = np.asarray(key_index.reverse_keys(), np.int64)
        new_keys = rev[self._n:n]
        buckets = self._place(new_keys)
        slots1 = np.arange(self._n + 1, n + 1, dtype=np.int32)
        self._upload(buckets, new_keys, slots1)
        inserted = n - self._n
        self._n = n
        return inserted

    def _upload(self, buckets: np.ndarray, keys: np.ndarray,
                slots1: np.ndarray) -> None:
        import jax.numpy as jnp
        from flink_tpu.ops.shapes import next_pow2

        m = buckets.size
        mp = next_pow2(max(m, 1), 64)   # bounded compile count
        b_p = np.full(mp, self.capacity, np.int32)   # pads: out of range
        b_p[:m] = buckets
        lo, hi = split_keys(keys)
        lo_p = np.zeros(mp, np.int32)
        hi_p = np.zeros(mp, np.int32)
        s1_p = np.zeros(mp, np.int32)
        lo_p[:m] = lo
        hi_p[:m] = hi
        s1_p[:m] = slots1
        self.tab_lo, self.tab_hi, self.tab_slot1 = self._insert_fn(
            self.tab_lo, self.tab_hi, self.tab_slot1,
            jnp.asarray(b_p), jnp.asarray(lo_p), jnp.asarray(hi_p),
            jnp.asarray(s1_p))

    def _grow(self, needed: int) -> None:
        """Sticky pow2 growth: double until ``needed`` fits the load
        factor, re-place every loaded key, upload the rebuilt table."""
        cap = self.capacity
        while needed > int(cap * self._max_load):
            cap <<= 1
        if cap == self.capacity:
            return
        loaded = self._n
        # keys currently in the table, in slot order, from the shadow-
        # independent source of truth we are mirroring: re-derive from the
        # caller at the next ensure_loaded — here we must rebuild NOW, so
        # read the old planes back (cheap relative to a rehash; growth is
        # O(log) per run)
        old_lo = np.asarray(self.tab_lo)
        old_hi = np.asarray(self.tab_hi)
        old_s1 = np.asarray(self.tab_slot1)
        occ = old_s1 > 0
        keys_u = (old_lo[occ].view(np.uint32).astype(np.uint64)
                  | (old_hi[occ].view(np.uint32).astype(np.uint64)
                     << np.uint64(32)))
        keys = keys_u.view(np.int64)
        slots1 = old_s1[occ]
        self._alloc(cap)
        self._n = loaded
        if keys.size:
            buckets = self._place(keys)
            self._upload(buckets, keys, slots1)


# ---------------------------------------------------------------------------
# measured A/B calibration (the --device-probe auto verdict)
# ---------------------------------------------------------------------------

_calibrated_probe: Optional[bool] = None
_calib_lock = threading.Lock()

#: env override: "on"/"off" skip the measurement ("auto" measures)
_ENV = "FLINK_TPU_DEVICE_PROBE"


def calibrated_device_probe() -> bool:
    """MEASURED verdict, cached process-wide: does the device-resident
    probe + delta fold beat the fused host C pass on THIS backend?  A/Bs a
    warm 32k-key table over three real-sized batches — the probe twin of
    the device-sync transport calibration and the native-shards A/B:
    measure, don't assume (on CPU the XLA scatter's ~0.5µs/update usually
    loses to the C fold; on a real accelerator the fold rides the already-
    dispatched step).  ``FLINK_TPU_DEVICE_PROBE=on|off`` short-circuits."""
    global _calibrated_probe
    if _calibrated_probe is not None:
        return _calibrated_probe
    with _calib_lock:
        if _calibrated_probe is not None:
            return _calibrated_probe
        env = os.environ.get(_ENV, "").lower()
        if env in ("on", "1", "true"):
            _calibrated_probe = True
            return True
        if env in ("off", "0", "false"):
            _calibrated_probe = False
            return False
        _calibrated_probe = _measure_device_probe()
        return _calibrated_probe


def _measure_device_probe() -> bool:
    import time

    import jax
    import jax.numpy as jnp

    from flink_tpu.native import get_lib
    lib = get_lib()
    if lib is None or not hasattr(lib, "wm_create"):
        # no native fused pass to beat: the host fallback is numpy — the
        # device probe wins by default wherever it is eligible at all
        return True
    n_keys = 1 << 15
    B = 1 << 15
    rng = np.random.default_rng(23)
    keys_all = np.ascontiguousarray(
        rng.integers(0, n_keys, 3 * B).astype(np.int64))
    vals_all = np.ascontiguousarray(rng.random(3 * B).astype(np.float32))

    # ---- host side: the fused C probe+fold at the shard count the real
    # fallback path would USE (calibrated_shards — measuring the serial
    # pass on a host whose calibration picked 4 shards would bias the A/B
    # toward the device)
    from flink_tpu.state.native_mirror import (calibrated_shards,
                                               measure_fused_probe)
    host_best = measure_fused_probe(lib, calibrated_shards(), n_keys, B,
                                    keys_all, vals_all)

    # ---- device side: probe + f64 delta fold dispatch (warm table)
    from flink_tpu.state.keyindex import KeyIndex
    ki = KeyIndex(initial_capacity=2 * n_keys)
    ki.lookup_or_insert(np.arange(n_keys, dtype=np.int64))
    dki = DeviceKeyIndex(initial_capacity=2 * n_keys)
    dki.ensure_loaded(ki)

    @jax.jit
    def step(tab_lo, tab_hi, tab_s1, dsum, dcnt, klo, khi, start, vals):
        slot = lax_probe(tab_lo, tab_hi, tab_s1, klo, khi, start)
        hit = slot >= 0
        ids = jnp.where(hit, slot, jnp.int32(np.iinfo(np.int32).max))
        new_sum = dsum.at[ids].add(vals.astype(dsum.dtype), mode="drop")
        new_cnt = dcnt.at[ids].add(1, mode="drop")
        miss = jnp.sum(~hit, dtype=jnp.int32)
        return new_sum, new_cnt, miss

    # the real delta fold accumulates in f64 (the mirror's precision) —
    # measure the same thing; enable_x64 scopes the wide dtype per-trace
    with jax.enable_x64():
        dsum = jnp.zeros(n_keys, jnp.float64)
        dcnt = jnp.zeros(n_keys, jnp.int32)
        dev_best = float("inf")
        for i in range(3):
            k = keys_all[i * B:(i + 1) * B]
            v = vals_all[i * B:(i + 1) * B]
            # the per-batch host hashing (prepare_batch) is part of the
            # device path's real cost: time it inside the sample
            t0 = time.perf_counter()
            klo, khi, start = dki.prepare_batch(k)
            dsum, dcnt, miss = step(*dki.table(), dsum, dcnt,
                                    jnp.asarray(klo), jnp.asarray(khi),
                                    jnp.asarray(start), jnp.asarray(v))
            jax.block_until_ready(dcnt)
            dt = time.perf_counter() - t0
            if i > 0:   # first timed round still pays compile: skip it
                dev_best = min(dev_best, dt)
    return dev_best < host_best
