"""Native (C++) write-through window mirror — the fire/mirror/probe hot path.

Python face of the ``WinMirror`` kernels in ``native/flink_native.cc``: the
host emit tier of :class:`~flink_tpu.operators.window_agg.WindowAggOperator`
keeps a write-through host value mirror of the device ACC cells so window
fires ship zero device->host bytes (decisive on egress-constrained links).
Round 3 ran that mirror in numpy (per-batch ``bincount``/``reduceat`` plus a
per-fire gather cascade); these kernels move the whole inner loop native:

- ``probe_update`` fuses the key-index probe and the mirror write-through
  into ONE C pass per micro-batch (the (slot, pane, value) triples are
  computed once and consumed twice), sharing the key dict with the Python
  :class:`~flink_tpu.state.keyindex.KeyIndex` so slot ids agree with the
  device state rows by construction.
- ``fire`` is one sequential C sweep that combines the window's panes,
  compacts non-empty rows, and resolves raw keys — fire cost becomes memory
  bandwidth instead of Python/numpy time.

This is the same make-the-inner-loop-native move as the reference's Cython
fast coders (``pyflink/fn_execution/table/window_aggregate_fast.pyx:51``)
applied to ``WindowOperator.processElement``/``emitWindowContents``
(``WindowOperator.java:300,574``).

Eligibility: scalar accumulator leaves, add/min/max combine kinds, an int64
native key index.  Anything else falls back to the numpy mirror in
``window_agg.py`` (same semantics, slower).
"""

from __future__ import annotations

import ctypes
import os
import threading
from typing import List, Optional, Sequence, Tuple

import numpy as np

#: numpy dtype -> native value-load tag (VDt in flink_native.cc)
_VDT = {np.dtype(np.float64): 0, np.dtype(np.float32): 1,
        np.dtype(np.int64): 2, np.dtype(np.int32): 3}
_KINDS = {"add": 0, "min": 1, "max": 2}


def auto_shards() -> int:
    """Default shard count for the native probe: one shard per core up to
    4 (the pass is memory-latency bound — beyond a few cores the misses in
    flight saturate the memory controller, and oversubscribing steals CPU
    from XLA's own thread pool).  ``FLINK_TPU_NATIVE_SHARDS`` overrides."""
    env = os.environ.get("FLINK_TPU_NATIVE_SHARDS")
    if env:
        try:
            return max(1, int(env))
        except ValueError:
            pass
    cores = 0
    from flink_tpu.native import get_lib
    lib = get_lib()
    if lib is not None and hasattr(lib, "fn_hw_threads"):
        cores = int(lib.fn_hw_threads())  # what the C worker pool sees
    return max(1, min(4, cores or os.cpu_count() or 1))


_calibrated_shards: Optional[int] = None
#: module-scope: lazily creating the lock would itself be a check-then-act
#: race between the first two calibrating threads
_calib_lock = threading.Lock()


def measure_fused_probe(lib, shards: int, n_keys: int, B: int,
                        keys_all: np.ndarray, vals_all: np.ndarray,
                        rounds: int = 3) -> float:
    """Best-of-``rounds`` wall seconds of the fused C probe+fold at
    ``shards`` over a warm ``n_keys`` table — the measurement of the
    native-shards A/B.  ``keys_all``/``vals_all`` hold ``rounds``
    consecutive batches of ``B``.  The throwaway keydict/mirror pair is
    released via try/finally even on a mid-measurement failure."""
    import time
    d = lib.keydict_create(2 * n_keys)
    h = None
    try:
        kind = (ctypes.c_uint8 * 1)(0)   # add
        lt = (ctypes.c_uint8 * 1)(0)     # f64 storage
        init = np.zeros(1, np.uint64)
        h = lib.wm_create(d, 1, kind, lt,
                          init.ctypes.data_as(ctypes.c_void_p))
        vdt = (ctypes.c_uint8 * 1)(1)    # VF32 input
        warm_k = np.arange(n_keys, dtype=np.int64)
        warm_p = np.zeros(n_keys, np.int64)
        warm_v = np.zeros(n_keys, np.float32)
        warm_s = np.empty(n_keys, np.int32)
        vptr = (ctypes.c_void_p * 1)(warm_v.ctypes.data)
        lib.wm_probe_update(h, warm_k.ctypes.data, warm_p.ctypes.data,
                            n_keys, vptr, vdt, warm_s.ctypes.data,
                            0, 0, 0, 0, shards)
        panes = np.zeros(B, np.int64)
        slots = np.empty(B, np.int32)
        best = float("inf")
        for i in range(rounds):
            k = np.ascontiguousarray(keys_all[i * B:(i + 1) * B])
            v = np.ascontiguousarray(vals_all[i * B:(i + 1) * B])
            vp = (ctypes.c_void_p * 1)(v.ctypes.data)
            t0 = time.perf_counter()
            lib.wm_probe_update(h, k.ctypes.data, panes.ctypes.data, B,
                                vp, vdt, slots.ctypes.data, 0, 0, 0, 0,
                                shards)
            best = min(best, time.perf_counter() - t0)
        return best
    finally:
        if h:
            lib.wm_destroy(h)
        lib.keydict_destroy(d)


def calibrated_shards() -> int:
    """MEASURED default shard count, cached process-wide: A/Bs the fused
    probe serially vs at :func:`auto_shards` on a throwaway keydict+mirror
    (~tens of ms, once per process) and returns the faster setting.  The
    core count alone cannot be trusted — on shared or steal-heavy vCPUs a
    single core's prefetch pipelining already saturates the memory
    subsystem and extra shards lose — so this is the shard twin of the
    device-sync transport calibration: measure, don't assume.  Explicit
    ``FLINK_TPU_NATIVE_SHARDS`` (via auto_shards) short-circuits the
    measurement."""
    global _calibrated_shards
    if _calibrated_shards is not None:
        return _calibrated_shards
    with _calib_lock:
        if _calibrated_shards is not None:
            return _calibrated_shards
        auto = auto_shards()
        if os.environ.get("FLINK_TPU_NATIVE_SHARDS"):
            _calibrated_shards = auto  # explicit: trust the operator
            return auto
        from flink_tpu.native import get_lib
        lib = get_lib()
        if auto <= 1 or lib is None or not hasattr(lib, "wm_create"):
            _calibrated_shards = 1
            return 1
        n_keys = 1 << 15
        B = 1 << 15  # >= the C pass's parallel threshold
        rng = np.random.default_rng(17)
        keys_all = np.ascontiguousarray(
            rng.integers(0, n_keys, 3 * B).astype(np.int64))
        vals_all = np.ascontiguousarray(
            rng.random(3 * B).astype(np.float32))
        timings = {shards: measure_fused_probe(lib, shards, n_keys, B,
                                               keys_all, vals_all)
                   for shards in (1, auto)}
        _calibrated_shards = min(timings, key=timings.get)
        return _calibrated_shards


class NativeWindowMirror:
    """ctypes handle to a C++ WinMirror sharing a KeyIndex's key dict."""

    def __init__(self, lib, key_index, handle, mirror_dtypes):
        self._lib = lib
        #: pins the KeyIndex (and thus the shared keydict) for our lifetime
        self._key_index = key_index
        self._h = handle
        self._mirror_dtypes = tuple(np.dtype(d) for d in mirror_dtypes)
        #: reusable fire output buffers (keys, counts, leaves) — a 1M-key
        #: fire would otherwise first-touch ~24MB of fresh pages per window
        self._fire_scratch = None
        #: reusable export buffers (counts, leaves) for the same reason;
        #: snapshots run inside the checkpointed hot path
        self._export_scratch = None

    @classmethod
    def try_create(cls, key_index, spec, kinds: Optional[Sequence[str]],
                   mirror_dtypes) -> Optional["NativeWindowMirror"]:
        """A mirror for this (key index, ACC spec), or None if ineligible."""
        from flink_tpu.native import get_lib

        lib = get_lib()
        dict_handle = getattr(key_index, "_handle", None)
        if lib is None or not hasattr(lib, "wm_create") or not dict_handle:
            return None
        if kinds is None or not all(k in _KINDS for k in kinds):
            return None
        if any(tuple(s) != () for s in spec.leaf_shapes):
            return None  # non-scalar leaves: numpy mirror handles them
        mdts = [np.dtype(d) for d in mirror_dtypes]
        if any(d not in (np.dtype(np.float64), np.dtype(np.int64))
               for d in mdts):
            return None
        nl = spec.num_leaves
        kind_b = (ctypes.c_uint8 * nl)(*[_KINDS[k] for k in kinds])
        lt_b = (ctypes.c_uint8 * nl)(
            *[1 if d == np.dtype(np.int64) else 0 for d in mdts])
        init = np.empty(nl, np.uint64)
        for j, (iv, d) in enumerate(zip(spec.leaf_inits, mdts)):
            init[j] = np.asarray(iv).astype(d).reshape(1).view(np.uint64)[0]
        h = lib.wm_create(dict_handle, nl, kind_b, lt_b,
                          init.ctypes.data_as(ctypes.c_void_p))
        if not h:
            return None
        return cls(lib, key_index, h, mdts)

    def __del__(self):
        h = getattr(self, "_h", None)
        if h:
            try:
                self._lib.wm_destroy(h)
            except Exception:  # noqa: BLE001 — interpreter teardown
                pass
            self._h = None

    # -- hot path ------------------------------------------------------------
    def probe_update(self, keys: np.ndarray, panes: np.ndarray,
                     lifted: List[np.ndarray], pane_mod: int = 0,
                     flat_out: Optional[np.ndarray] = None,
                     flat_fill: int = 0, shards: int = 1,
                     shard_div: int = 0,
                     shard_ns: Optional[np.ndarray] = None) -> np.ndarray:
        """Fused probe + mirror fold; returns int32 slot ids for the device
        scatter.  ``lifted`` is the agg's host_lift leaves, one [B] array per
        ACC leaf.  When ``flat_out`` (int32[>=n], contiguous) is given, the C
        pass also writes the device scatter ids slot * pane_mod +
        pane %% pane_mod into it — one pass instead of three numpy ops —
        and fills the padding tail flat_out[n:] with ``flat_fill`` (the
        dropped-row id), so a pow2 staging buffer comes back dispatch-ready.
        ``shards`` > 1 partitions the fold across the native worker pool
        (disjoint slot ownership, no locks) — results are bit-identical to
        the serial pass at any shard count.  Ownership defaults to
        slot %% shards classes; ``shard_div`` > 0 switches to CONTIGUOUS
        slot ranges [t*shard_div, (t+1)*shard_div) — the mesh runtime
        passes K_cap / n_devices so probe shard t owns exactly the
        key-group range whose device state block lives on mesh device t.
        ``shard_ns`` (int64[>=shards], contiguous) receives each shard's
        fold wall time in nanoseconds (the per-shard probe breakdown)."""
        keys = np.ascontiguousarray(keys, np.int64)
        panes = np.ascontiguousarray(panes, np.int64)
        n = keys.size
        slots = np.empty(n, np.int32)
        if n == 0:
            if flat_out is not None:
                flat_out[:] = flat_fill
            if shard_ns is not None:
                shard_ns[:] = 0
            return slots
        nl = len(self._mirror_dtypes)
        arrs = []
        vdt = (ctypes.c_uint8 * nl)()
        for j, l in enumerate(lifted):
            a = np.ascontiguousarray(l)
            if a.dtype not in _VDT:
                a = a.astype(np.float64)
            arrs.append(a)
            vdt[j] = _VDT[a.dtype]
        vals = (ctypes.c_void_p * nl)(*[a.ctypes.data for a in arrs])
        flat_ptr = 0
        flat_cap = 0
        if flat_out is not None:
            # hard checks (not asserts): a wrong buffer here is C-side
            # memory corruption, and pane_mod 0 is a divide-by-zero in C
            if (flat_out.dtype != np.int32 or not flat_out.flags.c_contiguous
                    or flat_out.size < n or pane_mod <= 0):
                raise ValueError(
                    "flat_out must be contiguous int32 with size >= n and "
                    "pane_mod > 0")
            flat_ptr = flat_out.ctypes.data
            flat_cap = flat_out.size
        ns_ptr = 0
        if shard_ns is not None:
            if (shard_ns.dtype != np.int64
                    or not shard_ns.flags.c_contiguous
                    or shard_ns.size < max(1, int(shards))):
                raise ValueError("shard_ns must be contiguous int64 with "
                                 "size >= shards")
            shard_ns[:] = 0
            ns_ptr = shard_ns.ctypes.data
        self._lib.wm_probe_update2(
            self._h, keys.ctypes.data, panes.ctypes.data, n, vals, vdt,
            slots.ctypes.data, pane_mod, flat_ptr, flat_cap,
            int(flat_fill), max(1, int(shards)), int(shard_div), ns_ptr)
        return slots

    def fire(self, panes: np.ndarray
             ) -> Tuple[np.ndarray, np.ndarray, List[np.ndarray]]:
        """Combine+compact the window's panes: (keys[m], counts[m],
        leaf arrays [m]) in ascending slot order."""
        n = self._key_index.num_keys
        panes = np.ascontiguousarray(panes, np.int64)
        if n == 0 or panes.size == 0:
            return (np.empty(0, np.int64), np.empty(0, np.int64),
                    [np.empty(0, d) for d in self._mirror_dtypes])
        sc = self._fire_scratch
        if sc is None or sc[0].size < n:
            cap = 1 << max(10, (n - 1).bit_length())
            sc = self._fire_scratch = (
                np.empty(cap, np.int64), np.empty(cap, np.int64),
                [np.empty(cap, d) for d in self._mirror_dtypes])
        out_keys, out_counts, out_leaves = sc
        ptrs = (ctypes.c_void_p * len(out_leaves))(
            *[a.ctypes.data for a in out_leaves])
        m = int(self._lib.wm_fire(self._h, panes.ctypes.data, panes.size,
                                  out_keys.ctypes.data,
                                  out_counts.ctypes.data, ptrs))
        # keys/leaves COPY out (they outlive this call in emitted batches);
        # counts are consumed-or-dropped by the caller, so a view suffices
        return (out_keys[:m].copy(), out_counts[:m],
                [a[:m].copy() for a in out_leaves])

    # -- pane lifecycle ------------------------------------------------------
    def drop_pane(self, pane: int) -> None:
        self._lib.wm_drop_pane(self._h, int(pane))

    def live_panes(self) -> np.ndarray:
        k = int(self._lib.wm_pane_count(self._h))
        out = np.empty(k, np.int64)
        if k:
            self._lib.wm_live_panes(self._h, out.ctypes.data)
        out.sort()
        return out

    # -- snapshots -----------------------------------------------------------
    def export_pane(self, pane: int, nrows: int
                    ) -> Tuple[bool, np.ndarray, List[np.ndarray]]:
        """(exists, counts[nrows] int64, leaf columns in mirror dtypes).

        Returns VIEWS into reusable scratch (overwritten by the next
        export): callers (snapshot column fill, verify) consume them
        before exporting the next pane."""
        sc = self._export_scratch
        if sc is None or sc[0].size < nrows:
            cap = 1 << max(10, (nrows - 1).bit_length())
            sc = self._export_scratch = (
                np.empty(cap, np.int64),
                [np.empty(cap, d) for d in self._mirror_dtypes])
        counts, leaves = sc[0], sc[1]
        ptrs = (ctypes.c_void_p * len(leaves))(
            *[a.ctypes.data for a in leaves])
        ex = int(self._lib.wm_export_pane(self._h, int(pane), nrows,
                                          counts.ctypes.data, ptrs))
        return bool(ex), counts[:nrows], [a[:nrows] for a in leaves]

    def import_pane(self, pane: int, counts: np.ndarray,
                    leaves: List[np.ndarray]) -> None:
        counts = np.ascontiguousarray(counts, np.int64)
        arrs = [np.ascontiguousarray(l, d)
                for l, d in zip(leaves, self._mirror_dtypes)]
        ptrs = (ctypes.c_void_p * len(arrs))(*[a.ctypes.data for a in arrs])
        self._lib.wm_import_pane(self._h, int(pane), counts.size,
                                 counts.ctypes.data, ptrs)
